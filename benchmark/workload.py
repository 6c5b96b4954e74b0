"""Keys, per-key limits, the initial table state and the request streams,
all from a configuration file, a traffic file and the seed.

Numpy only: the load clients import this module and never JAX.  Every
seed gets the same multiset of keys and of inter-arrival gaps; the seed
decides their order, so two seeds do the same work in another order.
"""

from __future__ import annotations

import operator

import numpy as np

NS_PER_SEC = 1_000_000_000


def rng_of(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of a run (any whole-number seed)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), stream])
    )


# --------------------------------------------------------------------- #
# Keys and their limits (configuration file).


def n_keys(config: dict) -> int:
    return int(config["keys"]["count"])


TENANT_DIGITS = 2
TENANT_DELIM = b":"  # the server's default THROTTLECRAB_TENANT_DELIM


def n_tenants(config: dict) -> int:
    """The configuration's tenant count T, or 0 where its keys have no
    tenant namespace."""
    return int(config["keys"].get("tenants", 0))


def tenant_of(config: dict, idx) -> np.ndarray:
    """The tenant of each key index: index i is tenant i mod T."""
    return np.asarray(idx, np.int64) % n_tenants(config)


def tenant_names(config: dict) -> list:
    """Tenant t's namespace as the server names it: the key's text
    before the first delimiter, `<prefix><t, 2 digits>`."""
    prefix = config["keys"]["prefix"]
    return [f"{prefix}{t:0{TENANT_DIGITS}d}" for t in range(n_tenants(config))]


def _key_form(config: dict) -> tuple:
    """(prefix bytes, tenant count, digits): a key is prefix + decimal
    index, or, with T tenants, prefix + tenant + ':' + user, each
    field zero-padded, so every key of a configuration has one width."""
    keys = config["keys"]
    tenants = n_tenants(config)
    if not 0 <= tenants <= 10 ** TENANT_DIGITS:
        raise ValueError(f"keys.tenants {tenants} outside 0..100")
    return keys["prefix"].encode(), tenants, int(keys["digits"])


def key_bytes(config: dict, idx) -> list:
    """Fixed-width keys: prefix + zero-padded decimal index; with T
    tenants, index i is user i div T of tenant i mod T,
    `<prefix><tenant>:<user>` (`t07:12345`)."""
    prefix, tenants, digits = _key_form(config)
    if not tenants:
        fmt = prefix + f"%0{digits}d".encode()
        return [fmt % int(i) for i in idx]
    fmt = prefix + f"%0{TENANT_DIGITS}d".encode() + TENANT_DELIM \
        + f"%0{digits}d".encode()
    return [fmt % (int(i) % tenants, int(i) // tenants) for i in idx]


def parse_keys(config: dict, keys: list) -> np.ndarray:
    """Key bytes back to indices, vectorised; -1 for a key that is not
    of the configuration's form."""
    prefix, tenants, digits = _key_form(config)
    head = len(prefix) + (TENANT_DIGITS + 1 if tenants else 0)
    width = head + digits
    out = np.full(len(keys), -1, np.int64)
    if not keys:
        return out
    ok = np.fromiter((len(k) == width for k in keys), bool, len(keys))
    good = [k for k, g in zip(keys, ok) if g] if not ok.all() else keys
    if not good:
        return out
    raw = np.frombuffer(b"".join(good), np.uint8).reshape(-1, width)
    valid = (raw[:, : len(prefix)] == np.frombuffer(prefix, np.uint8)).all(
        axis=1
    )
    val = _decimal(raw[:, head:], valid)
    if tenants:
        valid &= raw[:, head - 1] == TENANT_DELIM[0]
        tenant = _decimal(raw[:, len(prefix): head - 1], valid)
        valid &= tenant < tenants
        val = val * tenants + tenant
    val = np.where(valid & (val < n_keys(config)), val, -1)
    out[np.flatnonzero(ok)] = val
    return out


def _decimal(cols: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """The decimal number in each row of ASCII digit columns; clears
    `valid` (in place) where a row holds anything but digits."""
    d = cols.astype(np.int64) - 48
    valid &= ((d >= 0) & (d <= 9)).all(axis=1)
    return d @ (10 ** np.arange(cols.shape[1] - 1, -1, -1, dtype=np.int64))


def limits(config: dict, idx) -> tuple:
    """(burst, count, period) of each key index: `base + i % mod` per
    field, or `base` where `mod` is 0."""
    idx = np.asarray(idx, np.int64)
    out = []
    for field in ("burst", "count", "period"):
        spec = config["limits"][field]
        base, mod = int(spec["base"]), int(spec.get("mod", 0))
        out.append(base + (idx % mod if mod else np.zeros_like(idx)))
    return tuple(out)


def initial_state(config: dict, t0_ns: int) -> dict:
    """The table the server restores at boot: every key as if its first
    request (quantity 1) had been decided at `t0_ns` -- TAT `t0`, expiry
    `t0 + tolerance` -- which is the state a wire pass of one request per
    key leaves behind."""
    from .reference.gcra import intervals

    n = n_keys(config)
    _, tol = intervals(*limits(config, np.arange(n, dtype=np.int64)))
    tat = np.full(n, int(t0_ns), np.int64)
    return {"tat": tat, "expiry": tat + tol}


# --------------------------------------------------------------------- #
# Request streams (traffic file).


def key_counts(traffic: dict, n_keys_: int, n: int) -> np.ndarray:
    """How often each key is asked for in `n` requests: a fixed
    multiset, independent of the seed."""
    dist = traffic["keys"]
    if dist == "uniform":
        counts = np.full(n_keys_, n // n_keys_, np.int64)
        counts[: n % n_keys_] += 1
        return counts
    if dist == "zipf":
        p = np.arange(1, n_keys_ + 1, dtype=np.float64) ** -float(
            traffic["zipf_a"]
        )
        p *= n / p.sum()
        counts = np.floor(p).astype(np.int64)
        short = n - int(counts.sum())
        if short:
            # Largest remainders first; ties to the lower rank.
            order = np.argsort(-(p - counts), kind="stable")
            counts[order[:short]] += 1
        return counts
    raise ValueError(f"unknown key distribution {dist!r}")


def key_sequence(traffic: dict, n_keys_: int, n: int, seed: int):
    """`n` key indices: the fixed multiset in the seed's order.  Rank r
    of a skewed distribution is key r."""
    seq = np.repeat(
        np.arange(n_keys_, dtype=np.int64), key_counts(traffic, n_keys_, n)
    )
    rng_of(seed, 1).shuffle(seq)
    return seq


def sequence_length(traffic: dict, seconds: float) -> int:
    """Requests a run's stream holds.  A closed loop cycles through a
    fixed stream; an open loop holds every request due in the longest
    warm-up and the window, with 5% to spare."""
    if traffic["loop"] == "closed":
        return int(traffic["stream_requests"])
    span = float(traffic["warmup_max_s"]) + float(seconds)
    return int(np.ceil(float(traffic["rate"]) * span * 1.05)) + 64


def due_offsets_ns(traffic: dict, n: int, seed: int) -> np.ndarray:
    """Open loop: due times (ns after the start of the warm-up) of `n`
    requests.  Poisson arrivals at `rate`: the gaps are the n quantile
    midpoints of the exponential distribution, in the seed's order."""
    rate = float(traffic["rate"])
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng_of(seed, 2).shuffle(gaps)
    return (np.cumsum(gaps) * NS_PER_SEC).astype(np.int64)


def stream(traffic: dict, config: dict, seconds: float, seed: int):
    """The whole run's stream: (key indices, due offsets or None).
    Request j goes out on connection j % connections."""
    n = sequence_length(traffic, seconds)
    keys = key_sequence(traffic, n_keys(config), n, seed)
    due = (
        due_offsets_ns(traffic, n, seed)
        if traffic["loop"] == "open"
        else None
    )
    return keys, due


# --------------------------------------------------------------------- #
# RESP frames.


def frame_templates(config: dict, idx) -> dict:
    """`THROTTLE key burst count period` frame of each key in `idx`."""
    idx = np.unique(np.asarray(idx, np.int64))
    burst, count, period = limits(config, idx)
    out = {}
    for i, k, b, c, p in zip(
        idx.tolist(), key_bytes(config, idx), burst.tolist(),
        count.tolist(), period.tolist(),
    ):
        args = (b"THROTTLE", k, b"%d" % b, b"%d" % c, b"%d" % p)
        out[i] = b"*5\r\n" + b"".join(
            b"$%d\r\n%s\r\n" % (len(a), a) for a in args
        )
    return out


def frames(config: dict, seq) -> tuple:
    """(bytes of every request in `seq`, offsets[len(seq) + 1])."""
    seq = np.asarray(seq, np.int64)
    if not len(seq):
        return b"", np.zeros(1, np.int64)
    tpl = frame_templates(config, seq)
    parts = operator.itemgetter(*seq.tolist())(tpl)
    if len(seq) == 1:
        parts = (parts,)
    offsets = np.zeros(len(seq) + 1, np.int64)
    np.cumsum(np.fromiter(map(len, parts), np.int64, len(seq)),
              out=offsets[1:])
    return b"".join(parts), offsets


# --------------------------------------------------------------------- #
# RESP replies.

REPLY_HEAD = b"*5\r\n:"


def parse_replies(buf: bytes, n: int):
    """The 5 integers of each of `n` THROTTLE replies, i64[n, 5], and a
    bool[n] that is False where the reply was an error line.  Raises
    ValueError on anything else."""
    out = np.zeros((n, 5), np.int64)
    ok = np.ones(n, bool)
    if not n:
        if buf:
            raise ValueError("reply bytes with no request")
        return out, ok
    if buf.count(REPLY_HEAD) == n and buf.count(b"\r\n") == 6 * n:
        text = (
            buf.replace(REPLY_HEAD, b" ").replace(b"\r\n:", b" ")
            .replace(b"\r\n", b" ")
        )
        vals = np.fromstring(text, dtype=np.int64, sep=" ")
        if vals.size == 5 * n:
            return vals.reshape(n, 5), ok
    # Slow path: error lines among the replies.
    lines = buf.split(b"\r\n")
    if lines[-1] != b"":
        raise ValueError("truncated reply")
    pos = 0
    for i in range(n):
        head = lines[pos]
        if head.startswith(b"-"):
            ok[i] = False
            pos += 1
            continue
        if head != b"*5":
            raise ValueError(f"malformed reply {head[:40]!r}")
        out[i] = [int(x[1:]) for x in lines[pos + 1 : pos + 6]]
        pos += 6
    if pos != len(lines) - 1:
        raise ValueError("more replies than requests")
    return out, ok
