"""Round-5 differential fuzz campaign: the compact output-tier ladder
(w32 / cur / 4-plane) vs the scalar oracle, across every dispatch path.

Round 4's 1.5 M-request campaign targeted the batch/scan/wire/sharded
APIs; this one aims at what round 5 added — the w32 certificate's edges
and its cross-launch high-water marks:

  - params straddling the w32 field bounds (burst near 500-2100,
    tolerance near the 2047 s reset budget, retry near 1023 s);
  - big-tolerance keys that bump tol_hwm mid-stream and force later
    small-tol traffic down a tier;
  - tol >= 2^61 poison keys (cur_safe) mixed into the same stream;
  - degenerate probes (quantity 0), invalid lanes, duplicate segments,
    per-key param churn;
  - clock regressions (now stepping backward — the now_hwm guard);
  - mid-stream sweeps and snapshot save/restore (hwm recovery from
    restored TATs);

against single-device dispatch_many (native + python keymaps),
dispatch_wire_window (native prep + agg certificate), and the sharded
mesh dispatcher — all compared request-by-request to the scalar oracle
with the documented wire truncation (seconds, i32 saturation).

Usage: python scripts/fuzz_wire_tiers.py [--seeds N] [--steps M]
Exit 0 and a one-line tally on success; raises on first divergence.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

from throttlecrab_tpu.core.errors import CellError
from throttlecrab_tpu.core.rate_limiter import RateLimiter
from throttlecrab_tpu.core.store.periodic import PeriodicStore

NS = 1_000_000_000
T0 = 1_753_700_000 * NS
I32_MAX = (1 << 31) - 1

TOTAL = {"requests": 0, "windows": 0, "tiers": {"w32": 0, "cur": 0, "planes": 0}}


def draw_params(rng, profile):
    """One key's (burst, count, period).

    `profile` shapes the seed's traffic: "benign" stays inside the w32
    certificate (so whole streams ride the 4 B tier and its cross-launch
    bookkeeping), "edges" hugs the field bounds, "hostile" mixes in
    cur-only, poison (tol >= 2^61) and degenerate keys so the ladder
    keeps stepping down mid-stream.
    """
    r = rng.random()
    if profile == "benign":
        # em <= 1 s (count >= period) and burst <= 400 keeps tol within
        # ~400 s — comfortably inside every w32 field bound.
        period = int(rng.integers(1, 600))
        count = period * int(rng.integers(1, 120))
        return (int(rng.integers(2, 400)), count, period)
    if profile == "edges":
        if r < 0.6:
            # em = 1 s exactly; burst sweeps across the w32 reset
            # boundary (tol ~ 1024 s is where tol + hwm crosses 2047).
            period = int(rng.integers(1, 120))
            return (int(rng.integers(400, 2300)), period, period)
        period = int(rng.integers(1, 600))
        count = period * int(rng.integers(1, 120))
        return (int(rng.integers(2, 400)), count, period)
    # hostile
    if r < 0.25:
        return (int(rng.integers(2, 200)), int(rng.integers(1, 1000)),
                int(rng.integers(1, 600)))
    if r < 0.45:   # cur tier only (reset far past 2047 s)
        return (int(rng.integers(3000, 100_000)), 60, 60)
    if r < 0.58:   # tol >= 2^61 poison (4-plane + sticky cur_safe)
        return (3_000_000_000, 1, 1)
    if r < 0.72:   # degen material: burst 1 (tol 0)
        return (1, int(rng.integers(1, 50)), int(rng.integers(1, 60)))
    return (int(rng.integers(2, 50)), int(rng.integers(1, 3000)),
            int(rng.choice([1, 10, 60, 3600])))


def oracle_wire(oracle, keys, burst, count, period, qty, now_ns):
    n = len(keys)
    out = {
        "allowed": np.zeros(n, bool),
        "remaining": np.zeros(n, np.int64),
        "reset_s": np.zeros(n, np.int64),
        "retry_s": np.zeros(n, np.int64),
        "bad": np.zeros(n, bool),
    }
    for i in range(n):
        try:
            a, r = oracle.rate_limit(
                keys[i] if isinstance(keys[i], str) else keys[i].decode(),
                int(burst[i]), int(count[i]), int(period[i]), int(qty[i]),
                now_ns,
            )
        except CellError:
            out["bad"][i] = True
            continue
        out["allowed"][i] = a
        out["remaining"][i] = min(r.remaining, I32_MAX)
        out["reset_s"][i] = min(r.reset_after_ns // NS, I32_MAX)
        out["retry_s"][i] = min(r.retry_after_ns // NS, I32_MAX)
    return out


def check(res, want, ctx):
    ok = ~want["bad"]
    if not (np.asarray(res.status)[ok] == 0).all():
        raise AssertionError(f"{ctx}: unexpected status on valid lanes")
    for name, got in (
        ("allowed", np.asarray(res.allowed)),
        ("remaining", np.asarray(res.remaining)),
        ("reset_s", np.asarray(res.reset_after_s)),
        ("retry_s", np.asarray(res.retry_after_s)),
    ):
        g, w = got[ok], want[name][ok]
        if not (g == w).all():
            i = int(np.nonzero(g != w)[0][0])
            raise AssertionError(
                f"{ctx}: {name} diverged at valid lane {i}: "
                f"got {g[i]} want {w[i]}"
            )


def tier_of(handle):
    if getattr(handle, "_w32", False):
        return "w32"
    if getattr(handle, "_cur", False) or getattr(handle, "_now_list", None):
        return "cur"
    return "planes"


def run_seed(seed, steps, sharded_mesh, insight_single=False):
    """One differential seed.

    `insight_single=True` arms the insight tier (INS_WIDTH rows) on the
    single-device limiter too, so the ladder runs through the gcra_*_ins
    launches as well as the 4-wide ones.
    """
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter
    from throttlecrab_tpu.tpu.snapshot import load_snapshot, save_snapshot

    rng = np.random.default_rng(seed)
    native = bool(seed % 2)
    try:
        lim = TpuRateLimiter(
            capacity=512, keymap="native" if native else "python",
            insight=insight_single,
        )
    except RuntimeError:
        lim = TpuRateLimiter(capacity=512, insight=insight_single)
        native = False
    if sharded_mesh is not None:
        from throttlecrab_tpu.parallel.sharded import ShardedTpuRateLimiter

        # Alternating seeds run the mesh with the insight tier armed
        # (INS_WIDTH shard rows + psum'd totals riding every launch):
        # the differential below then pins sharded+insight decisions to
        # the scalar oracle across the whole tier ladder, and the even
        # seeds keep pinning the 4-wide kill-switch layout.
        shl = ShardedTpuRateLimiter(
            capacity_per_shard=256, mesh=sharded_mesh,
            insight=bool(seed % 2),
        )
    else:
        shl = None
    oracle = RateLimiter(PeriodicStore())
    oracle_sh = RateLimiter(PeriodicStore())

    profile = ("benign", "edges", "hostile")[seed % 3]
    pool = [f"z{seed}x{i}" for i in range(int(rng.integers(4, 14)))]
    params = {k: draw_params(rng, profile) for k in pool}
    now = T0
    # Clock regressions must never cross a sweep or snapshot-restore
    # point: both drop entries expired AS OF that moment (exactly like
    # the reference's retain-based cleanup), while the bare-store
    # oracle expires on read and would "resurrect" them at an earlier
    # timestamp.  The engine is right; the comparison must respect the
    # drop point.
    floor_now = 0
    for step in range(steps):
        # Occasional param churn, sweeps, clock moves (incl. regression).
        if rng.random() < 0.15:
            k = pool[rng.integers(len(pool))]
            params[k] = draw_params(rng, profile)
        if rng.random() < 0.12:
            jump = int(rng.integers(1, 7200)) * NS
            now += jump
            lim.sweep(now)
            if shl is not None:
                shl.sweep(now)
            floor_now = now
        # The oracle expires on read; only engines need explicit sweeps.
        n = int(rng.integers(2, 28))
        ks = [pool[rng.integers(len(pool))] for _ in range(n)]
        b = np.array([params[k][0] for k in ks], np.int64)
        c = np.array([params[k][1] for k in ks], np.int64)
        p = np.array([params[k][2] for k in ks], np.int64)
        # Quantity-0 probes appear in bursts on hostile streams only
        # (a single probe anywhere in a window forfeits the fast tiers).
        probe_p = 0.10 if profile == "hostile" else 0.0
        q = np.array(
            [0 if rng.random() < probe_p else 1 for _ in ks], np.int64
        )
        # windows of 1-3 batches through dispatch_many; each batch may
        # move the clock forward a little, or REGRESS it (now_hwm).
        batches = []
        wnow = now
        for _ in range(int(rng.integers(1, 4))):
            if rng.random() < 0.1:
                wnow = max(floor_now, wnow - int(rng.integers(1, 3 * NS)))
            batches.append((ks, b, c, p, q, wnow))
            wnow += int(rng.integers(0, NS))
        h = lim.dispatch_many(batches, wire=True)
        TOTAL["tiers"][tier_of(h)] += 1
        got = h.fetch()
        for bt, g in zip(batches, got):
            want = oracle_wire(oracle, *bt)
            check(g, want, f"seed{seed} step{step} single")
            TOTAL["requests"] += len(bt[0])
        TOTAL["windows"] += 1

        if shl is not None:
            h2 = shl.dispatch_many(batches, wire=True)
            TOTAL["tiers"][tier_of(h2)] += 1
            got2 = h2.fetch()
            for bt, g in zip(batches, got2):
                want = oracle_wire(oracle_sh, *bt)
                check(g, want, f"seed{seed} step{step} sharded")
                TOTAL["requests"] += len(bt[0])
            TOTAL["windows"] += 1
        now = wnow

        # Native wire window (agg certificate) every few steps.
        if native and step % 3 == 0 and hasattr(lim.keymap, "prepare_batch"):
            ks2 = [k.encode() for k in ks]
            blob = b"".join(ks2)
            offs = np.cumsum([0] + [len(k) for k in ks2]).astype(np.int64)
            pr = np.stack([b, c, p, q], axis=1)
            hw = lim.dispatch_wire_window([(blob, offs, pr)], now)
            if hw is not None:
                res = hw.fetch()[0]
                want = oracle_wire(oracle, ks, b, c, p, q, now)
                check(res, want, f"seed{seed} step{step} native-wire")
                TOTAL["requests"] += len(ks)
                TOTAL["windows"] += 1
            now += int(rng.integers(0, NS))

        # Mid-stream snapshot round trip (hwm recovery) occasionally.
        if step == steps // 2 and rng.random() < 0.5:
            with tempfile.TemporaryDirectory() as d:
                path = os.path.join(d, "fz")
                save_snapshot(lim, path)
                lim2 = TpuRateLimiter(
                    capacity=512,
                    keymap="native" if native else "python",
                    insight=insight_single,
                )
                load_snapshot(lim2, path + ".npz", now_ns=now)
                lim = lim2
                floor_now = now


def run_hotkey_deny_seed(seed, steps):
    """Hot-key abuse traffic (harness workload `hotkey-abuse`) through
    the front tier's deny cache: every per-request decision — status,
    allowed, limit, remaining, reset, retry — must be identical with the
    cache on and off, across param churn, clock jumps and sweeps.  The
    cache must also actually serve (hits > 0), or the equality is
    vacuous.  Returns the deny-cache hit count."""
    import asyncio

    from throttlecrab_tpu.front import DenyCache, FrontTier
    from throttlecrab_tpu.harness.workload import make_keys
    from throttlecrab_tpu.server.engine import BatchingEngine
    from throttlecrab_tpu.server.types import ThrottleRequest
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    rng = np.random.default_rng(seed)
    clock = {"now": T0}
    window = 24
    keys = make_keys("hotkey-abuse", steps * window, 2000, seed=seed)
    # Tight limits with slow emission so the hot keys saturate fast and
    # stay denied across windows — the deny cache's serving regime.
    key_params = {
        k: (int(rng.integers(2, 6)), int(rng.integers(1, 5)),
            int(rng.integers(10, 90)))
        for k in set(keys)
    }

    def norm(r):
        if isinstance(r, Exception):
            return (type(r).__name__, str(r))
        return (r.allowed, r.limit, r.remaining, r.reset_after,
                r.retry_after)

    async def run():
        front = FrontTier(DenyCache(4096), None)
        eng_on = BatchingEngine(
            TpuRateLimiter(capacity=512), batch_size=32, max_linger_us=200,
            now_fn=lambda: clock["now"], front=front,
        )
        eng_off = BatchingEngine(
            TpuRateLimiter(capacity=512), batch_size=32, max_linger_us=200,
            now_fn=lambda: clock["now"],
        )
        for step in range(steps):
            if rng.random() < 0.10:  # param churn on a random key
                k = keys[int(rng.integers(len(keys)))]
                key_params[k] = (
                    int(rng.integers(2, 6)), int(rng.integers(1, 5)),
                    int(rng.integers(10, 90)),
                )
            reqs = []
            for k in keys[step * window : (step + 1) * window]:
                burst, count, period = key_params[k]
                q = 0 if rng.random() < 0.02 else 1
                reqs.append(ThrottleRequest(k, burst, count, period, q))
            got_on, got_off = await asyncio.gather(
                asyncio.gather(
                    *[eng_on.throttle(r) for r in reqs],
                    return_exceptions=True,
                ),
                asyncio.gather(
                    *[eng_off.throttle(r) for r in reqs],
                    return_exceptions=True,
                ),
            )
            for i, (a, b) in enumerate(zip(got_on, got_off)):
                if norm(a) != norm(b):
                    raise AssertionError(
                        f"hotkey seed{seed} step{step} row {i} "
                        f"({reqs[i]}): cache-on {norm(a)} != "
                        f"cache-off {norm(b)}"
                    )
            TOTAL["requests"] += 2 * len(reqs)
            TOTAL["windows"] += 2
            clock["now"] += int(rng.integers(0, 3 * NS))
            if rng.random() < 0.06:  # expiry jump: vacate buckets
                clock["now"] += int(rng.integers(120, 600)) * NS
        await eng_on.shutdown()
        await eng_off.shutdown()
        return front.deny_cache.hits

    return asyncio.run(run())


def run_cluster_frame_fuzz(seed, iters):
    """Malformed-frame hardening for every elastic-cluster wire op:
    random truncations, byte flips and splices of valid frames must
    either decode cleanly or raise the typed ClusterProtocolError —
    never OverflowError/MemoryError/IndexError/struct.error, and never
    size an allocation from an attacker-controlled count.

    The mutation corpus is keyed off cluster.FRAME_DECODERS — the
    protocol's single source of truth — with one maker arm per OP_*
    constant.  A new op that lands without an arm here fails both the
    runtime sync assert below and, structurally, the wire-surface
    invariant checker (throttlecrab_tpu/analysis/wire_surface.py).
    Returns the number of frames exercised."""
    from throttlecrab_tpu.parallel.cluster import (
        FRAME_DECODERS,
        OP_DROUTE_BATCH,
        OP_JOIN,
        OP_LEAVE,
        OP_MIGRATE,
        OP_REPLICA,
        OP_RING,
        OP_RING_STATE,
        OP_ROUTE_BATCH,
        OP_THROTTLE_BATCH,
        OP_THROTTLE_REPLY,
        ClusterProtocolError,
        encode_batch,
        encode_droute,
        encode_join,
        encode_leave,
        encode_reply,
        encode_ring,
        encode_route,
        encode_rows,
    )

    rng = np.random.default_rng(seed)

    def mk_keys(n):
        return [
            bytes(rng.integers(0, 256, int(rng.integers(0, 40)),
                               dtype=np.uint8))
            for _ in range(n)
        ]

    def mk_params(n):
        return [
            tuple(int(x) for x in rng.integers(-(2**40), 2**40, 4))
            for _ in range(n)
        ]

    def mk_rows(op):
        n = int(rng.integers(0, 12))
        return encode_rows(
            op, int(rng.integers(0, 8)), int(rng.integers(0, 2**32)),
            mk_keys(n),
            rng.integers(-(2**62), 2**62, n),
            rng.integers(-(2**62), 2**62, n),
        )

    def mk_ring(op):
        return encode_ring(
            op, int(rng.integers(0, 2**32)),
            rng.random(int(rng.integers(0, 8))).tolist(),
        )

    def mk_batch(_op):
        n = int(rng.integers(0, 12))
        return encode_batch(
            mk_keys(n), mk_params(n), int(rng.integers(0, 2**62))
        )

    def mk_route(_op):
        n = int(rng.integers(0, 12))
        return encode_route(
            mk_keys(n), mk_params(n), int(rng.integers(0, 2**62)),
            int(rng.integers(0, 8)),
        )

    def mk_droute(_op):
        n = int(rng.integers(0, 12))
        return encode_droute(
            mk_keys(n), mk_params(n), int(rng.integers(0, 2**62)),
            int(rng.integers(0, 8)),
            rng.integers(-(2**62), 2**62, n),
        )

    def mk_reply(_op):
        n = int(rng.integers(0, 12))
        return encode_reply(
            rng.integers(0, 7, n), rng.integers(0, 2, n),
            rng.integers(-(2**62), 2**62, n),
            rng.integers(-(2**62), 2**62, n),
            rng.integers(0, 2**62, n), rng.integers(0, 2**62, n),
        )

    makers = {
        OP_THROTTLE_BATCH: mk_batch,
        OP_THROTTLE_REPLY: mk_reply,
        OP_MIGRATE: mk_rows,
        OP_RING: mk_ring,
        OP_JOIN: lambda _op: encode_join(int(rng.integers(0, 256))),
        OP_RING_STATE: mk_ring,
        OP_REPLICA: mk_rows,
        OP_ROUTE_BATCH: mk_route,
        OP_LEAVE: lambda _op: encode_leave(
            int(rng.integers(0, 256)), int(rng.integers(0, 2**32))
        ),
        OP_DROUTE_BATCH: mk_droute,
    }
    missing = set(FRAME_DECODERS) - set(makers)
    extra = set(makers) - set(FRAME_DECODERS)
    if missing or extra:
        raise SystemExit(
            f"fuzz arms out of sync with FRAME_DECODERS: "
            f"missing={sorted(missing)} extra={sorted(extra)}"
        )

    ops = sorted(makers)
    done = 0
    for _ in range(iters):
        op = ops[int(rng.integers(len(ops)))]
        frame = makers[op](op)
        decoder = FRAME_DECODERS[op][1]
        body = bytearray(frame[5:])  # strip _HDR, like the server does
        mode = rng.random()
        if mode < 0.35 and len(body):          # truncate
            body = body[: int(rng.integers(0, len(body)))]
        elif mode < 0.7 and len(body):         # flip bytes
            for _ in range(int(rng.integers(1, 4))):
                body[int(rng.integers(len(body)))] = int(
                    rng.integers(256)
                )
        elif mode < 0.85:                      # append garbage
            body += bytes(
                rng.integers(0, 256, int(rng.integers(1, 16)),
                             dtype=np.uint8)
            )
        try:
            decoder(bytes(body))
        except ClusterProtocolError:
            pass  # the typed rejection the wire contract promises
        done += 1
        TOTAL["requests"] += 1
    return done


def run_trace_frame_fuzz(seed, iters):
    """Malformed-frame hardening for the record/replay trace codec
    (throttlecrab_tpu/replay/trace.py): random truncations, byte flips,
    splices and explicit count-vs-size lies over valid traces must
    either decode cleanly or raise the typed TraceError — never
    struct.error/IndexError/MemoryError, and never size an allocation
    from an attacker-controlled count (a trace file is untrusted input:
    it may come off a crashed node or a bug report).  Returns the
    number of mutated inputs exercised."""
    import struct as _struct

    from throttlecrab_tpu.replay.trace import (
        _DECODERS,
        Trace,
        TraceError,
        TraceWriter,
    )

    rng = np.random.default_rng(seed)
    # Table-driven off the codec's own kind->decoder registry, so a new
    # REC_* kind is fuzzed the moment it is wired into _DECODERS.
    frame_decoders = tuple(fn for _, fn in sorted(_DECODERS.items()))
    done = 0
    for _ in range(iters):
        writer = TraceWriter()
        for _w in range(int(rng.integers(1, 4))):
            n = int(rng.integers(0, 10))
            keys = [
                bytes(rng.integers(0, 256, int(rng.integers(0, 24)),
                                   dtype=np.uint8))
                for _ in range(n)
            ]
            writer.add_window(
                int(rng.integers(0, 2**62)), int(rng.integers(0, 32)),
                keys,
                rng.integers(-(2**40), 2**40, (n, 4)),
                rng.integers(0, 2, n), rng.integers(0, 6, n),
                rng.integers(0, 2**16, n),
            )
        if rng.random() < 0.5:
            writer.add_event(
                int(rng.integers(0, 2**62)), "degrade", "x" * 5
            )
        if rng.random() < 0.5:
            writer.add_injection(
                "launch", "count", int(rng.integers(0, 1000)), 1.5
            )
        data = bytearray(writer.to_bytes())
        mode = rng.random()
        if mode < 0.30 and len(data) > 6:          # truncate
            data = data[: int(rng.integers(6, len(data)))]
        elif mode < 0.60 and len(data) > 6:        # flip bytes
            for _ in range(int(rng.integers(1, 5))):
                data[int(rng.integers(6, len(data)))] = int(
                    rng.integers(256)
                )
        elif mode < 0.75:                          # append garbage
            data += bytes(
                rng.integers(0, 256, int(rng.integers(1, 24)),
                             dtype=np.uint8)
            )
        elif mode < 0.9 and len(data) >= 6 + 5 + 13:
            # Explicit count-vs-size lie: overwrite the first window
            # frame's n field with a huge value (the decode_batch leak
            # class the PR-8 cluster fuzzer caught).
            _struct.pack_into(
                "<I", data, 6 + 5 + 9, int(rng.integers(2**20, 2**31))
            )
        try:
            Trace.loads(bytes(data))
        except TraceError:
            pass  # the typed rejection the trace contract promises
        # Bare frame bodies through each decoder (no file header).
        body = bytes(data[6:])
        dec = frame_decoders[int(rng.integers(len(frame_decoders)))]
        try:
            dec(body[: int(rng.integers(0, max(len(body), 1) + 1))])
        except TraceError:
            pass
        done += 1
        TOTAL["requests"] += 1
    return done


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=24)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--no-sharded", action="store_true")
    args = ap.parse_args()

    mesh = None
    if not args.no_sharded:
        from throttlecrab_tpu.parallel.sharded import make_mesh

        try:
            mesh = make_mesh(2)
        except ValueError:
            mesh = None
    for s in range(args.seeds):
        run_seed(3000 + s, args.steps, mesh)
        print(
            f"seed {3000 + s} ok — {TOTAL['requests']} requests, "
            f"tiers {TOTAL['tiers']}",
            file=sys.stderr, flush=True,
        )
    # Deny-cache differential: one hot-key abuse seed per ladder seed,
    # so fuzz campaigns exercise the front tier's exactness contract
    # under fresh param-churn/clock-jump interleavings (not just the
    # single CI-pinned seed).
    for s in range(args.seeds):
        hits = run_hotkey_deny_seed(4000 + s, args.steps * 2)
        print(
            f"hotkey seed {4000 + s} ok — {hits} deny-cache hits",
            file=sys.stderr, flush=True,
        )
    # Elastic-cluster wire hardening: mutated migrate/replica/ring/
    # route frames must fail typed, never crash.
    for s in range(args.seeds):
        n = run_cluster_frame_fuzz(5000 + s, args.steps * 40)
        print(
            f"cluster-frame seed {5000 + s} ok — {n} frames",
            file=sys.stderr, flush=True,
        )
    # Record/replay trace hardening: mutated trace files and bare
    # frames must fail with the typed TraceError, never crash.
    for s in range(args.seeds):
        n = run_trace_frame_fuzz(6000 + s, args.steps * 20)
        print(
            f"trace-frame seed {6000 + s} ok — {n} inputs",
            file=sys.stderr, flush=True,
        )
    print(
        f"PASS: {TOTAL['requests']} differential requests over "
        f"{TOTAL['windows']} windows; tier mix {TOTAL['tiers']}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
