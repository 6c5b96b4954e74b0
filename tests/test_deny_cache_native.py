"""The native deny cache against its plain Python oracle.

`tests/deny_cache_oracle.py` is the deny cache as it was written in
Python; `throttlecrab_tpu.front.DenyCache` keeps its state and window
operations in native/deny_cache.cpp.  Seeded random interleavings of
every public call run through both, and after every step the replies,
the counters, and the write records, cached denials and in-flight holds
(in eviction order) must be identical.  The C++ arithmetic is covered
here, not by the AST i64 lint.

The second half drives the native RESP driver's frame path
(`_front_filter` / `_observe_plan`) on crafted frames.
"""

import random
from types import SimpleNamespace

import numpy as np
import pytest

from deny_cache_oracle import DenyCache as OracleCache
from throttlecrab_tpu.front import (
    STATUS_OVERLOADED,
    AdmissionController,
    DenyCache,
    FrontTier,
    filter_frame,
)
from throttlecrab_tpu.front.deny_cache import ROW_SERVED
from throttlecrab_tpu.native import deny_available, toolchain_available
from throttlecrab_tpu.server.metrics import Metrics
from throttlecrab_tpu.tpu.limiter import STATUS_DEADLINE, derive_params

pytestmark = pytest.mark.skipif(
    not deny_available() and not toolchain_available(),
    reason="no C++ toolchain for the native deny cache",
)

NS = 1_000_000_000
T0 = 1_800_000_000 * NS
I32_MAX = (1 << 31) - 1

# Parameter combos: two ordinary plans, one sharing its (burst, count,
# period) with the first, one whose emission sits just under 2^61 (so
# quantity 2 crosses the bound), one whose tolerance crosses it, and an
# invalid one.
COMBOS = [
    (3, 60, 60),
    (5, 10, 1),
    (2, 2_305_843_009, 1),
    (2, 1, 2_305_843_009),
    (3, 1, 2_305_843_009),
    (0, 10, 60),
]
QUANTITIES = [0, 1, 1, 1, 2, 3, I32_MAX + 1]

STR_KEYS = ["hot", "warm", "kéy", "\udcff", "cold", "c2", "c3"]
BYTES_KEYS = [b"hot", b"warm", "kéy".encode(), b"\xff", b"cold",
              b"c2", b"c3"]


def enc(key):
    return key.encode("utf-8", "surrogateescape") if isinstance(
        key, str
    ) else bytes(key)


def oracle_state(o):
    records = [(enc(k), tuple(r)) for k, r in o._records.items()]
    entries = [
        (enc(k), (*pq, e.tat, e.emission, e.tolerance, e.increment,
                  e.limit, e.expiry))
        for (k, pq), e in o._entries.items()
    ]
    inflight = {enc(k): v for k, v in o._inflight.items()}
    return records, entries, inflight, len(o), o.hits, o.stale_evictions


def reply(r):
    """Comparable reply: the two modules each define their DenyHit."""
    if hasattr(r, "retry_after_ns"):
        return (r.limit, r.remaining, r.reset_after_ns, r.retry_after_ns)
    return r


def native_state(c):
    return (c.records(), c.entries(), c.inflight(), len(c), c.hits,
            c.stale_evictions)


class Stream:
    """One seeded stream of operations, applied to both caches."""

    def __init__(self, seed, keys):
        self.rng = random.Random(seed)
        self.keys = keys
        self.now = T0
        self.seq = 0
        # Keys holding an in-flight hold, as a serving loop would
        # release them (observe_window / release_window / fail_window).
        self.held = []

    def key(self):
        # Hot keys most of the time, so state accumulates per key.
        r = self.rng.random()
        return self.keys[0] if r < 0.35 else self.rng.choice(self.keys)

    def take_held(self, n):
        """Up to n held keys (released by the caller), else fresh ones."""
        out = [self.held.pop(self.rng.randrange(len(self.held)))
               for _ in range(min(n, len(self.held)))]
        return out or [self.key() for _ in range(n)]

    def cached(self, oracle):
        """A (key, params) pair: mostly one the oracle holds a cached
        denial for, so lookups exercise serving and staleness."""
        if oracle._entries and self.rng.random() < 0.7:
            key, pq = self.rng.choice(list(oracle._entries))
            return key, pq
        return self.key(), self.params()

    def params(self):
        mb, cpp, per = self.rng.choice(COMBOS[:2] * 6 + COMBOS)
        return mb, cpp, per, self.rng.choice(QUANTITIES)

    def tick(self):
        r = self.rng.random()
        if r < 0.8:
            self.now = max(self.now, T0) + self.rng.randrange(0, NS // 4)
        elif r < 0.9:
            self.now += self.rng.randrange(0, 20 * NS)
        elif r < 0.95:
            self.now = -self.rng.randrange(1, NS)  # pre-epoch
        else:
            self.now = T0 + self.rng.randrange(0, 200 * NS)

    def cur_for(self, oracle, key, allowed, mb, cpp, per, q):
        """A cur-plane value: the key's recorded TAT (so a denial can
        certify) or a plausible new TAT, sometimes out of range."""
        rec = oracle._records.get(key)
        r = self.rng.random()
        if rec is not None and not allowed and r < 0.7:
            return rec[0]
        if r < 0.05:
            return -5
        if r < 0.08:
            return 1 << 62
        base = max(self.now, 0)
        return base + self.rng.randrange(0, 10 * NS)

    def planes_for(self, oracle, key, mb, cpp, per, q):
        """reset/retry planes that name the recorded TAT (or not)."""
        rec = oracle._records.get(key)
        em, tol, _ = derive_params([max(mb, 1)], [max(cpp, 1)],
                                   [max(per, 1)])
        em, tol = int(em[0]), int(tol[0])
        tat = rec[0] if rec is not None else self.now + NS
        if self.rng.random() < 0.2:
            tat += 1
        reset = tat + tol - self.now
        retry = tat + em * max(q, 1) - tol - self.now
        return reset, retry

    def step(self, o, c):
        """Apply one random operation to both; returns both replies."""
        rng = self.rng
        op = rng.random()
        if len(self.held) > 24:
            # Keep holds bounded like a serving loop: observe or release.
            op = rng.choice([0.60, 0.75])
        if op < 0.05:
            self.tick()
            return None, None
        if op < 0.20:
            key, (mb, cpp, per, q) = self.cached(o)
            return (o.lookup(key, mb, cpp, per, q, self.now),
                    c.lookup(key, mb, cpp, per, q, self.now))
        if op < 0.35:
            n = rng.choice([1, 3, 8, 40])
            if rng.random() < 0.5:
                key, combo = self.cached(o)
                keys = [key if rng.random() < 0.6 else self.key()
                        for _ in range(n)]
                cols = [list(x) for x in zip(*[combo] * n)]
            else:
                rows = [self.cached(o) for _ in range(n)]
                keys = [k for k, _ in rows]
                cols = [list(x) for x in zip(*[p for _, p in rows])]
            if rng.random() < 0.3:
                cols = [np.asarray(col, np.int64) for col in cols]
            mark = rng.random() < 0.8
            a = o.lookup_window(keys, *cols, self.now, mark)
            b = c.lookup_window(keys, *cols, self.now, mark)
            if mark:
                self.held += [k for k, r in zip(keys, a[0]) if r is None]
            return a, b
        if op < 0.55:
            self.seq += rng.choice([0, 1, 1, 1, -1])
            key = self.key()
            mb, cpp, per, q = self.params()
            allowed = rng.random() < 0.5
            kw = {}
            mode = rng.random()
            if mode < 0.7:
                kw["cur_ns"] = self.cur_for(o, key, allowed, mb, cpp, per, q)
            elif mode < 0.9:
                reset, retry = self.planes_for(o, key, mb, cpp, per, q)
                kw["reset_after_ns"] = reset
                if rng.random() < 0.8:
                    kw["retry_after_ns"] = retry
            for cache in (o, c):
                cache.observe(key, mb, cpp, per, q, self.now, allowed,
                              self.seq, **kw)
            return None, None
        if op < 0.72:
            self.seq += rng.choice([0, 1, 1, -1])
            rows = []
            for key in self.take_held(rng.choice([1, 4, 12])):
                mb, cpp, per, q = self.params()
                allowed = rng.random() < 0.5
                cur = (
                    self.cur_for(o, key, allowed, mb, cpp, per, q)
                    if rng.random() < 0.85 else None
                )
                rows.append((key, mb, cpp, per, q, allowed, cur))
            for cache in (o, c):
                cache.observe_window(list(rows), self.now, self.seq)
            return None, None
        if op < 0.77:
            keys = self.take_held(rng.choice([1, 3, 20]))
            which = rng.choice(["release_window", "release_window",
                                "fail_window"])
            for cache in (o, c):
                getattr(cache, which)(keys)
            return None, None
        if op < 0.83:
            key = self.key()
            which = rng.choice(["begin_inflight", "end_inflight"])
            for cache in (o, c):
                getattr(cache, which)(key)
            return None, None
        if op < 0.87:
            keys = [self.key() for _ in range(rng.choice([1, 2, 4]))]
            return o.prewarm(keys), c.prewarm(keys)
        if op < 0.90:
            t = self.now + rng.randrange(-5 * NS, 30 * NS)
            return o.on_sweep(t), c.on_sweep(t)
        if op < 0.93:
            for cache in (o, c):
                cache.clear()
            return None, None
        if op < 0.96:
            cap = rng.choice([1, 2, 3, 5, 8, 64])
            for cache in (o, c):
                cache.capacity = cap
            return o.capacity, c.capacity
        return o.next_seq(), c.next_seq()


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("kind", ["str", "bytes"])
def test_native_cache_matches_oracle(seed, kind):
    keys = STR_KEYS if kind == "str" else BYTES_KEYS
    stream = Stream(seed * 7919 + (kind == "bytes"), keys)
    o, c = OracleCache(4), DenyCache(4)
    for i in range(1500):
        a, b = stream.step(o, c)
        assert reply(a) == reply(b), f"step {i}: reply {a!r} != {b!r}"
        assert oracle_state(o) == native_state(c), f"step {i}: state"


def test_native_cache_keeps_record_order_under_churn():
    """Cold-tail churn at capacity: FIFO eviction of write records by
    last write and of entries by insertion, exactly as the oracle's."""
    o, c = OracleCache(16), DenyCache(16)
    rng = random.Random(5)
    for w in range(60):
        rows = []
        for _ in range(50):
            key = f"k{rng.randrange(200)}"
            allowed = rng.random() < 0.6
            rec = o._records.get(key)
            cur = rec[0] if rec is not None and not allowed else (
                T0 + rng.randrange(1, 3) * NS
            )
            rows.append((key, 3, 60, 60, rng.choice([1, 2]), allowed, cur))
        for cache in (o, c):
            cache.observe_window(list(rows), T0 + w, w)
        assert oracle_state(o) == native_state(c)
    assert len(c.records()) == 16


def test_unencodable_str_key_never_hits():
    """A str key with a lone surrogate outside surrogateescape's range
    has no byte identity: it is never recorded and never served."""
    c = DenyCache(8)
    key = "\ud800"
    c.observe(key, 3, 60, 60, 1, T0, True, seq=1, cur_ns=T0 + 2 * NS)
    c.observe(key, 3, 60, 60, 3, T0 + 1, False, seq=2, cur_ns=T0 + 2 * NS)
    assert c.record(key) is None and len(c) == 0
    assert c.lookup(key, 3, 60, 60, 3, T0 + 2) is None
    rows, n = c.lookup_window([key, "ok"], [3, 3], [60, 60], [60, 60],
                              [3, 3], T0 + 2)
    assert rows == [None, None] and n == 0
    assert c.inflight() == {b"ok": 1}


# --------------------------------------------------------------------- #
# The native driver's frame path.
# --------------------------------------------------------------------- #


class _Insight:
    def __init__(self):
        self.calls = []

    def record_front_denied(self, keys, counts=None):
        self.calls.append((list(keys), counts))


def _transport(front):
    from throttlecrab_tpu.native import wire_available
    from throttlecrab_tpu.server.native_redis import NativeRedisTransport

    if not wire_available():
        pytest.skip("no native wire server")
    return NativeRedisTransport(
        "127.0.0.1", 0, object(), None, batch_size=64, front=front,
    )


def _batch(rows):
    """A captured frame: rows of (key bytes, burst, count, period, q,
    budget)."""
    keys = [r[0] for r in rows]
    offsets = np.zeros(len(rows) + 1, np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    params = np.array([r[1:5] for r in rows], np.int64).reshape(-1, 4)
    budgets = np.array([r[5] for r in rows], np.int64)
    n = len(rows)
    return (b"".join(keys), offsets, params, np.zeros(n, np.uint64),
            np.zeros(n, np.int32), budgets)


def _prime(cache, key):
    """Certify a denial of `key` under (3, 60, 60) at quantity 3."""
    tat = T0 + 2 * NS
    cache.observe(key, 3, 60, 60, 1, T0, True, seq=1, cur_ns=tat)
    cache.observe(key, 3, 60, 60, 3, T0 + 1, False, seq=2, cur_ns=tat)


@pytest.mark.parametrize("bytes_keys", [True, False])
def test_front_filter_frame_path(bytes_keys):
    metrics = Metrics()
    admission = AdmissionController(max_pending=10, peek_frac=0.5)
    front = FrontTier(DenyCache(64), admission, metrics=metrics,
                      bytes_keys=bytes_keys)
    insight = _Insight()
    front.insight = insight
    hot = "höt" if not bytes_keys else "höt".encode()
    _prime(front.deny_cache, hot)
    hot_b = enc(hot)
    now = T0 + 2
    expect = front.deny_cache.lookup(hot, 3, 60, 60, 3, now)
    hits_before = front.deny_cache.hits
    t = _transport(front)
    batch = _batch([
        (hot_b, 3, 60, 60, 3, 0),        # 0 served
        (b"cold", 3, 60, 60, 1, 0),      # 1 miss
        (b"cold", 3, 60, 60, 1, 5),      # 2 miss again (same key)
        (hot_b, 3, 60, 60, 3, -1),       # 3 expired (a hit, not served)
        (b"gone", 3, 60, 60, 1, -1),     # 4 expired miss
        (b"peek", 3, 60, 60, 0, 0),      # 5 peek at depth 6 >= 5: shed
        (hot_b, 3, 60, 60, 3, 0),        # 6 served
        (b"cold", 5, 10, 1, 1, 0),       # 7 miss, other params
    ])
    plan = t._front_filter(batch, now, depth=6)
    st = plan["status_pre"].tolist()
    assert st == [ROW_SERVED, 0, 0, STATUS_DEADLINE, STATUS_DEADLINE,
                  STATUS_OVERLOADED, ROW_SERVED, 0]
    hit_row = [0, expect.limit, expect.remaining, expect.reset_after_s,
               expect.retry_after_s]
    assert plan["hit_vals"][0].tolist() == hit_row
    assert plan["hit_vals"][6].tolist() == hit_row
    assert plan["miss_idx"].tolist() == [1, 2, 7]
    blob, offsets, params = plan["miss_frame"]
    assert blob == b"coldcoldcold"
    assert offsets.tolist() == [0, 4, 8, 12]
    assert params.tolist() == [[3, 60, 60, 1], [3, 60, 60, 1],
                               [5, 10, 1, 1]]
    # Served rows (the expired one included) count as hits, once a row.
    assert front.deny_cache.hits == hits_before + 3
    assert metrics.front_deny_hits == 3
    assert (admission.shed_peek, admission.shed_consume) == (1, 0)
    assert (metrics.front_shed_peek, metrics.front_shed_consume) == (1, 0)
    assert insight.calls == [([hot], [3])]
    # Only the three miss rows hold their key, until observed.
    assert front.deny_cache.inflight() == {b"cold": 3}
    res = SimpleNamespace(
        status=np.array([0, 0, 3], np.uint8),
        allowed=np.array([1, 0, 0], np.uint8),
        cur_ns=np.array([T0 + 5 * NS, T0 + 5 * NS, 0], np.int64),
    )
    t._observe_plan(plan, res, now, seq=7)
    assert front.deny_cache.inflight() == {}
    assert front.deny_cache.record(b"cold")[::2] == (T0 + 5 * NS, 7)
    # The denial at the recorded TAT certified an entry for cold.
    assert front.deny_cache.lookup(b"cold", 3, 60, 60, 1, now) is not None

    # Over the depth bound every consuming miss sheds too; the failure
    # path drops what the keys had certified along with their holds.
    batch = _batch([
        (b"cold", 3, 60, 60, 2, 0),      # consuming at depth 10: shed
        (b"peek", 3, 60, 60, 0, 0),      # peek: shed
    ])
    plan = t._front_filter(batch, now, depth=10)
    assert plan["status_pre"].tolist() == [STATUS_OVERLOADED] * 2
    assert plan["miss_frame"] is None and plan["miss_idx"].tolist() == []
    assert (admission.shed_peek, admission.shed_consume) == (2, 1)
    assert (metrics.front_shed_peek, metrics.front_shed_consume) == (2, 1)
    assert front.deny_cache.inflight() == {}
    plan = t._front_filter(_batch([(b"cold", 3, 60, 60, 2, 0)]), now, 0)
    assert front.deny_cache.inflight() == {b"cold": 1}
    t._observe_plan(plan, None, now, seq=8)
    assert front.deny_cache.inflight() == {}
    assert front.deny_cache.record(b"cold") is None
    assert front.deny_cache.lookup(b"cold", 3, 60, 60, 1, now) is None


def test_deadline_plan_without_front_tier():
    t = _transport(None)
    batch = _batch([
        (b"a", 3, 60, 60, 1, 0),
        (b"bb", 3, 60, 60, 1, -1),
        (b"ccc", 3, 60, 60, 0, 0),
    ])
    plan = t._deadline_plan(batch)
    assert plan["status_pre"].tolist() == [0, STATUS_DEADLINE, 0]
    blob, offsets, params = plan["miss_frame"]
    assert (blob, offsets.tolist()) == (b"accc", [0, 1, 4])
    assert params.tolist() == [[3, 60, 60, 1], [3, 60, 60, 0]]
    assert plan["miss_idx"].tolist() == [0, 2]


# --------------------------------------------------------------------- #
# The frame path at scale, and the handles it carries.
# --------------------------------------------------------------------- #

HOT = [b"hot%03d" % j for j in range(40)]


def _scale_key(i):
    """Distinct key i: mostly 10 bytes, and now and then the empty key,
    a non-UTF-8 one, one of 23-25 bytes (either side of the length kept
    inside a key's state) or a long one of up to 1,000 bytes."""
    if i == 0:
        return b""
    r = i % 53
    if r == 1:
        return b"\xff\xfe%d" % i
    if r == 2:
        return (b"%d:" % i).ljust(23 + i % 3, b"x")
    if r == 3:
        return (b"L%d:" % i).ljust(26 + (i * 7919) % 975, b"z")
    return b"k%09d" % i


def _frame_of(keys, params):
    offsets = np.zeros(len(keys) + 1, np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    return b"".join(keys), offsets, np.asarray(params, np.int64)


def _filter_both(front, o, keys, params, budgets, now):
    """One frame through FrontTier.filter_frame and, row for row, the
    oracle's window lookup plus the release of rows that answer without
    the device; the served rows must agree."""
    blob, offsets, p = _frame_of(keys, params)
    f = front.filter_frame(blob, offsets, p, np.asarray(budgets, np.int64),
                           now, 0, STATUS_DEADLINE, STATUS_OVERLOADED)
    rows, n_hits = o.lookup_window(keys, *p.T.tolist(), now, True)
    o.release_window([
        k for k, r, b in zip(keys, rows, budgets) if r is None and b < 0
    ])
    assert f.n_hits == n_hits
    for i, r in enumerate(rows):
        if budgets[i] < 0:
            assert f.status[i] == STATUS_DEADLINE
        elif r is None:
            assert f.status[i] == 0
        else:
            assert f.status[i] == ROW_SERVED
            assert f.vals[i].tolist() == [0, r[0], r[1], r[2] // NS,
                                          r[3] // NS]
    assert f.miss_idx.tolist() == [
        i for i, (r, b) in enumerate(zip(rows, budgets))
        if r is None and b >= 0
    ]
    return f


@pytest.mark.parametrize("seed", range(4))
def test_frame_path_matches_oracle_at_scale(seed):
    """Frames of 3,000 rows over more than 50,000 distinct keys at
    capacity 1,024, through filter_frame and observe_frame / fail_frame
    with the filter's handles: the key index grows, deletions shift long
    probe chains and pooled states recycle, and after every frame the
    records, entries, holds and counters equal the oracle's."""
    rng = random.Random(seed)
    o = OracleCache(1024)
    front = FrontTier(DenyCache(1024), None, bytes_keys=True)
    c = front.deny_cache
    now = T0
    next_id = 0
    recent = []
    seen = set()
    for frame in range(30):
        now += rng.randrange(NS // 8, NS // 2)
        keys, params, budgets = [], [], []
        for _ in range(3000):
            u = rng.random()
            if u < 0.25:
                key = rng.choice(HOT)
                p = (3, 60, 60, rng.choice([1, 1, 1, 2]))
            else:
                if u < 0.35 and recent:
                    key = rng.choice(recent)
                else:
                    key = _scale_key(next_id)
                    next_id += 1
                    recent.append(key)
                p = rng.choice([(3, 60, 60, 1), (3, 60, 60, 1),
                                (5, 10, 1, 1), (5, 10, 1, 0),
                                (0, 10, 60, 1)])
            keys.append(key)
            params.append(p)
            budgets.append(-1 if rng.random() < 0.03 else 0)
        recent = recent[-5000:]
        seen.update(keys)
        f = _filter_both(front, o, keys, params, budgets, now)
        miss = f.miss_idx.tolist()
        seq = front.next_seq()
        assert o.next_seq() == seq
        if frame % 9 == 4:
            # The launch failed after its writes may have committed.
            front.observe_frame(f.miss_frame, None, now, seq, f.miss_ks)
            o.fail_window([keys[i] for i in miss])
        elif miss:
            with_cur = rng.random() < 0.8
            status, allowed, cur, rows = [], [], [], []
            for i in miss:
                key, (mb, cpp, per, q) = keys[i], params[i]
                ok = rng.random() >= 0.02
                a = rng.random() < (0.15 if key in HOT else 0.8)
                rec = o._records.get(key)
                t = (rec[0] if rec is not None and not a
                     else now + rng.randrange(0, 3 * NS))
                status.append(0 if ok else 3)
                allowed.append(int(a))
                cur.append(t)
                rows.append((key, mb, cpp, per, q, ok and a,
                             t if ok and with_cur else None))
            res = SimpleNamespace(
                status=np.array(status, np.uint8),
                allowed=np.array(allowed, np.uint8),
                cur_ns=np.array(cur, np.int64) if with_cur else None,
            )
            front.observe_frame(f.miss_frame, res, now, seq, f.miss_ks)
            o.observe_window(rows, now, seq)
        if frame % 8 == 7:
            t = now - rng.randrange(0, 2 * NS)
            assert o.on_sweep(t) == c.on_sweep(t)
        assert oracle_state(o) == native_state(c), f"frame {frame}"
    assert len(seen) >= 50_000
    assert c.hits > 0 and 0 < len(c.records()) <= 1024
    assert c.handle_fallbacks == 0


@pytest.mark.parametrize("how", ["end_inflight", "release_window",
                                 "clear", "clear_release"])
@pytest.mark.parametrize("fail", [False, True])
def test_stale_handles_fall_back_to_a_lookup(how, fail):
    """A hold released out of band between filter_frame and
    observe_frame / fail_frame frees its key, whose pooled state may
    then hold another key: observing with the stale handles must leave
    the oracle's state, and the fallback counter counts those rows.
    clear() alone keeps held keys, so their handles stay good."""
    o = OracleCache(64)
    front = FrontTier(DenyCache(64), None, bytes_keys=True)
    c = front.deny_cache
    long_key = b"L" * 300
    for cache in (o, c):
        # A recorded key and a cached denial: both outlive a release.
        cache.observe(b"rec", 3, 60, 60, 1, T0, True, seq=1,
                      cur_ns=T0 + 2 * NS)
        _prime(cache, b"den")
    keys = [b"rec", b"free1", long_key, b"stay", b"den", b"free1"]
    params = [(3, 60, 60, 1)] * 3 + [(3, 60, 60, 1), (3, 60, 60, 2),
                                     (5, 10, 1, 1)]
    now = T0 + 2
    f = _filter_both(front, o, keys, params, [0] * 6, now)
    assert f.miss_idx.tolist() == [0, 1, 2, 3, 4, 5]
    released = [b"free1", long_key, b"free1"]
    for cache in (o, c):
        if how == "end_inflight":
            for k in released:
                cache.end_inflight(k)
        elif how == "release_window":
            cache.release_window(released)
        else:
            cache.clear()
            if how == "clear_release":
                cache.release_window(released)
        # A new key may take a freed state's place.
        cache.begin_inflight(b"newcomer")
    seq = front.next_seq()
    o.next_seq()
    if fail:
        front.observe_frame(f.miss_frame, None, now, seq, f.miss_ks)
        o.fail_window(keys)
    else:
        cur = [T0 + 3 * NS] * 4 + [T0 + 2 * NS, T0 + 4 * NS]
        allowed = [1, 1, 0, 1, 0, 1]
        res = SimpleNamespace(status=np.zeros(6, np.uint8),
                              allowed=np.array(allowed, np.uint8),
                              cur_ns=np.array(cur, np.int64))
        front.observe_frame(f.miss_frame, res, now, seq, f.miss_ks)
        o.observe_window([
            (k, *p, bool(a), t)
            for k, p, a, t in zip(keys, params, allowed, cur)
        ], now, seq)
    assert oracle_state(o) == native_state(c)
    assert c.inflight() == {b"newcomer": 1}
    # Both free1 rows and the long key's row lost their key's state;
    # clear() alone released nothing.
    assert c.handle_fallbacks == (0 if how == "clear" else 3)


def test_frame_handles_skip_a_second_lookup():
    """The ordinary frame path: handles from filter_frame name every
    miss row's key, so observe_frame and fail_frame never fall back, and
    a frame filtered without a cache carries none."""
    front = FrontTier(DenyCache(8), None, bytes_keys=True)
    keys = [b"a", b"", b"\xff" * 40, b"a"]
    blob, offsets, p = _frame_of(keys, [(3, 60, 60, 1)] * 4)
    budgets = np.zeros(4, np.int64)
    f = front.filter_frame(blob, offsets, p, budgets, T0, 0,
                           STATUS_DEADLINE, STATUS_OVERLOADED)
    assert f.miss_ks.tolist()[0] == f.miss_ks.tolist()[3]
    assert len(set(f.miss_ks.tolist())) == 3
    with pytest.raises(ValueError):
        front.observe_frame(f.miss_frame, None, T0, 1, f.miss_ks[:3])
    assert front.deny_cache.inflight() == {b"a": 2, b"": 1,
                                           b"\xff" * 40: 1}
    front.observe_frame(f.miss_frame, None, T0, front.next_seq(),
                        f.miss_ks)
    assert front.deny_cache.inflight() == {}
    assert front.deny_cache.handle_fallbacks == 0
    assert front.stats()["handle_fallbacks"] == 0
    g = filter_frame(None, blob, offsets, p, budgets, T0,
                     STATUS_DEADLINE, STATUS_OVERLOADED)
    assert g.miss_ks is None and g.miss_idx.tolist() == [0, 1, 2, 3]
