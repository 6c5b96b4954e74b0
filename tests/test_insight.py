"""Insight tier (L3.75): device analytics vs a host recount, sketch
bounds, the feedback loop, and truthful /stats across degrade→recover.

The acceptance contract (ISSUE 5):

  * with insight OFF the decision path is bit-identical to a limiter
    built without the subsystem (differential, every output tier);
  * with it ON, the device aggregates — running [allowed, denied]
    totals and the per-slot denied-hit counter column — match a host
    scalar recount of the very same results EXACTLY, under the
    tier-fuzz key patterns (hot-key abuse, flash crowd, chaos mix);
  * the space-saving sketch honors its documented error bound
    (estimate - error <= true <= estimate) and is exact below capacity;
  * /stats stays truthful across a chaos degrade→recover cycle: the
    host-oracle path keeps accounting while the device is down, and
    nothing is lost or double-counted over the whole lifecycle;
  * the feedback loop: confirmed hot-denied keys are refreshed against
    deny-cache eviction, and hot-set concentration tightens admission's
    peek shedding (weight 0 = exact old behavior).
"""

import json

import numpy as np
import pytest

from throttlecrab_tpu import faults
from throttlecrab_tpu.front import AdmissionController, DenyCache, FrontTier
from throttlecrab_tpu.harness.workload import flash_crowd_hot_sets, make_keys
from throttlecrab_tpu.insight import InsightTier, SpaceSavingSketch
from throttlecrab_tpu.insight.collector import RateWindow, SlotKeyResolver
from throttlecrab_tpu.server.supervisor import (
    STATE_DEGRADED,
    STATE_OK,
    SupervisedLimiter,
)
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

NS = 1_000_000_000
T0 = 1_700_000_000 * NS


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


def _recount(keys, results):
    """Host oracle recount: per-key denied counts + totals from the
    decided result planes themselves."""
    allowed = denied = 0
    per_key: dict = {}
    for ks, res in zip(keys, results):
        ok = res.status == 0
        for k, a, o in zip(ks, res.allowed, ok):
            if not o:
                continue
            if a:
                allowed += 1
            else:
                denied += 1
                per_key[k] = per_key.get(k, 0) + 1
    return allowed, denied, per_key


def _slot_counts(lim):
    """Fetch the whole denied-hit column as {key: count}."""
    tk = lim.table.insight_topk(lim.table.capacity)
    vals = np.asarray(tk[0]).tolist()
    ids = np.asarray(tk[1]).tolist()
    rev = lim.keymap._rev
    return {rev[s]: v for v, s in zip(vals, ids) if v > 0}


# --------------------------------------------------------------------- #
# Differential: device aggregates vs host recount, decisions unchanged.


@pytest.mark.parametrize(
    "pattern", ["hotkey-abuse", "flash-crowd", "chaos", "zipfian"]
)
def test_device_aggregates_match_host_recount(pattern):
    lim = TpuRateLimiter(capacity=1 << 12, keymap="python", insight=True)
    twin = TpuRateLimiter(capacity=1 << 12, keymap="python")
    stream = make_keys(pattern, 1024, 2000, seed=3)
    batches, results = [], []
    for i in range(8):
        ks = stream[i * 128 : (i + 1) * 128]
        now = T0 + i * NS // 10
        wire = i % 2 == 0
        res = lim.rate_limit_batch(
            ks, 3, 10, 60, 1, now, wire=wire, collect_cur=wire
        )
        ref = twin.rate_limit_batch(
            ks, 3, 10, 60, 1, now, wire=wire, collect_cur=wire
        )
        assert (res.allowed == ref.allowed).all()
        assert (res.remaining == ref.remaining).all()
        batches.append(ks)
        results.append(res)
    allowed, denied, per_key = _recount(batches, results)
    assert lim.table.insight_counts() == (allowed, denied)
    assert _slot_counts(lim) == per_key


def test_aggregates_exact_on_scan_and_degenerate_paths():
    lim = TpuRateLimiter(capacity=1 << 10, keymap="python", insight=True)
    batches, results = [], []
    # Scan path (rate_limit_many), duplicate keys within batches.
    wins = [
        ([f"d{i % 7}" for i in range(64)], 2, 6, 60, 1, T0),
        ([f"d{i % 3}" for i in range(64)], 2, 6, 60, 1, T0 + NS),
    ]
    for (ks, *_), res in zip(wins, lim.rate_limit_many(wins, wire=True)):
        batches.append(ks)
        results.append(res)
    # Degenerate path: burst-1 (tolerance 0) and quantity-0 probes.
    ks = [f"d{i % 5}" for i in range(32)]
    results.append(
        lim.rate_limit_batch(ks, 1, 10, 60, 1, T0 + 2 * NS)
    )
    batches.append(ks)
    results.append(
        lim.rate_limit_batch(ks, 2, 6, 60, 0, T0 + 3 * NS)
    )
    batches.append(ks)
    # Invalid rows must count nowhere.
    ks_bad = ["x", "y"]
    results.append(
        lim.rate_limit_batch(ks_bad, 0, 0, 0, 1, T0 + 4 * NS)
    )
    batches.append(ks_bad)
    allowed, denied, per_key = _recount(batches, results)
    assert lim.table.insight_counts() == (allowed, denied)
    assert _slot_counts(lim) == per_key


def test_kill_switch_decisions_bit_identical_and_state_layout():
    on = TpuRateLimiter(capacity=1 << 8, keymap="python", insight=True)
    off = TpuRateLimiter(capacity=1 << 8, keymap="python")
    assert off.table.state.shape[-1] == 4  # pre-insight layout intact
    assert on.table.state.shape[-1] > 4
    stream = make_keys("hotkey-abuse", 512, 500, seed=9)
    for i in range(4):
        ks = stream[i * 128 : (i + 1) * 128]
        a = on.rate_limit_batch(ks, 3, 10, 60, 1, T0 + i, wire=True)
        b = off.rate_limit_batch(ks, 3, 10, 60, 1, T0 + i, wire=True)
        for f in ("allowed", "remaining", "reset_after_s", "retry_after_s",
                  "status"):
            assert (getattr(a, f) == getattr(b, f)).all(), f
    # And the stored GCRA state is bit-identical column for column.
    cap = off.table.capacity
    np.testing.assert_array_equal(
        np.asarray(on.table.state)[:cap, :4],
        np.asarray(off.table.state)[:cap],
    )


def test_sweep_clears_heat_and_decay_halves():
    lim = TpuRateLimiter(capacity=1 << 8, keymap="python", insight=True)
    ks = ["a"] * 10
    # burst 2, 1/100s: the 10-deep segment allows 2 and denies 8.
    lim.rate_limit_batch(ks, 2, 1, 100, 1, T0)
    assert _slot_counts(lim) == {"a": 8}
    lim.table.insight_decay()
    assert _slot_counts(lim) == {"a": 4}
    lim.sweep(T0 + 10**15)  # everything expires; heat dies with slots
    assert _slot_counts(lim) == {}
    al, de = lim.table.insight_counts()
    assert (al, de) == (2, 8)  # totals are lifetime, not per-slot


# --------------------------------------------------------------------- #
# Space-saving sketch bounds.


def test_sketch_exact_below_capacity():
    s = SpaceSavingSketch(8)
    truth = {}
    for i, n in enumerate([5, 3, 8, 1]):
        for _ in range(n):
            s.record(f"k{i}")
        truth[f"k{i}"] = n
    assert dict(s.top(10)) == truth
    assert s.error_bound == 0
    assert all(e == 0 for _, _, e in s.top_with_error(10))


def test_sketch_error_bounds_hold_under_pressure():
    rng = np.random.default_rng(4)
    s = SpaceSavingSketch(16)
    truth: dict = {}
    # Zipf-ish stream over 10x the capacity.
    keys = rng.zipf(1.3, 5000) % 160
    for k in keys:
        s.record(int(k))
        truth[int(k)] = truth.get(int(k), 0) + 1
    for key, est, err in s.top_with_error(16):
        assert est >= truth.get(key, 0)          # never undercounts
        assert est - err <= truth.get(key, 0)    # documented bound
    # The heaviest true key survives compaction.
    heavy = max(truth, key=truth.get)
    assert heavy in dict(s.top(16))
    assert len(s) <= 16 * 3


def test_sketch_merge_partials_via_record_counts():
    s = SpaceSavingSketch(8)
    s.record("a", 10)
    s.record("b", 3)
    s.record("a", 5)
    assert dict(s.top(2)) == {"a": 15, "b": 3}


# --------------------------------------------------------------------- #
# Collector pieces.


def test_rate_window_rates_and_clock_regression():
    w = RateWindow(10.0)
    w.sample(T0, 0, 0)
    w.sample(T0 + 5 * NS, 50, 100)
    assert w.rates() == (10.0, 20.0)
    # Old samples roll out of the window.
    w.sample(T0 + 20 * NS, 50, 100)
    a, d = w.rates()
    assert a < 10.0
    # Regression restarts cleanly instead of emitting garbage.
    w.sample(T0, 60, 110)
    assert w.rates() == (0.0, 0.0)


def test_slot_key_resolver_python_and_items_backends():
    lim = TpuRateLimiter(capacity=64, keymap="python")
    lim.rate_limit_batch(["x", "y"], 2, 5, 60, 1, T0)
    r = SlotKeyResolver(lim.keymap)
    slot_x = lim.keymap._map["x"]
    assert r.keys_for([slot_x, 9999]) == ["x", None]

    class ItemsOnly:
        mutations = 0

        def items(self):
            return [(b"k", 3)]

    r2 = SlotKeyResolver(ItemsOnly())
    assert r2.keys_for([3, 4]) == [b"k", None]


@pytest.fixture
def native_items_forbidden(monkeypatch):
    """NativeKeyMap with items() made to raise: the slot→key path must
    never export the whole map."""
    from throttlecrab_tpu.native import NativeKeyMap, native_available

    if not native_available():
        pytest.skip("native keymap unavailable")

    def _export(self):
        raise AssertionError("NativeKeyMap.items() on the serving path")

    monkeypatch.setattr(NativeKeyMap, "items", _export)
    return NativeKeyMap


def test_slot_key_resolver_native_slot_lookup(native_items_forbidden):
    """Over the C++ keymap the resolver reads the slot column: a key
    inserted after a resolve is named at once (no stamp to wait for),
    a freed slot reads None, and a recycled slot names its new key."""
    km = native_items_forbidden(64)
    r = SlotKeyResolver(km)
    ones = np.ones(2, bool)
    (sa, sb), _, _, _ = km.resolve([b"a", b"b"], ones)
    assert r.keys_for([sa, sb, 9999, -1]) == [b"a", b"b", None, None]
    (sc,), _, _, _ = km.resolve([b"c"], ones[:1])
    assert r.keys_for([sc, sa]) == [b"c", b"a"]
    assert km.free_slots(np.array([sa], np.int32)) == 1
    assert r.keys_for([sa, sb]) == [None, b"b"]
    (sd,), _, _, _ = km.resolve([b"d"], ones[:1])
    assert sd == sa
    assert r.keys_for([sa, sb, sc]) == [b"d", b"b", b"c"]


def test_insight_poll_names_hot_key_without_export(native_items_forbidden):
    lim = TpuRateLimiter(capacity=1 << 10, keymap="native", insight=True)
    assert isinstance(lim.keymap, native_items_forbidden)
    ins = InsightTier(limiter=lim, poll_ms=1000, window_s=10.0, decay_s=0.0)
    lim.rate_limit_batch(["h"] * 50, 2, 5, 60, 1, T0, wire=True)
    assert ins.maybe_poll(T0)
    s = ins.stats(state="ok")
    assert ins.poll_failures == 0
    assert s["top_denied"][0]["key"] == "h"


# --------------------------------------------------------------------- #
# InsightTier: polling, /stats shape, feedback loop.


def _make_tier(front=None, **kw):
    lim = TpuRateLimiter(capacity=1 << 10, keymap="python", insight=True)
    defaults = dict(poll_ms=1000, window_s=10.0, decay_s=0.0)
    defaults.update(kw)
    return lim, InsightTier(limiter=lim, front=front, **defaults)


def test_poll_is_throttled_and_stats_truthful():
    lim, ins = _make_tier()
    ks = ["h"] * 50
    lim.rate_limit_batch(ks, 2, 5, 60, 1, T0, wire=True)
    assert ins.maybe_poll(T0)
    assert not ins.maybe_poll(T0 + ins.poll_ns - 1)  # throttled
    lim.rate_limit_batch(ks, 2, 5, 60, 1, T0 + NS, wire=True)
    assert ins.maybe_poll(T0 + 2 * NS)
    s = ins.stats(state="ok")
    assert s["totals"]["allowed"] + s["totals"]["denied"] == 100
    assert s["top_denied"][0]["key"] == "h"
    assert s["engine_state"] == "ok"
    assert json.loads(ins.stats_json(state="ok")) == s


def test_prewarm_refreshes_hot_keys_against_eviction():
    cache = DenyCache(capacity=4)
    front = FrontTier(cache, None)
    seq = cache.next_seq()
    # Certify a denial for the hot key.
    cache.observe("hot", 2, 5, 60, 1, T0, True, seq, cur_ns=T0 + 10 * NS)
    cache.observe("hot", 2, 5, 60, 1, T0, False, seq, cur_ns=T0 + 10 * NS)
    assert len(cache) == 1
    assert front.prewarm(["hot", "absent"]) == 1
    # Fill past capacity with other certified denials: without the
    # refresh "hot" (the oldest insert) would be evicted first.
    for i in range(4):
        k = f"cold{i}"
        cache.observe(k, 2, 5, 60, 1, T0, True, seq, cur_ns=T0 + 10 * NS)
        front.prewarm(["hot"])
        cache.observe(k, 2, 5, 60, 1, T0, False, seq, cur_ns=T0 + 10 * NS)
    assert cache.lookup("hot", 2, 5, 60, 1, T0 + NS) is not None


def test_hot_concentration_tightens_peek_shedding_only():
    adm = AdmissionController(max_pending=100, peek_frac=0.9)
    # Weight 0 (the kill-switch state): behavior is exactly the old one.
    adm.set_hot_concentration(1.0)
    assert adm.admit(89, peek=True)
    adm.hot_shed_weight = 0.5
    assert not adm.admit(89, peek=True)   # 0.9 * (1 - .5) = 0.45 bound
    assert adm.admit(99, peek=False)      # consume bound untouched
    assert not adm.admit(100, peek=False)


def test_topk_dropout_and_reentry_not_double_counted():
    # topk=1: a slot that leaves the top-K and later re-enters must
    # diff against its carried last-seen count, not restart from zero.
    lim = TpuRateLimiter(capacity=1 << 8, keymap="python", insight=True)
    ins = InsightTier(limiter=lim, poll_ms=1, topk=1)

    def deny(key, n, t):
        # burst 2 over 100 s: everything past the first 2 is denied.
        lim.rate_limit_batch([key] * n, 2, 1, 100, 1, T0 + t, wire=True)

    deny("a", 12, 0)              # a: 10 denied
    ins.poll(T0 + NS)             # top-1 = a(10)
    deny("b", 15, 2 * NS)         # b: 13 denied > a
    ins.poll(T0 + 3 * NS)         # top-1 = b(13); a drops out
    deny("a", 10, 4 * NS)         # a: 20 denied, re-enters top-1
    ins.poll(T0 + 5 * NS)
    counts = dict(ins.sketch.top(4))
    assert counts["a"] == 20      # not 30 (10 + full 20 re-record)
    assert counts["b"] == 13


def test_cache_served_denials_count_into_stats_totals():
    cache = DenyCache(capacity=64)
    front = FrontTier(cache, None)
    lim = TpuRateLimiter(capacity=1 << 8, keymap="python", insight=True)
    ins = InsightTier(limiter=lim, front=front, poll_ms=1000)
    assert front.insight is ins
    seq = cache.next_seq()
    cache.observe("hot", 2, 5, 60, 1, T0, True, seq, cur_ns=T0 + 10 * NS)
    cache.observe("hot", 2, 5, 60, 1, T0, False, seq, cur_ns=T0 + 10 * NS)
    # Scalar and bulk lookup paths both report their hits.
    assert front.lookup("hot", 2, 5, 60, 1, T0 + NS) is not None
    rows, n_hits = front.lookup_window(
        ["hot", "cold"], [2, 2], [5, 5], [60, 60], [1, 1], T0 + NS,
        mark_inflight=False,
    )
    assert n_hits == 1
    s = ins.stats()
    assert s["front_path"]["denied"] == 2
    assert s["totals"]["denied"] == 2
    assert dict((d["key"], d["count"]) for d in s["top_denied"]) == {
        "hot": 2
    }


def test_insight_feedback_sets_concentration_on_admission():
    front = FrontTier(DenyCache(64), AdmissionController(max_pending=100))
    lim = TpuRateLimiter(capacity=1 << 10, keymap="python", insight=True)
    ins = InsightTier(
        limiter=lim, front=front, poll_ms=1000, hot_denies=5,
        shed_weight=0.7, prewarm=8,
    )
    assert front.admission.hot_shed_weight == 0.7
    ks = ["hot0", "hot1"] * 32
    for t in range(4):
        lim.rate_limit_batch(ks, 2, 5, 60, 1, T0 + t * NS, wire=True)
        ins.maybe_poll(T0 + t * NS)
    assert front.admission.hot_concentration > 0.5
    assert ins.stats()["hot"]["concentration"] > 0.5


# --------------------------------------------------------------------- #
# Chaos: truthful accounting across a degrade→recover cycle.


def test_stats_truthful_across_degrade_recover_cycle():
    lim = TpuRateLimiter(capacity=1 << 10, keymap="python", insight=True)
    sup = SupervisedLimiter(
        lim, retries=1, backoff_us=0, probe_interval_ms=1,
        sleep_fn=lambda s: None,
    )
    ins = InsightTier(limiter=sup, poll_ms=1000)
    sup.insight = ins
    ks = ["c0", "c1"] * 16
    total = 0
    now = T0

    def decide(n_batches):
        nonlocal now, total
        for _ in range(n_batches):
            res = sup.rate_limit_batch(ks, 2, 5, 60, 1, now, wire=True)
            assert (res.status == 0).all()
            total += len(ks)
            now += NS
            ins.maybe_poll(now)

    decide(3)
    assert sup.state == STATE_OK
    # Device dies persistently: retries exhaust, host oracle takes over.
    faults.arm(faults.FaultInjector(
        faults.parse_spec("launch:persistent"), seed=1,
    ))
    decide(3)
    assert sup.state == STATE_DEGRADED
    # While degraded the host path keeps /stats truthful.
    s = ins.stats()
    assert s["totals"]["allowed"] + s["totals"]["denied"] == total
    assert s["host_path"]["allowed"] + s["host_path"]["denied"] > 0
    # Device heals; the next probe re-promotes.
    faults.disarm()
    decide(3)
    assert sup.state == STATE_OK
    s = ins.stats()
    # Nothing lost, nothing double-counted over the whole cycle.  The
    # one extra allowed row is the supervisor's successful recovery
    # probe — a real quantity-0 decision on the device, counted like
    # any other (the failed probes while faults were armed raised
    # before any device commit and count nowhere).
    assert s["totals"]["allowed"] + s["totals"]["denied"] == total + 1
    assert s["top_denied"][0]["key"] in ("c0", "c1")


def test_poll_survives_dead_device_mid_outage():
    lim, ins = _make_tier()
    lim.rate_limit_batch(["k"] * 8, 2, 5, 60, 1, T0, wire=True)
    ins.maybe_poll(T0)

    class Boom:
        def insight_counts(self):
            raise ConnectionError("UNAVAILABLE: device gone")

    real_table = ins.limiter.table
    ins.limiter.table = Boom()
    try:
        assert ins.maybe_poll(T0 + 2 * NS)  # no raise
        assert ins.poll_failures == 1
    finally:
        ins.limiter.table = real_table
    # Stats still answer from the last good data + host counters.
    assert ins.stats()["totals"]["allowed"] >= 1


# --------------------------------------------------------------------- #
# Server surfaces: /stats over HTTP, metrics export, config, factory.


def test_http_stats_route_shapes():
    import asyncio

    from throttlecrab_tpu.server.engine import BatchingEngine
    from throttlecrab_tpu.server.http import HttpTransport
    from throttlecrab_tpu.server.metrics import Metrics

    lim, ins = _make_tier()
    lim.rate_limit_batch(["s"] * 20, 2, 5, 60, 1, T0, wire=True)
    ins.maybe_poll(T0)

    async def run():
        engine = BatchingEngine(lim, insight=ins, now_fn=lambda: T0)
        t = HttpTransport("127.0.0.1", 0, engine, Metrics())
        status, payload, ctype = await t._route("GET", "/stats", b"")
        assert status == 200 and ctype == "application/json"
        doc = json.loads(payload)
        assert doc["insight"]["enabled"] is True
        assert doc["engine_state"] == "ok"
        # Disabled tier still answers with a stable shape.
        engine2 = BatchingEngine(lim, now_fn=lambda: T0)
        t2 = HttpTransport("127.0.0.1", 0, engine2, Metrics())
        _, payload2, _ = await t2._route("GET", "/stats", b"")
        assert json.loads(payload2) == {"insight": {"enabled": False}}

    asyncio.run(run())


def test_metrics_export_insight_gauges_and_top_denied_compat():
    from throttlecrab_tpu.server.metrics import Metrics

    m = Metrics(max_denied_keys=10)
    m.record_request_with_key("http", False, "u:1")
    m.record_request_with_key("http", False, "u:1")
    text = m.export_prometheus()
    # Byte-compatible leaderboard export on the sketch backend.
    assert 'throttlecrab_top_denied_keys{key="u:1",rank="1"} 2' in text
    for name in (
        "throttlecrab_tpu_insight_allowed_rate",
        "throttlecrab_tpu_insight_denied_rate",
        "throttlecrab_tpu_insight_hot_concentration",
        "throttlecrab_tpu_insight_tracked_keys",
        "throttlecrab_tpu_insight_prewarmed_total",
        "throttlecrab_tpu_insight_polls",
    ):
        assert name in text, name
    lim, ins = _make_tier()
    m.set_insight_stats_provider(ins.metric_stats)
    assert "throttlecrab_tpu_insight_polls 0" in m.export_prometheus()


def test_config_knobs_and_factory_wiring():
    from throttlecrab_tpu.server.config import Config, ConfigError
    from throttlecrab_tpu.server.metrics import Metrics
    from throttlecrab_tpu.server.store import (
        create_front_tier,
        create_insight,
        create_limiter,
    )

    cfg = Config(http=True, store_capacity=1 << 10)
    cfg.validate()
    limiter = create_limiter(cfg)
    assert limiter.table.insight  # default on
    metrics = Metrics()
    front = create_front_tier(cfg, metrics, limiter)
    ins = create_insight(cfg, metrics, limiter, front)
    assert ins is not None and ins.limiter is limiter
    # Kill switch: no insight table, no tier, 4-wide rows.
    cfg_off = Config(http=True, store_capacity=1 << 10, insight=False)
    lim_off = create_limiter(cfg_off)
    assert not lim_off.table.insight
    assert lim_off.table.state.shape[-1] == 4
    assert create_insight(cfg_off, metrics, lim_off, front) is None
    # Validation.
    with pytest.raises(ConfigError):
        Config(http=True, insight_shed_weight=1.5).validate()
    with pytest.raises(ConfigError):
        Config(http=True, insight_topk=0).validate()


def test_flash_crowd_pattern_shifts_hot_set():
    ks = make_keys("flash-crowd", 2000, 10_000, seed=1)
    set_a, set_b = flash_crowd_hot_sets(10_000)
    first, second = ks[:1000], ks[1000:]
    assert sum(k in set_a for k in first) > 700
    assert sum(k in set_b for k in first) == 0  # disjoint by design
    assert sum(k in set_b for k in second) > 700
    assert sum(k in set_a for k in second) == 0
    assert set_a.isdisjoint(set_b)
