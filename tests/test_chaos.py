"""Chaos suite: failure-domain supervision under injected faults.

Drives the fault registry (throttlecrab_tpu/faults/) through the launch
supervisor (server/supervisor.py) and pins the acceptance contract:

  * transient launch/fetch faults are absorbed by retries — the client
    sees ZERO failed requests;
  * a persistent device failure degrades to the host scalar oracle,
    whose decisions are byte-identical to core/ GCRA (differential,
    virtual time), and the server keeps serving;
  * recovery re-promotes host-mutated state with nothing lost or
    double-counted, invalidating the front tier via on_restore;
  * deterministic errors (keymap capacity, bad params) are never
    retried and never degrade — they are the request's fault;
  * everything is observable: /health and the supervisor metrics.

The fast slice here runs in tier-1 CI; the long soak is marked slow.
"""

import asyncio

import pytest

from throttlecrab_tpu import faults
from throttlecrab_tpu.core.rate_limiter import RateLimiter
from throttlecrab_tpu.core.store.mapstore import MapStore
from throttlecrab_tpu.server.engine import BatchingEngine, ThrottleError
from throttlecrab_tpu.server.metrics import Metrics
from throttlecrab_tpu.server.supervisor import (
    STATE_DEGRADED,
    STATE_OK,
    SupervisedLimiter,
    classify_exception,
    supervisor_state,
)
from throttlecrab_tpu.server.types import ThrottleRequest
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

NS = 1_000_000_000
T0 = 1_700_000_000 * NS


class VirtualClock:
    def __init__(self, start_ns=T0):
        self.now = start_ns

    def __call__(self):
        return self.now


class _PlainStore(MapStore):
    def _maybe_cleanup(self, now_ns):
        pass


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


def arm(spec: str, seed: int = 1) -> faults.FaultInjector:
    inj = faults.FaultInjector(
        faults.parse_spec(spec), seed=seed, sleep_fn=lambda s: None
    )
    faults.arm(inj)
    return inj


def make_supervised(capacity=1024, **kw):
    kw.setdefault("sleep_fn", lambda s: None)  # no real backoff waits
    return SupervisedLimiter(TpuRateLimiter(capacity=capacity), **kw)


def make_engine(limiter, clock=None, metrics=None, **kw):
    clock = clock or VirtualClock()
    engine = BatchingEngine(
        limiter, now_fn=clock, metrics=metrics, **kw
    )
    return engine, clock


def req(key="k", burst=10, count=100, period=60, quantity=1):
    return ThrottleRequest(key, burst, count, period, quantity)


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------------------ #
# The registry itself.


def test_parse_spec_validates():
    specs = faults.parse_spec("launch:transient:0.5, fetch:count:3")
    assert [s.site for s in specs] == ["launch", "fetch"]
    for bad in (
        "nope:persistent",
        "launch:explode",
        "launch:transient",     # missing required arg
        "launch:transient:2.0",  # p out of range
        "launch",
    ):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)


def test_injection_is_deterministic():
    """Same seed → same fault sequence; that is the replay contract."""

    def firing_pattern(seed):
        inj = faults.FaultInjector(
            faults.parse_spec("launch:transient:0.5"), seed=seed
        )
        out = []
        for _ in range(64):
            try:
                inj.check("launch")
                out.append(False)
            except faults.InjectedDeviceError:
                out.append(True)
        return out

    assert firing_pattern(7) == firing_pattern(7)
    assert firing_pattern(7) != firing_pattern(8)


def test_hang_mode_uses_injected_sleep():
    slept = []
    inj = faults.FaultInjector(
        faults.parse_spec("launch:hang:0.25"), sleep_fn=slept.append
    )
    inj.check("launch")  # stalls, then passes
    assert slept == [0.25]


def test_classifier_taxonomy():
    assert classify_exception(
        faults.InjectedDeviceError("UNAVAILABLE: device lost")
    ) == "transient"
    assert classify_exception(ConnectionError("peer gone")) == "transient"
    from throttlecrab_tpu.core.errors import InternalError

    assert classify_exception(InternalError("bucket table full")) == (
        "deterministic"
    )
    assert classify_exception(ValueError("bad input")) == "deterministic"


# ------------------------------------------------------------------ #
# Transient faults: retries absorb them — zero client failures.


def test_transient_launch_faults_zero_client_failures():
    arm("launch:count:3")
    metrics = Metrics()
    sup = make_supervised(retries=3, metrics=metrics)

    async def main():
        engine, _ = make_engine(
            sup, metrics=metrics, batch_size=32, max_linger_us=500
        )
        return await asyncio.gather(
            *[engine.throttle(req(key=f"t{i}")) for i in range(32)]
        )

    results = run(main())  # gather raises if any future failed
    assert all(r.allowed for r in results)
    assert sup.state == STATE_OK
    assert sup.retry_count == 3
    assert metrics.supervisor_retries == 3
    assert metrics.supervisor_degrades == 0


def test_transient_probability_faults_zero_client_failures():
    inj = arm("launch:transient:0.3", seed=42)
    sup = make_supervised(retries=8)

    async def main():
        engine, clock = make_engine(sup, batch_size=16, max_linger_us=500)
        out = []
        for wave in range(5):
            clock.now += NS
            out.extend(
                await asyncio.gather(
                    *[
                        engine.throttle(req(key=f"p{wave}-{i}"))
                        for i in range(16)
                    ]
                )
            )
        return out

    results = run(main())
    assert all(r.allowed for r in results)
    assert sup.state == STATE_OK
    assert inj.stats()["launch"] > 0  # faults really fired
    assert sup.degrade_count == 0


def test_transient_fetch_faults_zero_client_failures():
    """A fetch is a committed-state read: retrying it is always safe,
    so transient fetch faults are absorbed exactly like launch faults."""
    arm("fetch:count:2")
    sup = make_supervised(retries=3)

    async def main():
        engine, _ = make_engine(sup, batch_size=8, max_linger_us=500)
        return await asyncio.gather(
            *[engine.throttle(req(key=f"f{i}")) for i in range(8)]
        )

    results = run(main())
    assert all(r.allowed for r in results)
    assert sup.state == STATE_OK
    assert sup.retry_count == 2


# ------------------------------------------------------------------ #
# Persistent failure: degrade, serve, stay observable.


def test_persistent_failure_degrades_and_keeps_serving():
    arm("launch:persistent")
    metrics = Metrics()
    sup = make_supervised(retries=2, metrics=metrics)
    metrics.set_engine_state_provider(lambda: sup.state)

    async def main():
        engine, _ = make_engine(
            sup, metrics=metrics, batch_size=16, max_linger_us=500
        )
        results = await asyncio.gather(
            *[engine.throttle(req(key=f"d{i}", burst=5)) for i in range(16)]
        )
        return engine, results

    engine, results = run(main())
    # The device never answered — and the client never noticed.
    assert all(r.allowed for r in results)
    assert sup.state == STATE_DEGRADED
    assert engine.health_state() == "degraded"
    assert metrics.supervisor_degrades == 1
    text = metrics.export_prometheus()
    assert "throttlecrab_tpu_engine_state 2" in text
    assert "throttlecrab_tpu_supervisor_degrades 1" in text


def test_supervisor_mode_fail_raises_instead_of_degrading():
    arm("launch:persistent")
    sup = make_supervised(retries=1, mode="fail")

    async def main():
        engine, _ = make_engine(sup, batch_size=4, max_linger_us=500)
        return await asyncio.gather(
            *[engine.throttle(req(key=f"x{i}")) for i in range(4)],
            return_exceptions=True,
        )

    results = run(main())
    assert all(isinstance(r, ThrottleError) for r in results)
    assert sup.degrade_count == 0


def test_deterministic_error_not_retried_not_degraded():
    """Keymap capacity exhaustion is the request pattern's fault, not
    the device's: no retry (it cannot help), no degrade."""
    arm("keymap:persistent")
    sup = make_supervised(retries=3)

    async def main():
        engine, _ = make_engine(sup, batch_size=4, max_linger_us=500)
        return await asyncio.gather(
            *[engine.throttle(req(key=f"c{i}")) for i in range(4)],
            return_exceptions=True,
        )

    results = run(main())
    assert all(isinstance(r, ThrottleError) for r in results)
    assert sup.state == STATE_OK
    assert sup.retry_count == 0
    assert sup.degrade_count == 0


# ------------------------------------------------------------------ #
# Degraded-mode exactness and recovery (the tentpole's contract).


def _scalar_ref():
    return RateLimiter(_PlainStore())


def test_degraded_decisions_byte_identical_to_scalar_oracle():
    """Under a persistent device failure every field of every decision
    — allow bit, remaining, reset_after_ns, retry_after_ns — matches
    an uninterrupted scalar-oracle run of the same request sequence:
    the degrade handoff loses nothing."""
    arm("launch:count:2")
    sup = make_supervised(retries=0, probe_interval_ms=10_000_000)
    ref = _scalar_ref()

    t = T0
    for i in range(30):
        t += 3 * NS // 10
        keys = ["hot", f"cold{i % 7}"]
        res = sup.rate_limit_batch(keys, 3, 10, 60, 1, t)
        for j, key in enumerate(keys):
            ok, r = ref.rate_limit(key, 3, 10, 60, 1, t)
            assert bool(res.allowed[j]) == ok, (i, key)
            assert int(res.remaining[j]) == r.remaining, (i, key)
            assert int(res.reset_after_ns[j]) == r.reset_after_ns, (i, key)
            assert int(res.retry_after_ns[j]) == r.retry_after_ns, (i, key)
    assert sup.state == STATE_DEGRADED  # faults hit on launch 1, degraded
    assert len(sup) == len(ref.store._data)


def test_recovery_repromotes_no_lost_or_double_counted_state():
    """ok → degraded → recovering → ok, differentially against an
    uninterrupted scalar run: decisions before, during, and after the
    outage all match, so nothing was lost or double-counted across
    either transition; the front tier is invalidated via on_restore."""

    class FakeFront:
        restores = 0

        def on_restore(self):
            FakeFront.restores += 1

    arm("launch:count:6")
    sup = make_supervised(retries=1, probe_interval_ms=1000)
    sup.front = FakeFront()
    ref = _scalar_ref()

    t = T0
    saw = set()
    for i in range(40):
        t += 3 * NS // 10
        keys = ["hot", f"user{i % 5}"]
        res = sup.rate_limit_batch(keys, 3, 10, 60, 1, t)
        saw.add(sup.state)
        for j, key in enumerate(keys):
            ok, r = ref.rate_limit(key, 3, 10, 60, 1, t)
            assert bool(res.allowed[j]) == ok, (i, key, sup.state)
            assert int(res.remaining[j]) == r.remaining, (i, key)
    assert STATE_DEGRADED in saw
    assert sup.state == STATE_OK
    assert sup.degrade_count == 1
    assert sup.repromote_count == 1
    assert FakeFront.restores == 1  # re-promotion invalidated the cache


def test_degrade_wire_results_match_scalar_truncation():
    """Degraded-mode wire results apply the same seconds truncation and
    i32 clamps every transport emits."""
    arm("launch:persistent")
    sup = make_supervised(retries=0)
    ref = _scalar_ref()
    t = T0
    for i in range(8):
        t += NS // 5
        res = sup.rate_limit_batch(["w"], 2, 3, 1, 1, t, wire=True)
        ok, r = ref.rate_limit("w", 2, 3, 1, 1, t)
        assert bool(res.allowed[0]) == ok
        assert int(res.reset_after_s[0]) == r.reset_after_ns // NS
        assert int(res.retry_after_s[0]) == r.retry_after_ns // NS


def test_degraded_snapshot_exports_host_state(tmp_path):
    """A shutdown snapshot taken mid-outage captures the host oracle's
    state (the freshest view), and restores into a healthy limiter."""
    from throttlecrab_tpu.tpu.snapshot import load_snapshot, save_snapshot

    arm("launch:persistent")
    sup = make_supervised(retries=0)
    t = T0
    for i in range(5):
        t += NS // 10
        sup.rate_limit_batch([f"s{i}"], 5, 10, 60, 1, t)
    assert sup.state == STATE_DEGRADED
    path = tmp_path / "degraded.npz"
    n = save_snapshot(sup, path)
    assert n == 5
    faults.disarm()
    fresh = TpuRateLimiter(capacity=256)
    assert load_snapshot(fresh, path, t) == 5


# ------------------------------------------------------------------ #
# The other fault surfaces.


def test_peer_socket_fault_shape():
    """The peer site raises the ConnectionError shape the cluster
    forwarder's failure-containment path (breaker/backoff) catches."""
    from throttlecrab_tpu.parallel.cluster import PeerConnection

    arm("peer:persistent")
    peer = PeerConnection("127.0.0.1", 1)
    with pytest.raises(ConnectionError):
        peer.send_frame(b"frame")
    with pytest.raises(ConnectionError):
        peer.recv_frame()


def test_snapshot_io_fault_shape(tmp_path):
    from throttlecrab_tpu.tpu.snapshot import save_snapshot

    arm("snapshot:persistent")
    lim = TpuRateLimiter(capacity=64)
    lim.rate_limit_batch(["a"], 5, 10, 60, 1, T0)
    with pytest.raises(OSError):
        save_snapshot(lim, tmp_path / "s.npz")


# ------------------------------------------------------------------ #
# Observability end to end.


def test_health_route_reports_state_machine():
    """GET /health: the failure-domain state, then the device."""
    import jax

    from throttlecrab_tpu.runtime import (
        device_info,
        health_suffix,
        parse_health,
    )
    from throttlecrab_tpu.server.http import HttpTransport

    arm("launch:persistent")
    metrics = Metrics()
    sup = make_supervised(retries=0, metrics=metrics)

    async def main():
        engine, _ = make_engine(
            sup, metrics=metrics, batch_size=4, max_linger_us=500
        )
        transport = HttpTransport("127.0.0.1", 0, engine, metrics)
        ok_body = await transport._route("GET", "/health", b"")
        await asyncio.gather(
            *[engine.throttle(req(key=f"h{i}")) for i in range(4)]
        )
        degraded_body = await transport._route("GET", "/health", b"")
        return ok_body, degraded_body

    ok_body, degraded_body = run(main())
    device = (" " + health_suffix()).encode()
    assert ok_body == (200, b"OK" + device, "text/plain")
    assert degraded_body == (200, b"degraded" + device, "text/plain")
    assert parse_health(ok_body[1].decode()) == device_info()
    assert device_info()["platform"] == jax.devices()[0].platform


def test_supervisor_state_helper_walks_wrappers():
    sup = make_supervised(retries=0)

    class ClusterLike:
        local = sup

    assert supervisor_state(sup) == "ok"
    assert supervisor_state(ClusterLike()) == "ok"
    assert supervisor_state(TpuRateLimiter(capacity=64)) == "ok"


# ------------------------------------------------------------------ #
# Soak (not in tier-1: marked slow).


@pytest.mark.slow
def test_chaos_soak_mixed_transient_faults():
    """2 000 requests through the engine under mixed transient launch
    and fetch faults: zero client failures, exact burst accounting on
    the hot key, state machine back at ok."""
    arm("launch:transient:0.05,fetch:transient:0.05", seed=9)
    sup = make_supervised(capacity=8192, retries=8)

    async def main():
        engine, clock = make_engine(
            sup, batch_size=128, max_linger_us=500
        )
        results = []
        for wave in range(20):
            clock.now += NS
            results.extend(
                await asyncio.gather(
                    *[
                        engine.throttle(
                            req(key=f"soak{wave}-{i}", burst=3,
                                period=3600)
                        )
                        for i in range(100)
                    ]
                )
            )
        return results

    results = run(main())
    assert len(results) == 2000
    assert all(r.allowed for r in results)
    assert sup.state == STATE_OK
    assert sup.degrade_count == 0
