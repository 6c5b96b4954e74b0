"""Micro-batching engine tests.

The engine is the actor replacement: these mirror the reference's actor
tests (`actor_tests.rs:33-70` — N concurrent hits on a burst-B key allow
exactly B) plus batching-specific behavior (coalescing, linger flush,
per-request validation errors, cleanup policy integration).  The limiter
underneath is the real TPU engine on the virtual-CPU backend.
"""

import asyncio

import pytest

from throttlecrab_tpu.runtime import health_suffix
from throttlecrab_tpu.server.engine import BatchingEngine, ThrottleError
from throttlecrab_tpu.server.metrics import Metrics
from throttlecrab_tpu.server.types import ThrottleRequest
from throttlecrab_tpu.tpu.cleanup import PeriodicPolicy
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

NS = 1_000_000_000
T0 = 1_700_000_000 * NS


class VirtualClock:
    def __init__(self, start_ns=T0):
        self.now = start_ns

    def __call__(self):
        return self.now


def make_engine(**kwargs):
    clock = kwargs.pop("clock", VirtualClock())
    limiter = TpuRateLimiter(capacity=1024)
    engine = BatchingEngine(limiter, now_fn=clock, **kwargs)
    return engine, clock


def run(coro):
    return asyncio.run(coro)


def req(key="k", burst=10, count=100, period=60, quantity=1):
    return ThrottleRequest(key, burst, count, period, quantity)


def test_actor_invariant_exactly_burst_allowed():
    """actor_tests.rs:33-70: 20 concurrent requests, burst 10 → 10 allowed."""

    async def main():
        engine, _ = make_engine(batch_size=64, max_linger_us=1000)
        results = await asyncio.gather(
            *[engine.throttle(req(burst=10, period=3600)) for _ in range(20)]
        )
        return [r.allowed for r in results]

    allowed = run(main())
    assert sum(allowed) == 10
    # Arrival order: the first 10 get through.
    assert all(allowed[:10]) and not any(allowed[10:])


def test_actor_invariant_holds_across_batches():
    """Same 20-tasks/burst-10 invariant, but with batch_size=4 so the
    wave spans several device launches (and the scan path): exactly 10
    allowed, still in arrival order."""

    async def main():
        engine, _ = make_engine(batch_size=4, max_linger_us=500)
        results = await asyncio.gather(
            *[engine.throttle(req(burst=10, period=3600)) for _ in range(20)]
        )
        return [r.allowed for r in results]

    allowed = run(main())
    assert sum(allowed) == 10
    assert all(allowed[:10]) and not any(allowed[10:])


def test_full_batch_flushes_without_linger():
    async def main():
        engine, _ = make_engine(batch_size=4, max_linger_us=10_000_000)
        results = await asyncio.wait_for(
            asyncio.gather(
                *[engine.throttle(req(key=f"k{i}")) for i in range(4)]
            ),
            timeout=2.0,
        )
        return results

    results = run(main())
    assert all(r.allowed for r in results)


def test_linger_flushes_partial_batch():
    async def main():
        engine, _ = make_engine(batch_size=4096, max_linger_us=5_000)
        return await asyncio.wait_for(engine.throttle(req()), timeout=2.0)

    response = run(main())
    assert response.allowed
    assert response.limit == 10


def test_validation_error_is_per_request():
    async def main():
        engine, _ = make_engine(batch_size=3, max_linger_us=1000)
        good1 = engine.throttle(req(key="a"))
        bad = engine.throttle(req(key="b", burst=-1))
        good2 = engine.throttle(req(key="c"))
        results = await asyncio.gather(good1, bad, good2, return_exceptions=True)
        return results

    r1, r2, r3 = run(main())
    assert r1.allowed
    assert isinstance(r2, ThrottleError)
    assert r3.allowed


def test_negative_quantity_error_message():
    async def main():
        engine, _ = make_engine(batch_size=1)
        try:
            await engine.throttle(req(quantity=-1))
        except ThrottleError as e:
            return str(e)

    assert "negative" in run(main())


def test_seconds_truncation_at_type_boundary():
    """types.rs:87-97: durations are whole seconds on the wire."""

    async def main():
        engine, _ = make_engine(batch_size=1)
        # burst 2 @ 3/s → emission ~333ms; third hit denied with
        # retry_after ≈ 333ms, which truncates to 0 whole seconds.
        r = None
        for _ in range(3):
            r = await engine.throttle(req(key="t", burst=2, count=3, period=1))
        return r

    response = run(main())
    assert not response.allowed
    assert response.retry_after == 0  # 333ms truncates to 0 whole seconds


def test_metrics_launch_accounting():
    async def main():
        metrics = Metrics()
        limiter = TpuRateLimiter(capacity=256)
        engine = BatchingEngine(
            limiter, batch_size=8, max_linger_us=1000,
            metrics=metrics, now_fn=VirtualClock(),
        )
        await asyncio.gather(
            *[engine.throttle(req(key=f"m{i}")) for i in range(8)]
        )
        return metrics

    metrics = run(main())
    assert metrics.device_launches >= 1
    assert metrics.batched_requests == 8
    assert metrics.max_batch <= 8


def test_cleanup_policy_sweeps_between_batches():
    async def main():
        clock = VirtualClock()
        policy = PeriodicPolicy(interval_ns=60 * NS)
        limiter = TpuRateLimiter(capacity=256)
        engine = BatchingEngine(
            limiter, batch_size=1, cleanup_policy=policy, now_fn=clock,
        )
        # period 1s → TTL ~1s; expire it, then advance past the interval.
        await engine.throttle(req(key="x", burst=1, count=1, period=1))
        assert len(limiter) == 1
        clock.now += 120 * NS
        await engine.throttle(req(key="y"))  # arms the policy clock
        clock.now += 120 * NS
        await engine.throttle(req(key="z"))  # fires the sweep
        return limiter

    limiter = run(main())
    assert len(limiter) <= 2  # "x" (and possibly "y") swept


def test_shutdown_resolves_inflight_futures_when_final_flush_raises():
    """Drain-correct shutdown: even when the final flush's launch
    raises, every in-flight future must resolve (ThrottleError), never
    hang."""

    async def main():
        engine, _ = make_engine(batch_size=4096, max_linger_us=10_000_000)

        def boom(*a, **kw):
            raise RuntimeError("injected final-flush launch failure")

        engine.limiter.dispatch_many = boom
        engine.limiter.rate_limit_many = boom
        engine.limiter.rate_limit_batch = boom
        pending = [
            asyncio.ensure_future(engine.throttle(req(key=f"s{i}")))
            for i in range(5)
        ]
        await asyncio.sleep(0)  # requests land in the pending deque
        await asyncio.wait_for(engine.shutdown(), timeout=2.0)
        # Resolve (with the error), not hang: wait_for pins the "never
        # hang" half of the contract.
        return await asyncio.wait_for(
            asyncio.gather(*pending, return_exceptions=True), timeout=2.0
        )

    results = run(main())
    assert len(results) == 5
    assert all(isinstance(r, ThrottleError) for r in results)


def test_post_shutdown_requests_have_defined_status_per_transport():
    """After shutdown every transport maps the refusal to its
    protocol's error shape: engine ThrottleError("engine is shut
    down") → HTTP 500 {"error": ...} / RESP -ERR; /health says
    "shutdown"."""
    import json

    from throttlecrab_tpu.server.http import HttpTransport
    from throttlecrab_tpu.server.redis import RedisTransport
    from throttlecrab_tpu.server.resp import BulkString, Error

    async def main():
        engine, _ = make_engine(batch_size=8, max_linger_us=500)
        metrics = Metrics()
        await engine.shutdown()
        with pytest.raises(ThrottleError, match="shut down"):
            await engine.throttle(req(key="late"))

        http = HttpTransport("127.0.0.1", 0, engine, metrics)
        body = json.dumps(
            {"key": "late", "max_burst": 1, "count_per_period": 1,
             "period": 1}
        ).encode()
        status, payload, _ctype = await http._handle_throttle(body)
        health = await http._route("GET", "/health", b"")

        redis = RedisTransport("127.0.0.1", 0, engine, metrics)
        resp = await redis._handle_throttle(
            (BulkString("THROTTLE"), BulkString("late"), BulkString("1"),
             BulkString("1"), BulkString("1"))
        )
        return status, payload, health, resp

    status, payload, health, resp = run(main())
    assert status == 500
    assert "shut down" in json.loads(payload)["error"]
    assert health == (
        200, b"shutdown " + health_suffix().encode(), "text/plain"
    )
    assert isinstance(resp, Error)
    assert resp.value.startswith("ERR") and "shut down" in resp.value


def test_shutdown_flushes_then_refuses():
    async def main():
        engine, _ = make_engine(batch_size=4096, max_linger_us=10_000_000)
        pending = asyncio.ensure_future(engine.throttle(req(key="p")))
        await asyncio.sleep(0)  # request lands in the pending list
        await engine.shutdown()
        result = await pending
        with pytest.raises(ThrottleError):
            await engine.throttle(req(key="q"))
        return result

    assert run(main()).allowed


def test_oversized_wave_splits_into_batches():
    async def main():
        engine, _ = make_engine(batch_size=16, max_linger_us=1000)
        results = await asyncio.gather(
            *[engine.throttle(req(key=f"w{i % 5}", burst=50, period=3600))
              for i in range(100)]
        )
        return results

    results = run(main())
    assert all(r.allowed for r in results)  # 20 per key < burst 50


def test_double_buffered_backlog_preserves_exactness():
    """A deep backlog drains through overlapped dispatch/fetch launches;
    the burst accounting must stay exact across the launch boundary."""

    async def main():
        engine, _ = make_engine(
            batch_size=8, max_linger_us=500, max_scan_depth=2
        )
        # 64 concurrent hits on one burst-24 key: several scan windows,
        # dispatched with window N+1 in flight before N is fetched.
        results = await asyncio.gather(
            *[engine.throttle(req(key="db", burst=24, period=3600))
              for _ in range(64)]
        )
        return results

    results = run(main())
    assert sum(r.allowed for r in results) == 24


def test_dispatch_failure_fails_only_its_window():
    """A dispatch exception must fail that window's futures and leave the
    engine serving later requests."""

    async def main():
        engine, _ = make_engine(batch_size=4, max_linger_us=500)
        orig = engine.limiter.dispatch_many
        calls = {"n": 0}

        def flaky(batches, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected dispatch failure")
            return orig(batches, **kw)

        engine.limiter.dispatch_many = flaky
        first = await asyncio.gather(
            *[engine.throttle(req(key=f"f{i}")) for i in range(4)],
            return_exceptions=True,
        )
        second = await asyncio.gather(
            *[engine.throttle(req(key=f"g{i}")) for i in range(4)]
        )
        return first, second

    first, second = run(main())
    assert all(isinstance(r, ThrottleError) for r in first)
    assert all(r.allowed for r in second)


def test_adaptive_expired_ratio_fires_engine_sweep():
    """End-to-end adaptive trigger: traffic landing on expired entries
    feeds the kernel's device-side hit counter through the engine's
    drain (feed_expired_hits) into AdaptivePolicy, whose expired-ratio
    trigger fires a sweep BEFORE the 5 s time trigger could."""
    from throttlecrab_tpu.tpu.cleanup import AdaptivePolicy

    async def main():
        clock = VirtualClock()
        policy = AdaptivePolicy()
        limiter = TpuRateLimiter(capacity=1024)
        metrics = Metrics()
        engine = BatchingEngine(
            limiter, batch_size=128, max_linger_us=500,
            cleanup_policy=policy, now_fn=clock, metrics=metrics,
        )
        # 120 keys with ~1 s TTLs.
        await asyncio.gather(*[
            engine.throttle(req(key=f"e{i}", burst=1, count=1, period=1))
            for i in range(120)
        ])
        assert len(limiter) == 120
        # Expire them all; revisit 60 within the same policy window
        # (+2 s < the 5 s default interval, so only the ratio trigger
        # can fire: >50 hits, 60/120 = 0.5 > 0.25).
        clock.now += 2 * NS
        await asyncio.gather(*[
            engine.throttle(req(key=f"e{i}", burst=1, count=1, period=1))
            for i in range(60)
        ])
        # One more flush so the drained count reaches should_clean
        # (the hit fetch is throttled to 1/s and runs on the executor).
        clock.now += int(1.2 * NS)
        await engine.throttle(req(key="tick"))
        await asyncio.sleep(0.05)  # let the executor sweep land
        return limiter, policy, metrics

    limiter, policy, metrics = run(main())
    # The sweep collected the 60 still-expired entries (the revisited 60
    # were refreshed by their hits, exactly like the reference's
    # set_if_not_exists re-insert) and reset the policy's hit count.
    assert policy._last_total > 0  # after_sweep ran
    assert policy._expired == 0
    assert len(limiter) <= 62  # 120 + tick - 60 swept (y may survive)
    # The drained count is mirrored into /metrics.
    assert metrics.expired_hits == 60
    assert "throttlecrab_tpu_expired_hits 60" in metrics.export_prometheus()


# ------------------------------------------------- drain / deadlines #


def test_begin_drain_sheds_new_resolves_queued():
    """begin_drain() flips lame-duck serving: already-queued requests
    resolve with real decisions, new arrivals shed with OverloadError
    ("server draining" — 503, not a failure), and /health reports
    "draining" so balancers de-route before the listener closes."""
    from throttlecrab_tpu.server.engine import OverloadError

    async def main():
        engine, _ = make_engine(batch_size=64, max_linger_us=10_000_000)
        queued = [
            asyncio.ensure_future(engine.throttle(req(key=f"q{i}")))
            for i in range(3)
        ]
        await asyncio.sleep(0)  # requests land in the pending list
        engine.begin_drain()
        assert engine.health_state() == "draining"
        with pytest.raises(OverloadError, match="draining"):
            await engine.throttle(req(key="late"))
        await engine.drain()
        results = await asyncio.gather(*queued)
        return results, engine.drain_shed

    results, shed = run(main())
    assert all(r.allowed for r in results)
    assert shed == 1


def test_drain_then_shutdown_keeps_shutdown_semantics():
    """drain() is the graceful half; shutdown() after it must still
    pin the abrupt contract: health "shutdown" and ThrottleError (not
    OverloadError) for anything arriving after close."""

    async def main():
        engine, _ = make_engine(batch_size=64, max_linger_us=10_000_000)
        pending = asyncio.ensure_future(engine.throttle(req(key="p")))
        await asyncio.sleep(0)
        await engine.drain()
        result = await pending
        await engine.shutdown()
        assert engine.health_state() == "shutdown"
        with pytest.raises(ThrottleError):
            await engine.throttle(req(key="q"))
        return result

    assert run(main()).allowed


def test_deadline_shed_at_flush_spares_batchmates():
    """A queued request whose client deadline lapses before the flush
    sheds with DeadlineError — before any device dispatch — while its
    batchmates still get real decisions; deadline_default_ms stamps
    requests that carry no explicit deadline."""
    from throttlecrab_tpu.server.engine import DeadlineError

    async def main():
        clock = VirtualClock()
        engine, _ = make_engine(
            clock=clock, batch_size=64, max_linger_us=10_000_000,
            deadline_default_ms=50,
        )
        stale_req = req(key="a")
        stale = asyncio.ensure_future(engine.throttle(stale_req))
        await asyncio.sleep(0)
        # The default was stamped at ingest (absolute, engine clock).
        assert stale_req.deadline_ns == clock.now + 50 * 1_000_000
        clock.now += 100 * 1_000_000  # lapse it in-queue
        fresh_req = req(key="b")
        fresh_req.deadline_ns = clock.now + 1_000_000_000  # still live
        fresh = asyncio.ensure_future(engine.throttle(fresh_req))
        await asyncio.sleep(0)
        await engine.drain()  # flush everything queued
        with pytest.raises(DeadlineError, match="deadline exceeded"):
            await stale
        response = await fresh
        return response, engine.deadline_shed

    response, shed = run(main())
    assert response.allowed
    assert shed == 1
