"""Front-tier (L3.5) differential suite: the deny cache must be
*invisible* in every decision and the admission controller must surface
each protocol's overload status.

The load-bearing property is exactness: a deny served from the cache
must be byte-identical — allowed, limit, remaining, and the *decayed*
reset/retry fields — to what the engine would have produced at the same
virtual timestamp.  The main test runs the same hot-key abuse stream
(param churn, probes, expiry jumps, a mid-run snapshot round trip)
through two real BatchingEngines — one with the front tier, one
without — and compares every response, across all three store
policies.  The shed tests pin the overload status on every transport:
HTTP 503, gRPC RESOURCE_EXHAUSTED, RESP -ERR, and the native C++ wire
paths (epoll RESP + HTTP).
"""

import asyncio
import os
import tempfile

import numpy as np
import pytest

from throttlecrab_tpu.front import (
    AdmissionController,
    DenyCache,
    FrontTier,
    OverloadError,
)
from throttlecrab_tpu.server.engine import BatchingEngine, ThrottleError
from throttlecrab_tpu.server.metrics import Metrics
from throttlecrab_tpu.server.types import ThrottleRequest
from throttlecrab_tpu.tpu.cleanup import (
    AdaptivePolicy,
    PeriodicPolicy,
    ProbabilisticPolicy,
)
from throttlecrab_tpu.tpu.limiter import (
    TpuRateLimiter,
    limiter_uses_bytes_keys,
)

NS = 1_000_000_000
T0 = 1_800_000_000 * NS


class VirtualClock:
    def __init__(self, start_ns=T0):
        self.now = start_ns

    def __call__(self):
        return self.now


def make_front(metrics=None, limiter=None, deny=True, admission=None):
    return FrontTier(
        DenyCache(4096) if deny else None,
        admission,
        metrics=metrics,
        bytes_keys=(
            limiter_uses_bytes_keys(limiter) if limiter is not None else False
        ),
    )


def make_engine(front=None, clock=None, policy=None, limiter=None,
                **kwargs):
    clock = clock or VirtualClock()
    limiter = limiter or TpuRateLimiter(capacity=1024)
    if front is not None:
        front.bytes_keys = limiter_uses_bytes_keys(limiter)
    engine = BatchingEngine(
        limiter,
        now_fn=clock,
        front=front,
        cleanup_policy=policy,
        batch_size=kwargs.pop("batch_size", 64),
        max_linger_us=kwargs.pop("max_linger_us", 500),
        **kwargs,
    )
    return engine, clock, limiter


def req(key="k", burst=10, count=100, period=60, quantity=1):
    return ThrottleRequest(key, burst, count, period, quantity)


def norm(r):
    """Comparable shape for a response-or-exception."""
    if isinstance(r, Exception):
        return (type(r).__name__, str(r))
    return (r.allowed, r.limit, r.remaining, r.reset_after, r.retry_after)


# ===================================================================== #
# The differential: cache-on == cache-off, request by request.
# ===================================================================== #


def _abuse_window(rng, pool, params, size):
    """One window of hot-key abuse traffic: ~85 % of rows hammer the
    3 hot keys (mostly denies after the first burst), the rest touch
    the cold tail; a sprinkle of quantity-0 probes, quantity-2 spends,
    and invalid params."""
    reqs = []
    for _ in range(size):
        r = rng.random()
        if r < 0.85:
            key = pool[int(rng.integers(0, 3))]  # hot
        else:
            key = pool[int(rng.integers(3, len(pool)))]
        burst, count, period = params[key]
        q = 1
        p = rng.random()
        if p < 0.015:
            # Free probe.  Kept rare on purpose: a probe makes its whole
            # launch window degenerate, which drops the cur output tier
            # and forfeits certification for every denial in the window.
            q = 0
        elif p < 0.08:
            q = 2
        elif p < 0.10:
            burst = -1  # per-request validation error
        reqs.append(req(key, burst, count, period, q))
    return reqs


def _draw_params(rng):
    # Tight limits with slow emission (em = period/count between ~2.5 s
    # and 90 s) so hot keys saturate fast and *stay* denied across many
    # windows of 0-3 s clock steps — the deny cache's serving regime —
    # while the 120-600 s expiry jumps still vacate buckets mid-run.
    burst = int(rng.integers(2, 6))
    period = int(rng.integers(10, 90))
    count = int(rng.integers(1, 5))
    return burst, count, period


_POLICIES = {
    # Short periods/thresholds so every policy actually fires sweeps
    # inside the run (the differential must hold across sweep points).
    "periodic": lambda: PeriodicPolicy(interval_ns=20 * NS),
    "probabilistic": lambda: ProbabilisticPolicy(probability=257),
    "adaptive": lambda: AdaptivePolicy(
        min_interval_ns=10 * NS, max_interval_ns=120 * NS,
        max_operations=700,
    ),
}


@pytest.mark.parametrize("policy_name", sorted(_POLICIES))
def test_differential_cache_on_vs_off(policy_name):
    """≥ 3.5k virtual-time requests per store policy (10.5k across the
    parametrization), every response identical with and without the
    deny cache — including decayed retry/reset on cache hits, param
    churn, expiry jumps, sweeps, and a mid-run snapshot restore."""
    from throttlecrab_tpu.tpu.snapshot import load_snapshot, save_snapshot

    rng = np.random.default_rng(
        0xF2047 + {"periodic": 1, "probabilistic": 2, "adaptive": 3}[
            policy_name
        ]
    )
    n_windows, window = 112, 32

    async def run():
        clock = VirtualClock()
        front = make_front()
        eng_a, _, lim_a = make_engine(
            front=front, clock=clock, policy=_POLICIES[policy_name]()
        )
        eng_b, _, lim_b = make_engine(
            clock=clock, policy=_POLICIES[policy_name]()
        )
        pool = [f"fk:{i}" for i in range(16)]
        params = {k: _draw_params(rng) for k in pool}
        total = hits_before_restore = 0
        for step in range(n_windows):
            if rng.random() < 0.10:  # param churn on a hot key
                k = pool[int(rng.integers(0, 3))]
                params[k] = _draw_params(rng)
            reqs = _abuse_window(rng, pool, params, window)
            got_a, got_b = await asyncio.gather(
                asyncio.gather(
                    *[eng_a.throttle(r) for r in reqs],
                    return_exceptions=True,
                ),
                asyncio.gather(
                    *[eng_b.throttle(r) for r in reqs],
                    return_exceptions=True,
                ),
            )
            for i, (a, b) in enumerate(zip(got_a, got_b)):
                assert norm(a) == norm(b), (
                    f"{policy_name} step {step} row {i} "
                    f"({reqs[i]}): {norm(a)} != {norm(b)}"
                )
            total += len(reqs)
            # Decay: repeats inside a deny window land at later nows.
            clock.now += int(rng.integers(0, 3 * NS))
            if rng.random() < 0.08:  # expiry jump: vacate buckets
                clock.now += int(rng.integers(120, 600)) * NS
            if step == n_windows // 2:
                # Snapshot round trip mid-run: the restore rewrites
                # bucket state, so the cache must start over.
                hits_before_restore = front.deny_cache.hits
                assert len(front.deny_cache) > 0
                await eng_a.shutdown()
                await eng_b.shutdown()
                with tempfile.TemporaryDirectory() as d:
                    path = os.path.join(d, "snap")
                    save_snapshot(lim_a, path)
                    lim_a2 = TpuRateLimiter(capacity=1024)
                    lim_b2 = TpuRateLimiter(capacity=1024)
                    load_snapshot(
                        lim_a2, path + ".npz", now_ns=clock.now,
                        front=front,
                    )
                    load_snapshot(lim_b2, path + ".npz", now_ns=clock.now)
                assert len(front.deny_cache) == 0
                eng_a, _, lim_a = make_engine(
                    front=front, clock=clock,
                    policy=_POLICIES[policy_name](), limiter=lim_a2,
                )
                eng_b, _, lim_b = make_engine(
                    clock=clock, policy=_POLICIES[policy_name](),
                    limiter=lim_b2,
                )
        await eng_a.shutdown()
        await eng_b.shutdown()
        return total, front, hits_before_restore

    total, front, hits_before_restore = asyncio.run(run())
    assert total >= 3500
    # The equality above is vacuous unless the cache actually served:
    # the abuse mix must produce a solid hit count on both run halves.
    assert hits_before_restore > 100
    assert front.deny_cache.hits > hits_before_restore + 100


def test_param_change_never_serves_stale_denials():
    """A cached denial under params P must not leak into requests with
    params Q, and an allowed decision under Q must invalidate P's
    cached denials (the bucket moved)."""

    async def run():
        clock = VirtualClock()
        front = make_front()
        eng, _, _ = make_engine(front=front, clock=clock)
        ctl, _, _ = make_engine(clock=clock)
        out = []
        p1 = dict(burst=2, count=1, period=60)  # em = 60 s, tol = 60 s
        p2 = dict(burst=50, count=1, period=60)
        seq = (
            [req("pk", **p1)] * 4       # saturate + cache the deny
            + [req("pk", **p1)]         # served from cache
            + [req("pk", **p2)]         # bigger burst: engine, allowed
            + [req("pk", **p1)] * 2     # must re-decide (bucket moved)
        )
        for r in seq:
            a = await eng.throttle(r)
            b = await ctl.throttle(r)
            out.append((norm(a), norm(b)))
            clock.now += NS // 2
        await eng.shutdown()
        await ctl.shutdown()
        return out, front

    out, front = asyncio.run(run())
    for a, b in out:
        assert a == b
    assert front.deny_cache.hits >= 1
    # The p2 allowed decision must have dropped pk's cached denials —
    # nothing may still claim the pre-write window.
    assert out[5][0][0] is True


def test_snapshot_restore_clears_cache_direct():
    front = make_front()
    _prime(front.deny_cache, key="k")
    assert len(front.deny_cache) == 1
    front.on_restore()
    assert len(front.deny_cache) == 0
    assert front.deny_cache.entries() == []
    assert front.deny_cache.records() == []


# ===================================================================== #
# Admission control: shed status on every transport.
# ===================================================================== #


class _AlwaysShed(AdmissionController):
    """Deterministic overload for transport tests (queue depth varies
    with scheduling; forcing the verdict pins the wire mapping)."""

    def __init__(self):
        super().__init__(max_pending=1)

    def verdict(self, depth, peek):
        return False


def test_engine_sheds_with_overload_error():
    async def run():
        front = make_front(deny=False, admission=_AlwaysShed())
        eng, _, _ = make_engine(front=front)
        with pytest.raises(OverloadError):
            await eng.throttle(req())
        await eng.shutdown()

    asyncio.run(run())


def test_engine_depth_bound_sheds_deterministically():
    """The real controller: max_pending=1 admits the first (depth 0)
    and sheds the second (depth 1) while the first still lingers."""

    async def run():
        front = make_front(
            deny=False, admission=AdmissionController(max_pending=1)
        )
        eng, _, _ = make_engine(front=front, max_linger_us=200_000)
        t1 = asyncio.ensure_future(eng.throttle(req(key="d1")))
        await asyncio.sleep(0.01)  # t1 is pending, not yet flushed
        with pytest.raises(OverloadError):
            await eng.throttle(req(key="d2"))
        r1 = await t1
        await eng.shutdown()
        return r1, front

    r1, front = asyncio.run(run())
    assert r1.allowed
    assert front.admission.shed_consume == 1


def test_peek_class_sheds_first():
    """Probes (quantity 0) shed at peek_frac of the depth bound while
    consuming requests still pass."""
    adm = AdmissionController(max_pending=10, peek_frac=0.5)
    assert adm.admit(depth=6, peek=False)   # < 10: consuming passes
    assert not adm.admit(depth=6, peek=True)  # >= 10 * 0.5: probe sheds
    assert adm.shed_peek == 1 and adm.shed_consume == 0


def test_wait_bound_uses_ewma():
    adm = AdmissionController(max_pending=0, max_wait_us=100)
    assert adm.admit(depth=1000, peek=False)  # no samples yet: admit
    adm.record_launch(10, 0.001)  # 100 us per request
    assert adm.estimated_wait_us(5) == pytest.approx(500.0)
    assert not adm.admit(depth=5, peek=False)  # 500 us > 100 us bound
    assert adm.admit(depth=0, peek=False)


def test_http_shed_returns_503():
    from throttlecrab_tpu.server.http import HttpTransport

    from test_transports import http_request

    async def run():
        metrics = Metrics()
        front = make_front(metrics=metrics, deny=False,
                           admission=_AlwaysShed())
        eng, _, _ = make_engine(front=front)
        transport = HttpTransport("127.0.0.1", 0, eng, metrics)
        await transport.start()
        status, raw = await http_request(
            transport.bound_port, "POST", "/throttle",
            {"key": "s", "max_burst": 3, "count_per_period": 10,
             "period": 60},
        )
        await transport.stop()
        await eng.shutdown()
        return status, raw, metrics

    status, raw, metrics = asyncio.run(run())
    assert status == 503
    assert b"overloaded" in raw
    assert metrics.front_shed_consume == 1


def test_grpc_shed_returns_resource_exhausted():
    import grpc
    import grpc.aio

    from throttlecrab_tpu.server.grpc import GrpcTransport
    from throttlecrab_tpu.server.proto import throttlecrab_pb2 as pb

    async def run():
        metrics = Metrics()
        front = make_front(metrics=metrics, deny=False,
                           admission=_AlwaysShed())
        eng, _, _ = make_engine(front=front)
        transport = GrpcTransport("127.0.0.1", 0, eng, metrics)
        await transport.start()
        port = transport.bound_port
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as ch:
            method = ch.unary_unary(
                "/throttlecrab.RateLimiter/Throttle",
                request_serializer=pb.ThrottleRequest.SerializeToString,
                response_deserializer=pb.ThrottleResponse.FromString,
            )
            try:
                await method(
                    pb.ThrottleRequest(
                        key="s", max_burst=3, count_per_period=10,
                        period=60, quantity=1,
                    )
                )
                code = None
            except grpc.aio.AioRpcError as e:
                code = e.code()
        await transport.stop()
        await eng.shutdown()
        return code

    code = asyncio.run(run())
    import grpc

    assert code == grpc.StatusCode.RESOURCE_EXHAUSTED


def test_redis_shed_returns_err_overloaded():
    from throttlecrab_tpu.server.redis import RedisTransport

    from test_transports import resp_command

    async def run():
        metrics = Metrics()
        front = make_front(metrics=metrics, deny=False,
                           admission=_AlwaysShed())
        eng, _, _ = make_engine(front=front)
        transport = RedisTransport("127.0.0.1", 0, eng, metrics)
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        raw = await resp_command(
            reader, writer, "THROTTLE", "s", "3", "10", "60", "1"
        )
        writer.close()
        await transport.stop()
        await eng.shutdown()
        return raw

    raw = asyncio.run(run())
    assert raw.startswith(b"-ERR server overloaded")


# ===================================================================== #
# Native C++ wire paths (skipped without a toolchain, same as
# test_native_wire.py).
# ===================================================================== #


def _native_available():
    from throttlecrab_tpu.native import wire_available

    return wire_available()


needs_native = pytest.mark.skipif(
    not _native_available(), reason="no C++ toolchain for the wire server"
)


def _native_stack(transport_cls, front):
    metrics = Metrics(max_denied_keys=10)
    limiter = TpuRateLimiter(capacity=1024)
    front.metrics = metrics
    front.bytes_keys = limiter_uses_bytes_keys(limiter)
    transport = transport_cls(
        "127.0.0.1", 0, limiter, metrics,
        batch_size=64, max_linger_us=500, now_fn=lambda: T0, front=front,
    )
    return transport, metrics


@needs_native
def test_native_redis_shed_and_deny_cache():
    """The C++ epoll RESP path: shed rows answer -ERR server overloaded
    (ws_respond status 4), and a repeat denial is served byte-identical
    from the deny cache without a device launch."""
    from throttlecrab_tpu.server.native_redis import NativeRedisTransport

    async def shed():
        transport, _ = _native_stack(
            NativeRedisTransport,
            FrontTier(None, _AlwaysShed()),
        )
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        frame = b"*6\r\n$8\r\nTHROTTLE\r\n$1\r\ns\r\n$1\r\n3\r\n$2\r\n10\r\n$2\r\n60\r\n$1\r\n1\r\n"
        writer.write(frame)
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(4096), timeout=5.0)
        writer.close()
        await transport.stop()
        return raw

    raw = asyncio.run(shed())
    assert raw.startswith(b"-ERR server overloaded")

    async def deny_cache():
        transport, metrics = _native_stack(
            NativeRedisTransport, FrontTier(DenyCache(1024), None)
        )
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        replies = []
        for _ in range(4):  # burst 2: allow, allow, deny, deny(cached)
            frame = (
                b"*6\r\n$8\r\nTHROTTLE\r\n$2\r\nnk\r\n$1\r\n2\r\n"
                b"$2\r\n10\r\n$2\r\n60\r\n$1\r\n1\r\n"
            )
            writer.write(frame)
            await writer.drain()
            replies.append(
                await asyncio.wait_for(reader.read(4096), timeout=5.0)
            )
        launches = metrics.device_launches
        hits = metrics.front_deny_hits
        writer.close()
        await transport.stop()
        return replies, launches, hits, transport.front

    replies, launches, hits, front = asyncio.run(deny_cache())
    # Denied replies are byte-identical whether engine- or cache-served.
    assert replies[2] == replies[3]
    assert hits >= 1
    # The cached repeat must not have launched: fewer launches than
    # requests (3 at most: 2 allows + first deny).
    assert launches <= 3
    assert front.deny_cache.hits >= 1


@needs_native
def test_native_http_shed_returns_503():
    from throttlecrab_tpu.server.native_http import NativeHttpTransport

    async def run():
        transport, _ = _native_stack(
            NativeHttpTransport, FrontTier(None, _AlwaysShed())
        )
        await transport.start()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", transport.bound_port
        )
        body = (b'{"key": "s", "max_burst": 3, '
                b'"count_per_period": 10, "period": 60}')
        writer.write(
            b"POST /throttle HTTP/1.1\r\nHost: x\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\nConnection: close\r\n\r\n"
            + body
        )
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(4096), timeout=5.0)
        writer.close()
        await transport.stop()
        return raw

    raw = asyncio.run(run())
    head = raw.split(b"\r\n", 1)[0]
    assert b"503" in head and b"Service Unavailable" in head
    assert b"overloaded" in raw


# ===================================================================== #
# Deny-cache unit semantics.
# ===================================================================== #


def _prime(cache, key="u", burst=3, count=60, period=60, deny_q=3,
           now=T0):
    """Feed an allowed write that saturated the bucket (new TAT at the
    clamp, now + tol) then a certifying denial one ns later — the exact
    planes the engine's cur tier would hand over.  With burst 3 /
    count 60 / period 60: em = 1 s, tol = 2 s, and a quantity-3 denial
    opens a 3 s proven window (allow_at = tat + 3 em - tol)."""
    from throttlecrab_tpu.tpu.limiter import derive_params

    em, tol, _ = derive_params([burst], [count], [period])
    em, tol = int(em[0]), int(tol[0])
    inc = em * deny_q
    tat = now + tol  # saturated: the allowed write landed on the clamp
    cache.observe(key, burst, count, period, 1, now, True,
                  seq=1, cur_ns=tat)
    deny_now = now + 1
    cache.observe(
        key, burst, count, period, deny_q, deny_now, False, seq=2,
        cur_ns=tat,
    )
    return em, tol, inc, tat


def test_deny_cache_lookup_window_and_decay():
    cache = DenyCache(64)
    em, tol, inc, tat = _prime(cache)
    hit1 = cache.lookup("u", 3, 60, 60, 3, T0 + 2)
    hit2 = cache.lookup("u", 3, 60, 60, 3, T0 + 2 + NS)
    assert hit1 is not None and hit2 is not None
    # Decay: one second later, retry/reset shrink by exactly 1 s.
    assert hit1.retry_after_ns - hit2.retry_after_ns == NS
    assert hit1.reset_after_ns - hit2.reset_after_ns == NS
    assert cache.hits == 2


def test_deny_cache_misses_without_write_record():
    cache = DenyCache(64)
    # A denial with no observed allowed write can never certify.
    cache.observe("v", 3, 60, 60, 1, T0, False, seq=1, cur_ns=T0 + NS)
    assert cache.lookup("v", 3, 60, 60, 1, T0 + 1) is None
    assert len(cache) == 0


def test_deny_cache_allowed_invalidates():
    cache = DenyCache(64)
    _prime(cache)
    assert len(cache) == 1
    cache.observe("u", 30, 60, 60, 1, T0 + 2, True, seq=3,
                  cur_ns=T0 + 5 * NS)
    assert len(cache) == 0


def test_deny_cache_inflight_blocks_lookup():
    cache = DenyCache(64)
    _prime(cache)
    cache.begin_inflight("u")
    assert cache.lookup("u", 3, 60, 60, 3, T0 + 2) is None
    cache.end_inflight("u")
    assert cache.lookup("u", 3, 60, 60, 3, T0 + 2) is not None


def test_deny_cache_fail_window_drops_key_state():
    """A launch that fails AFTER its writes may have committed
    (fail_window) must release the hold AND conservatively drop the
    key's cached denials and write record — an unobserved allow may
    have moved the TAT, so neither can certify exactness any longer."""
    cache = DenyCache(64)
    _prime(cache)
    assert len(cache) == 1 and cache.record("u") is not None
    cache.begin_inflight("u")
    cache.fail_window(["u"])
    assert len(cache) == 0
    assert cache.record("u") is None
    # Hold released: a fresh prime certifies again.
    _prime(cache)
    assert cache.lookup("u", 3, 60, 60, 3, T0 + 2) is not None


def test_deny_cache_negative_now_misses():
    cache = DenyCache(64)
    _prime(cache)
    assert cache.lookup("u", 3, 60, 60, 3, -5) is None


def test_deny_cache_stale_seq_cannot_roll_back_record():
    cache = DenyCache(64)
    _prime(cache)  # record at seq 1, entry at seq 2
    # A late-arriving allowed observation from an older launch (seq 0)
    # must invalidate (an allow happened) but NOT overwrite the record.
    cache.observe("u", 3, 60, 60, 1, T0, True, seq=0, cur_ns=12345)
    assert len(cache) == 0
    rec = cache.record("u")
    assert rec is not None and rec[0] != 12345


def test_deny_cache_capacity_bound():
    cache = DenyCache(4)
    for i in range(8):
        _prime(cache, key=f"c{i}")
    assert len(cache) <= 4
    assert len(cache.records()) <= 4


def test_deny_cache_record_refresh_defers_eviction():
    """Write-record eviction is FIFO by LAST write, not first insert:
    a hot key refreshed moments ago must outlive cold-tail churn."""
    cache = DenyCache(2)
    _prime(cache, key="hot")
    _prime(cache, key="cold1")
    # Refresh the hot key's write record (a new allowed observation).
    cache.observe("hot", 3, 60, 60, 1, T0 + NS, True, seq=10,
                  cur_ns=T0 + 3 * NS)
    # Cold churn evicts ONE record: it must be cold1, not hot.
    _prime(cache, key="cold2")
    assert cache.record("hot") is not None
    assert cache.record("cold1") is None


def test_deny_cache_sweep_drops_expired():
    cache = DenyCache(64)
    em, tol, inc, tat = _prime(cache)
    assert len(cache) == 1
    before = cache.stale_evictions
    # The bucket's true expiry is tat + tol (writer's TTL).
    n = cache.on_sweep(tat + tol + 1)
    assert n == 1 and len(cache) == 0
    assert cache.stale_evictions == before + 1
    assert cache.lookup("u", 3, 60, 60, 3, T0 + 2) is None


def test_front_metrics_exported():
    metrics = Metrics()
    front = make_front(metrics=metrics)
    metrics.set_front_stats_provider(front.stats)
    metrics.record_front_hit()
    metrics.record_front_shed(peek=True)
    metrics.record_front_shed(peek=False)
    metrics.record_front_stale(3)
    text = metrics.export_prometheus()
    assert "throttlecrab_tpu_front_deny_hits 1" in text
    assert 'throttlecrab_tpu_front_shed{class="peek"} 1' in text
    assert 'throttlecrab_tpu_front_shed{class="consume"} 1' in text
    assert "throttlecrab_tpu_front_stale_evictions 3" in text
    assert "throttlecrab_tpu_front_deny_cache_size 0" in text


def test_config_front_knobs_validated():
    from throttlecrab_tpu.server.config import Config, ConfigError
    from throttlecrab_tpu.server.store import create_front_tier

    with pytest.raises(ConfigError):
        Config(front_peek_frac=0.0).validate()
    with pytest.raises(ConfigError):
        Config(front_deny_cache=-1).validate()
    limiter = TpuRateLimiter(capacity=64)
    cfg = Config()
    front = create_front_tier(cfg, None, limiter)
    assert front is not None
    assert front.deny_cache is not None and front.admission is not None
    off = Config(front_deny_cache=0, front_max_pending=0,
                 front_max_wait_us=0)
    assert create_front_tier(off, None, limiter) is None
