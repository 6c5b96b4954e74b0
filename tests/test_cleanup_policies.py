"""Cleanup policy triggers and adaptation (tpu/cleanup.py)."""

from throttlecrab_tpu.tpu.cleanup import (
    AdaptivePolicy,
    PeriodicPolicy,
    ProbabilisticPolicy,
    make_policy,
)

NS = 1_000_000_000
BASE = 1_753_700_000 * NS


class TestPeriodic:
    def test_fires_on_interval(self):
        p = PeriodicPolicy(interval_ns=10 * NS)
        assert not p.should_clean(BASE, 0, 1000)  # seeds
        assert not p.should_clean(BASE + 9 * NS, 0, 1000)
        assert p.should_clean(BASE + 10 * NS, 0, 1000)
        p.after_sweep(BASE + 10 * NS, 5, 10)
        assert not p.should_clean(BASE + 19 * NS, 0, 1000)
        assert p.should_clean(BASE + 20 * NS, 0, 1000)


class TestProbabilistic:
    def test_fires_per_op_rule_over_ranges(self):
        # probability 10, prime ≡ 1 (mod 10) → fires when ops crosses a
        # multiple of 10.
        p = ProbabilisticPolicy(probability=10)
        p.record_ops(9)
        assert not p.should_clean(BASE, 0, 1000)
        p.record_ops(1)  # ops = 10
        assert p.should_clean(BASE, 0, 1000)
        p.after_sweep(BASE, 0, 0)
        assert not p.should_clean(BASE, 0, 1000)
        p.record_ops(25)  # crosses 20 and 30
        assert p.should_clean(BASE, 0, 1000)

    def test_batch_crossing(self):
        p = ProbabilisticPolicy(probability=1000)
        p.record_ops(999)
        assert not p.should_clean(BASE, 0, 1000)
        p.record_ops(4096)  # crosses 1000
        assert p.should_clean(BASE, 0, 1000)


class TestAdaptive:
    def test_time_trigger_and_doubling(self):
        p = AdaptivePolicy()
        start = p.current_interval_ns
        assert not p.should_clean(BASE, 0, 1 << 20)  # seeds
        t = BASE + start
        assert p.should_clean(t, 0, 1 << 20)
        p.after_sweep(t, 0, 100)  # nothing removed → interval doubles
        assert p.current_interval_ns == start * 2

    def test_halving_on_productive_sweep(self):
        p = AdaptivePolicy()
        p.should_clean(BASE, 0, 1 << 20)
        start = p.current_interval_ns
        p.after_sweep(BASE, 80, 100)  # >50% removed → halves
        assert p.current_interval_ns == max(start // 2, p.min_interval_ns)

    def test_ops_trigger(self):
        p = AdaptivePolicy(max_operations=5000)
        p.should_clean(BASE, 0, 1 << 20)
        p.record_ops(4999)
        assert not p.should_clean(BASE + 1, 0, 1 << 20)
        p.record_ops(1)
        assert p.should_clean(BASE + 1, 0, 1 << 20)

    def test_pressure_trigger(self):
        p = AdaptivePolicy()
        p.should_clean(BASE, 0, 1000)
        assert not p.should_clean(BASE + 1, 750, 1000)
        assert p.should_clean(BASE + 1, 751, 1000)

    def test_interval_clamped(self):
        p = AdaptivePolicy(min_interval_ns=NS, max_interval_ns=8 * NS)
        p.should_clean(BASE, 0, 1 << 20)
        for _ in range(10):
            p.after_sweep(BASE, 0, 0)
        assert p.current_interval_ns == 8 * NS
        for _ in range(10):
            p.after_sweep(BASE, 10, 10)
        assert p.current_interval_ns == NS


def test_factory():
    assert isinstance(make_policy("periodic"), PeriodicPolicy)
    assert isinstance(make_policy("adaptive"), AdaptivePolicy)
    assert isinstance(make_policy("probabilistic"), ProbabilisticPolicy)
    p = make_policy("periodic", cleanup_interval_secs=5)
    assert p.interval_ns == 5 * NS


class TestAdaptiveExpiredRatio:
    """The expired-ratio trigger with its dynamic threshold, mirroring
    adaptive_cleanup.rs:150-163 (and the scalar oracle's
    core/store/adaptive.py _should_clean)."""

    def _seeded(self):
        p = AdaptivePolicy()
        assert not p.should_clean(BASE, 100, 100_000)  # seeds the clock
        return p

    def test_needs_more_than_50_hits(self):
        p = self._seeded()
        p.record_expired(50)
        # ratio 50/100 = 0.5 > any threshold, but the >50 floor gates it.
        assert not p.should_clean(BASE + NS, 100, 100_000)
        p.record_expired(1)
        assert p.should_clean(BASE + NS, 100, 100_000)

    def test_dynamic_threshold_unproductive_last_sweep(self):
        p = self._seeded()
        # Unproductive history: threshold = 0.2 * 1.25 = 0.25.
        p.after_sweep(BASE, 0, 1000)
        p.record_expired(60)
        assert not p.should_clean(BASE + NS, 300, 100_000)  # 0.2 <= 0.25
        p.record_expired(40)
        assert p.should_clean(BASE + NS, 300, 100_000)  # 0.33 > 0.25

    def test_dynamic_threshold_productive_last_sweep(self):
        p = self._seeded()
        # Productive history (removed > total/4): threshold = 0.1.
        p.after_sweep(BASE, 500, 1000)
        p.record_expired(60)
        assert p.should_clean(BASE + NS, 500, 100_000)  # 0.12 > 0.1

    def test_expired_hits_block_interval_doubling(self):
        # adaptive_cleanup.rs:187: removed == 0 only relaxes the interval
        # when no traffic hit an expired entry since the last sweep.
        p = self._seeded()
        start = p.current_interval_ns
        p.record_expired(10)
        p.after_sweep(BASE, 0, 1000)
        assert p.current_interval_ns == start
        p.after_sweep(BASE, 0, 1000)  # now expired == 0 again
        assert p.current_interval_ns == start * 2
        assert p._expired == 0  # reset on sweep


def test_kernel_expired_hits_ride_the_launch():
    """The device accumulator counts exactly the reference's signal: one
    hit per segment-leading valid request that lands on a REAL stored
    entry past its expiry — never first touches, never refreshed
    entries, never later ranks of the same segment."""
    import numpy as np

    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    NSEC = NS
    lim = TpuRateLimiter(capacity=256)
    t0 = BASE
    # 10 keys with a ~6 s TTL (burst 5, count 10, period 10 s with long
    # tolerance -> expiry = tat - now + tol later; use small period).
    keys = [f"k{i}" for i in range(10)]
    lim.rate_limit_batch(keys, 5, 10, 10, 1, t0)
    assert lim.table.expired_hits() == 0  # first touches are not hits

    # Hit them again while still live: no expired hits.
    lim.rate_limit_batch(keys, 5, 10, 10, 1, t0 + NSEC)
    assert lim.table.expired_hits() == 0

    # Far future: every stored entry is now expired; duplicates in the
    # batch still count ONE hit per key (rank-0 lanes only).
    far = t0 + 10_000 * NSEC
    lim.rate_limit_batch(keys + keys, 5, 10, 10, 1, far)
    assert lim.table.expired_hits() == 10

    # The refreshed entries are live again: no further hits.
    lim.rate_limit_batch(keys, 5, 10, 10, 1, far + NSEC)
    assert lim.table.expired_hits() == 10

    # Denied requests never reach the store's write path, so a DENIED
    # request on an expired entry is NOT a hit (mapstore.py
    # set_if_not_exists only runs for allowed requests; the oracle
    # counts nothing here either).
    far2 = far + 20_000 * NSEC
    lim.rate_limit_batch(keys, 5, 10, 10, 6, far2)  # q=6 > burst: denied
    assert lim.table.expired_hits() == 10


def test_take_expired_hits_throttles_fetch():
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    lim = TpuRateLimiter(capacity=64)
    t0 = BASE
    lim.rate_limit_batch(["a", "b"], 5, 10, 10, 1, t0)
    far = t0 + 10_000 * NS
    lim.rate_limit_batch(["a", "b"], 5, 10, 10, 1, far)
    assert lim.take_expired_hits(far) == 2
    # Second read within the throttle window: no fetch, no double count.
    lim.rate_limit_batch(["a", "b"], 5, 10, 10, 1, far + 20_000 * NS)
    assert lim.take_expired_hits(far + NS // 2) == 0
    # Past the window the delta arrives.
    assert lim.take_expired_hits(far + 2 * NS) == 2


def test_sharded_expired_hits_ride_the_counters():
    import numpy as np

    from conftest import require_devices
    from throttlecrab_tpu.parallel.sharded import (
        ShardedTpuRateLimiter,
        make_mesh,
    )

    require_devices(2)
    lim = ShardedTpuRateLimiter(capacity_per_shard=64, mesh=make_mesh(2))
    keys = [f"k{i}" for i in range(8)]
    t0 = BASE
    lim.rate_limit_batch(keys, 5, 10, 10, 1, t0)
    assert lim.take_expired_hits() == 0
    lim.rate_limit_batch(keys, 5, 10, 10, 1, t0 + 10_000 * NS)
    assert lim.take_expired_hits() == 8
    assert lim.take_expired_hits() == 0  # drained


def test_boot_compiles_the_sweep_before_serving():
    """A server with a cleanup policy compiles its table's sweep at
    boot: once it serves, its first sweep compiles nothing (JAX's own
    compile events, as the benchmark's CompileWatch reads them), and the
    boot's sweep freed no key.  The capacity is one no other test uses,
    so no earlier compile in this process can stand in for the boot's."""
    import asyncio
    import os
    import signal
    import socket
    import time

    import jax

    import throttlecrab_tpu.server.__main__ as server_main
    from benchmark.harness import CompileWatch
    from throttlecrab_tpu.server.config import Config

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    built = []
    create = server_main.create_limiter

    def capture(config):
        built.append(create(config))
        return built[-1]

    async def health() -> bytes:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /health HTTP/1.1\r\nHost: x\r\n"
                     b"Connection: close\r\n\r\n")
        await writer.drain()
        raw = await reader.read(-1)
        writer.close()
        return raw

    async def boot_then_sweep():
        cfg = Config(http=True, http_host="127.0.0.1", http_port=port,
                     store="adaptive", store_capacity=3000)
        task = asyncio.create_task(server_main.run_server(cfg))
        for _ in range(600):
            if task.done():
                task.result()  # surface boot failures
            try:
                if b"OK" in await health():
                    break
            except OSError:
                await asyncio.sleep(0.05)
        limiter = built[0]
        assert len(limiter) == 0
        watch = CompileWatch()
        t0 = time.monotonic_ns()
        try:
            freed = limiter.sweep(time.time_ns())
            compiled = watch.since(t0)
        finally:
            jax.monitoring.unregister_scalar_listener(watch._opened)
            jax.monitoring.unregister_event_duration_listener(watch._closed)
        os.kill(os.getpid(), signal.SIGINT)
        await asyncio.wait_for(task, timeout=60)
        return freed, compiled

    server_main.create_limiter = capture
    try:
        freed, compiled = asyncio.run(boot_then_sweep())
    finally:
        server_main.create_limiter = create
    assert freed == 0
    assert compiled == []
