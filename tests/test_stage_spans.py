"""Spans and stage clocks of the native driver loop (tpu/profiling.py):
the /metrics counters account for the driver thread's time, and under a
profiler trace every stage of a decided window is a named host span
inside its `driver.window`."""

import asyncio
import gc
import time
from pathlib import Path

import jax
import pytest

from throttlecrab_tpu.front import AdmissionController, DenyCache, FrontTier
from throttlecrab_tpu.insight import InsightTier
from throttlecrab_tpu.native import native_available, wire_available
from throttlecrab_tpu.server.metrics import GC_SECONDS, Metrics
from throttlecrab_tpu.tpu import profiling
from throttlecrab_tpu.tpu.cleanup import make_policy
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

pytestmark = pytest.mark.skipif(
    not (wire_available() and native_available()),
    reason="no C++ toolchain for the wire server and keymap",
)

T0 = 1_700_000_000 * 1_000_000_000
COUNTERS = (
    "throttlecrab_tpu_wire_wait_seconds_total",
    "throttlecrab_tpu_driver_busy_seconds_total",
    "throttlecrab_tpu_front_seconds_total",
    "throttlecrab_tpu_keymap_seconds_total",
    "throttlecrab_tpu_fetch_seconds_total",
    "throttlecrab_tpu_cleanup_seconds_total",
    "throttlecrab_process_gc_seconds_total",
)
STAGES = (
    "wire.capture", "front.filter", "keymap.prepare", "driver.launch",
    "driver.fetch", "keymap.finish", "front.observe", "driver.merge",
    "recorder.capture", "wire.respond", "metrics.record", "insight.poll",
    "control.tick", "checkpoint.tick", "cleanup.sweep",
)


class _Periodic:
    """The insight poll, control tick and checkpointer as the driver
    calls them; each call does nothing."""

    def maybe_poll(self, now_ns, lock):
        pass

    def maybe_tick(self, now_ns, lock):
        pass

    def note_keys(self, keys):
        for _ in keys:
            pass


def make_transport(limiter=None, **kwargs):
    from throttlecrab_tpu.server.native_redis import NativeRedisTransport

    metrics = Metrics(max_denied_keys=10)
    if limiter is None:
        limiter = TpuRateLimiter(capacity=1024, keymap="native")
    front = FrontTier(
        DenyCache(1024), AdmissionController(max_pending=10**6),
        metrics=metrics, bytes_keys=True,
    )
    transport = NativeRedisTransport(
        "127.0.0.1", 0, limiter, metrics, batch_size=64, max_linger_us=500,
        now_fn=lambda: T0, front=front, **kwargs,
    )
    return transport, metrics


def scrape(metrics) -> dict:
    """Unlabelled samples of the /metrics text, as a scraper reads it."""
    out = {}
    for line in metrics.export_prometheus().splitlines():
        name, _, value = line.partition(" ")
        if name and not name.startswith("#") and "{" not in name:
            out[name] = float(value)
    return out


def _frame(key: str) -> bytes:
    parts = [b"THROTTLE", key.encode(), b"3", b"10", b"60"]
    return b"*5\r\n" + b"".join(b"$%d\r\n%s\r\n" % (len(p), p) for p in parts)


async def pipeline(port: int, n: int, prefix: str, keys: int = 40) -> int:
    """`n` pipelined THROTTLEs over `keys` fresh keys (burst 3: most
    repeat requests are denied, so the deny cache answers some); returns
    the replies read."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    frames = [_frame(f"{prefix}{i % keys}") for i in range(n)]
    writer.write(b"".join(frames))
    await writer.drain()
    data = b""
    while data.count(b"*5\r\n") < n:
        chunk = await asyncio.wait_for(reader.read(1 << 16), timeout=60.0)
        if not chunk:
            break
        data += chunk
    writer.close()
    return data.count(b"*5\r\n")


def test_stage_counters_account_for_the_driver_thread():
    async def main():
        transport, metrics = make_transport()
        seen = [scrape(metrics)]
        t_start = time.monotonic()
        await transport.start()
        for i in range(3):
            port = transport.bound_port
            assert await pipeline(port, 200, f"c{i}:") == 200
            seen.append(scrape(metrics))
        await asyncio.sleep(0.3)
        seen.append(scrape(metrics))
        wall = time.monotonic() - t_start
        await transport.stop()
        return seen, wall, metrics

    seen, wall, metrics = asyncio.run(main())
    assert metrics.device_launches > 0
    for name in COUNTERS:
        readings = [s[name] for s in seen]  # present, unlabelled
        assert readings == sorted(readings), name
    d = {name: seen[-1][name] - seen[0][name] for name in COUNTERS}
    # Wait and busy split the driver thread's life from its loop's start
    # to its latest wake-up: at most a linger and the thread's start-up
    # short of the wall time since start().
    accounted = d["throttlecrab_tpu_wire_wait_seconds_total"] + d[
        "throttlecrab_tpu_driver_busy_seconds_total"]
    assert wall - 0.1 <= accounted <= wall + 1e-3
    stages = (d["throttlecrab_tpu_front_seconds_total"]
              + d["throttlecrab_tpu_keymap_seconds_total"]
              + d["throttlecrab_tpu_fetch_seconds_total"])
    assert stages <= d["throttlecrab_tpu_driver_busy_seconds_total"]
    for name in ("throttlecrab_tpu_front_seconds_total",
                 "throttlecrab_tpu_keymap_seconds_total",
                 "throttlecrab_tpu_fetch_seconds_total"):
        assert d[name] > 0, name


def test_a_collection_advances_the_gc_counter():
    profiling.install_gc_span(GC_SECONDS)
    profiling.install_gc_span(GC_SECONDS)
    assert gc.callbacks.count(profiling._gc_hook) == 1
    before = scrape(Metrics())["throttlecrab_process_gc_seconds_total"]
    gc.collect()
    after = scrape(Metrics())["throttlecrab_process_gc_seconds_total"]
    assert after > before


def _host_lines(trace_dir: Path):
    from benchmark import trace_reduce

    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    profile = trace_reduce.load(path)
    return [line for plane in profile.planes
            if plane.name.startswith("/host:CPU") for line in plane.lines]


def test_every_stage_is_a_span_inside_its_window(tmp_path):
    """Under a CPU profiler trace: each stage's bare name on the driver
    thread's line, each inside a driver.window of the same line."""
    profiling.install_gc_span(GC_SECONDS)
    periodic = _Periodic()

    async def main():
        transport, _ = make_transport(
            insight=periodic, control=periodic, checkpointer=periodic,
            cleanup_policy=make_policy("periodic", cleanup_interval_secs=60),
        )
        await transport.start()
        assert await pipeline(transport.bound_port, 200, "w:") == 200
        # The trace opens and closes while the driver waits: no window
        # straddles either end.
        await asyncio.sleep(0.3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            assert await pipeline(transport.bound_port, 200, "t:") == 200
            gc.collect()
            await asyncio.sleep(0.3)
        finally:
            jax.profiler.stop_trace()
        await transport.stop()

    trace_dir = tmp_path / "trace"
    asyncio.run(main())
    lines = _host_lines(trace_dir)
    driver = [line for line in lines
              if any(ev.name == "driver.window" for ev in line.events)]
    assert len(driver) == 1
    events = [(ev.name, int(ev.start_ns),
               int(ev.start_ns + ev.duration_ns)) for ev in driver[0].events]
    windows = [(s, e) for name, s, e in events if name == "driver.window"]
    names = {name for name, _, _ in events}
    assert "wire.next_batch" in names
    assert not any("#" in name for name in names)
    for stage in STAGES:
        spans = [(s, e) for name, s, e in events if name == stage]
        assert spans, stage
        for s, e in spans:
            assert any(w0 <= s and e <= w1 for w0, w1 in windows), stage
    assert any(ev.name == "process.gc" for line in lines
               for ev in line.events)


def test_insight_resolve_is_a_span_inside_the_poll(tmp_path):
    """A real insight tier on the native driver, traced: each poll's
    slot→key resolution is an `insight.resolve` span inside its
    `insight.poll`, and the poll names the hot keys."""
    limiter = TpuRateLimiter(capacity=1024, keymap="native", insight=True)
    insight = InsightTier(limiter=limiter, poll_ms=1, decay_s=0.0)
    t_start = time.monotonic_ns()

    async def main():
        transport, _ = make_transport(limiter=limiter, insight=insight)
        transport.now_fn = lambda: T0 + time.monotonic_ns() - t_start
        await transport.start()
        assert await pipeline(transport.bound_port, 200, "w:") == 200
        await asyncio.sleep(0.3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            assert await pipeline(transport.bound_port, 200, "t:") == 200
            await asyncio.sleep(0.3)
        finally:
            jax.profiler.stop_trace()
        await transport.stop()

    trace_dir = tmp_path / "trace"
    asyncio.run(main())
    events = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
              for line in _host_lines(trace_dir) for ev in line.events]
    polls = [(s, e) for name, s, e in events if name == "insight.poll"]
    resolves = [(s, e) for name, s, e in events if name == "insight.resolve"]
    assert resolves
    for s, e in resolves:
        assert any(p0 <= s and e <= p1 for p0, p1 in polls)
    assert insight.poll_failures == 0
    top = {row["key"] for row in insight.stats(state="ok")["top_denied"]}
    assert top and all(k.startswith(("w:", "t:")) for k in top)


SWEEP_STAGES = ("cleanup.drain", "cleanup.compact", "cleanup.free",
                "cleanup.front")


def test_a_sweep_is_spanned_and_counted(tmp_path):
    """A sweep under a profiler trace: its stages are spans inside
    `cleanup.sweep`, inside a `driver.window`; the cleanup clock, the
    sweep counters and the keymap's arena gauge move on /metrics.  The
    clock jumps a minute a window, so every bucket (burst 3, 10 per
    60 s: 12 s of tolerance) has expired at the next window's sweep."""
    clock = iter(range(T0, T0 + 10**6 * 60 * 10**9, 60 * 10**9))

    async def main():
        transport, metrics = make_transport(
            cleanup_policy=make_policy("adaptive", max_operations=1),
        )
        transport.now_fn = lambda: next(clock)
        before = scrape(metrics)
        await transport.start()
        assert await pipeline(transport.bound_port, 200, "s:") == 200
        await asyncio.sleep(0.3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            assert await pipeline(transport.bound_port, 200, "u:") == 200
            await asyncio.sleep(0.3)
        finally:
            jax.profiler.stop_trace()
        await transport.stop()
        return before, scrape(metrics)

    trace_dir = tmp_path / "trace"
    before, after = asyncio.run(main())
    d = {k: after[k] - before[k] for k in after if k in before}
    assert d["throttlecrab_tpu_sweeps"] >= 2
    assert d["throttlecrab_tpu_slots_freed"] > 0
    assert d["throttlecrab_tpu_sweep_occupied_total"] >= \
        d["throttlecrab_tpu_slots_freed"]
    assert d["throttlecrab_tpu_cleanup_seconds_total"] > 0
    assert after["throttlecrab_tpu_keymap_arena_bytes"] > 0
    events = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
              for line in _host_lines(trace_dir) for ev in line.events]
    sweeps = [(s, e) for name, s, e in events if name == "cleanup.sweep"]
    windows = [(s, e) for name, s, e in events if name == "driver.window"]
    assert all(any(w0 <= s and e <= w1 for w0, w1 in windows)
               for s, e in sweeps)
    for stage in SWEEP_STAGES:
        spans = [(s, e) for name, s, e in events if name == stage]
        assert spans, stage
        for s, e in spans:
            assert any(w0 <= s and e <= w1 for w0, w1 in sweeps), stage


def test_stage_clock_loses_no_add_between_threads():
    """Threads add to one clock without a lock, each to its own cell:
    with more threads than cores and a short switch interval, the total
    is still every add."""
    import os
    import sys
    import threading

    clock = profiling.StageClock("stress_seconds_total", "stress")
    n_threads = 4 * (os.cpu_count() or 1)
    adds = 5_000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(
                target=lambda: [clock.add_ns(3) for _ in range(adds)])
            for _ in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert clock.seconds() == n_threads * adds * 3 / 1e9


def test_profile_dir_traces_the_native_path(tmp_path):
    """THROTTLECRAB_PROFILE_DIR's capture starts at the first launch of
    whichever transport launches: here the native RESP driver."""

    async def main():
        transport, _ = make_transport()
        await transport.start()
        profiling.capture_first_launches(str(tmp_path), launches=2)
        try:
            assert await pipeline(transport.bound_port, 200, "p:") == 200
        finally:
            profiling.stop_capture()
        await transport.stop()

    asyncio.run(main())
    names = {ev.name for line in _host_lines(tmp_path) for ev in line.events}
    assert {"driver.launch", "driver.fetch"} <= names
