"""Spans and stage clocks on the JAX profiler's timeline.

`span(name, clock=None)` opens a `jax.profiler.TraceAnnotation(name)`:
inside a profiler trace it is a host event on the profiler's own clock,
the clock the device trace is on, so an idle gap on the chip lines up
with the host stage that made it.  Outside a trace it costs well under a
microsecond.  Names follow `<layer>.<stage>` (`driver.fetch`,
`keymap.prepare`); `gcra_` names only device programs.

Where a `StageClock` is given, the span also adds its wall time to that
process-wide total, which GET /metrics exports as a counter.  This
module holds the clocks of its own layer's stages: the keymap's
(`KEYMAP_SECONDS`) and the fetch's (`FETCH_SECONDS`).  Each thread adds
to a cell of its own, so the hot path takes no lock; a scrape sums the
cells, so where two threads run one stage at once its clock advances at
twice the wall clock.

`install_gc_span(clock)` marks every garbage collection in the process
as a `process.gc` span, in whatever thread runs it, and times it on
`clock`.

`capture_first_launches(dir)` is `THROTTLECRAB_PROFILE_DIR`: an xprof
trace of the process's first launches, on whichever path launches them
(the asyncio engine and the native drivers both call `launch_tick()`).

Usage:
    from throttlecrab_tpu.tpu.profiling import trace, span, KEYMAP_SECONDS

    with trace("/tmp/tc-trace"):        # captures device + host timeline
        engine_work()

    with span("keymap.prepare", KEYMAP_SECONDS):
        km.prepare_batch(...)
"""

from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager

from jax import profiler as _profiler

_TraceAnnotation = _profiler.TraceAnnotation


class StageClock:
    """Seconds spent in one kind of stage, summed over the process's
    threads; exported on /metrics as the unlabelled counter `name`."""

    def __init__(self, name: str, help_: str) -> None:
        self.name = name
        self.help = help_
        self._local = threading.local()
        # One [ns] per thread that has added.  No lock: list.append and
        # tuple(list) are single steps under the GIL, and a lock here
        # could be re-entered by the GC hook in the middle of a scrape.
        self._cells: list = []

    def add_ns(self, ns: int) -> None:
        try:
            cell = self._local.cell
        except AttributeError:
            cell = self._local.cell = [0]
            self._cells.append(cell)
        cell[0] += ns

    def seconds(self) -> float:
        return sum(c[0] for c in tuple(self._cells)) / 1e9


KEYMAP_SECONDS = StageClock(
    "throttlecrab_tpu_keymap_seconds_total",
    "Wire-window time in the native keymap (spans keymap.prepare, "
    "keymap.finish)",
)
FETCH_SECONDS = StageClock(
    "throttlecrab_tpu_fetch_seconds_total",
    "Wire-window time copying launch results off the device "
    "(span driver.fetch)",
)


class span:
    """A named host span (`TraceAnnotation`); with a `clock`, also its
    wall time on that StageClock, and the monotonic_ns readings at its
    start and end as `t0` and `t1`.  Keyword arguments ride the trace
    event as metadata; the event keeps the bare name."""

    __slots__ = ("_ann", "_clock", "t0", "t1")

    def __init__(self, name: str, clock: StageClock = None, **meta) -> None:
        self._ann = _TraceAnnotation(name, **meta)
        self._clock = clock

    def __enter__(self) -> "span":
        self._ann.__enter__()
        if self._clock is not None:
            self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self._clock is not None:
            self.t1 = time.monotonic_ns()
            self._clock.add_ns(self.t1 - self.t0)
        self._ann.__exit__(*exc)


# -- garbage collection ---------------------------------------------- #

_gc_open = None  # (annotation, start ns) of the collection in progress
_gc_clock = None  # the StageClock install_gc_span was given


def _gc_hook(phase: str, info: dict) -> None:
    # Collections never nest and one runs at a time (the GIL), so one
    # slot holds the open span whatever thread runs it.
    global _gc_open
    if phase == "start":
        ann = _TraceAnnotation("process.gc")
        ann.__enter__()
        _gc_open = (ann, time.monotonic_ns())
    elif _gc_open is not None:
        ann, t0 = _gc_open
        _gc_open = None
        _gc_clock.add_ns(time.monotonic_ns() - t0)
        ann.__exit__(None, None, None)


def install_gc_span(clock: StageClock) -> None:
    """Mark every collection as a `process.gc` span timed on `clock`
    (idempotent)."""
    global _gc_clock
    _gc_clock = clock
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)


# -- THROTTLECRAB_PROFILE_DIR ------------------------------------------ #

_capture_lock = threading.Lock()
_capture = {"dir": None, "remaining": 0, "on": False}


def capture_first_launches(log_dir: str, launches: int = 50) -> None:
    """Arm an xprof trace into `log_dir` of the process's first
    `launches` launches: it starts at the first `launch_tick()` and
    stops at the one after the last, or at `stop_capture()`."""
    with _capture_lock:
        _capture.update(dir=log_dir, remaining=launches, on=False)


def launch_tick() -> None:
    """Called before each launch; free unless a capture is armed."""
    if not _capture["dir"]:
        return
    with _capture_lock:
        if _capture["remaining"] <= 0:
            _stop_locked()
            return
        if not _capture["on"]:
            _profiler.start_trace(_capture["dir"])
            _capture["on"] = True
        _capture["remaining"] -= 1


def stop_capture() -> None:
    """End an armed capture now (server shutdown); it never restarts."""
    with _capture_lock:
        _stop_locked()


def _stop_locked() -> None:
    if _capture["on"]:
        _profiler.stop_trace()
    _capture.update(dir=None, remaining=0, on=False)


@contextmanager
def trace(log_dir: str):
    """Capture a JAX profiler trace (xprof) into `log_dir`."""
    _profiler.start_trace(log_dir)
    try:
        yield
    finally:
        _profiler.stop_trace()
