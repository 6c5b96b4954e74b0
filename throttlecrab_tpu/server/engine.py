"""Micro-batching engine: the TPU replacement for the reference's actor.

The reference serializes every request through one mpsc channel into a
single-threaded actor that decides them one at a time
(`actor.rs:102-236`).  Here the same funnel point instead *coalesces*:
requests from every transport append to a pending list with a future; a
flush (triggered by the batch filling or a linger deadline) stamps the batch
with one server-side timestamp, resolves keys, runs the batched device
kernel, and completes every future.  The two tunables — `batch_size` and
`max_linger_us` — are the throughput/latency knob pair that the actor's
`buffer_size` becomes.

Decisions execute on a worker thread (one at a time, preserving the actor's
sequential-state guarantee) so the event loop keeps accepting requests while
the device is busy — the host/device pipeline is the analog of the
reference's transport-task/actor-task split.

Cleanup runs between batches: the engine consults a `CleanupPolicy`
(tpu/cleanup.py — periodic / probabilistic / adaptive, the reference's three
store flavors) and triggers the expiry-compaction sweep on the device.

Failure domains: launch supervision lives in the limiter wrapper
(server/supervisor.py) shared with the native drivers — a launch
exception reaching this engine's except-branches means the supervisor
already retried transient faults and either degraded to the host oracle
(in which case the "launch" succeeds against it and no exception
arrives) or classified the failure as deterministic/unrecoverable, so
failing the window's futures is the correct terminal answer.  The
engine surfaces the supervisor's state machine through `health_state()`
(GET /health).
"""

from __future__ import annotations

import asyncio
from typing import Optional

from ..front import OverloadError  # re-exported for the transports
from ..tpu.cleanup import CleanupPolicy
from ..tpu.limiter import (
    STATUS_DEADLINE,
    STATUS_INTERNAL,
    STATUS_INVALID_PARAMS,
    STATUS_NEGATIVE_QUANTITY,
    STATUS_OK,
    STATUS_TENANT_QUOTA,
)
from ..tpu.profiling import launch_tick, span
from .types import ThrottleRequest, ThrottleResponse

__all__ = [
    "BatchingEngine", "DeadlineError", "OverloadError", "ThrottleError",
]

STATUS_MESSAGES = {
    STATUS_NEGATIVE_QUANTITY: "quantity cannot be negative",
    STATUS_INVALID_PARAMS: "invalid rate limit parameters",
    STATUS_INTERNAL: "internal error",
    STATUS_TENANT_QUOTA: "tenant capacity quota exceeded",
    STATUS_DEADLINE: "deadline exceeded",
}


class ThrottleError(Exception):
    """Per-request validation failure, mapped by each transport to its
    protocol's error shape (the reference returns 500 JSON / gRPC
    Status::internal / RESP -ERR)."""


class DeadlineError(ThrottleError):
    """The request outlived its client deadline while queued: shed
    before device dispatch.  Each transport maps it to its protocol's
    timeout shape (HTTP 504 / gRPC DEADLINE_EXCEEDED / RESP -ERR)."""


class BatchingEngine:
    """Coalesces transport requests into device batches."""

    def __init__(
        self,
        limiter,
        batch_size: int = 4096,
        max_linger_us: int = 200,
        cleanup_policy: Optional[CleanupPolicy] = None,
        metrics=None,
        now_fn=None,
        max_scan_depth: int = 16,
        front=None,
        insight=None,
        control=None,
        deadline_default_ms: int = 0,
        checkpointer=None,
    ) -> None:
        """`limiter` is a TpuRateLimiter / ShardedTpuRateLimiter (or any
        object with rate_limit_batch + sweep).  `now_fn` injects time for
        tests (time is an input, never ambient — rate_limiter.rs:109).
        `max_scan_depth` caps backlog sub-batches decided per launch.
        `front` is an optional front.FrontTier (L3.5): requests are run
        through its admission control (shed with OverloadError instead
        of queueing unboundedly) and its exact deny cache (repeat
        denials answered without a device launch) before they ever
        reach the pending queue.  `insight` is an optional
        insight.InsightTier (L3.75): the engine drives its throttled
        device poll between flushes (on the executor — the poll fetch
        synchronizes with in-flight launches) and serves its document
        on GET /stats.  `control` is an optional control.ControlPlane
        (L3.9): the engine drives its throttled tick between flushes
        under the same discipline (None — the default — means no
        sensor read and no knob ever moves).  `deadline_default_ms` > 0
        stamps that default deadline on requests that did not carry
        one (0 — the default — stamps nothing)."""
        import threading
        import time

        self.limiter = limiter
        self.front = front
        self.insight = insight
        self.control = control
        #: Optional persist.Checkpointer: decided windows mark their
        #: keys dirty (host-side set insert — the device hot loop is
        #: untouched) and the housekeeping path drives its throttled
        #: tick, same discipline as insight/control.
        self.checkpointer = checkpointer
        # Serializes device access with native transports that drive the
        # same limiter from their own threads (server/native_redis.py).
        self.limiter_lock = threading.Lock()
        # Serving always wants the wire fast path (compact i32 whole-second
        # outputs + degenerate-case certification) when the limiter offers
        # it; fall back gracefully for duck-typed limiters that don't.
        # Checked per method — a limiter may support wire on one but not
        # the other.
        import inspect

        # A deny-caching front tier needs the exact observed-TAT plane
        # (result.cur_ns) to certify entries — ask limiters that support
        # it to collect it (they trade the w32 tier's halved fetch for
        # the cur tier's TAT plane; decisions are identical).
        want_cur = front is not None and front.deny_cache is not None

        def wire_kw(fn):
            try:
                params = inspect.signature(fn).parameters
            except (TypeError, ValueError):
                return {}
            kw = {}
            if "wire" in params:
                kw["wire"] = True
            if want_cur and "collect_cur" in params:
                kw["collect_cur"] = True
            return kw

        self._wire_kw = wire_kw(limiter.rate_limit_batch)
        self._wire_many_kw = wire_kw(
            getattr(limiter, "rate_limit_many", None)
        )
        self.batch_size = batch_size
        self.max_linger_s = max_linger_us / 1e6
        self.cleanup_policy = cleanup_policy
        self.metrics = metrics
        self.now_fn = now_fn or time.time_ns
        self.max_scan_depth = max_scan_depth
        from collections import deque

        # deque: the flush loop pops whole windows from the left while
        # transports append on the right — the old list paid O(n) element
        # shifting per launch (`del pending[:take]`).
        self._pending: deque = deque()
        self._flush_handle: Optional[asyncio.TimerHandle] = None
        self._flush_lock = asyncio.Lock()
        self._closed = False
        #: Draining (graceful shutdown): new requests shed with
        #: OverloadError while queued ones still resolve with real
        #: decisions; /health reports "draining" so balancers de-route.
        self._draining = False
        self.deadline_default_ms = int(deadline_default_ms)
        #: ClusterLimiter advertises this: forwards can carry each
        #: row's remaining deadline budget to the owning node.
        self._limiter_takes_deadlines = bool(
            getattr(limiter, "accepts_deadlines", False)
        )
        # Shed diagnostics (exported by metrics as *_total counters).
        self.drain_shed = 0
        self.deadline_shed = 0
        # Strong refs: the event loop only weakly references tasks, and a
        # GC'd flush task would strand its batch's futures forever.
        self._flush_tasks: set = set()

    # ------------------------------------------------------------------ #

    async def throttle(self, request: ThrottleRequest) -> ThrottleResponse:
        """Decide one request; resolves when its batch comes back.

        With a front tier attached the request first consults the deny
        cache (a provably exact repeat denial returns immediately — no
        queue slot, no device launch), then passes admission control
        (OverloadError when shed — each transport maps it to its
        protocol's overload status).  Cache hits bypass admission on
        purpose: they never occupy the queue the controller protects,
        and under the abuse traffic that fills the queue they are the
        relief valve, not the load."""
        if self._closed:
            raise ThrottleError("engine is shut down")
        if self._draining:
            # Graceful drain: the listener may race a few last arrivals
            # in; they are shed as overload (503) while already-queued
            # requests still get real decisions.
            self.drain_shed += 1
            if self.metrics is not None:
                self.metrics.record_drain_shed()
            raise OverloadError("server draining")
        if request.deadline_ns is None and self.deadline_default_ms > 0:
            request.deadline_ns = (
                self.now_fn() + self.deadline_default_ms * 1_000_000
            )
        front = self.front
        if front is not None:
            hit = front.lookup(
                request.key, request.max_burst, request.count_per_period,
                request.period, request.quantity, self.now_fn(),
            )
            if hit is not None:
                return ThrottleResponse(
                    allowed=False,
                    limit=hit.limit,
                    remaining=hit.remaining,
                    reset_after=hit.reset_after_s,
                    retry_after=hit.retry_after_s,
                )
            if not front.admit(len(self._pending), request.quantity == 0):
                raise OverloadError()
            # From here until this request's result is observed, same-key
            # lookups must miss (we may be about to mutate the bucket).
            front.begin_inflight(request.key)
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending.append((request, fut))
        if len(self._pending) == self.batch_size:
            # Threshold crossing: one flush task drains everything pending,
            # so later arrivals must not spawn redundant tasks.
            self._schedule_flush(loop)
        elif self._flush_handle is None:
            self._flush_handle = loop.call_later(
                self.max_linger_s, self._linger_fired, loop
            )
        return await fut

    def _linger_fired(self, loop) -> None:
        self._flush_handle = None
        if self._pending:
            self._schedule_flush(loop)

    def _schedule_flush(self, loop) -> None:
        if self._flush_handle is not None:
            self._flush_handle.cancel()
            self._flush_handle = None
        task = loop.create_task(self._flush())
        self._flush_tasks.add(task)
        task.add_done_callback(self._flush_tasks.discard)

    async def _flush(self) -> None:
        """Decide everything pending (in arrival order).

        A backlog deeper than one batch drains through the scan path — up
        to max_scan_depth full batches in a single device launch
        (limiter.rate_limit_many), amortizing the fixed dispatch cost.

        When the limiter offers the dispatch/fetch split (dispatch_many),
        the loop double-buffers: window N+1 is assembled and dispatched
        while the device still executes window N, and only then are N's
        results fetched — the host assembly cost hides behind device time
        instead of adding to it (SURVEY §7.4 hard part 3)."""
        can_scan = hasattr(self.limiter, "rate_limit_many")
        can_async = hasattr(self.limiter, "dispatch_many")
        async with self._flush_lock:
            if not can_async:
                while self._pending:
                    windows = self._take_windows(can_scan)
                    if len(windows) > 1:
                        await self._decide_many(windows)
                    else:
                        await self._decide(windows[0])
                return

            loop = asyncio.get_running_loop()
            in_flight = None  # (windows, handle, now_ns)
            while self._pending or in_flight is not None:
                windows = self._take_windows(can_scan)
                launched = None
                if windows:
                    now_ns = self.now_fn()
                    launch_tick()
                    dl_kw = self._deadline_many_kw(windows)

                    def do_dispatch(ws=windows, t=now_ns, dk=dl_kw):
                        with self.limiter_lock, span("engine.dispatch"):
                            # Dispatch-order stamp for the deny cache:
                            # taken under the same lock that serializes
                            # device launches across transports, so seq
                            # order == launch order.
                            seq = (
                                self.front.next_seq() if self.front else 0
                            )
                            return seq, self.limiter.dispatch_many(
                                [
                                    (
                                        [r.key for r, _ in w],
                                        [r.max_burst for r, _ in w],
                                        [
                                            r.count_per_period
                                            for r, _ in w
                                        ],
                                        [r.period for r, _ in w],
                                        [r.quantity for r, _ in w],
                                        t,
                                    )
                                    for w in ws
                                ],
                                **self._wire_many_kw,
                                **dk,
                            )

                    try:
                        seq, handle = await loop.run_in_executor(
                            None, do_dispatch
                        )
                        launched = (windows, handle, now_ns, seq)
                    except Exception as exc:
                        self._fail_windows(windows, exc)

                if in_flight is not None:
                    await self._fetch_complete(in_flight)
                in_flight = launched
            return

    def _take_windows(self, can_scan: bool) -> list:
        """Pop up to max_scan_depth × batch_size pending requests, chunked
        into batch-sized windows (arrival order preserved).  Requests
        whose client deadline already lapsed are shed HERE — before any
        device dispatch — with DeadlineError (HTTP 504 / gRPC
        DEADLINE_EXCEEDED / RESP -ERR per transport)."""
        if not self._pending:
            return []
        n_batches = (
            min(
                max(len(self._pending) // self.batch_size, 1),
                self.max_scan_depth,
            )
            if can_scan
            else 1
        )
        take = min(n_batches * self.batch_size, len(self._pending))
        flat = [self._pending.popleft() for _ in range(take)]
        if any(r.deadline_ns is not None for r, _ in flat):
            now_ns = self.now_fn()
            live = []
            shed = []
            for r, fut in flat:
                if r.deadline_ns is not None and r.deadline_ns <= now_ns:
                    shed.append((r, fut))
                else:
                    live.append((r, fut))
            if shed:
                self.deadline_shed += len(shed)
                if self.metrics is not None:
                    self.metrics.record_deadline_shed(len(shed))
                front = self.front
                if front is not None and front.deny_cache is not None:
                    # The rows never reach a launch: release their
                    # in-flight holds (the shed-path twin the native
                    # driver's _front_filter uses), nothing to fail.
                    norm = [
                        k
                        for r, _ in shed
                        if (k := front._norm_key(r.key)) is not None
                    ]
                    front.release_window(norm)
                for r, fut in shed:
                    if not fut.done():
                        fut.set_exception(
                            DeadlineError(STATUS_MESSAGES[STATUS_DEADLINE])
                        )
            flat = live
        return [
            flat[i : i + self.batch_size]
            for i in range(0, take, self.batch_size)
            if flat[i : i + self.batch_size]
        ]

    def _deadline_many_kw(self, windows) -> dict:
        """Per-window remaining-deadline columns for a deadline-aware
        limiter (ClusterLimiter: forwards carry the budget so a
        hop-chained request can't outlive its client).  Empty dict —
        byte-identical legacy call — when the limiter doesn't take them
        or no request in the flush carries one."""
        if not self._limiter_takes_deadlines:
            return {}
        if not any(
            r.deadline_ns is not None for w in windows for r, _ in w
        ):
            return {}
        return {
            "deadlines": [
                [r.deadline_ns or 0 for r, _ in w] for w in windows
            ]
        }

    def _fail_windows(self, windows, exc) -> None:
        front = self.front
        if front is not None and front.deny_cache is not None:
            # The launch may have COMMITTED before the failure (a fetch
            # error lands here too): release the holds and drop the
            # keys' cached denials/write records — an unobserved allow
            # may have moved their TATs.
            front.fail_window(
                [r.key for window in windows for r, _ in window]
            )
        for window in windows:
            for _, fut in window:
                if not fut.done():
                    fut.set_exception(ThrottleError(str(exc)))

    def _record_windows(self, windows, results, now_ns) -> None:
        """Flight-recorder capture (replay/): one call per decided
        window — runs on the executor, off the event loop."""
        from ..replay.recorder import active_recorder
        from ..replay.trace import SOURCE_ENGINE

        rec = active_recorder()
        if rec is None:
            return
        for window, result in zip(windows, results):
            rec.record_window(
                now_ns,
                [r.key for r, _ in window],
                [
                    (r.max_burst, r.count_per_period, r.period, r.quantity)
                    for r, _ in window
                ],
                result.allowed,
                result.status,
                source=SOURCE_ENGINE,
            )

    async def _maybe_record(self, windows, results, now_ns) -> None:
        """Per-batch capture hook (the fault hooks' one-None-check
        discipline when disarmed; armed captures hop to the executor so
        trace encoding never runs on the event loop)."""
        from ..replay.recorder import active_recorder

        if active_recorder() is None:
            return
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, self._record_windows, windows, results, now_ns
        )

    def _observe_window(self, window, result, now_ns, seq) -> None:
        """Feed one decided window's rows to the front tier (in arrival
        order): allowed rows invalidate/refresh write records, denied
        rows may certify deny-cache entries, and every row releases its
        in-flight hold."""
        front = self.front
        cur = getattr(result, "cur_ns", None)
        wire = hasattr(result, "reset_after_s")
        # One C-level tolist() per plane instead of a numpy scalar
        # round trip per row — per-element int(arr[i]) is ~10x the cost
        # and this loop runs once per engine-decided request.
        status_l = result.status.tolist()
        allowed_l = result.allowed.tolist()
        cur_l = cur.tolist() if cur is not None else None
        if cur_l is not None or wire:
            # Bulk path (one cache-lock acquisition per window, the
            # native driver's twin): a row's cur_ns is None on the
            # whole-second tiers — allowed rows still invalidate,
            # denials can't certify there — and a non-OK row never
            # reached the table, so it rides along as an
            # uncertifiable denial purely to release its hold.
            rows = []
            for i, (r, _) in enumerate(window):
                k = front._norm_key(r.key)
                if k is None:
                    continue  # begin_inflight was a no-op for it too
                ok = status_l[i] == STATUS_OK
                rows.append((
                    k, r.max_burst, r.count_per_period, r.period,
                    r.quantity, ok and bool(allowed_l[i]),
                    cur_l[i] if (ok and cur_l is not None) else None,
                ))
            front.observe_window(rows, now_ns, seq)
            return
        # Full-nanosecond planes: per-row observe — the exact TAT is
        # reconstructed from reset/retry, which the bulk rows don't
        # carry.
        for i, (r, _) in enumerate(window):
            try:
                if status_l[i] != STATUS_OK:
                    continue
                front.observe(
                    r.key, r.max_burst, r.count_per_period, r.period,
                    r.quantity, now_ns, bool(allowed_l[i]), seq,
                    reset_after_ns=int(result.reset_after_ns[i]),
                    retry_after_ns=int(result.retry_after_ns[i]),
                )
            finally:
                front.end_inflight(r.key)

    def _note_dirty(self, windows) -> None:
        """Mark every decided key dirty for the next checkpoint delta
        (host-side set insert; rides the same post-decision path as the
        front-tier observe so the device hot loop is untouched)."""
        ck = self.checkpointer
        if ck is not None:
            ck.note_keys(r.key for w in windows for r, _ in w)

    async def _fetch_complete(self, in_flight) -> None:
        """Fetch an in-flight launch's results and resolve its futures."""
        windows, handle, now_ns, seq = in_flight
        loop = asyncio.get_running_loop()
        import time

        t0 = time.monotonic()
        try:
            results = await loop.run_in_executor(None, handle.fetch)
        except Exception as exc:
            self._fail_windows(windows, exc)
            return
        elapsed = time.monotonic() - t0
        total = 0
        for window, result in zip(windows, results):
            total += len(window)
            self._complete(window, result)
            if self.front is not None and self.front.deny_cache is not None:
                # Admission-only fronts skip the per-row observe loop:
                # every call inside it would be a no-op.
                self._observe_window(window, result, now_ns, seq)
        self._note_dirty(windows)
        await self._maybe_record(windows, results, now_ns)
        if self.front is not None:
            self.front.record_launch(total, elapsed)
        if self.metrics is not None:
            self.metrics.record_launch(total)
        await self._maybe_sweep(now_ns, total)

    async def _decide_many(self, windows) -> None:
        """Backlog path: K sub-batches, one launch, shared timestamp."""
        import time

        now_ns = self.now_fn()
        loop = asyncio.get_running_loop()
        launch_tick()
        dl_kw = self._deadline_many_kw(windows)

        def launch():
            with self.limiter_lock, span("engine.scan_decide"):
                seq = self.front.next_seq() if self.front else 0
                return seq, self.limiter.rate_limit_many(
                    [
                        (
                            [r.key for r, _ in window],
                            [r.max_burst for r, _ in window],
                            [r.count_per_period for r, _ in window],
                            [r.period for r, _ in window],
                            [r.quantity for r, _ in window],
                            now_ns,
                        )
                        for window in windows
                    ],
                    **self._wire_many_kw,
                    **dl_kw,
                )

        t0 = time.monotonic()
        try:
            seq, results = await loop.run_in_executor(None, launch)
        except Exception as exc:
            self._fail_windows(windows, exc)
            return
        elapsed = time.monotonic() - t0

        total = 0
        for window, result in zip(windows, results):
            total += len(window)
            self._complete(window, result)
            if self.front is not None and self.front.deny_cache is not None:
                # Admission-only fronts skip the per-row observe loop:
                # every call inside it would be a no-op.
                self._observe_window(window, result, now_ns, seq)
        self._note_dirty(windows)
        await self._maybe_record(windows, results, now_ns)
        if self.front is not None:
            self.front.record_launch(total, elapsed)
        if self.metrics is not None:
            self.metrics.record_launch(total)
        await self._maybe_sweep(now_ns, total)

    async def _decide(self, batch) -> None:
        import time

        requests = [r for r, _ in batch]
        now_ns = self.now_fn()
        loop = asyncio.get_running_loop()
        launch_tick()
        dl_kw = {}
        if self._limiter_takes_deadlines and any(
            r.deadline_ns is not None for r in requests
        ):
            dl_kw = {
                "deadlines_ns": [r.deadline_ns or 0 for r in requests]
            }

        def launch():
            with self.limiter_lock, span("engine.batch_decide"):
                seq = self.front.next_seq() if self.front else 0
                return seq, self.limiter.rate_limit_batch(
                    [r.key for r in requests],
                    [r.max_burst for r in requests],
                    [r.count_per_period for r in requests],
                    [r.period for r in requests],
                    [r.quantity for r in requests],
                    now_ns,
                    **self._wire_kw,
                    **dl_kw,
                )

        t0 = time.monotonic()
        try:
            seq, result = await loop.run_in_executor(None, launch)
        except Exception as exc:  # internal failure fails the whole batch
            self._fail_windows([batch], exc)
            return

        if self.front is not None:
            self.front.record_launch(len(batch), time.monotonic() - t0)
        if self.metrics is not None:
            self.metrics.record_launch(len(batch))
        self._complete(batch, result)
        if self.front is not None and self.front.deny_cache is not None:
            self._observe_window(batch, result, now_ns, seq)
        self._note_dirty([batch])
        await self._maybe_record([batch], [result], now_ns)
        await self._maybe_sweep(now_ns, len(batch))

    @staticmethod
    def _complete(batch, result) -> None:
        """Resolve each request's future from its batch-result row."""
        wire = hasattr(result, "reset_after_s")
        for i, (_, fut) in enumerate(batch):
            if fut.done():
                continue
            status = int(result.status[i])
            if status == STATUS_TENANT_QUOTA:
                # A capacity condition, not a server bug: surface it as
                # the protocol overload status (HTTP 503 / gRPC
                # RESOURCE_EXHAUSTED / RESP -ERR) so clients can tell
                # "tenant over quota, back off" from a 500-class fault.
                fut.set_exception(
                    OverloadError(STATUS_MESSAGES[STATUS_TENANT_QUOTA])
                )
            elif status == STATUS_DEADLINE:
                # Shed at a cluster hop (the owner saw the budget lapse):
                # same protocol shape as the engine's own flush-time shed.
                fut.set_exception(
                    DeadlineError(STATUS_MESSAGES[STATUS_DEADLINE])
                )
            elif status != STATUS_OK:
                fut.set_exception(
                    ThrottleError(
                        STATUS_MESSAGES.get(status, "internal error")
                    )
                )
            elif wire:
                # Compact kernel output is already whole seconds.
                fut.set_result(
                    ThrottleResponse(
                        allowed=bool(result.allowed[i]),
                        limit=int(result.limit[i]),
                        remaining=int(result.remaining[i]),
                        reset_after=int(result.reset_after_s[i]),
                        retry_after=int(result.retry_after_s[i]),
                    )
                )
            else:
                fut.set_result(
                    ThrottleResponse.from_ns(
                        allowed=bool(result.allowed[i]),
                        limit=int(result.limit[i]),
                        remaining=int(result.remaining[i]),
                        reset_after_ns=int(result.reset_after_ns[i]),
                        retry_after_ns=int(result.retry_after_ns[i]),
                    )
                )

    # ------------------------------------------------------------------ #

    async def _maybe_sweep(self, now_ns: int, n_ops: int) -> None:
        insight = self.insight
        if insight is not None and insight.poll_due(now_ns):
            # Throttled insight poll (~1/s): the accumulator fetch and
            # top-K launch block on the device, so it runs on the
            # executor, under the lock that serializes device access
            # (the limiter lock here; the cluster device lock when the
            # tier's poll_lock overrides it).
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                None, insight.maybe_poll, now_ns, self.limiter_lock
            )
        control = self.control
        if control is not None and control.tick_due(now_ns):
            # Throttled control tick (L3.9): sensor snapshot + feedback
            # step, off the event loop under the same lock discipline
            # as the insight poll (the sensors it reads are the leaf
            # locks ranked above its own in analysis/lockorder.toml).
            depth = len(self._pending)
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                None,
                lambda: control.maybe_tick(
                    now_ns, self.limiter_lock, queue_depth=depth
                ),
            )
        checkpointer = self.checkpointer
        if checkpointer is not None and checkpointer.tick_due(now_ns):
            # Throttled checkpoint write (persist/): the device export
            # happens under the limiter lock (a "device"-kind hold, like
            # the insight poll); encode + CRC + fsync run outside it,
            # all off the event loop.
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                None, checkpointer.maybe_tick, now_ns, self.limiter_lock
            )
        policy = self.cleanup_policy
        if policy is None:
            return
        # The policy instance may be shared with a native transport's
        # driver thread (server/native_redis.py): all policy state moves
        # under limiter_lock.
        from ..tpu.cleanup import feed_expired_hits

        with self.limiter_lock:
            policy.record_ops(n_ops)
            # Adaptive policies consume the kernel's expired-hit count.
            # The single-device drain is a blocking device→host scalar
            # fetch that synchronizes on every in-flight launch — never
            # run it on the event-loop thread; when the limiter says a
            # fetch is due (throttled to ~1/s) the drain moves to the
            # executor below.  Sharded drains are host-side counters
            # (free) and stay inline.
            fetch_due = getattr(policy, "uses_expired_signal", False) and (
                getattr(self.limiter, "expired_hits_fetch_due", None)
                is not None
                and self.limiter.expired_hits_fetch_due(now_ns)
            )
            n_hits = 0
            if not fetch_due:
                n_hits = feed_expired_hits(policy, self.limiter, now_ns)
            live = len(self.limiter)
            capacity = getattr(self.limiter, "total_capacity", 1 << 62)
            should = fetch_due or policy.should_clean(now_ns, live, capacity)
        if n_hits and self.metrics is not None:
            self.metrics.record_expired_hits(n_hits)
        if should:
            loop = asyncio.get_running_loop()

            def locked_policy_step():
                drained = 0
                with self.limiter_lock:
                    live_now = live
                    if fetch_due:
                        drained += feed_expired_hits(
                            policy, self.limiter, now_ns
                        )
                        live_now = len(self.limiter)
                        if not policy.should_clean(
                            now_ns, live_now, capacity
                        ):
                            return None, drained, live_now
                    # Attribute hits already counted on-device to the
                    # window this sweep closes (after_sweep resets the
                    # policy's count — a late drain would leak them into
                    # the fresh window).  Redundant when fetch_due: the
                    # drain above just ran under this same lock hold.
                    if not fetch_due:
                        drained += feed_expired_hits(
                            policy, self.limiter, now_ns, force=True
                        )
                    freed = self.limiter.sweep(now_ns)
                    policy.after_sweep(now_ns, freed, live_now)
                    return freed, drained, live_now

            freed, drained, live_now = await loop.run_in_executor(
                None, locked_policy_step
            )
            if freed is not None and self.front is not None:
                # Swept buckets are gone even for a later regressed
                # clock: drop the deny-cache entries they backed.
                self.front.on_sweep(now_ns)
            if self.metrics is not None:
                if drained:
                    self.metrics.record_expired_hits(drained)
                if freed is not None:
                    self.metrics.record_sweep(freed, live_now)

    def health_state(self) -> str:
        """The failure-domain state for GET /health: "ok" | "retrying"
        | "degraded" | "recovering" ("ok" for unsupervised limiters,
        and "shutdown" once the engine refuses new requests)."""
        if self._closed:
            return "shutdown"
        if self._draining:
            return "draining"
        from .supervisor import supervisor_state

        return supervisor_state(self.limiter)

    def begin_drain(self) -> None:
        """Flip to lame-duck serving: new requests shed with
        OverloadError, /health says "draining" (balancers de-route),
        queued requests keep resolving with real decisions."""
        self._draining = True

    async def drain(self) -> None:
        """Graceful half of shutdown: stop taking requests, then flush
        everything already queued with *real* decisions (shutdown()'s
        pinned abrupt behavior also flushes, but nothing stops arrivals
        racing in behind it — drain closes the front door first)."""
        self.begin_drain()
        if self._flush_handle is not None:
            self._flush_handle.cancel()
            self._flush_handle = None
        await self._flush()

    async def shutdown(self) -> None:
        """Flush outstanding requests and refuse new ones."""
        self._closed = True
        if self._flush_handle is not None:
            self._flush_handle.cancel()
            self._flush_handle = None
        await self._flush()
