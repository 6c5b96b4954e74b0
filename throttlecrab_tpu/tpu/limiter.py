"""TpuRateLimiter: the batched, TPU-backed rate-limiting engine.

The TPU-native equivalent of `RateLimiter<S: Store>` (`rate_limiter.rs:42-58`)
plus the actor's serialized hot loop: requests arrive as whole batches,
string keys are resolved to table slots on the host, GCRA parameters are
derived with the reference's exact f64 pipeline, and all decisions execute in
one jitted device kernel against the HBM bucket table.

Exactness notes vs the scalar oracle (core/rate_limiter.py):

- Per-request validation errors (negative quantity / non-positive params) are
  reported in `BatchResult.status` instead of raising, since one bad request
  must not fail its batchmates (each transport maps status → its protocol
  error, like the reference server does per request).
- Duplicate keys in one batch are serialized with exact arrival-order
  semantics (see kernel.py).  A key whose *parameters change mid-batch* is
  split into consecutive param-runs processed as sub-rounds, preserving
  order.
- `now_ns` is a single server-side timestamp per batch (the reference server
  also stamps every request at the transport, `http.rs:127-128`).  The
  scalar-compat wrapper applies the pre-epoch clock-skew fallback per call.
- Emission intervals are clamped to i64::MAX ns (~292 years); the reference
  wraps them to negative i64 through `as_nanos() as i64` in that range.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..core.errors import InternalError, InvalidRateLimit, NegativeQuantity
from ..core.rate_limiter import RateLimitResult, normalize_now_ns
from ..faults import maybe_fail
from .kernel import EMPTY_EXPIRY
from .keymap import PyKeyMap
from .profiling import FETCH_SECONDS, KEYMAP_SECONDS, span
from .table import BucketTable


def _native_available() -> bool:
    from ..native import native_available

    return native_available()

I64_MAX = (1 << 63) - 1

STATUS_OK = 0
STATUS_NEGATIVE_QUANTITY = 1
STATUS_INVALID_PARAMS = 2
STATUS_INTERNAL = 3
# 4 is the front tier's STATUS_OVERLOADED (front/admission.py).
# A NEW key refused by its tenant's slot-capacity quota (the sharded
# limiter's namespace layer, parallel/tenants.py); the tenant's
# existing keys keep deciding normally.
STATUS_TENANT_QUOTA = 5
# Request outlived its client deadline: shed host-side before device
# dispatch (server/engine.py) or at a cluster hop (parallel/cluster.py).
# Like 3/4, excluded from replay differentials — load-dependent, not a
# GCRA outcome.
STATUS_DEADLINE = 6


def segment_info(slots, mask):
    """Per-request duplicate-key structure for the kernel.

    For each masked-in request: `rank` = its key's occurrence number within
    the batch, `is_last` = whether it is the key's final occurrence.  One
    dict pass on the host — the C++ keymap computes this for free during
    slot resolution.
    """
    n = len(slots)
    rank = np.zeros(n, np.int32)
    is_last = np.ones(n, bool)
    state: dict = {}
    for i in np.flatnonzero(mask):
        sl = int(slots[i])
        st = state.get(sl)
        if st is None:
            state[sl] = [1, i]
        else:
            rank[i] = st[0]
            st[0] += 1
            is_last[st[1]] = False
            st[1] = i
    return rank, is_last


@dataclass
class BatchResult:
    """Per-request outcomes of one batch (numpy arrays, length B).

    `cur_ns` (optional) is each request's exact observed TAT — new TAT
    for allowed rows, effective TAT for denied rows — populated when the
    launch rode the compact="cur" output tier with `collect_cur=True`.
    The front tier's deny cache certifies entries from it; None
    elsewhere (invalid lanes carry garbage: consumers must gate on
    status).
    """

    allowed: np.ndarray
    limit: np.ndarray
    remaining: np.ndarray
    reset_after_ns: np.ndarray
    retry_after_ns: np.ndarray
    status: np.ndarray
    cur_ns: Optional[np.ndarray] = None


@dataclass
class WireBatchResult:
    """Per-request outcomes in wire units, from the compact kernel output.

    reset_after_s / retry_after_s are whole seconds and remaining saturates
    at i32::MAX — exactly what every transport emits (the reference
    truncates Durations to seconds at the type boundary, types.rs:87-97,
    and its gRPC proto is int32, throttlecrab.proto:15-21).  Fetching i32
    seconds instead of i64 nanoseconds halves device→host bytes per
    decision.
    """

    allowed: np.ndarray
    limit: np.ndarray
    remaining: np.ndarray
    reset_after_s: np.ndarray
    retry_after_s: np.ndarray
    status: np.ndarray
    # Exact observed TATs when fetched through the cur tier with
    # collect_cur=True (see BatchResult.cur_ns); None otherwise.
    cur_ns: Optional[np.ndarray] = None


# Segment arithmetic in the fast path multiplies inc by at most the
# batch size (segment ranks); certifying inc * MAX_SEGMENT < 2^62 on the
# host lets the kernel use plain multiplies instead of saturating ones
# (each saturating multiply hides an i64 division for its overflow
# probe).  Derived from the table scratch bound — the hard cap on batch
# width and therefore on any segment rank.  native/keymap.cpp mirrors
# the same certificate (tk_prepare_batch); a test pins the two together.
MAX_SEGMENT = BucketTable.SCRATCH
_MUL_SAFE = float(1 << 62)


def has_degenerate(valid, emission, tolerance, quantity) -> bool:
    """True when any valid request needs the kernel's exact path:
    quantity-0 probes, burst-1 (tolerance 0), zero emission intervals, a
    wrapped-negative tolerance (the reference's truncating
    emission*(burst-1) product can wrap, rate_limiter.rs:122), or an
    increment big enough that segment arithmetic could overflow i64.
    When absent the engine compiles the degenerate machinery out AND
    swaps the general saturating ops for cheap certified forms
    (`with_degen=False`) — certified per batch, so correctness never
    depends on traffic shape."""
    big_inc = (
        emission.astype(np.float64)
        * np.maximum(quantity, 1).astype(np.float64)
        * float(MAX_SEGMENT)
        >= _MUL_SAFE
    )
    return bool(
        np.any(
            valid
            & (
                (emission == 0)
                | (tolerance <= 0)
                | (quantity == 0)
                | big_inc
            )
        )
    )


def prepare_batch(n, max_burst, count_per_period, period, quantity):
    """Broadcast request params to length n, validate, derive GCRA params.

    The shared prologue of every batch engine (single-device and sharded).
    Returns (max_burst, quantity, emission, tolerance, status, valid).
    """
    max_burst = np.broadcast_to(np.asarray(max_burst, np.int64), (n,))
    count_per_period = np.broadcast_to(
        np.asarray(count_per_period, np.int64), (n,)
    )
    period = np.broadcast_to(np.asarray(period, np.int64), (n,))
    quantity = np.broadcast_to(np.asarray(quantity, np.int64), (n,))

    status = np.zeros(n, np.uint8)
    emission, tolerance, invalid = derive_params(
        max_burst, count_per_period, period
    )
    status[invalid] = STATUS_INVALID_PARAMS
    status[quantity < 0] = STATUS_NEGATIVE_QUANTITY
    valid = status == STATUS_OK
    return max_burst, quantity, emission, tolerance, status, valid


def param_rounds(rounds, slots, positions, emission, tolerance, quantity):
    """Assign arrival-order param-run rounds into `rounds` at `positions`.

    Round r holds each key's r-th maximal run of identical (emission,
    tolerance, quantity), so processing rounds in order reproduces the
    reference's sequential per-request semantics when a key's parameters
    change mid-batch.
    """
    state: dict = {}
    for i in positions:
        sl = int(slots[i])
        p = (int(emission[i]), int(tolerance[i]), int(quantity[i]))
        st = state.get(sl)
        if st is None:
            state[sl] = [p, 0]
        elif st[0] == p:
            rounds[i] = st[1]
        else:
            st[0] = p
            st[1] += 1
            rounds[i] = st[1]
    return rounds


def limiter_uses_bytes_keys(limiter) -> bool:
    """Whether a limiter's host keymap stores bytes keys (native backend)
    or str keys (python backend).  Transports that receive raw bytes must
    match the identity str-keyed transports use, or one client key becomes
    two buckets.  Works across TpuRateLimiter (.keymap), the sharded
    limiter (._bytes_keys), and cluster wrappers (delegated _bytes_keys).
    """
    km = getattr(limiter, "keymap", None)
    if km is not None:
        return bool(getattr(km, "BYTES_KEYS", False))
    return bool(getattr(limiter, "_bytes_keys", False))


def sequential_fallback(batches, decide_fn, error_result_fn, wire,
                        **decide_kw):
    """Decide a rate_limit_many window batch-by-batch when the scan path
    cannot express it (a key changed parameters mid-batch — the multi-round
    sub-protocol interleaves with later sub-batches in ways one scan can't;
    rare, and exactness beats speed there).

    Errors are isolated per batch: earlier batches' decisions are already
    committed on-device and must still be delivered; later batches after a
    failure return all-internal-error results.
    """
    out = []
    failed = False
    for b in batches:
        if failed:
            out.append(error_result_fn(len(b[0]), wire=wire))
            continue
        try:
            out.append(decide_fn(*b, wire=wire, **decide_kw))
        except Exception:
            failed = True
            out.append(error_result_fn(len(b[0]), wire=wire))
    return out


class ScalarCompatMixin:
    """Scalar `rate_limit` (the reference library API) over a batch engine.

    Mirrors `RateLimiter::rate_limit` (`rate_limiter.rs:102-117`): raising
    validation errors, applying the pre-epoch clock-skew fallback, and
    unpacking the single-request batch result.
    """

    def rate_limit(
        self,
        key,
        max_burst: int,
        count_per_period: int,
        period: int,
        quantity: int,
        now_ns: int,
    ):
        if quantity < 0:
            raise NegativeQuantity(quantity)
        if max_burst <= 0 or count_per_period <= 0 or period <= 0:
            raise InvalidRateLimit()
        now_ns = normalize_now_ns(now_ns, period)
        res = self.rate_limit_batch(
            [key], [max_burst], [count_per_period], [period], [quantity], now_ns
        )
        return bool(res.allowed[0]), RateLimitResult(
            limit=int(res.limit[0]),
            remaining=int(res.remaining[0]),
            reset_after_ns=int(res.reset_after_ns[0]),
            retry_after_ns=int(res.retry_after_ns[0]),
        )


def derive_params(max_burst, count_per_period, period):
    """(emission_ns, tolerance_ns, invalid) via the reference f64 pipeline.

    Mirrors `rate/mod.rs:164-176` (f64 multiply/divide, truncating u64 cast)
    and `rate_limiter.rs:122` (tolerance = emission * ((burst-1) as u32),
    with the product truncated to 64 bits).
    """
    max_burst = np.asarray(max_burst, np.int64)
    count_per_period = np.asarray(count_per_period, np.int64)
    period = np.asarray(period, np.int64)

    invalid = (max_burst <= 0) | (count_per_period <= 0) | (period <= 0)
    safe_count = np.where(count_per_period == 0, 1, count_per_period)
    emission_f = period.astype(np.float64) * 1e9 / safe_count.astype(np.float64)
    with np.errstate(invalid="ignore"):
        # Out-of-range casts are overridden by the I64_MAX clamp below;
        # numpy's warning about them is noise.
        emission = np.where(
            emission_f >= float(1 << 63),
            I64_MAX,
            emission_f.astype(np.int64),
        )
    emission = np.where(emission < 0, 0, emission)

    b32 = (max_burst - 1).astype(np.uint64) & np.uint64(0xFFFFFFFF)
    # Deliberately WRAPPING u64 product (rate_limiter.rs:122 semantics).
    tolerance = (
        emission.astype(np.uint64) * b32  # inv: allow(i64-raw-op)
    ).astype(np.int64)
    return emission, tolerance, invalid


class _ReadyLaunch:
    """dispatch_many handle whose results are already on the host (empty
    windows and the sequential multi-round fallback)."""

    def __init__(self, results: list) -> None:
        self._results = results

    def fetch(self) -> list:
        return self._results


class _PendingLaunch:
    """An in-flight device launch; `.fetch()` blocks on the device output
    and distributes it into per-batch results.  Created by dispatch_many —
    the device is already executing (or queued behind the table-state
    dependency chain) by the time the caller holds this."""

    def __init__(
        self, out_dev, prepared, valid_s, wire, cur=False, w32=False
    ) -> None:
        self._out_dev = out_dev
        self._prepared = prepared
        self._valid_s = valid_s
        self._wire = wire
        self._cur = cur
        self._w32 = w32

    def fetch(self) -> list:
        maybe_fail("fetch")
        out = np.asarray(self._out_dev)
        wire = self._wire
        if self._cur:
            from .kernel import finish_cur
        if self._w32:
            from .kernel import finish_w32
        results = []
        for j, (n, slots, rank, is_last, emission, tolerance, quantity,
                valid, now_ns, max_burst, status) in enumerate(
            self._prepared
        ):
            cur_plane = None
            if self._w32:
                # 4 B/request "w32" fetch: the device packed the exact
                # wire values; unpack is shifts and masks.
                o = np.stack(finish_w32(out[j, :n]))
            elif self._cur:
                # 8 B/request "cur" fetch, host-finished to the exact
                # i32 wire planes (kernel.finish_cur).
                o = np.stack(
                    finish_cur(
                        out[j, :n], emission, tolerance, quantity, now_ns
                    )
                )
                # The word is cur*2 + allowed; the arithmetic shift
                # recovers the exact observed TAT (the deny cache's
                # certification input — free on this tier).
                cur_plane = out[j, :n] >> 1
            else:
                o = out[j, :, :n]
            mask = self._valid_s[j, :n]
            fields = dict(
                allowed=(o[0] != 0) & mask,
                limit=np.where(valid, max_burst, 0),
                remaining=np.where(mask, o[1], 0),
                status=status,
                cur_ns=cur_plane,
            )
            if wire:
                results.append(
                    WireBatchResult(
                        reset_after_s=np.where(mask, o[2], 0),
                        retry_after_s=np.where(mask, o[3], 0),
                        **fields,
                    )
                )
            else:
                results.append(
                    BatchResult(
                        reset_after_ns=np.where(mask, o[2], 0),
                        retry_after_ns=np.where(mask, o[3], 0),
                        **fields,
                    )
                )
        return results


class _PendingWireLaunch:
    """In-flight launch from dispatch_wire_window; .fetch() distributes
    the compact device output into per-frame WireBatchResults.

    Two device output formats (limiter picks at dispatch):
      - 4-plane compact i32[K, 4, B] (`finish=None`), or
      - compact="cur" i64[K, B] — 8 B/request instead of 16 —
        completed to the exact i32 wire values by the
        native keymap's tk_finish (`finish` is the keymap.finish bound
        method; requires the certified non-degenerate path and
        fits_cur_wire, which the limiter checked before dispatch).
    """

    def __init__(
        self, out_dev, prepared, finish=None, now_ns=0, w32=False
    ) -> None:
        self._out_dev = out_dev
        self._prepared = prepared
        self._finish = finish
        self._now_ns = now_ns
        self._w32 = w32

    def fetch(self) -> list:
        maybe_fail("fetch")
        with span("driver.fetch", FETCH_SECONDS):
            out = np.asarray(self._out_dev)
        finished = None
        if self._w32:
            from .kernel import finish_w32
        elif self._finish is not None:
            # tk_finish completes the cur tier's words, every frame of
            # the window under one span.
            with span("keymap.finish", KEYMAP_SECONDS):
                finished = [
                    self._finish(packed, out[j, : len(status)], self._now_ns)
                    for j, (packed, status, _) in enumerate(self._prepared)
                ]
        results = []
        for j, (packed, status, params) in enumerate(self._prepared):
            n = len(status)
            valid = (packed[:, 2] & 2) != 0
            cur_plane = None
            if self._w32:
                o = np.stack(finish_w32(out[j, :n]))
            elif finished is not None:
                o = finished[j].T
                # cur*2 + allowed words: expose the exact observed TATs
                # for the front tier's deny cache (see BatchResult).
                cur_plane = out[j, :n] >> 1
            else:
                o = out[j, :, :n]
            results.append(
                WireBatchResult(
                    allowed=(o[0] != 0) & valid,
                    limit=np.where(valid, params[:, 0], 0),
                    remaining=np.where(valid, o[1], 0),
                    reset_after_s=np.where(valid, o[2], 0),
                    retry_after_s=np.where(valid, o[3], 0),
                    status=status,
                    cur_ns=cur_plane,
                )
            )
        return results


class TpuRateLimiter(ScalarCompatMixin):
    """Batched GCRA over a device bucket table + host keymap."""

    # Batches are padded to a power of two of at least MIN_PAD lanes:
    # few distinct jit-cache shapes as traffic varies.
    MIN_PAD = 16

    def __init__(
        self,
        capacity: int = 1 << 20,
        keymap="python",
        device=None,
        auto_grow: bool = True,
        insight: bool = False,
    ) -> None:
        """`keymap` selects the host key→slot backend: "python" (default,
        hashable keys of any kind), "native" (C++ batch resolver, bytes
        keys), "auto" (native when the toolchain built it), or a ready
        keymap object exposing resolve/free_slots/grow/capacity.
        `insight=True` arms the L3.75 analytics accumulators on the
        table (see BucketTable.enable_insight); off, the decision path
        is bit-identical to a limiter built without the subsystem."""
        self.table = BucketTable(capacity, device=device, insight=insight)
        if keymap == "auto":
            keymap = "native" if _native_available() else "python"
        if keymap == "python":
            self.keymap = PyKeyMap(capacity)
        elif keymap == "native":
            from ..native import NativeKeyMap

            self.keymap = NativeKeyMap(capacity)
        else:
            self.keymap = keymap
        self.auto_grow = auto_grow
        self._exp_hits_read = 0
        self._exp_hits_last_fetch_ns: Optional[int] = None

    # ------------------------------------------------------------------ #

    def expired_hits_fetch_due(
        self, now_ns: int, min_period_ns: int = 1_000_000_000
    ) -> bool:
        """True when take_expired_hits would actually hit the device —
        lets callers on latency-sensitive threads (the asyncio engine)
        route the blocking scalar fetch to an executor instead."""
        last = self._exp_hits_last_fetch_ns
        return last is None or now_ns - last >= min_period_ns

    def take_expired_hits(
        self, now_ns: int, min_period_ns: int = 1_000_000_000
    ) -> int:
        """New expired-hit count since the last call, for the adaptive
        cleanup policy's expired-ratio trigger.

        The count lives in a device-resident accumulator that rides
        every decision launch for free (kernel gcra_*_acc); reading it
        is one scalar device→host fetch, so the read is throttled to
        once per `min_period_ns` (default 1 s — the policy's own minimum
        cleanup interval; all its triggers operate at >= 1 s
        granularity, so a staler signal is indistinguishable).  Returns
        0 between fetches."""
        last = self._exp_hits_last_fetch_ns
        if last is not None and now_ns - last < min_period_ns:
            return 0
        self._exp_hits_last_fetch_ns = now_ns
        total = self.table.expired_hits()
        delta = total - self._exp_hits_read
        self._exp_hits_read = total
        return delta

    def rate_limit_batch(
        self,
        keys,
        max_burst,
        count_per_period,
        period,
        quantity,
        now_ns: int,
        wire: bool = False,
        collect_cur: bool = False,
    ) -> BatchResult:
        """Decide a batch of requests at one server timestamp.

        `keys` is a sequence of hashable keys (str/bytes); the numeric
        parameters broadcast to its length.  `now_ns` must be >= 0.

        `wire=True` takes the serving fast path: compact i32 whole-second
        outputs (returns WireBatchResult) and the degenerate-case kernel
        machinery compiled out whenever this batch provably has no
        quantity-0 / burst-1 / zero-emission / wrapped-negative-tolerance
        request (see has_degenerate).

        `collect_cur=True` (wire mode only) rides the compact="cur"
        output tier when its certificate holds, attaching each request's
        exact observed TAT as `result.cur_ns` (what the front tier's
        deny cache certifies entries from); cur_ns is None whenever cur
        is uncertifiable.  Decisions are identical either way.
        """
        (n, max_burst, quantity, emission, tolerance, status, valid,
         slots, rank0, is_last0, rounds) = self._prepare_one(
            keys, max_burst, count_per_period, period, quantity, now_ns
        )
        maybe_fail("launch")
        degen = has_degenerate(valid, emission, tolerance, quantity)
        with_degen = not wire or degen
        from .kernel import cur_wire_safe

        params_cur_safe = cur_wire_safe(valid, tolerance, now_ns)
        use_cur = (
            wire
            and collect_cur
            and not degen
            and params_cur_safe
            and self.table.cur_safe
        )
        if use_cur:
            from .kernel import finish_cur

        pad = max(self.MIN_PAD, 1 << (n - 1).bit_length())
        slots_p = np.zeros(pad, np.int32)
        slots_p[:n] = slots
        em_p = np.zeros(pad, np.int64)
        em_p[:n] = emission
        tol_p = np.zeros(pad, np.int64)
        tol_p[:n] = tolerance
        q_p = np.zeros(pad, np.int64)
        q_p[:n] = quantity

        allowed = np.zeros(n, bool)
        remaining = np.zeros(n, np.int64)
        reset_after = np.zeros(n, np.int64)
        retry_after = np.zeros(n, np.int64)
        cur_plane = np.zeros(n, np.int64) if use_cur else None

        n_rounds = int(rounds.max()) + 1 if n else 1
        for r in range(n_rounds):
            mask = valid & (rounds == r)
            if not mask.any():
                continue
            valid_p = np.zeros(pad, bool)
            valid_p[:n] = mask
            if n_rounds == 1:
                # Segment info came for free from the keymap pass.
                rank = np.zeros(pad, np.int32)
                rank[:n] = rank0
                is_last = np.ones(pad, bool)
                is_last[:n] = is_last0
            else:
                rank, is_last = segment_info(slots_p, valid_p)
            out_dev = self.table.check_batch(
                slots_p, rank, is_last, em_p, tol_p, q_p, valid_p, now_ns,
                with_degen=with_degen, compact="cur" if use_cur else wire,
                params_cur_safe=params_cur_safe,
            )
            # One device→host fetch per round; rounds beyond 0 are rare.
            if use_cur:
                # cur*2 + allowed words: finish to the exact i32 wire
                # planes on the host and keep the observed-TAT plane.
                words = np.asarray(out_dev)[:n]
                out = np.stack(
                    finish_cur(words, emission, tolerance, quantity,
                               now_ns)
                )
                cur_plane[mask] = (words >> 1)[mask]
            else:
                out = np.asarray(out_dev)[:, :n]
            allowed[mask] = out[0][mask] != 0
            remaining[mask] = out[1][mask]
            reset_after[mask] = out[2][mask]
            retry_after[mask] = out[3][mask]

        limit = np.where(valid, max_burst, 0)
        if wire:
            return WireBatchResult(
                allowed=allowed,
                limit=limit,
                remaining=remaining,
                reset_after_s=reset_after,
                retry_after_s=retry_after,
                status=status,
                cur_ns=cur_plane,
            )
        return BatchResult(
            allowed=allowed,
            limit=limit,
            remaining=remaining,
            reset_after_ns=reset_after,
            retry_after_ns=retry_after,
            status=status,
        )

    # ------------------------------------------------------------------ #

    def _prepare_one(
        self, keys, max_burst, count_per_period, period, quantity, now_ns
    ):
        """Shared per-batch prologue: validate, derive params, resolve
        slots (growing on full), emit segment structure + conflict rounds.
        One implementation for both the single-batch and scan paths."""
        if now_ns < 0:
            raise ValueError(
                "batch now_ns must be non-negative; apply "
                "normalize_now_ns per request for pre-epoch clocks"
            )
        n = len(keys)
        if getattr(self.keymap, "BYTES_KEYS", False):
            keys = [k.encode() if isinstance(k, str) else k for k in keys]
        max_burst, quantity, emission, tolerance, status, valid = (
            prepare_batch(n, max_burst, count_per_period, period, quantity)
        )
        slots, rank0, is_last0, n_full = self.keymap.resolve(keys, valid)
        maybe_fail("keymap")
        while n_full:
            if not self.auto_grow:
                raise InternalError("bucket table full")
            new_capacity = max(self.keymap.capacity * 2, 1024)
            self.keymap.grow(new_capacity)
            self.table.grow(new_capacity)
            missing = valid & (slots == -1)
            slots2, _, _, n_full = self.keymap.resolve(keys, missing)
            slots = np.where(missing, slots2, slots)
            # Segment info must cover the merged batch.
            rank0, is_last0 = segment_info(slots, valid)
        rounds = self._conflict_rounds(
            slots, valid, emission, tolerance, quantity
        )
        return (n, max_burst, quantity, emission, tolerance, status, valid,
                slots, rank0, is_last0, rounds)

    @staticmethod
    def _error_result(n, status_code=STATUS_INTERNAL, wire=False):
        """All-requests-failed result (engine maps status → error)."""
        zeros = np.zeros(n, np.int64)
        status = np.full(n, status_code, np.uint8)
        if wire:
            return WireBatchResult(
                allowed=np.zeros(n, bool), limit=zeros, remaining=zeros,
                reset_after_s=zeros, retry_after_s=zeros, status=status,
            )
        return BatchResult(
            allowed=np.zeros(n, bool), limit=zeros, remaining=zeros,
            reset_after_ns=zeros, retry_after_ns=zeros, status=status,
        )

    def rate_limit_many(
        self, batches, wire: bool = False, collect_cur: bool = False
    ) -> list:
        """Decide K whole batches in ONE device launch (gcra_scan).

        `batches` is a list of (keys, max_burst, count_per_period, period,
        quantity, now_ns) tuples, in arrival order; each sub-batch sees the
        table state left by the previous one (lax.scan carry), exactly as K
        separate rate_limit_batch calls would — but with one launch and one
        fetch, amortizing the fixed dispatch cost that dominates when the
        serving engine drains a backlog.  Returns a list of BatchResult.

        Sub-batches whose keys change parameters mid-batch (conflict
        rounds > 0) fall back to the per-batch path, preserving exact
        ordering; that case is rare in serving traffic.
        """
        return self.dispatch_many(
            batches, wire=wire, collect_cur=collect_cur
        ).fetch()

    def dispatch_many(
        self, batches, wire: bool = False, collect_cur: bool = False
    ):
        """The dispatch half of rate_limit_many: host-prepare the window,
        launch it on the device, and return a handle whose `.fetch()`
        blocks for the results.

        `collect_cur=True` (wire mode only) asks for the exact observed
        TATs alongside the wire values: the dispatcher prefers the cur
        output tier over w32 (8 B/request instead of 4 — the TAT plane
        is what the front tier's deny cache certifies entries from) and
        attaches it as `result.cur_ns`.  Falls back to the 4-plane tier
        with cur_ns=None whenever cur is uncertifiable; decisions are
        identical either way.

        Device dispatch is asynchronous, so the caller can assemble and
        dispatch window N+1 while the device executes window N and only
        then fetch N's results — the double-buffering that hides the fixed
        per-launch round-trip cost (the engine's flush loop does exactly
        this).  Launches are sequenced by the
        donated table state, so results are identical to sequential calls.
        """
        if not batches:
            return _ReadyLaunch([])

        prepared = []
        width = self.MIN_PAD
        any_degen = False
        for keys, max_burst, count_per_period, period, quantity, now_ns in (
            batches
        ):
            (n, max_burst, quantity, emission, tolerance, status, valid,
             slots, rank, is_last, rounds) = self._prepare_one(
                keys, max_burst, count_per_period, period, quantity, now_ns
            )
            if rounds.any():
                return _ReadyLaunch(
                    sequential_fallback(
                        batches, self.rate_limit_batch,
                        self._error_result, wire,
                        collect_cur=collect_cur,
                    )
                )
            any_degen = any_degen or has_degenerate(
                valid, emission, tolerance, quantity
            )
            prepared.append(
                (n, slots, rank, is_last, emission, tolerance, quantity,
                 valid, now_ns, max_burst, status)
            )
            width = max(width, 1 << max(n - 1, 0).bit_length())

        K = len(prepared)
        # Pad the scan depth to a power of two with empty sub-batches so the
        # jit cache sees few distinct (K, width) shapes as backlog varies.
        K_pad = 1 << (K - 1).bit_length()
        shape = (K_pad, width)
        slots_s = np.zeros(shape, np.int32)
        rank_s = np.zeros(shape, np.int32)
        last_s = np.ones(shape, bool)
        em_s = np.zeros(shape, np.int64)
        tol_s = np.zeros(shape, np.int64)
        q_s = np.zeros(shape, np.int64)
        valid_s = np.zeros(shape, bool)
        now_s = np.full(K_pad, prepared[-1][8], np.int64)
        for j, (n, slots, rank, is_last, emission, tolerance, quantity,
                valid, now_ns, _mb, _st) in enumerate(prepared):
            slots_s[j, :n] = slots
            rank_s[j, :n] = rank
            last_s[j, :n] = is_last
            em_s[j, :n] = emission
            tol_s[j, :n] = tolerance
            q_s[j, :n] = quantity
            valid_s[j, :n] = valid
            now_s[j] = now_ns

        # One fused host→device buffer for the whole window: one transfer
        # call per launch instead of eight per-array ones.
        from .kernel import cur_wire_safe, fits_w32_wire, pack_requests

        packed = pack_requests(
            slots_s, rank_s, last_s, em_s, tol_s, q_s, valid_s
        )
        # The 8 B/request "cur" output halves the fetch whenever the
        # certified fast path applies and the valid-masked cur bound
        # holds (now/tol < 2^61); finished to identical wire values on
        # the host in _PendingLaunch.fetch.  table.cur_safe extends the
        # certificate across launches: a prior big-tolerance launch can
        # persist a TAT >= 2^62 whose cur word would wrap (ADVICE r4).
        now_max = int(now_s.max(initial=0))
        params_cur_safe = cur_wire_safe(valid_s, tol_s, now_max)
        max_tol = int(np.where(valid_s, tol_s, 0).max(initial=0))
        # Cheapest eligible output tier: w32 (4 B/request, device-packed
        # exact wire values) → cur (8 B, host-finished) → 4-plane i32.
        # w32's stored-TAT bound needs timestamps non-decreasing within
        # the window and no earlier than any prior launch's.
        use_w32 = (
            wire
            and not collect_cur
            and not any_degen
            and now_max < (1 << 61)
            and bool((np.diff(now_s) >= 0).all())
            and fits_w32_wire(
                valid_s, em_s, tol_s, q_s, int(now_s[0]),
                self.table.tol_hwm, self.table.now_hwm,
            )
        )
        use_cur = (
            not use_w32
            and wire
            and not any_degen
            and params_cur_safe
            and self.table.cur_safe
        )
        maybe_fail("launch")
        out_dev = self.table.check_many_packed(
            packed, now_s,
            with_degen=not wire or any_degen,
            compact="w32" if use_w32 else ("cur" if use_cur else wire),
            params_cur_safe=params_cur_safe,
            max_tolerance=max_tol,
        )
        return _PendingLaunch(
            out_dev, prepared, valid_s, wire, cur=use_cur, w32=use_w32
        )

    # ------------------------------------------------------------------ #

    def dispatch_wire_window(
        self, frames, now_ns: int, collect_cur: bool = False
    ):
        """The fully-native serving dispatch: each frame is
        (key_blob, offsets i64[n+1], params i64[n, 4]) exactly as the C++
        wire layer hands batches over.  One C++ call per frame validates,
        derives GCRA params (exact f64 pipeline), resolves slots, and
        writes the packed rows (native/keymap.cpp tk_prepare_batch);
        Python's per-batch work is reduced to pow-2 padding and the
        launch.  Returns a handle with .fetch() -> [WireBatchResult], or
        None when the window needs the exact Python path (non-native
        keymap, a mid-batch param change, or a full table — preparation
        is idempotent, so the fallback simply re-resolves)."""
        km = self.keymap
        if not hasattr(km, "prepare_batch"):
            return None
        if now_ns < 0:
            # Part of the with_degen=False certificate (kernel.py): the
            # nonneg saturating forms require now >= 0.  Same contract as
            # _prepare_one.
            raise ValueError(
                "batch now_ns must be non-negative; apply "
                "normalize_now_ns per request for pre-epoch clocks"
            )
        from ..native import PREP_BIGTOL, PREP_CONFLICT, PREP_DEGEN, PREP_FULL

        prepared = []
        width = self.MIN_PAD
        any_degen = False
        any_bigtol = False
        # Per-window w32-certificate aggregates, folded across frames
        # (C++ computes them per frame during the same prep pass).
        agg = np.empty(4, np.int64)
        max_tol = 0
        min_tol = 1 << 62
        max_inc = 0
        rem_bound = 0
        with span("keymap.prepare", KEYMAP_SECONDS):
            for blob, offsets, params in frames:
                packed, status, flags = km.prepare_batch(
                    blob, offsets, params, agg=agg
                )
                if flags & (PREP_CONFLICT | PREP_FULL):
                    return None
                any_degen = any_degen or bool(flags & PREP_DEGEN)
                any_bigtol = any_bigtol or bool(flags & PREP_BIGTOL)
                max_tol = max(max_tol, int(agg[0]))
                # agg[0] > 0 ⇔ the frame had a valid lane with tol > 0
                # (tol <= 0 lanes carry PREP_DEGEN, and any_degen
                # refuses w32 outright, so the 0-sentinel min never
                # leaks in).
                if int(agg[0]) > 0:
                    min_tol = min(min_tol, int(agg[1]))
                max_inc = max(max_inc, int(agg[2]))
                rem_bound = max(rem_bound, int(agg[3]))
                prepared.append((packed, status, params))
                n = len(status)
                width = max(width, 1 << max(n - 1, 0).bit_length())

        from .kernel import PACK_WIDTH

        # 8 B/request "cur" output (host-finished by C++ tk_finish) when
        # the certified fast path and the fits_cur_wire bound both hold;
        # else the 4-plane compact i32 output.  Same exact wire values
        # either way (tests/test_wire_path.py pins the equivalence).
        # table.cur_safe carries the certificate across launches (a
        # prior big-tol launch can store a TAT >= 2^62 — ADVICE r4).
        # PREP_BIGTOL is set only for VALID lanes (invalid params skip
        # derivation in tk_prepare_batch), and degenerate lanes obey the
        # same write bound, so bigtol + now alone decide state safety.
        params_cur_safe = not any_bigtol and now_ns < (1 << 61)
        use_cur = (
            not any_degen
            and params_cur_safe
            and self.table.cur_safe
            and hasattr(km, "finish")
        )

        # w32 tier (4 B/request, device-packed exact wire values): the
        # certificate runs on the C++ prep's aggregates — no Python pass
        # over the rows, and the fetch is half the cur tier's.
        use_w32 = False
        if not any_degen and not any_bigtol and not collect_cur:
            # collect_cur: the caller (a front-tier serving loop) wants
            # the observed-TAT plane, which only the cur tier carries.
            from .kernel import fits_w32_wire_agg

            use_w32 = fits_w32_wire_agg(
                max_tol, min_tol, max_inc, rem_bound, now_ns,
                self.table.tol_hwm, self.table.now_hwm,
            )
        use_cur = use_cur and not use_w32

        maybe_fail("launch")
        with span("driver.launch"):
            K = len(prepared)
            K_pad = 1 << max(K - 1, 0).bit_length()
            stack = np.zeros((K_pad, width, PACK_WIDTH), np.int32)
            for j, (packed, _, _) in enumerate(prepared):
                stack[j, : len(packed)] = packed
            out_dev = self.table.check_many_packed(
                stack,
                np.full(K_pad, now_ns, np.int64),
                with_degen=any_degen,
                compact="w32" if use_w32 else ("cur" if use_cur else True),
                params_cur_safe=params_cur_safe,
                max_tolerance=max_tol,
            )
        if use_w32:
            return _PendingWireLaunch(out_dev, prepared, w32=True)
        if use_cur:
            return _PendingWireLaunch(
                out_dev, prepared, finish=km.finish, now_ns=now_ns
            )
        return _PendingWireLaunch(out_dev, prepared)

    def sweep(self, now_ns: int) -> int:
        """Run a cleanup sweep; returns the number of slots freed."""
        with span("cleanup.compact"):
            expired = self.table.sweep(now_ns)
        with span("cleanup.free"):
            return self.keymap.free_slots(np.flatnonzero(expired))

    def prewarm_sweep(self) -> None:
        """Compile the table's sweep now (the server's boot), not at a
        policy's first sweep, which fires under traffic.  A sweep at the
        empty rows' sentinel clock (kernel.EMPTY_EXPIRY) vacates only
        rows that are empty already, so nothing is freed."""
        self.table.sweep(EMPTY_EXPIRY)

    def __len__(self) -> int:
        return len(self.keymap)

    @property
    def total_capacity(self) -> int:
        """Slots available before growth (for capacity-pressure policies)."""
        return self.table.capacity

    # ------------------------------------------------------------------ #

    @staticmethod
    def _conflict_rounds(slots, valid, emission, tolerance, quantity):
        """Arrival-order rounds for keys whose params change mid-batch.

        Round r holds each key's r-th maximal run of identical parameters,
        so processing rounds in order reproduces the reference's sequential
        per-request semantics exactly.
        """
        n = len(slots)
        rounds = np.zeros(n, np.int32)
        if n == 0:
            return rounds
        vslots = slots[valid]
        if len(np.unique(vslots)) == len(vslots):
            return rounds  # no duplicates at all: single round

        uniq, first_idx, inv = np.unique(slots, return_index=True, return_inverse=True)
        canon = first_idx[inv]
        conflict = valid & (
            (emission != emission[canon])
            | (tolerance != tolerance[canon])
            | (quantity != quantity[canon])
        )
        if not conflict.any():
            return rounds
        return param_rounds(
            rounds, slots, np.flatnonzero(valid), emission, tolerance, quantity
        )
