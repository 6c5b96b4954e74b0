"""HBM-resident bucket table: the TPU replacement for the HashMap stores.

Structure-of-Arrays layout instead of the reference's
`HashMap<String, (i64, Option<SystemTime>)>` (`periodic.rs:39-47`): string
keys are resolved to dense slot indices on the host (see keymap.py); the
device only ever sees integer slots.  Each slot's (TAT, expiry) pair is
stored as one packed i32[4] row — TPU scatters cost per *row*, and one 4×i32
row write is ~4.5x cheaper than two separate i64 scatters (see
kernel.pack_state).  16 bytes of HBM per slot — 1M keys is 16 MB — plus a
scratch tail of `SCRATCH` rows that absorbs suppressed writes at unique
indices.

All mutation goes through the donated-buffer kernels in kernel.py, so the
array is updated in place batch after batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .sat import I64_MAX
from .kernel import (
    EMPTY_EXPIRY,
    gcra_batch_acc,
    gcra_batch_ins,
    gcra_scan_acc,
    gcra_scan_byid_acc,
    gcra_scan_ids_acc,
    gcra_scan_ins,
    gcra_scan_packed_acc,
    gcra_scan_packed_ins,
    pack_id_rows,
    pack_state,
    sweep_expired,
    sweep_expired_ins,
    unpack_state,
)


# Stored-TAT bound for the compact="cur" output: the device emits
# `cur * 2 + allowed` in i64, and a denied lane's cur can be the STORED
# TAT verbatim (kernel t0 = max(stored_tat, now - tol) with m_raw = 0),
# so every live TAT must sit in [0, 2^62) for the shift to be exact.
# Launches whose params satisfy the per-launch certificate (no
# degenerate request, tol/now < 2^61) only ever write TATs in
# [0, now + tol] ⊂ [0, 2^62); any other launch may store values
# anywhere in i64 (the 4-plane paths handle those exactly — the cur
# shift alone would wrap).
CUR_TAT_BOUND = 1 << 62


def track_cur_safety(table, compact, params_cur_safe) -> None:
    """Cross-launch half of the compact="cur" certificate.

    fits_cur_wire (kernel.py) bounds only the CURRENT launch; a prior
    big-tolerance launch can persist a TAT >= 2^62 for a key, and a
    later normal-tolerance cur-mode launch on that key would wrap
    `cur * 2 + allowed`.  So the table tracks a sticky `cur_safe` flag:
    a launch preserves it iff its own params are certified — either
    implicitly (compact="cur" callers certify by contract) or via
    `params_cur_safe=True`.  Dispatchers consult `table.cur_safe`
    before choosing the cur wire mode.
    """
    if compact not in ("cur", "w32") and not params_cur_safe:
        # compact="w32" implies safety: its certificate (fits_w32_wire)
        # bounds every valid tolerance to seconds-scale, far below 2^61.
        table.cur_safe = False


def _host_max_now(now_ns):
    """Max launch timestamp for BucketTable.note_launch_now — host
    values only (a jax.Array reports unknown, saturating the mark)."""
    if isinstance(now_ns, jax.Array):
        return None
    a = np.asarray(now_ns, np.int64)
    return int(a.max(initial=0)) if a.ndim else int(a)


def _host_max_tol(valid, tolerance):
    """Masked max tolerance for BucketTable.note_max_tolerance — host
    arrays only (a jax.Array would force a device sync, so it reports
    unknown instead and the mark saturates)."""
    if isinstance(tolerance, jax.Array) or isinstance(valid, jax.Array):
        return None
    v = np.asarray(valid, bool)
    return int(
        np.where(v, np.asarray(tolerance, np.int64), 0).max(initial=0)
    )


def tats_cur_safe(tats) -> bool:
    """Host-side audit of raw i64 TAT values: True iff every one is in
    [0, CUR_TAT_BOUND) — the condition under which compact="cur"
    launches are exact against state holding them.  Snapshot restore
    uses this to re-derive `cur_safe` for foreign state."""
    tat = np.asarray(tats, np.int64)
    return tat.size == 0 or bool(
        ((tat >= 0) & (tat < CUR_TAT_BOUND)).all()
    )


def _fused_enabled() -> bool:
    """Route decision windows through the fused Pallas kernel
    (pallas_fused.py; THROTTLECRAB_PALLAS_FUSED=1).  Read per dispatch
    — the fused wrappers and the composed-XLA twins are separate jit
    entry points, so the flag flips between launches without retracing
    tricks and unset preserves byte-identical current behavior.  The
    check itself must not import pallas_fused: with the kill switch
    engaged the default path stays isolated from the experimental
    pallas stack (kernel.pallas_fused_enabled is the canonical parse).
    """
    from .kernel import pallas_fused_enabled

    return pallas_fused_enabled()


class StaleIdRowsError(RuntimeError):
    """Device-resident by-id parameter rows refer to slots the keymap has
    since remapped (sweep freed them or the table grew); re-run
    upload_id_rows before the next by-id launch."""


class ResidentIdRows:
    """Device-resident by-id parameter rows plus a staleness guard.

    Pins the keymap's `mutations` counter at build time; any later
    sweep, growth, or intern of new ids bumps it, and the next by-id
    launch raises StaleIdRowsError instead of silently deciding against
    stale or uncovered slots.
    """

    def __init__(self, rows: jax.Array, keymap) -> None:
        self.rows = rows
        self._keymap = keymap
        self._stamp = getattr(keymap, "mutations", 0)

    def rows_checked(self) -> jax.Array:
        current = getattr(self._keymap, "mutations", 0)
        if current != self._stamp:
            raise StaleIdRowsError(
                "by-id parameter rows are stale: the keymap remapped "
                f"slots since upload (mutations {self._stamp} -> "
                f"{current}); re-run upload_id_rows"
            )
        return self.rows


class HwmMarksMixin:
    """The compact="w32" certificate's cross-launch high-water marks,
    shared by BucketTable and ShardedBucketTable: every stored TAT is
    <= its writing launch's now + tol <= now_hwm + tol_hwm, which
    fits_w32_wire needs to bound reset/retry fields.  A launch that
    cannot report a value saturates its mark (w32 off until rebuild).
    Subclass __init__ sets `tol_hwm = now_hwm = 0`."""

    def note_max_tolerance(self, max_tol) -> None:
        """Record a launch's max valid-lane tolerance (None = unknown)."""
        if max_tol is None:
            self.tol_hwm = I64_MAX
        else:
            self.tol_hwm = max(self.tol_hwm, int(max_tol))

    def note_launch_now(self, now_ns) -> None:
        """Record a launch's max timestamp (None = unknown)."""
        if now_ns is None:
            self.now_hwm = I64_MAX
        else:
            self.now_hwm = max(self.now_hwm, int(now_ns))


class BucketTable(HwmMarksMixin):
    """Per-slot GCRA state on a single device."""

    SCRATCH = 1 << 16  # max batch size; scratch rows for suppressed writes

    def __init__(
        self, capacity: int, device=None, insight: bool = False
    ) -> None:
        self.capacity = capacity
        self.device = device
        self.state = self._alloc(capacity + self.SCRATCH)
        # Insight tier (L3.75) accumulators: a per-slot denied-hit
        # counter fused into the packed state rows (kernel.INS_WIDTH —
        # maintained by the decision path's own row gather/scatter, so
        # it is close to free) + running [allowed, denied] totals,
        # updated inside every decision launch (the gcra_*_ins kernel
        # twins) and read only at the insight tier's throttled poll.
        # Rides ONLY the engine serving paths (check_batch / check_many
        # / check_many_packed); the by-id bench paths bypass it.  Off
        # by default: the plain *_acc kernels run on 4-wide rows and
        # the decision path is bit-identical to a table built without
        # insight.
        self.insight = False
        self.ins_counts = None
        if insight:
            self.enable_insight()
        # True while every stored TAT provably sits in [0, 2^62) — the
        # cross-launch precondition of the compact="cur" wire mode (see
        # track_cur_safety).  Fresh state is all-zero TATs: safe.
        self.cur_safe = True
        # Device-resident expired-hit accumulator: donated through every
        # decision launch (kernel gcra_*_acc), read only on demand — the
        # signal behind the adaptive cleanup policy's expired-ratio
        # trigger (adaptive_cleanup.rs:150-163).
        ctx = (
            jax.default_device(self.device)
            if self.device is not None
            else _nullcontext()
        )
        with ctx:
            self.exp_acc = jnp.zeros((), jnp.int64)
        # High-water marks backing the compact="w32" certificate
        # (kernel.fits_w32_wire): every stored TAT is <= its writing
        # launch's now + tol <= now_hwm + tol_hwm, so a later launch at
        # now >= now_hwm can bound its reset/retry fields.  A launch at
        # an EARLIER now (clock regression / caller-supplied timestamp)
        # breaks that inequality, so w32 also requires now >= now_hwm.
        # Launches that cannot report their values saturate the marks.
        self.tol_hwm = 0
        self.now_hwm = 0

    def expired_hits(self) -> int:
        """Total expired-hit count since construction.  One scalar
        device→host fetch — callers throttle (see
        TpuRateLimiter.take_expired_hits)."""
        return int(self.exp_acc)

    # ---- insight tier (L3.75) accumulators ---------------------------- #

    def enable_insight(self) -> None:
        """Widen the state rows to kernel.INS_WIDTH (appending
        zero-initialized denied-hit counter columns), allocate the
        totals accumulator, and route decision launches through the
        gcra_*_ins kernel twins.  Idempotent.  The fused decision
        kernel (THROTTLECRAB_PALLAS_FUSED) is width-polymorphic — its
        6-wide template folds the denied-hit counter into the same row
        DMAs, so insight and the fused Pallas path coexist.
        """
        from .kernel import INS_WIDTH

        if self.insight:
            return
        ctx = (
            jax.default_device(self.device)
            if self.device is not None
            else _nullcontext()
        )
        with ctx:
            pad = jnp.zeros(
                (self.state.shape[0], INS_WIDTH - 4), jnp.int32
            )
            self.state = jnp.concatenate([self.state, pad], axis=-1)
            self.ins_counts = jnp.zeros((2,), jnp.int64)
        self.insight = True

    def insight_counts(self) -> tuple:
        """(allowed_total, denied_total) decided through the insight
        launch paths since construction.  One small device→host fetch
        that synchronizes on in-flight launches — callers throttle
        (the insight tier polls ~1/s)."""
        if not self.insight:
            return (0, 0)
        counts = np.asarray(self.ins_counts)
        return int(counts[0]), int(counts[1])

    def insight_topk(self, k: int):
        """Device-side partial top-K of the denied-hit counter column:
        (counts, slot_ids) DEVICE arrays, highest count first — the
        fetch is the caller's (np.asarray), so it can stay deferred.
        One tiny extra launch per call; the insight tier invokes it
        only at its poll cadence, never per decision."""
        from .kernel import insight_topk

        if not self.insight:
            return None
        k = max(1, min(int(k), self.capacity))
        return insight_topk(self.state, capacity=self.capacity, k=k)

    def insight_decay(self) -> None:
        """Halve the denied-hit counter columns (periodic heat decay)."""
        from .kernel import insight_decay

        if self.insight:
            self.state = insight_decay(self.state)

    def _alloc(self, rows: int) -> jax.Array:
        ctx = (
            jax.default_device(self.device)
            if self.device is not None
            else _nullcontext()
        )
        with ctx:
            return pack_state(
                jnp.zeros((rows,), jnp.int64),
                jnp.full((rows,), EMPTY_EXPIRY, jnp.int64),
            )

    @property
    def tat(self) -> jax.Array:
        """i64 TAT column (diagnostics/tests; excludes scratch)."""
        return unpack_state(self.state)[0][: self.capacity]

    @property
    def expiry(self) -> jax.Array:
        """i64 expiry column (diagnostics/tests; excludes scratch)."""
        return unpack_state(self.state)[1][: self.capacity]

    def check_batch(
        self,
        slots: np.ndarray,
        rank: np.ndarray,
        is_last: np.ndarray,
        emission: np.ndarray,
        tolerance: np.ndarray,
        quantity: np.ndarray,
        valid: np.ndarray,
        now_ns: int,
        with_degen: bool = True,
        compact: bool = False,
        params_cur_safe: bool = False,
    ) -> jax.Array:
        """Run one decision batch; updates the table state in place.

        Returns the stacked device output [4, B]: rows are (allowed,
        remaining, reset_after, retry_after) — fetch with one np.asarray.

        `params_cur_safe=True` asserts this launch's params satisfy the
        cur certificate (no degenerate request, tol/now < 2^61) so the
        table's `cur_safe` flag survives; compact="cur" implies it.
        """
        assert len(slots) <= self.SCRATCH, "batch exceeds scratch region"
        track_cur_safety(self, compact, params_cur_safe)
        self.note_max_tolerance(_host_max_tol(valid, tolerance))
        self.note_launch_now(_host_max_now(now_ns))
        args = (
            jnp.asarray(slots, jnp.int32),
            jnp.asarray(rank, jnp.int32),
            jnp.asarray(is_last, bool),
            jnp.asarray(emission, jnp.int64),
            jnp.asarray(tolerance, jnp.int64),
            jnp.asarray(quantity, jnp.int64),
            jnp.asarray(valid, bool),
            now_ns,
        )
        if _fused_enabled():
            from . import pallas_fused

            if self.insight:
                self.state, self.exp_acc, self.ins_counts, out = (
                    pallas_fused.gcra_batch_fused_ins(
                        self.state, self.exp_acc, self.ins_counts, *args,
                        with_degen=with_degen, compact=compact,
                    )
                )
            else:
                self.state, self.exp_acc, out = (
                    pallas_fused.gcra_batch_fused_acc(
                        self.state, self.exp_acc, *args,
                        with_degen=with_degen, compact=compact,
                    )
                )
        elif self.insight:
            self.state, self.exp_acc, self.ins_counts, out = (
                gcra_batch_ins(
                    self.state, self.exp_acc, self.ins_counts, *args,
                    with_degen=with_degen, compact=compact,
                )
            )
        else:
            self.state, self.exp_acc, out = gcra_batch_acc(
                self.state, self.exp_acc, *args,
                with_degen=with_degen, compact=compact,
            )
        return out

    def check_many(
        self,
        slots: np.ndarray,
        rank: np.ndarray,
        is_last: np.ndarray,
        emission: np.ndarray,
        tolerance: np.ndarray,
        quantity: np.ndarray,
        valid: np.ndarray,
        now_ns: np.ndarray,
        with_degen: bool = True,
        compact: bool = False,
        params_cur_safe: bool = False,
    ) -> jax.Array:
        """K stacked micro-batches ([K, B] inputs, i64[K] timestamps) in one
        launch; returns the [K, 4, B] stacked device output."""
        assert slots.shape[1] <= self.SCRATCH, "batch exceeds scratch region"
        track_cur_safety(self, compact, params_cur_safe)
        self.note_max_tolerance(_host_max_tol(valid, tolerance))
        self.note_launch_now(_host_max_now(now_ns))
        args = (
            jnp.asarray(slots, jnp.int32),
            jnp.asarray(rank, jnp.int32),
            jnp.asarray(is_last, bool),
            jnp.asarray(emission, jnp.int64),
            jnp.asarray(tolerance, jnp.int64),
            jnp.asarray(quantity, jnp.int64),
            jnp.asarray(valid, bool),
            jnp.asarray(now_ns, jnp.int64),
        )
        if _fused_enabled():
            from . import pallas_fused

            if self.insight:
                self.state, self.exp_acc, self.ins_counts, out = (
                    pallas_fused.gcra_scan_fused_ins(
                        self.state, self.exp_acc, self.ins_counts, *args,
                        with_degen=with_degen, compact=compact,
                    )
                )
            else:
                self.state, self.exp_acc, out = (
                    pallas_fused.gcra_scan_fused_acc(
                        self.state, self.exp_acc, *args,
                        with_degen=with_degen, compact=compact,
                    )
                )
        elif self.insight:
            self.state, self.exp_acc, self.ins_counts, out = (
                gcra_scan_ins(
                    self.state, self.exp_acc, self.ins_counts, *args,
                    with_degen=with_degen, compact=compact,
                )
            )
        else:
            self.state, self.exp_acc, out = gcra_scan_acc(
                self.state, self.exp_acc, *args,
                with_degen=with_degen, compact=compact,
            )
        return out

    def check_many_packed(
        self,
        packed,
        now_ns,
        with_degen: bool = True,
        compact=False,
        params_cur_safe: bool = False,
        max_tolerance=None,
    ) -> jax.Array:
        """K stacked micro-batches from ONE packed i32[K, B, PACK_WIDTH]
        buffer (see kernel.pack_requests); `now_ns` is i64[K].

        `compact` may be False (i64[K, 4, B] ns outputs), True (i32 wire
        planes), or "cur" (i64[K, B], one `cur*2+allowed` word per
        request for host-side completion via kernel.finish_cur / native
        tk_finish — requires with_degen=False and the fits_cur_wire
        certificate; 8 B/request, the cheapest device→host fetch).

        Unlike check_many this does NOT convert the output — it returns the
        device array untouched so a pipelined caller can defer the fetch
        (dispatch launch N+1 before reading launch N's results; dispatch
        is asynchronous).  `packed` may be a numpy array
        or an already-transferred device array.
        """
        assert packed.shape[1] <= self.SCRATCH, "batch exceeds scratch region"
        track_cur_safety(self, compact, params_cur_safe)
        # Packed rows hide the tolerances; the caller reports its masked
        # max (None saturates the mark — see note_max_tolerance).
        self.note_max_tolerance(max_tolerance)
        self.note_launch_now(_host_max_now(now_ns))
        fn, carry = self._packed_launch()
        *carry, out = fn(
            *carry,
            packed
            if isinstance(packed, jax.Array)
            else jnp.asarray(packed, jnp.int32),
            jnp.asarray(now_ns, jnp.int64),
            with_degen=with_degen, compact=compact,
        )
        if self.insight:
            self.state, self.exp_acc, self.ins_counts = carry
        else:
            self.state, self.exp_acc = carry
        return out

    def _packed_launch(self):
        """The jitted packed scan check_many_packed dispatches, and the
        table buffers it carries."""
        if self.insight:
            carry = (self.state, self.exp_acc, self.ins_counts)
        else:
            carry = (self.state, self.exp_acc)
        if _fused_enabled():
            from . import pallas_fused

            return (
                pallas_fused.gcra_scan_packed_fused_ins
                if self.insight
                else pallas_fused.gcra_scan_packed_fused_acc
            ), carry
        return (
            gcra_scan_packed_ins if self.insight else gcra_scan_packed_acc
        ), carry

    def compile_launch(self, depth: int, batch: int, *, with_degen, compact):
        """Compile, without running, the launch check_many_packed makes
        for `depth` sub-batches of `batch` lanes (the boot gate of
        pallas_fused.require_compiles)."""
        from .kernel import PACK_WIDTH

        fn, carry = self._packed_launch()
        on = self.state.sharding
        return fn.lower(
            *(jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on)
              for x in carry),
            jax.ShapeDtypeStruct((depth, batch, PACK_WIDTH), jnp.int32,
                                 sharding=on),
            jax.ShapeDtypeStruct((depth,), jnp.int64, sharding=on),
            with_degen=with_degen, compact=compact,
        ).compile()

    def upload_id_rows(
        self, slots, emission, tolerance, keymap=None
    ):
        """Build and upload the by-id parameter rows for check_many_byid:
        i32[n_ids, IDROW_WIDTH] = [slot, em_lo/hi, tol_lo/hi, pad].  One
        untimed setup transfer; the rows then stay device-resident so a
        request costs 8 bytes host→device instead of the 36-byte packed
        row.

        A sweep or growth remaps slots and silently invalidates the
        uploaded rows; pass the `keymap` the slots came from to get a
        ResidentIdRows guard that raises StaleIdRowsError instead of
        deciding against stale slots (re-upload to refresh).  Without
        `keymap` the raw device array is returned and freshness is the
        caller's contract."""
        rows = jax.device_put(
            pack_id_rows(slots, emission, tolerance), self.device
        )
        # The rows' tolerances bound every future by-id write, so noting
        # them here covers all subsequent check_many_byid/ids launches
        # (which therefore skip per-launch reporting).
        self.note_max_tolerance(
            None
            if isinstance(tolerance, jax.Array)
            else int(np.max(np.asarray(tolerance, np.int64), initial=0))
        )
        if keymap is None:
            return rows
        return ResidentIdRows(rows, keymap)

    def check_many_byid(
        self,
        id_rows,
        words,
        now_ns,
        quantity: int = 1,
        with_degen: bool = True,
        compact=False,
        params_cur_safe: bool = False,
    ) -> jax.Array:
        """K stacked micro-batches of 8-byte request words (i64[K, B],
        tk_assemble_ids layout) against resident `id_rows` (a raw device
        array or a ResidentIdRows guard, which is freshness-checked).
        `quantity` is launch-uniform.  Returns the device output per
        `compact` (see check_many_packed) without fetching."""
        if isinstance(id_rows, ResidentIdRows):
            id_rows = id_rows.rows_checked()
        assert words.shape[1] <= self.SCRATCH, "batch exceeds scratch region"
        track_cur_safety(self, compact, params_cur_safe)
        self.note_launch_now(_host_max_now(now_ns))
        self.state, self.exp_acc, out = gcra_scan_byid_acc(
            self.state,
            self.exp_acc,
            id_rows,
            words
            if isinstance(words, jax.Array)
            else jnp.asarray(words, jnp.int64),
            jnp.asarray(now_ns, jnp.int64),
            quantity,
            with_degen=with_degen,
            compact=compact,
        )
        return out

    def check_many_ids(
        self,
        id_rows,
        ids,
        now_ns,
        quantity: int = 1,
        with_degen: bool = True,
        compact=False,
        params_cur_safe: bool = False,
    ) -> jax.Array:
        """K stacked micro-batches of RAW key ids (i32[K, B], negative =
        padding) against resident `id_rows`: 4 bytes per request on the
        wire, duplicate-segment structure derived on-device
        (kernel.gcra_scan_ids).  Accepts a ResidentIdRows guard like
        check_many_byid.  Returns the device output per `compact`."""
        if isinstance(id_rows, ResidentIdRows):
            id_rows = id_rows.rows_checked()
        assert ids.shape[1] <= self.SCRATCH, "batch exceeds scratch region"
        track_cur_safety(self, compact, params_cur_safe)
        self.note_launch_now(_host_max_now(now_ns))
        self.state, self.exp_acc, out = gcra_scan_ids_acc(
            self.state,
            self.exp_acc,
            id_rows,
            ids
            if isinstance(ids, jax.Array)
            else jnp.asarray(ids, jnp.int32),
            jnp.asarray(now_ns, jnp.int64),
            quantity,
            with_degen=with_degen,
            compact=compact,
        )
        return out

    def check_many_ids20(
        self,
        id_rows,
        packed,
        now_ns,
        quantity: int = 1,
        with_degen: bool = True,
        compact=False,
        params_cur_safe: bool = False,
    ) -> jax.Array:
        """K stacked micro-batches of 20-bit packed key ids
        (u16[K, B + B//4], kernel.pack_ids20): 2.5 bytes per request on
        the wire.  Requires the resident table to stay below the
        padding sentinel so padding can never alias a real key."""
        from .kernel import IDS20_SENTINEL, gcra_scan_ids20_acc

        if isinstance(id_rows, ResidentIdRows):
            id_rows = id_rows.rows_checked()
        if id_rows.shape[0] > IDS20_SENTINEL:
            raise ValueError(
                "20-bit id stream needs n_ids <= 2^20 - 1 (the padding "
                f"sentinel); table has {id_rows.shape[0]} id rows"
            )
        # Loudly reject a sibling API's buffer (raw i32 ids would be
        # silently truncated into in-range garbage decisions).
        if packed.shape[1] % 5 or packed.dtype != np.uint16:
            raise ValueError(
                "packed must be the u16[K, B + B//4] stream from "
                f"kernel.pack_ids20 (got {packed.dtype}"
                f"[..., {packed.shape[1]}])"
            )
        assert packed.shape[1] * 4 // 5 <= self.SCRATCH
        track_cur_safety(self, compact, params_cur_safe)
        self.note_launch_now(_host_max_now(now_ns))
        self.state, self.exp_acc, out = gcra_scan_ids20_acc(
            self.state,
            self.exp_acc,
            id_rows,
            packed
            if isinstance(packed, jax.Array)
            else jnp.asarray(packed, jnp.uint16),
            jnp.asarray(now_ns, jnp.int64),
            quantity,
            with_degen=with_degen,
            compact=compact,
        )
        return out

    def sweep(self, now_ns: int) -> np.ndarray:
        """Vacate expired slots; returns the boolean expired mask (host)."""
        if self.insight:
            # A vacated slot's denied-hit count dies with it: the slot
            # is about to be recycled for a different key.
            self.state, expired = sweep_expired_ins(
                now_ns, self.state, self.capacity
            )
        else:
            self.state, expired = sweep_expired(
                now_ns, self.state, self.capacity
            )
        return np.asarray(expired)

    def grow(self, new_capacity: int) -> None:
        """Double-style reallocation, like HashMap growth in the reference."""
        if new_capacity <= self.capacity:
            return
        extra = self._alloc(new_capacity - self.capacity)
        real = self.state[: self.capacity]
        scratch = self.state[self.capacity :]
        if self.insight:
            # New rows arrive 4-wide from _alloc; widen them to match
            # the insight row layout (zero heat).
            from .kernel import INS_WIDTH

            extra = jnp.concatenate(
                [
                    extra,
                    jnp.zeros(
                        (extra.shape[0], INS_WIDTH - 4), jnp.int32
                    ),
                ],
                axis=-1,
            )
        self.state = jnp.concatenate([real, extra[: new_capacity - self.capacity], scratch])
        self.capacity = new_capacity

    def live_count(self, now_ns: int) -> int:
        """Number of live (non-expired) entries; diagnostic only."""
        return int(jnp.sum(self.expiry > now_ns))


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False
