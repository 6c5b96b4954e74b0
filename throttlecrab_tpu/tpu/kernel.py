"""Batched GCRA decision kernel.

One jitted function replaces the reference's request-at-a-time actor hot loop
(`rate_limiter.rs:146-238` behind `actor.rs:217-236`): it takes a tensor of B
requests (slot index + per-request GCRA parameters), gathers the per-slot
state (TAT + expiry) from the HBM-resident bucket table, computes all B
decisions with pure elementwise + segment ops (VPU work — no sort, no
data-dependent control flow), scatters the surviving state back, and returns
per-request results.  Buffers are donated, so the table is updated in place
batch after batch without reallocation.

Intra-batch duplicate keys
==========================

The reference serializes duplicate keys through its single-threaded CAS loop;
a batched kernel must reproduce that *sequential* semantics inside one batch.
The host keymap — which already walks every key to resolve slots — emits the
segment structure for free: for each request, `rank` (its key's occurrence
number within the batch) and `is_last` (whether it is the key's final
occurrence).  With that, the sequential fold per key is evaluated in closed
form — no device-side sort and no segment reductions (TPU scatter-adds
serialize; a measured ~0.5 ms per segment_sum).  For a segment with uniform
parameters (the engine guarantees each key has one (emission, tolerance,
quantity) per batch):

- **Main case** (`inc > 0 and tol > 0`): an allowed request advances TAT by
  `inc = emission * quantity`, a denied one leaves it unchanged, and the
  allow-condition `tat + inc <= now + tol` is monotone in the number of prior
  allows — so the allowed set is exactly a prefix of the segment whose length
  has the direct closed form `m_raw = floor((now + tol - t0) / inc)`.  The
  request at rank r is allowed iff `r < m_raw`; a denied request's observed
  TAT is `t0 + m_raw*inc` (denial implies `m_raw <= rank`, so the segment
  total never exceeds m_raw); and the write-back at the `is_last` position
  uses segment size `rank + 1`.  Every output follows per-position — no
  cross-position communication at all.  No mid-batch expiry is possible
  here: every allowed write has ttl >= tol > 0.

- **Degenerate case** (`inc == 0 or tol == 0`, i.e. quantity=0 probes,
  burst=1, or sub-ns emission intervals): an allowed write can carry ttl == 0
  and expire *instantly* (the burst-1 quirk pinned in
  tests/test_gcra_math.py::test_burst_one_ttl_zero_quirk), or carry a
  negative raw ttl that wraps to an effectively-immortal entry whose stored
  TAT then gets clamped *up* on re-read.  Model each request as a transition
  on the "view" v (the clamped/initialised TAT it observes): denial leaves v
  unchanged (absorbing — the next request sees the identical state), a dead
  write resets v to the fresh-miss value `now - emission`, and a live write
  moves v to `max(new_tat, now - tolerance)`.  Within one batch `now` is
  fixed, so the view orbit is eventually periodic with pre-period <= 1 and
  period <= 2: the entire segment is described by the three views
  v0, v1 = f(v0), v2 = f(v1) (with v3 = v1), and every request's outputs
  select among those three by rank parity.  All closed form, no scan.

Launch amortization
===================

Every launch and every device→host fetch has a fixed cost, so the engine
processes K micro-batches per launch with `gcra_scan` (a `lax.scan` over
stacked [K, B] inputs, each
sub-batch with its own server timestamp) and fetches one stacked [K, 4, B]
output.  Single-batch `gcra_batch` is the same body without the scan.

Within one launch the body compiles to 5+ composed XLA ops per
sub-batch (unpack, gather, closed forms, pack, scatter).  These
composed-XLA programs are the only decision path: BucketTable and
ShardedBucketTable call them directly.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .sat import (
    I64_MAX,
    div_trunc,
    sat_add,
    sat_add_nn,
    sat_mul_nonneg,
    sat_sub,
    sat_sub_nn,
)

EMPTY_EXPIRY = -(1 << 63)  # expiry sentinel: always in the past

_U32 = (1 << 32) - 1

# Packed request row: one i32[PACK_WIDTH] word group per request, so a whole
# launch travels host→device as ONE buffer instead of eight arrays: one
# transfer call per launch instead of eight.
#   w0 slot | w1 rank | w2 flags(bit0 is_last, bit1 valid)
#   w3/w4 emission lo/hi | w5/w6 tolerance lo/hi | w7/w8 quantity lo/hi
PACK_WIDTH = 9
PACK_FLAG_IS_LAST = 1
PACK_FLAG_VALID = 2


def pack_state(tat, expiry):
    """(i64[N], i64[N]) → i32[N, 4] rows [tat_lo, tat_hi, exp_lo, exp_hi].

    TPU scatter cost is per-row with poor i64 lowering; one 4×i32 row
    scatter is ~4.5x cheaper than two separate i64 scatters (measured on
    v5e), so the table lives split into 32-bit halves.
    """
    def split(x):
        lo = (x & _U32).astype(jnp.uint32).astype(jnp.int32)
        hi = (x >> 32).astype(jnp.int32)
        return lo, hi

    tat_lo, tat_hi = split(tat)
    exp_lo, exp_hi = split(expiry)
    return jnp.stack([tat_lo, tat_hi, exp_lo, exp_hi], axis=-1)


def unpack_state(state):
    """i32[..., W] rows → (tat i64[...], expiry i64[...]); extra
    columns (the insight-widened layout) are ignored."""
    def join(lo, hi):
        return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) & _U32)

    return (
        join(state[..., 0], state[..., 1]),
        join(state[..., 2], state[..., 3]),
    )


# Insight-widened row: [tat_lo, tat_hi, exp_lo, exp_hi, deny_lo,
# deny_hi] — the per-slot denied-hit counter lives INSIDE the packed
# state row so the decision path's one row gather + one row scatter
# maintain it for free (scatter cost is per row, not per column —
# that's why the table is packed rows in the first place).
INS_WIDTH = 6


def unpack_deny(state):
    """Denied-hit counter column of insight-widened rows (i64[...])."""
    return (state[..., 5].astype(jnp.int64) << 32) | (
        state[..., 4].astype(jnp.int64) & _U32
    )


def _split_cols(x):
    """i64[...] → i32[..., 2] lo/hi column pair."""
    lo = (x & _U32).astype(jnp.uint32).astype(jnp.int32)
    hi = (x >> 32).astype(jnp.int32)
    return jnp.stack([lo, hi], axis=-1)


def pack_requests(slots, rank, is_last, emission, tolerance, quantity, valid):
    """Host-side packing: [...]-shaped request arrays → i32[..., PACK_WIDTH].

    numpy fallback for the C++ assembler (native/keymap.cpp tk_assemble),
    which writes the same layout straight from key ids with no intermediate
    arrays.
    """
    import numpy as np

    out = np.empty(np.shape(slots) + (PACK_WIDTH,), np.int32)
    out[..., 0] = slots
    out[..., 1] = rank
    out[..., 2] = np.asarray(is_last, np.int32) * PACK_FLAG_IS_LAST + (
        np.asarray(valid, np.int32) * PACK_FLAG_VALID
    )
    for base, arr in ((3, emission), (5, tolerance), (7, quantity)):
        a = np.asarray(arr, np.int64)
        out[..., base] = (a & _U32).astype(np.uint32).view(np.int32)
        out[..., base + 1] = (a >> 32).astype(np.int32)
    return out


def fits_cur_wire(tolerance, now_ns) -> bool:
    """Certificate for the compact="cur" output mode (8 B/request).

    The mode transmits one i64 per request: `cur * 2 + allowed`, where
    `cur` is the request's observed TAT.  Exactness requires the shift to
    never overflow: cur <= now + tol, so `now < 2**61 and tol < 2**61`
    guarantees cur < 2**62 (and the certified fast path bounds cur below
    by ~-(2**52): t0 >= now - max(emission, tolerance) with the segment
    advance certified < 2**62).  tol >= 2**61 means a burst window over
    73 years; now >= 2**61 is a wall clock past year 2043 — the engine
    falls back to the 4-plane compact output for either.
    """
    import numpy as np

    return bool(now_ns < (1 << 61)) and bool(
        np.max(tolerance, initial=0) < (1 << 61)
    )


# compact="w32" field widths: allowed(1) + remaining(10) + reset_s(11)
# + retry_s(10) = 32.  The bounds are generous for real rate limits
# (remaining <= 1023 tokens of headroom, reset <= ~34 min, retry <=
# ~17 min); anything bigger falls back to compact="cur".
W32_REM_MAX = (1 << 10) - 1
W32_RESET_MAX = (1 << 11) - 1
W32_RETRY_MAX = (1 << 10) - 1


def fits_w32_wire(
    valid, emission, tolerance, quantity, now_ns, tol_hwm, now_hwm=0
) -> bool:
    """Certificate for the compact="w32" output mode (4 B/request).

    Exactness needs every valid lane's wire values inside the packed
    field widths.  From cur ∈ [now - max(em, tol), now + max(tol, hwm)]
    (the kernel clamps t0 below, the allow condition bounds new TATs
    above by now + tol, and every stored TAT is <= prior_now + hwm
    where `tol_hwm` is the table's high-water mark of valid tolerances
    ever launched — BucketTable.tol_hwm):

      remaining <= (tol + max(em, tol)) // em   <= W32_REM_MAX
      reset_s   <= (tol + hwm) // 1e9           <= W32_RESET_MAX
      retry_s   <= (inc + max(hwm - tol, 0)) // 1e9 <= W32_RETRY_MAX

    The stored-TAT bound `stored <= now + hwm` additionally needs this
    launch's clock at or past every prior launch's (`now_ns >= now_hwm`
    — BucketTable.now_hwm); a regressed clock can push reset_s past its
    field by the regression amount, so it forfeits w32 (the cur tier
    absorbs regressions fine).  Callers must ALSO hold the
    with_degen=False certificate (has_degenerate) — the degenerate
    views have no packable closed form — and now_ns >= 0.
    """
    import numpy as np

    v = np.asarray(valid, bool)
    if not bool(np.any(v)):
        return True
    if not 0 <= now_ns < (1 << 61):
        return False
    if now_ns < int(now_hwm):
        return False
    hwm = int(tol_hwm)
    if hwm >= (1 << 61):
        return False
    em = np.where(v, np.asarray(emission, np.int64), 1)
    tol = np.where(v, np.asarray(tolerance, np.int64), 0)
    q = np.where(v, np.asarray(quantity, np.int64), 0)
    if int(tol.max(initial=0)) >= (1 << 61):
        # A legal big-tolerance lane (e.g. burst 5e6 at em 1000 s) wraps
        # the int64 bound sums below and would falsely certify w32 while
        # the true reset is orders of magnitude past the 2047 s field —
        # and the stored TAT >= 2^62 would corrupt cur_safe for later
        # launches.  Mirror TK_PREP_BIGTOL / fits_w32_wire_agg's C++
        # twin: refuse before any arithmetic can wrap.
        return False
    hwm = max(hwm, int(tol.max(initial=0)))
    em_safe = np.maximum(em, 1)  # degen-free cert guarantees em > 0
    inc = em * q
    rem_bound = (tol + np.maximum(em, tol)) // em_safe
    reset_bound = (tol + hwm) // _NS_PER_SEC
    retry_bound = (inc + np.maximum(hwm - tol, 0)) // _NS_PER_SEC
    return bool(
        (np.where(v, rem_bound, 0) <= W32_REM_MAX).all()
        and (np.where(v, reset_bound, 0) <= W32_RESET_MAX).all()
        and (np.where(v, retry_bound, 0) <= W32_RETRY_MAX).all()
    )


def fits_w32_wire_agg(
    max_tol, min_tol, max_inc, rem_bound, now_ns, tol_hwm, now_hwm=0
) -> bool:
    """fits_w32_wire from precomputed valid-lane aggregates — the O(1)
    form fed by the C++ prep's `agg` output (native/keymap.cpp
    tk_prepare_batch), so the native serving path never re-walks the
    packed rows in Python.  `max_inc + (hwm - min_tol)` is the array
    version's per-lane retry bound taken conservatively (a lane's own
    inc with another lane's smaller tol can only over-estimate)."""
    if not 0 <= now_ns < (1 << 61) or now_ns < int(now_hwm):
        return False
    hwm = int(tol_hwm)
    if hwm >= (1 << 61):
        return False
    hwm = max(hwm, int(max_tol))
    if int(rem_bound) > W32_REM_MAX:
        return False
    if (int(max_tol) + hwm) // _NS_PER_SEC > W32_RESET_MAX:
        return False
    retry_bound = int(max_inc) + max(hwm - int(min_tol), 0)
    return retry_bound // _NS_PER_SEC <= W32_RETRY_MAX


def finish_w32(words):
    """Host-side unpack of the compact="w32" device output: i32 words →
    (allowed, remaining, reset_after_secs, retry_after_secs), all i32 —
    bit-exact to the 4-plane compact output on every valid lane (the
    device packed the final values; this is three shifts and masks, no
    reconstruction arithmetic)."""
    import numpy as np

    u = np.ascontiguousarray(words, np.int32).view(np.uint32)
    return (
        (u & 1).astype(np.int32),
        ((u >> 1) & np.uint32(W32_REM_MAX)).astype(np.int32),
        ((u >> 11) & np.uint32(W32_RESET_MAX)).astype(np.int32),
        ((u >> 22) & np.uint32(W32_RETRY_MAX)).astype(np.int32),
    )


def cur_wire_safe(valid, tolerance, now_ns) -> bool:
    """Valid-lane-masked fits_cur_wire, for batches that carry rejected
    or padding lanes.

    The cur certificate only concerns lanes that are actually decided
    and written: a rejected request's wrapped-garbage tolerance (e.g.
    burst 0 → u32-wrapped tol ~4.3e18) must neither forfeit the current
    launch's cur output (invalid lanes are don't-care in the wire) nor
    poison the table's cross-launch `cur_safe` flag.  The same bound
    serves both purposes because every allowed write is <= now + tol of
    its own lane (saturating paths included), so `now < 2^61` plus
    `tol < 2^61` on every VALID lane keeps all stored TATs < 2^62 —
    degenerate lanes (quantity-0 probes, zero emission, big-inc) obey
    the same write bound and need no special case.  tk_prepare_batch's
    PREP_BIGTOL is the C++ twin (it skips invalid lanes the same way).
    """
    import numpy as np

    return bool(now_ns < (1 << 61)) and not bool(
        np.any(np.asarray(valid) & (np.asarray(tolerance) >= (1 << 61)))
    )


def finish_cur(cur2, emission, tolerance, quantity, now_ns):
    """Host-side completion of the compact="cur" device output (numpy).

    Reconstructs the exact 4-plane compact wire values — (allowed,
    remaining, reset_after_secs, retry_after_secs), all i32 — from the
    single i64-per-request device output.  Under the fits_cur_wire +
    with_degen=False certificate every intermediate fits i64, so plain
    arithmetic reproduces the device's saturating ops bit-for-bit on
    every VALID lane.  (valid=False lanes are don't-care: the wire bit
    carries the masked `allowed & valid`, so a padding lane whose
    unmasked decision was "allowed" finishes with a nonzero retry where
    the 4-plane compact output has 0 — all consumers mask those lanes.)
    The C++ twin is native/keymap.cpp tk_finish (reads emission/
    tolerance/quantity straight from the packed request rows).
    """
    import numpy as np

    cur2 = np.asarray(cur2, np.int64)
    allowed = (cur2 & 1) != 0
    cur = cur2 >> 1  # arithmetic shift: exact for negative cur too
    em = np.asarray(emission, np.int64)
    tol = np.asarray(tolerance, np.int64)
    inc = em * np.asarray(quantity, np.int64)
    room = now_ns + tol - cur
    remaining = np.maximum(
        np.where(em > 0, room // np.where(em > 0, em, 1), 0), 0
    )
    reset = np.maximum(cur - now_ns + tol, 0)
    retry = np.where(allowed, 0, np.maximum(cur + inc - tol - now_ns, 0))
    i32max = _I32_MAX
    return (
        allowed.astype(np.int32),
        np.minimum(remaining, i32max).astype(np.int32),
        np.minimum(reset // 1_000_000_000, i32max).astype(np.int32),
        np.minimum(retry // 1_000_000_000, i32max).astype(np.int32),
    )


def _unpack_requests(packed, now):
    """i32[B, PACK_WIDTH] → the _gcra_body batch tuple (device side)."""

    def join(lo, hi):
        return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) & _U32)

    flags = packed[..., 2]
    return (
        packed[..., 0],                                   # slots
        packed[..., 1].astype(jnp.int64),                 # rank
        (flags & PACK_FLAG_IS_LAST) != 0,                 # is_last
        join(packed[..., 3], packed[..., 4]),             # emission
        join(packed[..., 5], packed[..., 6]),             # tolerance
        join(packed[..., 7], packed[..., 8]),             # quantity
        (flags & PACK_FLAG_VALID) != 0,                   # valid
        now,
    )


def _request_outputs(t, inc, emission, tol, now):
    """Outcome of one GCRA check from state `t` (all i64, vectorized).

    Mirrors rate_limiter.rs:168-238 for a single request whose (possibly
    clamped or miss-initialised) TAT is `t`.
    Returns (allowed, remaining, reset_after, retry_after, new_tat, ttl).
    """
    new_tat = sat_add(t, inc)
    allow_at = sat_sub(new_tat, tol)
    allowed = now >= allow_at
    cur = jnp.where(allowed, new_tat, t)
    # WRAPPING add, not saturating: the reference computes burst_limit
    # with a wrapping i64 sum (rate_limiter.rs / core oracle
    # `wrap_i64(now + tol)`), so a tolerance big enough to overflow
    # now + tol wraps negative and `remaining` collapses to 0.  XLA's
    # plain i64 add has exactly those two's-complement semantics.
    burst_limit = now + tol  # inv: allow(i64-raw-op)
    room = sat_sub(burst_limit, cur)
    remaining = jnp.where(
        emission > 0, jnp.maximum(div_trunc(room, emission), 0), 0
    )
    reset_after = jnp.maximum(sat_add(sat_sub(cur, now), tol), 0)
    retry_after = jnp.where(
        allowed, 0, jnp.maximum(sat_sub(allow_at, now), 0)
    )
    ttl = sat_add(sat_sub(new_tat, now), tol)
    return allowed, remaining, reset_after, retry_after, new_tat, ttl


def _gcra_body(state, batch, *, with_degen=True, compact=False,
               count_expired=False):
    """Decide one micro-batch; returns (state, out), plus the batch's
    expired-hit count when count_expired=True.

    `state` is the packed i32[N, 4] table (see pack_state).

    with_degen=False compiles out the degenerate-case machinery — legal only
    when the host certifies the batch has no quantity-0, burst-1,
    zero-emission, or wrapped-negative-tolerance requests (the engine checks
    per batch via has_degenerate).  The certificate also guarantees
    tolerance > 0 and inc >= 0, so this path swaps the general saturating
    add/sub for the 2-op nonneg forms (sat.py sat_add_nn/sat_sub_nn) —
    together ~40% less VPU work than the exact path.

    compact=True returns i32[4, B] (allowed, remaining, reset_after_secs,
    retry_after_secs) instead of i64 nanosecond outputs — the exact wire
    semantics of the reference server, whose responses are whole seconds
    (types.rs:87-97) and whose gRPC proto is int32 (throttlecrab.proto:15-21).
    Values saturate at i32::MAX.  Halves the device→host bytes per decision.
    """
    (slots, rank, is_last, emission, tolerance, quantity, valid, now) = batch
    N = state.shape[0]
    now = now.astype(jnp.int64)
    # Insight-widened rows (INS_WIDTH: the per-slot denied-hit counter
    # rides columns 4/5 of the SAME packed row, so its maintenance is
    # absorbed by the one gather + one scatter the decision path already
    # pays — measured free on the CPU backend, where an extra scatter
    # op would cost ~40% of the whole launch).  Static shape ⇒ the
    # plain 4-wide table compiles the identical graph as before.
    ins = state.shape[-1] > 4

    s = jnp.clip(slots, 0, N - 1).astype(jnp.int32)
    rows_g = state[s]
    stored_tat, stored_exp = unpack_state(rows_g)
    stored_deny = unpack_deny(rows_g) if ins else None
    v = valid
    live = v & (stored_exp > now)

    em = emission
    tol = tolerance

    # The with_degen=False certificate (has_degenerate + the engine's
    # now_ns >= 0 validation; direct kernel callers must uphold both)
    # guarantees tol > 0, em >= 0, inc >= 0, now >= 0, AND
    # inc * MAX_SEGMENT < 2^63 — which licenses the 2-op nonneg
    # saturating forms below (every second operand is tol, em, now, or a
    # segment product) and PLAIN multiplies for the segment arithmetic
    # (a saturating multiply hides an i64 division in its overflow
    # probe).  No certified product can overflow, via two different
    # arguments: rank-bounded multipliers (quantity's inc, rank+1, and
    # min(m_raw, rank+1)) are <= MAX_SEGMENT with inc*MAX_SEGMENT
    # certified < 2^62; the UNCLAMPED m_raw multiplier is instead bounded
    # by the division identity m_raw = num // inc => m_raw*inc <= num.
    # On the exact path the same names bind the GENERAL ops, so
    # s_add/s_sub/s_mul carry no precondition there.
    if with_degen:
        s_add, s_sub, s_mul = sat_add, sat_sub, sat_mul_nonneg
    else:
        s_add, s_sub = sat_add_nn, sat_sub_nn

        def s_mul(a, b):
            return a * b

    inc = s_mul(em, quantity)

    # Initial TAT of the segment: stored value clamped to now - tol, or the
    # first-touch value now - emission (rate_limiter.rs:158-166).  Identical
    # at every position of a segment since all inputs are per-slot uniform.
    t0 = jnp.where(
        live, jnp.maximum(stored_tat, s_sub(now, tol)), s_sub(now, em)
    )

    # ---- main case: prefix closed form ------------------------------------
    # m_raw = how many sequential allows fit before the limit; rank r is
    # allowed iff r < m_raw.  Division is exact (inc > 0 in the main case).
    num = sat_sub(s_add(now, tol), t0)
    m_raw = jnp.maximum(div_trunc(num, inc), 0)
    allowed_main = rank < m_raw

    new_tat_r = s_add(t0, s_mul(rank + 1, inc))
    # Observed TAT: own new_tat when allowed; t0 + m_raw*inc when denied
    # (all m_raw allowed requests precede any denied one).  m_raw*inc
    # never overflows on the certified path: m_raw = num // inc, so the
    # product is <= num, itself bounded by now + tol - t0.
    tat_denied = s_add(t0, s_mul(m_raw, inc))
    cur_main = jnp.where(allowed_main, new_tat_r, tat_denied)
    # Segment write-back, evaluated at the is_last position where the
    # segment size is rank + 1.
    tat_fin_main = s_add(
        t0, s_mul(jnp.minimum(m_raw, rank + 1), inc)
    )

    # WRAPPING add (see _request_outputs): the reference's burst_limit
    # wraps on i64 overflow; a saturating add here made `remaining`
    # huge instead of 0 for wrapped-positive tolerances near i64::MAX
    # (caught by differential fuzzing, round 4).  The certified fast
    # path does NOT bound tol, so the overflow case is reachable there
    # too; for every non-overflowing input the plain add is identical
    # (and cheaper).  `num` above must STAY saturating — the closed
    # form's allow condition matches the oracle's saturating chain.
    burst_limit = now + tol  # inv: allow(i64-raw-op)
    room_main = sat_sub(burst_limit, cur_main)
    remaining_main = jnp.where(
        em > 0, jnp.maximum(div_trunc(room_main, em), 0), 0
    )
    reset_main = jnp.maximum(s_add(s_sub(cur_main, now), tol), 0)
    retry_main = jnp.where(
        allowed_main,
        0,
        jnp.maximum(s_sub(s_sub(s_add(cur_main, inc), tol), now), 0),
    )

    # The reference's adaptive store counts requests that land on an
    # entry past its expiry — but only via the WRITE path: an expired
    # entry makes get() return None, and only an ALLOWED request then
    # reaches set_if_not_exists, which sees the stale entry, counts the
    # hit, and refreshes it (adaptive_cleanup.rs:267; denied requests
    # never touch the store again, and later ranks of the segment see
    # the refreshed entry).  So the signal is: rank-0 valid lane, real
    # stored expiry (not the EMPTY_EXPIRY sentinel) <= now, and that
    # lane allowed.  (One knowing deviation: a ttl-0 "dead" write's
    # allowed re-hits within the same batch are not re-counted.)
    if count_expired:
        exp_hit_base = (
            v
            & (rank == 0)
            & (stored_exp != EMPTY_EXPIRY)
            & (stored_exp <= now)
        )

    # ---- degenerate case: three-view closed form ---------------------------
    if not with_degen:
        ins_row = None
        if ins:
            # Denied count of the whole segment, at its is_last lane:
            # the first min(m_raw, size) ranks were allowed, the rest
            # denied (the prefix closed form above).
            seg_n = rank + 1
            denied_seg = seg_n - jnp.minimum(m_raw, seg_n)
            ins_row = (
                stored_tat, stored_exp, stored_deny, denied_seg,
                v & is_last,
            )
        st_out = _finish(
            state, s, N, now, tol,
            allowed_main & v,
            remaining_main,
            reset_main,
            retry_main,
            (m_raw >= 1) & v & is_last,
            tat_fin_main,
            compact,
            s_add, s_sub,
            cur=cur_main,
            ins_row=ins_row,
        )
        if count_expired:
            n_exp = jnp.sum(
                (exp_hit_base & allowed_main).astype(jnp.int64)
            )
            return (*st_out, n_exp)
        return st_out

    degen = (inc == 0) | (tol == 0)

    def view_step(t):
        """One request's outputs from view t, plus the successor view.

        A write "dies" iff its raw ttl is exactly 0 (ttl < 0 wraps to a huge
        u64 duration in the reference — effectively immortal, see
        rate_limiter.rs:179-183 + core/i64.py wrap_u64); a live write's
        stored TAT is re-clamped to now - tol by the next reader.
        """
        outs = _request_outputs(t, inc, em, tol, now)
        allowed_t, _, _, _, new_t, ttl_t = outs
        dead = allowed_t & (ttl_t == 0)
        t_next = jnp.where(
            ~allowed_t,
            t,
            jnp.where(
                dead, sat_sub(now, em), jnp.maximum(new_t, sat_sub(now, tol))
            ),
        )
        return outs, t_next

    outs0, v1 = view_step(t0)
    outs1, v2 = view_step(v1)
    outs2, _ = view_step(v2)
    a0, a1, a2 = outs0[0], outs1[0], outs2[0]

    def pick(main, o0, o1, o2):
        """Select a degen output by rank: v0 at rank 0; then v1/v2 by parity
        until the first denial, which is absorbing (the view stops moving)."""
        alternating = jnp.where((rank - 1) % 2 == 0, o1, o2)
        tail = jnp.where(rank == 1, o1, jnp.where(a2, alternating, o2))
        degen_out = jnp.where(
            ~a0, o0, jnp.where(~a1, jnp.where(rank == 0, o0, o1),
                               jnp.where(rank == 0, o0, tail))
        )
        return jnp.where(degen, degen_out, main)

    allowed_out = pick(allowed_main, a0, a0 & a1, a0 & a1 & a2) & v
    remaining_out = pick(remaining_main, outs0[1], outs1[1], outs2[1])
    reset_out = pick(reset_main, outs0[2], outs1[2], outs2[2])
    retry_out = pick(retry_main, outs0[3], outs1[3], outs2[3])

    # ---- write-back --------------------------------------------------------
    # Evaluated at the is_last position, where own rank == segment size - 1.

    # Degenerate final state: the write of the last *allowed* rank L.
    # L = 0 if only rank 0 got through (or k == 1), L = 1 if denial started
    # at rank 2, else L = k-1 with the view alternating v1/v2.
    new0_t, new1_t, new2_t = outs0[4], outs1[4], outs2[4]
    last_rank = rank
    alt_last = jnp.where((last_rank - 1) % 2 == 0, new1_t, new2_t)
    tat_fin_degen = jnp.where(
        (last_rank == 0) | ~a1,
        new0_t,
        jnp.where(~a2 | (last_rank == 1), new1_t, alt_last),
    )
    wrote_degen = a0

    wrote = jnp.where(degen, wrote_degen, m_raw >= 1) & v & is_last
    tat_fin = jnp.where(degen, tat_fin_degen, tat_fin_main)
    ins_row = None
    if ins:
        # Segment denied counts, at the is_last lane.  Main case: the
        # prefix closed form (first min(m_raw, size) ranks allowed).
        # Degenerate case: the three-view orbit — nothing after the
        # first denial is allowed, so the allowed count is 0 / 1 /
        # min(2, size) / size by which view first denies.
        seg_n = rank + 1
        allowed_cnt_main = jnp.minimum(m_raw, seg_n)
        allowed_cnt_degen = jnp.where(
            ~a0,
            0,
            jnp.where(
                ~a1, 1, jnp.where(~a2, jnp.minimum(seg_n, 2), seg_n)
            ),
        )
        denied_seg = seg_n - jnp.where(
            degen, allowed_cnt_degen, allowed_cnt_main
        )
        ins_row = (
            stored_tat, stored_exp, stored_deny, denied_seg, v & is_last
        )
    st_out = _finish(
        state, s, N, now, tol,
        allowed_out, remaining_out, reset_out, retry_out,
        wrote, tat_fin, compact,
        sat_add, sat_sub,
        ins_row=ins_row,
    )
    if count_expired:
        # allowed_out already carries & v.
        n_exp = jnp.sum((exp_hit_base & allowed_out).astype(jnp.int64))
        return (*st_out, n_exp)
    return st_out


_I32_MAX = (1 << 31) - 1
_NS_PER_SEC = 1_000_000_000


def _finish(
    state, s, N, now, tol, allowed, remaining, reset_after,
    retry_after, wrote, tat_fin, compact,
    s_add, s_sub, cur=None, ins_row=None,
):
    """Write back the surviving state (one packed-row scatter) and stack the
    outputs.  `add_nn`/`sub_nn` are the caller's saturating ops (the
    certified fast path passes the 2-op nonneg forms).

    `ins_row` (insight-widened tables only) is (stored_tat, stored_exp,
    stored_deny, denied_seg, touch): the scatter then covers every
    decided segment's is_last lane — suppressed GCRA writes re-write
    their row's stored tat/expiry verbatim (bit-identical state) while
    the deny counter columns advance by the segment's denied count.
    Same one-row-scatter cost; unique_indices still holds (one is_last
    lane per slot).

    compact="cur" (certified path only — the degenerate views have no
    single `cur`) emits ONE i64 per request, `cur * 2 + allowed`, and
    leaves remaining/reset/retry to the host (kernel.finish_cur /
    native tk_finish): XLA dead-code-eliminates their two emulated i64
    divisions from the kernel, and the device→host fetch halves to
    8 B/request.  Requires the fits_cur_wire
    certificate so the shift cannot overflow."""
    ttl_fin = s_add(s_sub(tat_fin, now), tol)
    # expiry = now + ttl; ttl < 0 wraps to a ~584-year duration in the
    # reference, which we saturate to "never expires".
    expiry_fin = jnp.where(ttl_fin < 0, I64_MAX, s_add(tat_fin, tol))

    # Suppressed writes land in the table's scratch tail (the last B rows,
    # beyond every real slot) at distinct indices, keeping the
    # unique_indices promise honest.
    B = s.shape[0]
    scratch = N - B + jnp.arange(B, dtype=jnp.int32)
    if ins_row is None:
        scatter_idx = jnp.where(wrote, s, scratch).astype(jnp.int32)
        rows = pack_state(tat_fin, expiry_fin)
    else:
        stored_tat, stored_exp, stored_deny, denied_seg, touch = ins_row
        rows = jnp.concatenate(
            [
                pack_state(
                    jnp.where(wrote, tat_fin, stored_tat),
                    jnp.where(wrote, expiry_fin, stored_exp),
                ),
                _split_cols(stored_deny + denied_seg),
            ],
            axis=-1,
        )
        scatter_idx = jnp.where(touch, s, scratch).astype(jnp.int32)
    state = state.at[scatter_idx].set(
        rows, unique_indices=True, mode="drop"
    )

    # One stacked output → one device-to-host fetch.
    if compact == "cur":
        assert cur is not None, 'compact="cur" requires with_degen=False'
        # fits_cur_wire certifies |cur| < 2**62, so the shift-and-tag
        # word cannot overflow.
        out = cur * 2 + allowed.astype(jnp.int64)  # inv: allow(i64-raw-op)
    elif compact == "w32":
        # 4 B/request: the four exact wire values packed into one i32 —
        # allowed(1) | remaining(10) | reset_s(11) | retry_s(22..31).
        # Legal only under fits_w32_wire (host-checked bounds keep every
        # valid lane's fields inside their widths; invalid lanes may
        # overflow within their own don't-care word).  Halves the fetch
        # vs compact="cur"; the i64 divisions run on device.
        assert cur is not None, 'compact="w32" requires with_degen=False'
        out = (
            allowed.astype(jnp.int32)
            | (remaining.astype(jnp.int32) << 1)
            | ((reset_after // _NS_PER_SEC).astype(jnp.int32) << 11)
            | ((retry_after // _NS_PER_SEC).astype(jnp.int32) << 22)
        )
    elif compact:
        out = jnp.stack(
            [
                allowed.astype(jnp.int32),
                jnp.minimum(remaining, _I32_MAX).astype(jnp.int32),
                jnp.minimum(reset_after // _NS_PER_SEC, _I32_MAX).astype(
                    jnp.int32
                ),
                jnp.minimum(retry_after // _NS_PER_SEC, _I32_MAX).astype(
                    jnp.int32
                ),
            ]
        )
    else:
        out = jnp.stack(
            [
                allowed.astype(jnp.int64),
                remaining.astype(jnp.int64),
                reset_after.astype(jnp.int64),
                retry_after.astype(jnp.int64),
            ]
        )
    return state, out


@partial(
    jax.jit, donate_argnums=(0,), static_argnames=("with_degen", "compact")
)
def gcra_batch(
    state, slots, rank, is_last, emission, tolerance, quantity,
    valid, now, *, with_degen=True, compact=False,
):
    """Decide B rate-limit requests against the bucket table.

    Args:
      state:     i32[N, 4] packed (tat, expiry) rows (donated; see
                 pack_state).  The last B rows are scratch for suppressed
                 writes — real slots must stay below N - B.
      slots:     i32[B] slot index per request.
      rank:      i32[B] occurrence number of this request for its key.
      is_last:   bool[B] final occurrence of this key in the batch.
      emission:  i64[B] emission interval ns (>= 0; host f64 pipeline).
      tolerance: i64[B] delay variation tolerance ns.
      quantity:  i64[B] tokens requested (>= 0; validation is host-side).
      valid:     bool[B] False for padding / rejected requests.
      now:       i64 scalar, ns since epoch (server-side timestamp).
                 Must be >= 0 when with_degen=False (part of the fast
                 path's certificate; the engine validates it).

    Duplicate slots within the batch MUST share (emission, tolerance,
    quantity); the engine defers conflicting requests to a later batch to
    preserve exact arrival-order semantics.

    Returns (state, out[4, B]) where out rows are (allowed, remaining,
    reset_after, retry_after).
    """
    return _gcra_body(
        state,
        (
            slots,
            rank.astype(jnp.int64),
            is_last,
            emission,
            tolerance,
            quantity,
            valid,
            jnp.asarray(now, jnp.int64),
        ),
        with_degen=with_degen,
        compact=compact,
    )


@partial(
    jax.jit, donate_argnums=(0,), static_argnames=("with_degen", "compact")
)
def gcra_scan(
    state, slots, rank, is_last, emission, tolerance, quantity,
    valid, now, *, with_degen=True, compact=False,
):
    """K micro-batches in one launch: inputs stacked [K, B], now is i64[K].

    Amortizes the fixed per-launch and per-fetch cost; each sub-batch carries its own server timestamp and sees the table state
    left by the previous one (lax.scan carry), exactly as if dispatched
    separately.  Returns (state, out[K, 4, B]).
    """

    def step(state, batch):
        state, out = _gcra_body(
            state, batch, with_degen=with_degen, compact=compact
        )
        return state, out

    state, outs = jax.lax.scan(
        step,
        state,
        (
            slots,
            rank.astype(jnp.int64),
            is_last,
            emission,
            tolerance,
            quantity,
            valid,
            now.astype(jnp.int64),
        ),
    )
    return state, outs


@partial(
    jax.jit, donate_argnums=(0,), static_argnames=("with_degen", "compact")
)
def gcra_scan_packed(state, packed, now, *, with_degen=True, compact=False):
    """gcra_scan with the whole launch in ONE packed buffer.

    Args:
      state:  i32[N, 4] packed table rows (donated).
      packed: i32[K, B, PACK_WIDTH] request rows (see pack_requests).
      now:    i64[K] per-sub-batch server timestamps.

    Semantically identical to gcra_scan on the unpacked arrays; the packed
    form sends one host→device buffer per launch instead of eight.
    Returns (state, out[K, 4, B]).
    """

    def step(state, kb):
        packed_k, now_k = kb
        return _gcra_body(
            state,
            _unpack_requests(packed_k, now_k),
            with_degen=with_degen,
            compact=compact,
        )

    return jax.lax.scan(step, state, (packed, now.astype(jnp.int64)))


# By-id request words (native/keymap.cpp tk_assemble_ids):
#   low 32 bits: key id | high 32: rank(14) | is_last<<14 | valid<<15
# The device gathers (slot, emission, tolerance) from resident id rows —
# an i32[n_ids, 8] table built by BucketTable.upload_id_rows — so a
# request costs 8 bytes host→device instead of the 36-byte packed row.
IDROW_WIDTH = 8


def pack_id_rows(slots, emission, tolerance, width=IDROW_WIDTH):
    """Host-side build of the resident by-id parameter rows:
    i32[n, width] = [slot, em_lo, em_hi, tol_lo, tol_hi, pad...].

    The by-id kernels read only columns 0-4, so any width >= 5 works;
    the default is 8-wide (scripts/probe_byid_ablation.py's width
    ablation measures whether a narrower gather buys anything on a
    chip).
    """
    import numpy as np

    if width < 5:
        raise ValueError("id rows need at least 5 columns")
    n = len(slots)
    rows = np.zeros((n, width), np.int32)
    rows[:, 0] = slots
    for base, arr in ((1, emission), (3, tolerance)):
        a = np.asarray(arr, np.int64)
        rows[:, base] = (a & _U32).astype(np.uint32).view(np.int32)
        rows[:, base + 1] = (a >> 32).astype(np.int32)
    return rows


def _rows_to_batch(rows, rank, is_last, valid, quantity, now_k):
    """Shared tail of the by-id scan steps: expand gathered id rows into
    the _gcra_body batch tuple.  One implementation so the host-words
    (gcra_scan_byid) and raw-ids (gcra_scan_ids) paths cannot drift."""

    def join(lo, hi):
        return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) & _U32)

    return (
        rows[:, 0],                                   # slots
        rank,
        is_last,
        join(rows[:, 1], rows[:, 2]),                 # emission
        join(rows[:, 3], rows[:, 4]),                 # tolerance
        jnp.full(rank.shape, quantity, jnp.int64),    # quantity
        valid,
        now_k,
    )


@partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=("with_degen", "compact"),
)
def gcra_scan_byid(
    state, id_rows, words, now, quantity, *, with_degen=True, compact=False,
):
    """gcra_scan fed by 8-byte request words + resident id rows.

    Args:
      state:    i32[N, 4] packed table rows (donated).
      id_rows:  i32[n_ids, IDROW_WIDTH] resident parameter rows (NOT
                donated — reused launch after launch; see pack_id_rows).
      words:    i64[K, B] request words (tk_assemble_ids layout).
      now:      i64[K] per-sub-batch timestamps.
      quantity: i64 scalar, uniform per launch (the bench/serving caller
                certifies uniformity before taking this path).

    Semantically identical to gcra_scan on the expanded arrays; requests
    whose valid bit is 0 are padding.  Returns (state, out) with `out`
    per the `compact` mode.
    """
    def step(state, kb):
        w, now_k = kb
        return _gcra_body(
            state,
            _byid_batch(w, now_k, id_rows, quantity),
            with_degen=with_degen,
            compact=compact,
        )

    return jax.lax.scan(step, state, (words, now.astype(jnp.int64)))


def _byid_batch(w, now_k, id_rows, quantity):
    """One sub-batch of 8-byte request words → the _gcra_body tuple
    (shared by gcra_scan_byid and its expired-counting twin)."""
    n_ids = id_rows.shape[0]
    idx = jnp.clip((w & _U32).astype(jnp.int32), 0, n_ids - 1)
    meta = w >> 32
    rows = id_rows[idx]
    # Same -1-slot defense as gcra_scan_ids: an unresolved id row
    # (resolve_all on a full table) carries slot -1, which would
    # otherwise clip to slot 0 and corrupt another key's bucket.
    valid = ((meta & (1 << 15)) != 0) & (rows[:, 0] >= 0)
    return _rows_to_batch(
        rows,
        meta & 0x3FFF,                                # rank (i64)
        (meta & (1 << 14)) != 0,                      # is_last
        valid,
        quantity,
        now_k,
    )


def _device_segments(segkey):
    """rank / is_last per lane from a per-lane segment key, on device.

    The host assemblers derive the duplicate-segment structure while
    walking the batch; this is the device twin: one stable argsort
    groups equal keys while preserving arrival order, a max-scan finds
    each run's start, and the inverse permutation (a second argsort —
    a gather, not a scatter) maps ranks back to arrival positions, so
    the precomputed structure need not be sent with the ids.
    """
    B = segkey.shape[0]
    order = jnp.argsort(segkey, stable=True)
    sk = segkey[order]
    pos = jnp.arange(B, dtype=jnp.int32)
    run_start = jnp.concatenate(
        [jnp.ones((1,), bool), sk[1:] != sk[:-1]]
    )
    start_pos = jax.lax.associative_scan(
        jnp.maximum, jnp.where(run_start, pos, 0)
    )
    rank_sorted = pos - start_pos
    last_sorted = jnp.concatenate(
        [sk[1:] != sk[:-1], jnp.ones((1,), bool)]
    )
    inv = jnp.argsort(order, stable=True)
    return rank_sorted[inv].astype(jnp.int64), last_sorted[inv]


@partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=("with_degen", "compact"),
)
def gcra_scan_ids(
    state, id_rows, ids, now, quantity, *, with_degen=True, compact=False,
):
    """gcra_scan fed by RAW key ids — 4 bytes per request on the wire.

    The leanest launch: `ids` is i32[K, B] (negative = padding); the
    device gathers (slot, emission, tolerance) from the resident
    `id_rows` AND derives the duplicate-segment structure itself
    (_device_segments), so the host ships nothing but the id stream —
    no C++ assembly on the dispatch path at all.

    Segments are keyed by SLOT (like the host assemblers), so two ids
    sharing a slot still serialize exactly; padding lanes get per-lane
    sentinel keys beyond every real slot so they can never join — or
    split — a real segment.  Semantically identical to gcra_scan_byid
    on tk_assemble_ids words (pinned by tests/test_packed_path.py).
    """

    def step(state, kb):
        w, now_k = kb
        return _gcra_body(
            state,
            _ids_batch(w, now_k, id_rows, quantity),
            with_degen=with_degen,
            compact=compact,
        )

    return jax.lax.scan(step, state, (ids, now.astype(jnp.int64)))


def _ids_batch(w, now_k, id_rows, quantity):
    """One sub-batch of raw key ids → the _gcra_body tuple (shared by
    gcra_scan_ids and its expired-counting twin)."""
    n_ids = id_rows.shape[0]
    # In-range check mirrors the host assembler's n_bad contract: an
    # id beyond the resident rows (interned after upload, or
    # corrupt) must be invalid, never clipped onto another key.
    valid = (w >= 0) & (w < n_ids)
    idx = jnp.clip(w, 0, n_ids - 1)
    rows = id_rows[idx]
    slots = rows[:, 0]
    # An unresolved id row carries slot -1 (resolve_all on a full
    # table); never decide those against clipped slot 0.
    valid = valid & (slots >= 0)
    B = w.shape[0]
    pos = jnp.arange(B, dtype=jnp.int32)
    # Segment key: the slot for real lanes; a distinct out-of-range
    # sentinel per invalid lane (slots are clipped to [0, N) by the
    # kernel, so I32_MAX - pos can collide with nothing real).
    segkey = jnp.where(valid, slots, _I32_MAX - pos)
    rank, is_last = _device_segments(segkey)
    return _rows_to_batch(rows, rank, is_last, valid, quantity, now_k)


# ---- expired-hit accounting twins -------------------------------------- #
# Same decisions (bit-for-bit) as their namesakes plus a device-resident
# accumulator: a donated i64 scalar that grows by each sub-batch's
# expired-hit count (see _gcra_body count_expired — the signal behind the
# reference adaptive store's expired-ratio cleanup trigger,
# adaptive_cleanup.rs:150-163).  BucketTable routes every launch through
# these; the plain entry points above remain the public single-concern
# kernel API (tests, probes, examples, and external callers that bring
# their own state arrays).  Both halves share _gcra_body and the
# _byid_batch/_ids_batch builders, so they cannot drift.  The count
# rides the launch — no extra dispatch, no extra fetch; the host reads
# the scalar only when the cleanup policy wants it
# (BucketTable.expired_hits).


@partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_batch_acc(
    state, exp_acc, slots, rank, is_last, emission, tolerance, quantity,
    valid, now, *, with_degen=True, compact=False,
):
    """gcra_batch + expired-hit accumulation; returns (state, acc, out)."""
    state, out, n_exp = _gcra_body(
        state,
        (
            slots,
            rank.astype(jnp.int64),
            is_last,
            emission,
            tolerance,
            quantity,
            valid,
            jnp.asarray(now, jnp.int64),
        ),
        with_degen=with_degen,
        compact=compact,
        count_expired=True,
    )
    return state, exp_acc + n_exp, out


@partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_scan_acc(
    state, exp_acc, slots, rank, is_last, emission, tolerance, quantity,
    valid, now, *, with_degen=True, compact=False,
):
    """gcra_scan + expired-hit accumulation; returns (state, acc, out)."""

    def step(carry, batch):
        st, acc = carry
        st, out, n = _gcra_body(
            st, batch, with_degen=with_degen, compact=compact,
            count_expired=True,
        )
        return (st, acc + n), out

    (state, exp_acc), outs = jax.lax.scan(
        step,
        (state, exp_acc),
        (
            slots,
            rank.astype(jnp.int64),
            is_last,
            emission,
            tolerance,
            quantity,
            valid,
            now.astype(jnp.int64),
        ),
    )
    return state, exp_acc, outs


@partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_scan_packed_acc(
    state, exp_acc, packed, now, *, with_degen=True, compact=False,
):
    """gcra_scan_packed + expired-hit accumulation."""

    def step(carry, kb):
        st, acc = carry
        p, now_k = kb
        st, out, n = _gcra_body(
            st, _unpack_requests(p, now_k),
            with_degen=with_degen, compact=compact, count_expired=True,
        )
        return (st, acc + n), out

    (state, exp_acc), outs = jax.lax.scan(
        step, (state, exp_acc), (packed, now.astype(jnp.int64))
    )
    return state, exp_acc, outs


# ---- insight twins (L3.75 analytics) ------------------------------------ #
# Same decisions (bit-for-bit) as the *_acc kernels plus the insight
# accumulators riding the SAME launch: the per-slot denied-hit counter
# lives inside the widened state rows (INS_WIDTH — maintained by the
# decision path's own row gather/scatter, see _finish's ins_row), and
# `ins_counts` (i64[2] running [allowed, denied] totals) folds in after
# the scan from the launch's outputs — every output tier carries the
# valid-masked allowed bit, so the totals cost two reductions.  Used
# only when the BucketTable was built with insight enabled; with it off
# the plain *_acc kernels run on 4-wide rows and the XLA graph is
# untouched — the THROTTLECRAB_INSIGHT=0 kill switch is a different
# jit entry point + table layout, not a traced branch.  Everything is
# donated and device-resident; the host reads the accumulators only at
# the insight tier's throttled poll (BucketTable.insight_counts /
# insight_topk), so analytics add zero launches and zero fetches to the
# decision path.


def _lanes_allowed(out, compact):
    """The valid-masked allowed bit of any output tier, [..., B]."""
    if compact in ("cur", "w32"):
        return (out & 1) != 0
    return out[..., 0, :] != 0


def _insight_totals(ins_counts, valid, out, compact):
    """Advance the [allowed, denied] totals from one launch's outputs.
    Allowed planes are already masked with `valid`, so `valid &
    ~allowed` is exactly the decided-and-denied lanes; padding and
    rejected lanes count nowhere."""
    allowed = _lanes_allowed(out, compact)
    denied = valid & ~allowed
    return ins_counts + jnp.stack(
        [
            jnp.sum(allowed.astype(jnp.int64)),
            jnp.sum(denied.astype(jnp.int64)),
        ]
    )


@partial(
    jax.jit,
    donate_argnums=(0, 1, 2),
    static_argnames=("with_degen", "compact"),
)
def gcra_batch_ins(
    state, exp_acc, ins_counts, slots, rank, is_last, emission,
    tolerance, quantity, valid, now, *, with_degen=True, compact=False,
):
    """gcra_batch_acc + insight accumulation; returns
    (state, exp_acc, ins_counts, out).  `state` must be INS_WIDTH rows.
    """
    state, out, n_exp = _gcra_body(
        state,
        (
            slots,
            rank.astype(jnp.int64),
            is_last,
            emission,
            tolerance,
            quantity,
            valid,
            jnp.asarray(now, jnp.int64),
        ),
        with_degen=with_degen,
        compact=compact,
        count_expired=True,
    )
    ins_counts = _insight_totals(ins_counts, valid, out, compact)
    return state, exp_acc + n_exp, ins_counts, out


@partial(
    jax.jit,
    donate_argnums=(0, 1, 2),
    static_argnames=("with_degen", "compact"),
)
def gcra_scan_ins(
    state, exp_acc, ins_counts, slots, rank, is_last, emission,
    tolerance, quantity, valid, now, *, with_degen=True, compact=False,
):
    """gcra_scan_acc + insight accumulation (INS_WIDTH rows)."""

    def step(carry, batch):
        st, acc = carry
        st, out, n = _gcra_body(
            st, batch, with_degen=with_degen, compact=compact,
            count_expired=True,
        )
        return (st, acc + n), out

    (state, exp_acc), outs = jax.lax.scan(
        step,
        (state, exp_acc),
        (
            slots,
            rank.astype(jnp.int64),
            is_last,
            emission,
            tolerance,
            quantity,
            valid,
            now.astype(jnp.int64),
        ),
    )
    ins_counts = _insight_totals(ins_counts, valid, outs, compact)
    return state, exp_acc, ins_counts, outs


@partial(
    jax.jit,
    donate_argnums=(0, 1, 2),
    static_argnames=("with_degen", "compact"),
)
def gcra_scan_packed_ins(
    state, exp_acc, ins_counts, packed, now, *,
    with_degen=True, compact=False,
):
    """gcra_scan_packed_acc + insight accumulation (the valid flags
    come straight off the packed request rows; INS_WIDTH rows)."""

    def step(carry, kb):
        st, acc = carry
        p, now_k = kb
        st, out, n = _gcra_body(
            st, _unpack_requests(p, now_k),
            with_degen=with_degen, compact=compact, count_expired=True,
        )
        return (st, acc + n), out

    (state, exp_acc), outs = jax.lax.scan(
        step, (state, exp_acc), (packed, now.astype(jnp.int64))
    )
    ins_counts = _insight_totals(
        ins_counts,
        (packed[..., 2] & PACK_FLAG_VALID) != 0,
        outs,
        compact,
    )
    return state, exp_acc, ins_counts, outs


@partial(jax.jit, static_argnames=("capacity", "k"))
def insight_topk(state, *, capacity, k):
    """Device-side partial top-K of the denied-hit counter column of an
    insight-widened table: (counts i64[k], slot ids i32[k]), highest
    first.  One tiny launch per insight poll (~1/s), never on the
    decision path; rows past `capacity` (the scratch tail) are
    excluded."""
    vals, idx = jax.lax.top_k(unpack_deny(state[:capacity]), k)
    return vals, idx.astype(jnp.int32)


@partial(jax.jit, donate_argnums=(0,))
def insight_decay(state):
    """Halve the denied-hit counter columns (the insight tier's
    periodic decay: old heat fades, so the top-K tracks the CURRENT hot
    set).  Floor division keeps counts exact against the host twin's
    `// 2`; tat/expiry columns pass through untouched."""
    return jnp.concatenate(
        [state[..., :4], _split_cols(unpack_deny(state) // 2)], axis=-1
    )


@partial(jax.jit, donate_argnums=(1,), static_argnames=("capacity",))
def sweep_expired_ins(now, state, capacity):
    """sweep_expired for insight-widened rows: a vacated slot's
    denied-hit count dies with it (the empty row zeroes ALL columns),
    or the next key recycled into the slot would inherit the old key's
    heat.  Returns (state, expired[:capacity])."""
    now = jnp.asarray(now, jnp.int64)
    _, expiry = unpack_state(state)
    expired = expiry <= now
    empty_rows = jnp.concatenate(
        [
            pack_state(
                jnp.zeros_like(expiry), jnp.full_like(expiry, EMPTY_EXPIRY)
            ),
            jnp.zeros(state.shape[:-1] + (state.shape[-1] - 4,), jnp.int32),
        ],
        axis=-1,
    )
    state = jnp.where(expired[:, None], empty_rows, state)
    return state, expired[:capacity]


@partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_scan_byid_acc(
    state, exp_acc, id_rows, words, now, quantity, *,
    with_degen=True, compact=False,
):
    """gcra_scan_byid + expired-hit accumulation."""

    def step(carry, kb):
        st, acc = carry
        w, now_k = kb
        st, out, n = _gcra_body(
            st, _byid_batch(w, now_k, id_rows, quantity),
            with_degen=with_degen, compact=compact, count_expired=True,
        )
        return (st, acc + n), out

    (state, exp_acc), outs = jax.lax.scan(
        step, (state, exp_acc), (words, now.astype(jnp.int64))
    )
    return state, exp_acc, outs


@partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_scan_ids_acc(
    state, exp_acc, id_rows, ids, now, quantity, *,
    with_degen=True, compact=False,
):
    """gcra_scan_ids + expired-hit accumulation."""

    def step(carry, kb):
        st, acc = carry
        w, now_k = kb
        st, out, n = _gcra_body(
            st, _ids_batch(w, now_k, id_rows, quantity),
            with_degen=with_degen, compact=compact, count_expired=True,
        )
        return (st, acc + n), out

    (state, exp_acc), outs = jax.lax.scan(
        step, (state, exp_acc), (ids, now.astype(jnp.int64))
    )
    return state, exp_acc, outs


# ---- 20-bit id stream ---------------------------------------------------- #
# The leanest host→device encoding for tables under 2^20 - 1 keys:
# 2.5 bytes per request in ONE fused u16 buffer (B low-16 lanes, then
# B/4 lanes of packed high nibbles), decoded on device with two gathers
# and shifts.  With the w32 output tier the whole round trip is
# 6.5 B/request (vs 8 for raw i32 ids + w32, 12 for ids + cur).

IDS20_SENTINEL = (1 << 20) - 1  # padding marker (never a real id)


def pack_ids20(ids):
    """i32[K, B] raw key ids (negative = padding) → u16[K, B + B//4].

    Requires B % 4 == 0 and every real id < 2^20 - 1 (the all-ones
    pattern is the padding sentinel; the device decodes it to an
    out-of-range id, which gcra_scan_ids' in-range check masks
    invalid — callers must also keep n_ids <= IDS20_SENTINEL so the
    sentinel can never alias a real key).
    """
    import numpy as np

    ids = np.asarray(ids)
    K, B = ids.shape
    if B % 4:
        raise ValueError("ids20 batch width must be a multiple of 4")
    if (ids >= IDS20_SENTINEL).any():
        raise ValueError(
            "ids must be < 2^20 - 1 for the 20-bit id stream"
        )
    u = np.where(ids < 0, IDS20_SENTINEL, ids).astype(np.uint32)
    lo = (u & 0xFFFF).astype(np.uint16)
    hi4 = (u >> 16).astype(np.uint16).reshape(K, B // 4, 4)
    hibuf = (
        hi4[..., 0]
        | (hi4[..., 1] << 4)
        | (hi4[..., 2] << 8)
        | (hi4[..., 3] << 12)
    )
    return np.concatenate([lo, hibuf], axis=1)


def _ids20_decode(buf, B):
    """One sub-batch's u16[B + B//4] stream → i32[B] ids (device)."""
    pos = jnp.arange(B, dtype=jnp.int32)
    lo = buf[:B].astype(jnp.int32)
    hw = buf[B + (pos >> 2)].astype(jnp.int32)
    hi = (hw >> ((pos & 3) * 4)) & 0xF
    return (hi << 16) | lo


@partial(
    jax.jit, donate_argnums=(0,), static_argnames=("with_degen", "compact")
)
def gcra_scan_ids20(
    state, id_rows, packed, now, quantity, *, with_degen=True, compact=False,
):
    """gcra_scan_ids fed by the 2.5 B/request 20-bit id stream.

    `packed` is u16[K, B + B//4] (pack_ids20); semantics are identical
    to gcra_scan_ids on the decoded ids (padding decodes to
    IDS20_SENTINEL, out of range for any conforming table, so the
    in-range check masks it exactly like a negative id).
    """
    W = packed.shape[1]
    if W % 5:
        # A misaligned buffer (e.g. a raw id stream handed to the wrong
        # kernel) would mis-split the high-nibble plane into in-range
        # garbage ids and decide against the wrong buckets; fail loudly
        # instead (pack_ids20 / check_many_ids20 enforce the same
        # contract for indirect callers).
        raise ValueError(
            f"ids20 stream width must be a multiple of 5 (got {W})"
        )
    B = W * 4 // 5

    def step(state, kb):
        buf, now_k = kb
        return _gcra_body(
            state,
            _ids_batch(_ids20_decode(buf, B), now_k, id_rows, quantity),
            with_degen=with_degen,
            compact=compact,
        )

    return jax.lax.scan(step, state, (packed, now.astype(jnp.int64)))


@partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_scan_ids20_acc(
    state, exp_acc, id_rows, packed, now, quantity, *,
    with_degen=True, compact=False,
):
    """gcra_scan_ids20 + expired-hit accumulation."""
    W = packed.shape[1]
    if W % 5:
        raise ValueError(
            f"ids20 stream width must be a multiple of 5 (got {W})"
        )
    B = W * 4 // 5

    def step(carry, kb):
        st, acc = carry
        buf, now_k = kb
        st, out, n = _gcra_body(
            st,
            _ids_batch(_ids20_decode(buf, B), now_k, id_rows, quantity),
            with_degen=with_degen, compact=compact, count_expired=True,
        )
        return (st, acc + n), out

    (state, exp_acc), outs = jax.lax.scan(
        step, (state, exp_acc), (packed, now.astype(jnp.int64))
    )
    return state, exp_acc, outs


@partial(jax.jit, donate_argnums=(1,), static_argnames=("capacity",))
def sweep_expired(now, state, capacity):
    """Cleanup-as-compaction: vacate every expired slot, report which.

    The reference's `retain(|_, (_, expiry)| expiry > now)` sweep
    (`periodic.rs:131-141`) becomes a boolean mask over the expiry column;
    the host frees the corresponding key→slot entries from the returned
    mask (first `capacity` rows only — the rest is scratch).
    """
    now = jnp.asarray(now, jnp.int64)
    _, expiry = unpack_state(state)
    expired = expiry <= now
    empty_rows = pack_state(
        jnp.zeros_like(expiry), jnp.full_like(expiry, EMPTY_EXPIRY)
    )
    state = jnp.where(expired[:, None], empty_rows, state)
    return state, expired[:capacity]
