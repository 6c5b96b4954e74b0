"""Fused Pallas decision kernel: one launch per decision window.

The composed-XLA decision window (kernel.py `gcra_scan_packed_*`) is a
chain of 5+ XLA ops per sub-batch — request unpack, row gather, the
GCRA closed forms, output pack, row scatter — each materializing its
intermediates to HBM before the next op starts.  This module fuses the
ENTIRE per-window decision into a single `pallas_call`: the grid walks
the K sub-batches in order (the table state is the carried buffer, via
input/output aliasing), each grid step unpacks its `PACK_WIDTH`-wide
request rows from VMEM, pulls the per-slot state rows out of the
HBM-resident table through a RING-deep async-DMA pipeline, evaluates
the closed forms (main prefix + degenerate three-view orbit) entirely
in VPU registers, packs the wire outputs, and streams the surviving
rows back with a second DMA ring at unique indices.  No intermediate
ever round-trips HBM and the host dispatches ONE launch per window.

The cost attacked here is the inter-op HBM round trips and the per-op
dispatch overhead of the composed graph.  Every loop scalar is
i32 and the kernel is traced with x64 off (Mosaic cannot lower i64
scalars), and serving batches arrive padded to at least the ring depth
(limiter MIN_PAD).

Status on the chip: compiled for a TPU v5e, Mosaic still refuses the
kernel.  The i64 literals, the reduce-to-scalar and the (1, B) output
blocks were repaired; what remains is the layout of the body's 1-D
[B] column vectors ("Invalid relayout" inside `_udiv64`), and the
4-wide row DMAs into a (B, width) VMEM block ("slice shape along
dimension 1 must be aligned to tiling (128)").
THROTTLECRAB_PALLAS_FUSED=1 therefore fails the server at boot on a
TPU (`require_compiles`).

i64 math on 32-bit lanes
========================

TPU vector lanes are 32-bit; the i64 TAT/tolerance arithmetic is
therefore decomposed into (lo, hi) i32 pairs — the exact split the
packed table rows and request rows already store (kernel.pack_state /
pack_requests).  The helpers below reproduce the `sat.py` saturating
discipline bit-for-bit on pairs: wrapping pair add/sub with explicit
carries, the sign-pattern overflow clamps of `sat_add`/`sat_sub`, the
2-op nonneg forms of the certified fast path, a widening 32x32
multiply that powers both the wrapping i64 product and the
`sat_mul_nonneg` overflow probe (the 128-bit high half replaces the
hidden i64 division of XLA's probe), and a restoring 64-step long
division for the two closed-form quotients (`m_raw`, `remaining`) and
the whole-second wire fields.  Unsigned compares ride the usual
sign-bias trick (`x ^ 0x8000_0000` then signed compare).

Width polymorphism and the mesh
===============================

The kernel is a static `row_width ∈ {4, INS_WIDTH}` template: the
6-wide instantiation folds the denied-hit counter into the same row
DMAs (the counter columns advance at each segment's is_last lane,
exactly like the XLA `_finish` ins_row), so `THROTTLECRAB_INSIGHT=1`
and Pallas coexist — the insight→Pallas downgrade of the legacy row
kernels does not apply here.  `fused_window` is plain traceable JAX,
so `ShardedBucketTable`'s shard-mapped bodies call it per shard: each
device runs the identical fused program on its slice and the per-launch
counter psums are untouched.

Enable with THROTTLECRAB_PALLAS_FUSED=1 (read per dispatch on the
host, so the composed-XLA path stays the default and the kill switch).
The kernel runs in interpret mode only where a caller asks
(`INTERPRET`, set by the CPU test harness) — bit-exact, which is what
the differential tests pin, but orders of magnitude slower than the
compiled XLA path; an interpret-mode rate is never a measurement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import (
    INS_WIDTH,
    PACK_FLAG_IS_LAST,
    PACK_FLAG_VALID,
    PACK_WIDTH,
    _insight_totals,
)

RING = 16  # row DMAs kept in flight per direction (gather / scatter)

#: Pallas interpret mode.  Never inferred from the backend: the kernel
#: is compiled by Mosaic unless a caller asks otherwise, and only the
#: CPU test harness does (tests/conftest.py sets this to True).
INTERPRET = False

_LANES = 128  # vector lane count: the per-step counter block's width
_I32_MAX = (1 << 31) - 1
_NS_PER_SEC = 1_000_000_000
_SIGN = -(1 << 31)  # i32 sign bit, for the unsigned-compare bias trick


# The enable check deliberately does NOT live here: the dispatchers
# (table._fused_enabled, sharded._step/_scan_step) call
# kernel.pallas_fused_enabled, so the kill-switch read never pays this
# module's jax.experimental.pallas imports.  Flipping the env between
# launches takes effect immediately — the composed-XLA twins and the
# fused wrappers are separate jit entry points, never a traced branch.

# --------------------------------------------------------------------- #
# i64-as-(lo, hi) i32 pair arithmetic.
#
# A "pair" is a (lo, hi) tuple of i32 arrays: lo carries the low 32
# bits (as raw bits in a signed carrier), hi the high 32 (signed).
# Every helper mirrors one XLA i64 op from kernel.py/sat.py and is
# pinned bit-identical by tests/test_pallas_fused.py's property sweep.
# The raw `+ - * <<` below are the POINT: deliberately wrapping 32-bit
# half-word steps of exact 64-bit arithmetic, never i64 value math.
# --------------------------------------------------------------------- #


def _const64(v: int):
    """Python int (i64 range) -> constant pair.

    Components stay PYTHON ints (weakly-typed literals): a pallas
    kernel body may not capture array constants, and a literal mixed
    into any i32 array op inlines at i32 for free."""
    lo = v & 0xFFFFFFFF
    if lo >= 1 << 31:
        lo -= 1 << 32
    hi = (v >> 32) & 0xFFFFFFFF
    if hi >= 1 << 31:
        hi -= 1 << 32
    return lo, hi


_ZERO64 = _const64(0)
_ONE64 = _const64(1)
_I64MAX = _const64((1 << 63) - 1)
_I64MIN = _const64(-(1 << 63))
_EMPTY_EXPIRY64 = _I64MIN  # kernel.EMPTY_EXPIRY == i64::MIN


def _shrl(x, s):
    """Logical (zero-fill) right shift on the i32 bit carrier."""
    x = jnp.asarray(x)
    return lax.shift_right_logical(
        x, jnp.broadcast_to(jnp.asarray(s, x.dtype), x.shape)
    )


def _ult(a, b):
    """Unsigned 32-bit a < b on i32 carriers (sign-bias trick)."""
    return (a ^ _SIGN) < (b ^ _SIGN)


def _add64(a, b):
    lo = a[0] + b[0]  # inv: allow(i64-raw-op)
    carry = _ult(lo, a[0]).astype(jnp.int32)
    return lo, a[1] + b[1] + carry  # inv: allow(i64-raw-op)


def _sub64(a, b):
    borrow = _ult(a[0], b[0]).astype(jnp.int32)
    return a[0] - b[0], a[1] - b[1] - borrow  # inv: allow(i64-raw-op)


def _eq64(a, b):
    return (a[0] == b[0]) & (a[1] == b[1])


def _lt64(a, b):
    """Signed 64-bit a < b."""
    return (a[1] < b[1]) | ((a[1] == b[1]) & _ult(a[0], b[0]))


def _le64(a, b):
    return _lt64(a, b) | _eq64(a, b)


def _ult64(a, b):
    """Unsigned 64-bit a < b."""
    return _ult(a[1], b[1]) | ((a[1] == b[1]) & _ult(a[0], b[0]))


def _is_neg(a):
    return a[1] < 0


def _is_zero(a):
    return (a[0] == 0) & (a[1] == 0)


def _is_pos(a):
    return ~_is_neg(a) & ~_is_zero(a)


def _sel64(c, a, b):
    return jnp.where(c, a[0], b[0]), jnp.where(c, a[1], b[1])


def _max64(a, b):
    return _sel64(_lt64(a, b), b, a)


def _min64(a, b):
    return _sel64(_lt64(a, b), a, b)


def _sat_add64(a, b):
    """sat.sat_add on pairs."""
    s = _add64(a, b)
    pos_of = _is_pos(a) & _is_pos(b) & _is_neg(s)
    neg_of = _is_neg(a) & _is_neg(b) & ~_is_neg(s)
    return _sel64(pos_of, _I64MAX, _sel64(neg_of, _I64MIN, s))


def _sat_sub64(a, b):
    """sat.sat_sub on pairs."""
    d = _sub64(a, b)
    pos_of = ~_is_neg(a) & _is_neg(b) & _is_neg(d)
    neg_of = _is_neg(a) & _is_pos(b) & ~_is_neg(d)
    return _sel64(pos_of, _I64MAX, _sel64(neg_of, _I64MIN, d))


def _sat_add_nn64(a, b):
    """sat.sat_add_nn on pairs (b >= 0: overflow iff s < a)."""
    s = _add64(a, b)
    return _sel64(_lt64(s, a), _I64MAX, s)


def _sat_sub_nn64(a, b):
    """sat.sat_sub_nn on pairs (b >= 0: overflow iff d > a)."""
    d = _sub64(a, b)
    return _sel64(_lt64(a, d), _I64MIN, d)


def _umul32(a, b):
    """Widening 32x32 -> 64 multiply (unsigned interpretation of the
    i32 bit carriers), as a pair.  16-bit half products; every partial
    is exact because (2^16-1)^2 < 2^32."""
    a0 = a & 0xFFFF
    a1 = _shrl(a, 16)
    b0 = b & 0xFFFF
    b1 = _shrl(b, 16)
    ll = a0 * b0  # inv: allow(i64-raw-op)
    mid1 = a0 * b1  # inv: allow(i64-raw-op)
    mid = mid1 + a1 * b0  # inv: allow(i64-raw-op)
    midc = _ult(mid, mid1).astype(jnp.int32)
    lo = ll + (mid << 16)  # inv: allow(i64-raw-op)
    k = _ult(lo, ll).astype(jnp.int32)
    hi = (
        a1 * b1 + _shrl(mid, 16) + (midc << 16) + k  # inv: allow(i64-raw-op)
    )
    return lo, hi


def _mul64_lo(a, b):
    """Wrapping i64 multiply on pairs (the certified fast path's plain
    product — the host certificate rules overflow out)."""
    lo, hi = _umul32(a[0], b[0])
    hi = hi + a[0] * b[1] + a[1] * b[0]  # inv: allow(i64-raw-op)
    return lo, hi


def _sat_mul_nonneg64(a, b):
    """sat.sat_mul_nonneg on pairs (operands >= 0 on every live lane,
    the only case GCRA needs — same contract as the XLA helper).

    XLA's overflow probe `a > I64_MAX // max(b, 1)` hides an i64
    division; for a, b >= 0 it is exactly `a*b >= 2^63`, read here off
    the 128-bit product: any nonzero contribution to the high 64 bits,
    or the sign bit of the low 64.
    """
    pll = _umul32(a[0], b[0])
    plh = _umul32(a[0], b[1])
    phl = _umul32(a[1], b[0])
    phh = _umul32(a[1], b[1])
    mid = _add64(plh, phl)
    cmid = _ult64(mid, plh)
    lo_hi = pll[1] + mid[0]  # inv: allow(i64-raw-op)
    k = _ult(lo_hi, pll[1])
    overflow = (
        (phh[0] != 0)
        | (phh[1] != 0)
        | cmid
        | (mid[1] != 0)
        | k
        | (lo_hi < 0)
    )
    return _sel64(overflow, _I64MAX, (pll[0], lo_hi))


def _udiv64(num, den):
    """Unsigned 64 / 64 restoring long division on pairs; den >= 1
    (callers clamp).  64 shift-compare-subtract rounds in a fori_loop —
    every loop scalar i32 (Mosaic cannot lower i64 scalars).  Covers all
    kernel quotients: both closed-form divisions take nonneg operands
    after their max(.., 0) guards, matching lax.div's trunc-toward-zero
    there, and the whole-second wire fields divide nonneg ns values."""
    i32 = jnp.int32

    def body(i, carry):
        rlo, rhi, qlo, qhi = carry
        s = i32(63) - i
        bit = (
            jnp.where(
                s >= 32,
                _shrl(num[1], jnp.maximum(s - i32(32), 0)),
                _shrl(num[0], jnp.minimum(s, i32(31))),
            )
            & 1
        )
        rhi = (rhi << 1) | _shrl(rlo, 31)  # inv: allow(i64-raw-op)
        rlo = (rlo << 1) | bit  # inv: allow(i64-raw-op)
        ge = ~_ult64((rlo, rhi), den)
        nlo, nhi = _sub64((rlo, rhi), den)
        rlo = jnp.where(ge, nlo, rlo)
        rhi = jnp.where(ge, nhi, rhi)
        qhi = (qhi << 1) | _shrl(qlo, 31)  # inv: allow(i64-raw-op)
        qlo = (qlo << 1) | ge.astype(i32)  # inv: allow(i64-raw-op)
        return rlo, rhi, qlo, qhi

    z = jnp.zeros_like(num[0])
    _, _, qlo, qhi = lax.fori_loop(i32(0), i32(64), body, (z, z, z, z))
    return qlo, qhi


def _div_nonneg(num, den_raw):
    """max(div_trunc(num, den_raw), 0) on pairs — the exact shape both
    closed-form quotients take in kernel.py: negative numerators clamp
    to 0 (trunc toward zero then max), den_raw <= 0 divides by 1."""
    q = _udiv64(num, _max64(den_raw, _ONE64))
    return _sel64(_is_neg(num), _ZERO64, q)


def _clamp_i32(p):
    """jnp.minimum(x, i32::MAX).astype(int32) for nonneg pair x."""
    return jnp.where((p[1] != 0) | (p[0] < 0), jnp.int32(_I32_MAX), p[0])


def _div_sec_lo(p):
    """(nonneg ns pair // 1e9) low word — the wire seconds fields."""
    return _udiv64(p, _const64(_NS_PER_SEC))


# --------------------------------------------------------------------- #
# The GCRA closed forms on pairs: a lockstep transcription of
# kernel._gcra_body (+ its _finish / _request_outputs) with every i64
# op replaced by its pair twin.  Pure traced JAX over [B] vectors — the
# pallas kernel body calls it on VMEM-resident data, and the tests call
# it directly to pin it against the XLA body outside pallas too.
# --------------------------------------------------------------------- #


def _gcra_pairs(rows, packed, now, *, width, with_degen, compact):
    """Decide one sub-batch from gathered rows.

    Args:
      rows:   i32[B, width] gathered state rows.
      packed: i32[B, PACK_WIDTH] request rows (kernel.pack_requests).
      now:    scalar pair (the sub-batch server timestamp).

    Returns (rows_out i32[B, width], outs, n_exp i32[1, 1]) where
    `outs` is a tuple of i32 arrays per `compact`:
      False -> (lo[4, B], hi[4, B])   i64 ns planes, join outside
      True  -> (planes[4, B],)        exact i32 wire planes
      "cur" -> (lo[B], hi[B])         cur*2+allowed words, join outside
      "w32" -> (words[B],)            device-packed 4-byte wire words
    """
    rank = packed[:, 1]
    flags = packed[:, 2]
    is_last = (flags & PACK_FLAG_IS_LAST) != 0
    v = (flags & PACK_FLAG_VALID) != 0
    em = (packed[:, 3], packed[:, 4])
    tol = (packed[:, 5], packed[:, 6])
    q = (packed[:, 7], packed[:, 8])
    stored_tat = (rows[:, 0], rows[:, 1])
    stored_exp = (rows[:, 2], rows[:, 3])
    ins = width > 4
    live = v & _lt64(now, stored_exp)  # stored_exp > now

    if with_degen:
        s_add, s_sub, s_mul = _sat_add64, _sat_sub64, _sat_mul_nonneg64
    else:
        s_add, s_sub, s_mul = _sat_add_nn64, _sat_sub_nn64, _mul64_lo

    inc = s_mul(em, q)
    t0 = _sel64(
        live, _max64(stored_tat, s_sub(now, tol)), s_sub(now, em)
    )

    # ---- main case: prefix closed form (num stays general-saturating,
    # burst_limit stays wrapping — kernel.py documents both) ----------- #
    rank1 = (rank + 1, jnp.zeros_like(rank))
    num = _sat_sub64(s_add(now, tol), t0)
    m_raw = _div_nonneg(num, inc)
    allowed_main = _lt64((rank, jnp.zeros_like(rank)), m_raw)
    new_tat_r = s_add(t0, s_mul(rank1, inc))
    tat_denied = s_add(t0, s_mul(m_raw, inc))
    cur_main = _sel64(allowed_main, new_tat_r, tat_denied)
    tat_fin_main = s_add(t0, s_mul(_min64(m_raw, rank1), inc))

    burst_limit = _add64(now, tol)
    room_main = _sat_sub64(burst_limit, cur_main)
    remaining_main = _sel64(
        _is_pos(em), _div_nonneg(room_main, em), _ZERO64
    )
    reset_main = _max64(s_add(s_sub(cur_main, now), tol), _ZERO64)
    retry_main = _sel64(
        allowed_main,
        _ZERO64,
        _max64(s_sub(s_sub(s_add(cur_main, inc), tol), now), _ZERO64),
    )

    exp_hit_base = (
        v
        & (rank == 0)
        & ~_eq64(stored_exp, _EMPTY_EXPIRY64)
        & _le64(stored_exp, now)
    )

    if not with_degen:
        allowed_out = allowed_main & v
        remaining_out, reset_out, retry_out = (
            remaining_main, reset_main, retry_main,
        )
        wrote = _lt64(_ZERO64, m_raw) & v & is_last
        tat_fin = tat_fin_main
        cur_out = cur_main
        n_exp_mask = exp_hit_base & allowed_main
        if ins:
            seg_n = rank1
            denied_seg = _sub64(seg_n, _min64(m_raw, seg_n))
    else:
        # ---- degenerate case: three-view closed form ----------------- #
        degen = _is_zero(inc) | _is_zero(tol)

        def request_outputs(t):
            new_tat = _sat_add64(t, inc)
            allow_at = _sat_sub64(new_tat, tol)
            allowed = _le64(allow_at, now)
            cur = _sel64(allowed, new_tat, t)
            room = _sat_sub64(burst_limit, cur)
            remaining = _sel64(
                _is_pos(em), _div_nonneg(room, em), _ZERO64
            )
            reset = _max64(
                _sat_add64(_sat_sub64(cur, now), tol), _ZERO64
            )
            retry = _sel64(
                allowed,
                _ZERO64,
                _max64(_sat_sub64(allow_at, now), _ZERO64),
            )
            ttl = _sat_add64(_sat_sub64(new_tat, now), tol)
            return allowed, remaining, reset, retry, new_tat, ttl

        def view_step(t):
            outs = request_outputs(t)
            allowed_t, _, _, _, new_t, ttl_t = outs
            dead = allowed_t & _is_zero(ttl_t)
            t_next = _sel64(
                ~allowed_t,
                t,
                _sel64(
                    dead,
                    _sat_sub64(now, em),
                    _max64(new_t, _sat_sub64(now, tol)),
                ),
            )
            return outs, t_next

        outs0, v1 = view_step(t0)
        outs1, v2 = view_step(v1)
        outs2, _ = view_step(v2)
        a0, a1, a2 = outs0[0], outs1[0], outs2[0]
        # alternating/tail only reach the output for rank >= 2, so the
        # (rank-1)&1 parity equals the XLA (rank-1)%2 there.
        alt_even = ((rank - 1) & 1) == 0

        def pick(sel, main, o0, o1, o2):
            alternating = sel(alt_even, o1, o2)
            tail = sel(rank == 1, o1, sel(a2, alternating, o2))
            degen_out = sel(
                ~a0,
                o0,
                sel(
                    ~a1,
                    sel(rank == 0, o0, o1),
                    sel(rank == 0, o0, tail),
                ),
            )
            return sel(degen, degen_out, main)

        allowed_out = (
            pick(jnp.where, allowed_main, a0, a0 & a1, a0 & a1 & a2) & v
        )
        remaining_out = pick(
            _sel64, remaining_main, outs0[1], outs1[1], outs2[1]
        )
        reset_out = pick(_sel64, reset_main, outs0[2], outs1[2], outs2[2])
        retry_out = pick(_sel64, retry_main, outs0[3], outs1[3], outs2[3])

        new0_t, new1_t, new2_t = outs0[4], outs1[4], outs2[4]
        alt_last = _sel64(alt_even, new1_t, new2_t)
        tat_fin_degen = _sel64(
            (rank == 0) | ~a1,
            new0_t,
            _sel64(~a2 | (rank == 1), new1_t, alt_last),
        )
        wrote = (
            jnp.where(degen, a0, _lt64(_ZERO64, m_raw)) & v & is_last
        )
        tat_fin = _sel64(degen, tat_fin_degen, tat_fin_main)
        cur_out = None
        n_exp_mask = exp_hit_base & allowed_out
        if ins:
            seg_n = rank1
            allowed_cnt_main = _min64(m_raw, seg_n)
            two = _const64(2)
            allowed_cnt_degen = _sel64(
                ~a0,
                _ZERO64,
                _sel64(
                    ~a1,
                    _ONE64,
                    _sel64(~a2, _min64(seg_n, two), seg_n),
                ),
            )
            denied_seg = _sub64(
                seg_n, _sel64(degen, allowed_cnt_degen, allowed_cnt_main)
            )

    # ---- write-back (kernel._finish) --------------------------------- #
    ttl_fin = s_add(s_sub(tat_fin, now), tol)
    expiry_fin = _sel64(
        _is_neg(ttl_fin), _I64MAX, s_add(tat_fin, tol)
    )
    tat_w = _sel64(wrote, tat_fin, stored_tat)
    exp_w = _sel64(wrote, expiry_fin, stored_exp)
    cols = [tat_w[0], tat_w[1], exp_w[0], exp_w[1]]
    if ins:
        stored_deny = (rows[:, 4], rows[:, 5])
        deny_new = _add64(stored_deny, denied_seg)
        cols += [deny_new[0], deny_new[1]]
    rows_out = jnp.stack(cols, axis=-1)

    if compact == "cur":
        assert cur_out is not None, 'compact="cur" requires with_degen=False'
        wlo = (cur_out[0] << 1) | allowed_out.astype(  # inv: allow(i64-raw-op)
            jnp.int32
        )
        whi = (cur_out[1] << 1) | _shrl(  # inv: allow(i64-raw-op)
            cur_out[0], 31
        )
        outs = (wlo, whi)
    elif compact == "w32":
        assert cur_out is not None, 'compact="w32" requires with_degen=False'
        outs = (
            allowed_out.astype(jnp.int32)
            | (remaining_out[0] << 1)  # inv: allow(i64-raw-op)
            | (_div_sec_lo(reset_out)[0] << 11)  # inv: allow(i64-raw-op)
            | (_div_sec_lo(retry_out)[0] << 22),  # inv: allow(i64-raw-op)
        )
    elif compact:
        outs = (
            jnp.stack(
                [
                    allowed_out.astype(jnp.int32),
                    _clamp_i32(remaining_out),
                    _clamp_i32(_div_sec_lo(reset_out)),
                    _clamp_i32(_div_sec_lo(retry_out)),
                ]
            ),
        )
    else:
        z = jnp.zeros_like(rank)
        outs = (
            jnp.stack(
                [
                    allowed_out.astype(jnp.int32),
                    remaining_out[0],
                    reset_out[0],
                    retry_out[0],
                ]
            ),
            jnp.stack([z, remaining_out[1], reset_out[1], retry_out[1]]),
        )
    # A (1, 1) reduction, not a scalar: Mosaic lowers a reduce-to-scalar
    # through jnp at lowering time, where x64 promotes it to i64.
    n_exp = jnp.sum(
        n_exp_mask.astype(jnp.int32)[None, :], axis=1, keepdims=True
    )
    return rows_out, outs, n_exp


# --------------------------------------------------------------------- #
# The pallas kernel: DMA rings around _gcra_pairs, one grid step per
# sub-batch, the table buffer carried across steps via aliasing.
# --------------------------------------------------------------------- #


def _dma_ring(n, copy):
    """Issue `n` row DMAs through a RING-deep in-flight window
    (start/wait/drain, all scalars i32)."""
    i32 = jnp.int32

    def body(i, _):
        @pl.when(i >= RING)
        def _():
            copy(i - i32(RING)).wait()

        copy(i).start()
        return i32(0)

    lax.fori_loop(i32(0), i32(n), body, i32(0))

    def drain(i, _):
        copy(i32(max(n - RING, 0)) + i).wait()
        return i32(0)

    lax.fori_loop(i32(0), i32(min(RING, n)), drain, i32(0))


def _make_kernel(B, width, with_degen, compact, n_out):
    def kernel(gs_ref, now_ref, packed_ref, state_in_ref, st_out, *rest):
        outs_refs = rest[:n_out]
        nexp_ref = rest[n_out]
        rows, rows_out, gsem, ssem = rest[n_out + 1:]
        del state_in_ref  # aliased with st_out; all access goes there
        k = pl.program_id(0)
        base = k * jnp.int32(B)

        def gcopy(i):
            return pltpu.make_async_copy(
                st_out.at[gs_ref[0, base + i]], rows.at[i], gsem.at[i % RING]
            )

        _dma_ring(B, gcopy)

        now = (now_ref[k, 0], now_ref[k, 1])
        new_rows, outs, n_exp = _gcra_pairs(
            rows[:],
            packed_ref[0],
            now,
            width=width,
            with_degen=with_degen,
            compact=compact,
        )
        rows_out[:] = new_rows
        for ref, val in zip(outs_refs, outs):
            ref[0] = val.reshape(ref.shape[1:])
        nexp_ref[0] = jnp.broadcast_to(n_exp, (1, _LANES))

        def scopy(i):
            return pltpu.make_async_copy(
                rows_out.at[i], st_out.at[gs_ref[1, base + i]], ssem.at[i % RING]
            )

        _dma_ring(B, scopy)

    return kernel


def _join64(lo, hi):
    return (hi.astype(jnp.int64) << 32) | (  # inv: allow(i64-raw-op)
        lo.astype(jnp.int64) & 0xFFFFFFFF
    )


def fused_window(state, packed, now, *, with_degen=True, compact=False,
                 interpret=None):
    """Decide one K-deep window in ONE fused launch (traceable JAX).

    Semantically identical to kernel.gcra_scan_packed + the expired-hit
    count of the *_acc twins: `state` is the i32[N, W] packed table
    (W in {4, INS_WIDTH}; the 6-wide template maintains the denied-hit
    columns in the same row traffic), `packed` is i32[K, B, PACK_WIDTH],
    `now` i64[K].  Returns (state, out, n_exp i64[K]) with `out` shaped
    exactly like the XLA twin's for the given `compact`.

    Callable from jit and from shard_map bodies (ShardedBucketTable) —
    each shard then runs the identical fused program on its slice.
    """
    state = jnp.asarray(state)
    packed = jnp.asarray(packed, jnp.int32)
    K, B, _pw = packed.shape
    N, width = state.shape
    assert _pw == PACK_WIDTH
    assert width in (4, INS_WIDTH)

    slots = packed[..., 0]
    flags = packed[..., 2]
    gather = jnp.clip(slots, 0, N - 1).astype(jnp.int32)
    # Suppressed-write lanes land in the scratch tail at distinct
    # indices (the same rows the XLA _finish uses), keeping the
    # unique-indices contract; real-slot rows whose GCRA write is
    # suppressed get their gathered bytes streamed back verbatim —
    # bit-identical state, no data-dependent DMA addressing.
    write_lane = ((flags & PACK_FLAG_IS_LAST) != 0) & (
        (flags & PACK_FLAG_VALID) != 0
    )
    scratch = (N - B + jnp.arange(B, dtype=jnp.int32))[None, :]
    scatter = jnp.where(write_lane, gather, scratch)
    gs = jnp.stack([gather.reshape(-1), scatter.reshape(-1)])
    now = jnp.asarray(now, jnp.int64)
    nows = jnp.stack(
        [
            (now & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32),
            (now >> 32).astype(jnp.int32),
        ],
        axis=-1,
    )

    if compact == "cur":
        out_shapes = [
            jax.ShapeDtypeStruct((K, 1, B), jnp.int32),
            jax.ShapeDtypeStruct((K, 1, B), jnp.int32),
        ]
        out_block = pl.BlockSpec((1, 1, B), lambda k, *_: (k, 0, 0))
    elif compact == "w32":
        out_shapes = [jax.ShapeDtypeStruct((K, 1, B), jnp.int32)]
        out_block = pl.BlockSpec((1, 1, B), lambda k, *_: (k, 0, 0))
    elif compact:
        out_shapes = [jax.ShapeDtypeStruct((K, 4, B), jnp.int32)]
        out_block = pl.BlockSpec((1, 4, B), lambda k, *_: (k, 0, 0))
    else:
        out_shapes = [
            jax.ShapeDtypeStruct((K, 4, B), jnp.int32),
            jax.ShapeDtypeStruct((K, 4, B), jnp.int32),
        ]
        out_block = pl.BlockSpec((1, 4, B), lambda k, *_: (k, 0, 0))
    n_out = len(out_shapes)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, B, PACK_WIDTH), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            *([out_block] * n_out),
            pl.BlockSpec((1, 1, _LANES), lambda k, *_: (k, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, width), jnp.int32),
            pltpu.VMEM((B, width), jnp.int32),
            pltpu.SemaphoreType.DMA((RING,)),
            pltpu.SemaphoreType.DMA((RING,)),
        ],
    )
    # Trace the kernel body with x64 off: under x64 every Python-int
    # literal becomes an i64 constant, and Mosaic's element-type
    # conversion recurses on i64 scalars.  All kernel operands are i32.
    with jax.enable_x64(False):
        res = pl.pallas_call(
            _make_kernel(B, width, with_degen, compact, n_out),
            grid_spec=grid_spec,
            out_shape=(
                jax.ShapeDtypeStruct(state.shape, state.dtype),
                *out_shapes,
                jax.ShapeDtypeStruct((K, 1, _LANES), jnp.int32),
            ),
            # Operand indices include the 2 scalar-prefetch args:
            # 0 = gs, 1 = nows, 2 = packed, 3 = state -> state aliases
            # output 0, so the table is updated in place launch after
            # launch exactly like the donated XLA twins.
            input_output_aliases={3: 0},
            interpret=INTERPRET if interpret is None else interpret,
        )(gs, nows, packed, state)
    state = res[0]
    nexp = res[-1][:, 0, 0].astype(jnp.int64)
    if compact == "cur":
        out = _join64(res[1][:, 0], res[2][:, 0])
    elif compact == "w32":
        out = res[1][:, 0]
    elif compact:
        out = res[1]
    else:
        out = _join64(res[1], res[2])
    return state, out, nexp


# --------------------------------------------------------------------- #
# Jitted drop-in twins for the kernel.py entry points BucketTable
# dispatches through (gcra_batch/scan/scan_packed _acc and _ins).
# --------------------------------------------------------------------- #


def pack_requests_traced(slots, rank, is_last, emission, tolerance,
                          quantity, valid):
    """kernel.pack_requests as traced jnp (device-side packing for the
    unpacked entry points and the shard-mapped bodies)."""
    def split(x):
        x = jnp.asarray(x, jnp.int64)
        lo = (x & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32)
        return lo, (x >> 32).astype(jnp.int32)

    flags = (
        jnp.asarray(is_last, jnp.int32) * PACK_FLAG_IS_LAST
        + jnp.asarray(valid, jnp.int32) * PACK_FLAG_VALID
    )
    em_lo, em_hi = split(emission)
    tol_lo, tol_hi = split(tolerance)
    q_lo, q_hi = split(quantity)
    return jnp.stack(
        [
            jnp.asarray(slots, jnp.int32),
            jnp.asarray(rank, jnp.int32),
            flags,
            em_lo, em_hi, tol_lo, tol_hi, q_lo, q_hi,
        ],
        axis=-1,
    )


@functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_scan_packed_fused_acc(
    state, exp_acc, packed, now, *, with_degen=True, compact=False
):
    """Fused twin of kernel.gcra_scan_packed_acc."""
    state, out, nexp = fused_window(
        state, packed, now, with_degen=with_degen, compact=compact
    )
    return state, exp_acc + jnp.sum(nexp), out


@functools.partial(
    jax.jit,
    donate_argnums=(0, 1, 2),
    static_argnames=("with_degen", "compact"),
)
def gcra_scan_packed_fused_ins(
    state, exp_acc, ins_counts, packed, now, *, with_degen=True,
    compact=False,
):
    """Fused twin of kernel.gcra_scan_packed_ins (INS_WIDTH rows)."""
    packed = jnp.asarray(packed, jnp.int32)
    state, out, nexp = fused_window(
        state, packed, now, with_degen=with_degen, compact=compact
    )
    ins_counts = _insight_totals(
        ins_counts, (packed[..., 2] & PACK_FLAG_VALID) != 0, out, compact
    )
    return state, exp_acc + jnp.sum(nexp), ins_counts, out


@functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_scan_fused_acc(
    state, exp_acc, slots, rank, is_last, emission, tolerance, quantity,
    valid, now, *, with_degen=True, compact=False,
):
    """Fused twin of kernel.gcra_scan_acc ([K, B] unpacked inputs)."""
    packed = pack_requests_traced(
        slots, rank, is_last, emission, tolerance, quantity, valid
    )
    state, out, nexp = fused_window(
        state, packed, now, with_degen=with_degen, compact=compact
    )
    return state, exp_acc + jnp.sum(nexp), out


@functools.partial(
    jax.jit,
    donate_argnums=(0, 1, 2),
    static_argnames=("with_degen", "compact"),
)
def gcra_scan_fused_ins(
    state, exp_acc, ins_counts, slots, rank, is_last, emission, tolerance,
    quantity, valid, now, *, with_degen=True, compact=False,
):
    """Fused twin of kernel.gcra_scan_ins."""
    packed = pack_requests_traced(
        slots, rank, is_last, emission, tolerance, quantity, valid
    )
    state, out, nexp = fused_window(
        state, packed, now, with_degen=with_degen, compact=compact
    )
    ins_counts = _insight_totals(
        ins_counts, jnp.asarray(valid, bool), out, compact
    )
    return state, exp_acc + jnp.sum(nexp), ins_counts, out


@functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("with_degen", "compact")
)
def gcra_batch_fused_acc(
    state, exp_acc, slots, rank, is_last, emission, tolerance, quantity,
    valid, now, *, with_degen=True, compact=False,
):
    """Fused twin of kernel.gcra_batch_acc (single sub-batch)."""
    packed = pack_requests_traced(
        slots, rank, is_last, emission, tolerance, quantity, valid
    )[None]
    state, out, nexp = fused_window(
        state,
        packed,
        jnp.reshape(jnp.asarray(now, jnp.int64), (1,)),
        with_degen=with_degen,
        compact=compact,
    )
    return state, exp_acc + jnp.sum(nexp), out[0]


@functools.partial(
    jax.jit,
    donate_argnums=(0, 1, 2),
    static_argnames=("with_degen", "compact"),
)
def gcra_batch_fused_ins(
    state, exp_acc, ins_counts, slots, rank, is_last, emission, tolerance,
    quantity, valid, now, *, with_degen=True, compact=False,
):
    """Fused twin of kernel.gcra_batch_ins."""
    packed = pack_requests_traced(
        slots, rank, is_last, emission, tolerance, quantity, valid
    )[None]
    state, out, nexp = fused_window(
        state,
        packed,
        jnp.reshape(jnp.asarray(now, jnp.int64), (1,)),
        with_degen=with_degen,
        compact=compact,
    )
    out = out[0]
    ins_counts = _insight_totals(
        ins_counts, jnp.asarray(valid, bool), out, compact
    )
    return state, exp_acc + jnp.sum(nexp), ins_counts, out


# The (with_degen, compact) launch variants a served window takes: the
# certified w32 tier, and the general tier any window can fall back to.
SERVED_VARIANTS = ((False, "w32"), (True, True))


def require_compiles(table, depth: int, batch: int) -> None:
    """Compile the table's served launches (single-device packed scan or
    sharded scan step) with the fused kernel, or raise.

    THROTTLECRAB_PALLAS_FUSED=1 asks for this kernel: a boot where it
    does not compile fails here, instead of serving from XLA or from
    interpret mode."""
    kind = next(iter(table.state.sharding.device_set)).device_kind
    for with_degen, compact in SERVED_VARIANTS:
        try:
            table.compile_launch(
                depth, batch, with_degen=with_degen, compact=compact
            )
        except Exception as e:
            raise RuntimeError(
                "THROTTLECRAB_PALLAS_FUSED=1, but the fused kernel does "
                f"not compile for {kind} at "
                f"K={depth}, B={batch}, compact={compact!r}: "
                f"{type(e).__name__}: {str(e)[:500]}"
            ) from e
