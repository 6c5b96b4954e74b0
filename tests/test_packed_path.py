"""Packed-launch path: one fused i32 buffer must decide identically to the
eight-array scan path, the served packed scans must decide as the scalar
oracle does, and the C++ assembler must emit exactly what the Python
resolve + numpy packing emits."""

import numpy as np
import pytest

import jax.numpy as jnp

from throttlecrab_tpu.core.i64 import I64_MAX
from throttlecrab_tpu.core.rate_limiter import RateLimiter
from throttlecrab_tpu.core.store.mapstore import MapStore
from throttlecrab_tpu.tpu.kernel import (
    EMPTY_EXPIRY,
    INS_WIDTH,
    PACK_WIDTH,
    finish_cur,
    finish_w32,
    fits_cur_wire,
    fits_w32_wire,
    gcra_scan,
    gcra_scan_packed,
    gcra_scan_packed_acc,
    gcra_scan_packed_ins,
    pack_requests,
    pack_state,
    unpack_deny,
    unpack_state,
)
from throttlecrab_tpu.tpu.limiter import has_degenerate

NS = 1_000_000_000
BASE = 1_753_700_000 * NS
N = 1024  # table rows incl. scratch tail
K, B = 4, 64


def make_table():
    return pack_state(
        jnp.zeros((N,), jnp.int64),
        jnp.full((N,), EMPTY_EXPIRY, jnp.int64),
    )


def segment_info(slots_2d, valid_2d):
    rank = np.zeros_like(slots_2d, np.int32)
    is_last = np.ones(slots_2d.shape, bool)
    for k in range(slots_2d.shape[0]):
        seen: dict = {}
        for i in range(slots_2d.shape[1]):
            if not valid_2d[k, i]:
                continue
            s = int(slots_2d[k, i])
            if s in seen:
                rank[k, i] = seen[s][0]
                seen[s][0] += 1
                is_last[k, seen[s][1]] = False
                seen[s][1] = i
            else:
                seen[s] = [1, i]
    return rank, is_last


def random_launch(rng, degen=False):
    slots = rng.integers(0, 48, (K, B)).astype(np.int32)
    valid = rng.random((K, B)) > 0.1
    rank, is_last = segment_info(slots, valid)
    em = np.full((K, B), 600_000_000, np.int64)
    tol = em * rng.integers(0 if degen else 1, 9, (K, B))
    q = rng.integers(0 if degen else 1, 3, (K, B)).astype(np.int64)
    # Uniform params per slot within each micro-batch (engine invariant).
    for k in range(K):
        first: dict = {}
        for i in range(B):
            s = int(slots[k, i])
            if s in first:
                tol[k, i] = tol[k, first[s]]
                q[k, i] = q[k, first[s]]
            else:
                first[s] = i
    now = BASE + np.arange(K, dtype=np.int64) * 50_000_000
    return slots, rank, is_last, em, tol, q, valid, now


@pytest.mark.parametrize("degen", [False, True])
@pytest.mark.parametrize("compact", [False, True])
def test_packed_scan_matches_unpacked(degen, compact):
    rng = np.random.default_rng(11)
    slots, rank, is_last, em, tol, q, valid, now = random_launch(rng, degen)

    st_a, out_a = gcra_scan(
        make_table(),
        jnp.asarray(slots), jnp.asarray(rank), jnp.asarray(is_last),
        jnp.asarray(em), jnp.asarray(tol), jnp.asarray(q),
        jnp.asarray(valid), jnp.asarray(now),
        with_degen=True, compact=compact,
    )

    packed = pack_requests(slots, rank, is_last, em, tol, q, valid)
    assert packed.shape == (K, B, PACK_WIDTH)
    st_b, out_b = gcra_scan_packed(
        make_table(), jnp.asarray(packed), jnp.asarray(now),
        with_degen=True, compact=compact,
    )

    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))
    tat_a, exp_a = (np.asarray(x) for x in unpack_state(st_a))
    tat_b, exp_b = (np.asarray(x) for x in unpack_state(st_b))
    np.testing.assert_array_equal(tat_a, tat_b)
    np.testing.assert_array_equal(exp_a, exp_b)


def test_pack_requests_roundtrips_i64_extremes():
    I64_MAX = (1 << 63) - 1
    vals = np.array([0, 1, -1, I64_MAX, -I64_MAX - 1, 1 << 33], np.int64)
    n = len(vals)
    packed = pack_requests(
        np.zeros(n, np.int32), np.zeros(n, np.int32), np.ones(n, bool),
        vals, vals, vals, np.ones(n, bool),
    )
    lo = packed[:, 3].view(np.uint32).astype(np.int64)
    hi = packed[:, 4].astype(np.int64)
    np.testing.assert_array_equal((hi << 32) | lo, vals)


# ---------------------------------------------------------------------- #
# The served packed scans (gcra_scan_packed_acc on 4-wide rows,
# gcra_scan_packed_ins on INS_WIDTH rows) against the scalar oracle,
# request by request, on hostile windows.


def _fresh_state(rows, width):
    st = pack_state(
        jnp.zeros((rows,), jnp.int64),
        jnp.full((rows,), EMPTY_EXPIRY, jnp.int64),
    )
    if width > 4:
        st = jnp.concatenate(
            [st, jnp.zeros((rows, width - 4), jnp.int32)], axis=-1
        )
    return st


def _rand_window(rng, K, B, cap, degen, w32=False):
    """A hostile window: duplicate segments, degenerate params (when
    `degen`), invalid lanes, saturating-scale values.  `w32` draws the
    certified params inside the w32 output tier's field widths."""
    slots = rng.integers(0, cap, (K, B)).astype(np.int32)
    em = rng.choice([0, 1, 1000, NS, 7 * NS, 1 << 62], (K, B)).astype(
        np.int64
    )
    tol = rng.choice(
        [0, 5, NS, 100 * NS, (1 << 61) + 7, -(3 * NS)], (K, B)
    ).astype(np.int64)
    q = rng.choice([0, 1, 2, 50], (K, B)).astype(np.int64)
    if w32:
        em = rng.choice([NS // 10, NS, 7 * NS], (K, B)).astype(np.int64)
        tol = em * rng.integers(1, 60, (K, B))
        q = np.maximum(q, 1)
    elif not degen:
        em = np.maximum(em % (10 * NS), 1)
        tol = np.abs(tol) % (100 * NS) + 1
        q = np.maximum(q, 1)
    valid = rng.random((K, B)) < 0.9
    rank = np.zeros((K, B), np.int32)
    is_last = np.ones((K, B), bool)
    for k in range(K):
        first: dict = {}
        state: dict = {}
        for i in range(B):
            if not valid[k, i]:
                continue
            s = int(slots[k, i])
            if s in state:
                cnt, last = state[s]
                rank[k, i] = cnt
                is_last[k, last] = False
                state[s] = (cnt + 1, i)
                j = first[s]  # uniform params per segment
                em[k, i], tol[k, i], q[k, i] = (
                    em[k, j], tol[k, j], q[k, j],
                )
            else:
                state[s] = (1, i)
                first[s] = i
    now = BASE + np.sort(rng.integers(0, 100 * NS, K)).astype(np.int64)
    return (slots, rank, is_last, em, tol, q, valid), now


class _SlotStore(MapStore):
    """The oracle's store, keyed by table slot, with cleanup off.  It
    counts the expired entries a segment's FIRST request replaces: the
    kernel's expired-hit accumulator (which knowingly leaves out the
    re-hits of a ttl-0 write later in the same segment)."""

    def __init__(self):
        super().__init__()
        self.first_of_segment = False
        self.expired_hits = 0

    def _maybe_cleanup(self, now_ns):
        pass

    def _on_expired_hit_set(self):
        self.expired_hits += self.first_of_segment


def _oracle_window(oracle, deny, window, now):
    """Decide a window request by request with the scalar GCRA; returns
    the i64[K, 4, B] (allowed, remaining, reset_ns, retry_ns) planes,
    valid lanes only, and advances `deny` (denied requests per slot)."""
    slots, rank, _, em, tol, q, valid = window
    K, B = slots.shape
    out = np.zeros((K, 4, B), np.int64)
    for k in range(K):
        for i in np.flatnonzero(valid[k]):
            slot = int(slots[k, i])
            oracle.store.first_of_segment = rank[k, i] == 0
            allowed, r = oracle.rate_limit_intervals(
                slot, int(em[k, i]), int(tol[k, i]), int(q[k, i]),
                int(now[k]),
            )
            out[k, :, i] = (
                allowed,
                r.remaining,
                min(r.reset_after_ns, I64_MAX),
                min(r.retry_after_ns, I64_MAX),
            )
            deny[slot] = deny.get(slot, 0) + (not allowed)
    return out


def _wire(planes):
    """i64 (allowed, remaining, reset_ns, retry_ns) → the i32 wire
    values every compact tier carries."""
    i32 = (1 << 31) - 1
    return np.stack(
        [
            planes[:, 0],
            np.minimum(planes[:, 1], i32),
            np.minimum(planes[:, 2] // NS, i32),
            np.minimum(planes[:, 3] // NS, i32),
        ],
        axis=1,
    )


def _served_planes(out, compact, window, now):
    """The launch's output in the oracle's plane layout [K, 4, B]."""
    out = np.asarray(out)
    if compact == "w32":
        return np.stack(finish_w32(out), axis=1).astype(np.int64)
    if compact == "cur":
        _, _, _, em, tol, q, _ = window
        return np.stack(
            [
                np.stack(finish_cur(out[k], em[k], tol[k], q[k], now[k]))
                for k in range(len(now))
            ]
        ).astype(np.int64)
    return out.astype(np.int64)


def _assert_rows_match(state, oracle, deny, cap, ctx):
    """Every real slot holds what the oracle's store holds (fresh where
    it holds nothing), and on INS_WIDTH rows the denied-hit counter."""
    rows = np.asarray(state)[:cap]
    tat, exp = (np.asarray(x) for x in unpack_state(jnp.asarray(rows)))
    for slot in range(cap):
        tat_o, exp_o = oracle.store._data.get(slot, (0, EMPTY_EXPIRY))
        assert tat[slot] == tat_o, f"{ctx}: tat of slot {slot}"
        assert exp[slot] == min(exp_o, I64_MAX), f"{ctx}: expiry {slot}"
    if rows.shape[-1] > 4:
        want = np.array([deny.get(s, 0) for s in range(cap)], np.int64)
        got = np.asarray(unpack_deny(jnp.asarray(rows)))
        np.testing.assert_array_equal(got, want, err_msg=f"{ctx}: deny")


def _run_served(seed, K, B, cap, width, compact, with_degen, steps=2):
    """Drive the served packed scan at `width` over `steps` consecutive
    windows and hold it to the scalar oracle: each valid lane's reply,
    each real slot's stored row, the expired-hit accumulator and, at
    INS_WIDTH, the [allowed, denied] totals."""
    rng = np.random.default_rng(seed)
    state = _fresh_state(cap + B, width)
    exp_acc = jnp.zeros((), jnp.int64)
    ins_counts = jnp.zeros((2,), jnp.int64)
    oracle, deny = RateLimiter(_SlotStore()), {}
    totals = np.zeros(2, np.int64)
    tol_hwm = now_hwm = 0
    for step in range(steps):
        window, now = _rand_window(
            rng, K, B, cap, with_degen, w32=compact == "w32"
        )
        now = now + step * 200 * NS
        slots, rank, is_last, em, tol, q, valid = window
        if not with_degen:
            # The certificates the served path checks before it takes
            # the certified body and the one-word output tiers.
            assert not has_degenerate(valid, em, tol, q)
            for k in range(K):
                assert fits_cur_wire(tol[k][valid[k]], int(now[k]))
                if compact == "w32":
                    assert fits_w32_wire(
                        valid[k], em[k], tol[k], q[k], int(now[k]),
                        tol_hwm, now_hwm,
                    )
                tol_hwm = max(tol_hwm, int(tol[k].max(initial=0)))
                now_hwm = max(now_hwm, int(now[k]))
        packed = jnp.asarray(
            pack_requests(slots, rank, is_last, em, tol, q, valid)
        )
        if width > 4:
            state, exp_acc, ins_counts, out = gcra_scan_packed_ins(
                state, exp_acc, ins_counts, packed, jnp.asarray(now),
                with_degen=with_degen, compact=compact,
            )
        else:
            state, exp_acc, out = gcra_scan_packed_acc(
                state, exp_acc, packed, jnp.asarray(now),
                with_degen=with_degen, compact=compact,
            )
        want = _oracle_window(oracle, deny, window, now)
        allowed = want[:, 0][valid] != 0
        totals += [allowed.sum(), (~allowed).sum()]
        if compact is not False:
            want = _wire(want)
        got = _served_planes(out, compact, window, now)
        ctx = f"{compact=} {with_degen=} {width=} step {step}"
        bad = (got != want) & valid[:, None, :]
        assert not bad.any(), f"{ctx}: replies {np.argwhere(bad)[:4]}"
        _assert_rows_match(state, oracle, deny, cap, ctx)
        assert int(exp_acc) == oracle.store.expired_hits, ctx
        if width > 4:
            np.testing.assert_array_equal(np.asarray(ins_counts), totals)


@pytest.mark.parametrize("width", [4, INS_WIDTH])
@pytest.mark.parametrize(
    "compact,with_degen",
    [(False, True), (True, True), (True, False), ("cur", False),
     ("w32", False)],
)
def test_served_window_matches_oracle(width, compact, with_degen):
    """Every output tier at both row widths, exact and certified
    bodies: duplicate segments, degenerate orbits, invalid lanes, and
    state carried across consecutive windows."""
    _run_served(
        7 * width + len(str(compact)), K=2, B=16, cap=32,
        width=width, compact=compact, with_degen=with_degen,
    )


@pytest.mark.parametrize("K,B", [(1, 4), (1, 16), (3, 8), (2, 48)])
@pytest.mark.parametrize(
    "width,compact,with_degen",
    [(4, True, True), (INS_WIDTH, "w32", False), (INS_WIDTH, True, True)],
    ids=["w4-general", "served-w32", "served-general"],
)
def test_served_scan_shape_edges(K, B, width, compact, with_degen):
    """One and several sub-batches, lane counts that are and are not
    powers of two; the last two variants are SERVED_VARIANTS at the
    served row width."""
    _run_served(99 + K * B, K=K, B=B, cap=64, width=width,
                compact=compact, with_degen=with_degen, steps=1)


@pytest.mark.parametrize("width", [4, INS_WIDTH])
def test_suppressed_writes_land_in_scratch_tail(width):
    """A denied-everywhere window (one key, tolerance 0, quantity 2):
    the real rows match the oracle, and the suppressed writes land in
    the last B rows, never on a real slot nor elsewhere past capacity."""
    B, cap = 16, 8
    rows = cap + 2 * B
    slots = np.zeros((1, B), np.int32)
    rank = np.arange(B, dtype=np.int32)[None]
    is_last = np.zeros((1, B), bool)
    is_last[0, -1] = True
    em = np.full((1, B), NS, np.int64)
    tol = np.zeros((1, B), np.int64)
    q = np.full((1, B), 2, np.int64)
    valid = np.ones((1, B), bool)
    window = (slots, rank, is_last, em, tol, q, valid)
    now = np.array([BASE], np.int64)
    packed = jnp.asarray(pack_requests(*window))
    fresh = _fresh_state(rows, width)
    if width > 4:
        state, _, _, out = gcra_scan_packed_ins(
            fresh, jnp.zeros((), jnp.int64), jnp.zeros((2,), jnp.int64),
            packed, jnp.asarray(now), with_degen=True, compact=True,
        )
    else:
        state, _, out = gcra_scan_packed_acc(
            fresh, jnp.zeros((), jnp.int64), packed, jnp.asarray(now),
            with_degen=True, compact=True,
        )
    oracle, deny = RateLimiter(_SlotStore()), {}
    want = _wire(_oracle_window(oracle, deny, window, now))
    assert not want[:, 0].any(), "the window must deny everywhere"
    np.testing.assert_array_equal(np.asarray(out), want)
    _assert_rows_match(state, oracle, deny, cap, f"{width=}")
    np.testing.assert_array_equal(
        np.asarray(state)[cap : rows - B],
        np.asarray(_fresh_state(rows, width))[cap : rows - B],
    )


# ---------------------------------------------------------------------- #
# C++ assembler vs Python resolve + numpy packing.

from throttlecrab_tpu.native import (  # noqa: E402
    keymap_build_error,
    native_available,
    toolchain_available,
)

needs_native = pytest.mark.skipif(
    not toolchain_available(), reason="no C++ toolchain in environment"
)


@needs_native
def test_native_assemble_matches_resolve():
    assert native_available(), keymap_build_error()
    from throttlecrab_tpu.native import NativeKeyMap

    keys = [b"key:%d" % i for i in range(200)]
    em_by_id = (np.arange(200, dtype=np.int64) + 1) * 1_000_000
    tol_by_id = em_by_id * 4

    km_a = NativeKeyMap(512)
    first = km_a.intern(keys)
    assert first == 0

    rng = np.random.default_rng(5)
    ids = rng.integers(0, 200, K * B).astype(np.int32)
    packed, n_full = km_a.assemble(ids, B, em_by_id, tol_by_id, quantity=2)
    assert n_full == 0
    assert packed.shape == (K * B, PACK_WIDTH)

    # Reference: per-micro-batch resolve through a fresh keymap + numpy pack.
    km_b = NativeKeyMap(512)
    for k in range(K):
        sel = ids[k * B : (k + 1) * B]
        batch_keys = [keys[i] for i in sel]
        slots, rank, is_last, nf = km_b.resolve(
            batch_keys, np.ones(B, bool)
        )
        assert nf == 0
        expect = pack_requests(
            slots, rank, is_last,
            em_by_id[sel], tol_by_id[sel],
            np.full(B, 2, np.int64), np.ones(B, bool),
        )
        np.testing.assert_array_equal(
            packed[k * B : (k + 1) * B], expect,
            err_msg=f"micro-batch {k}",
        )


@needs_native
def test_native_assemble_padding_and_full():
    from throttlecrab_tpu.native import NativeKeyMap

    km = NativeKeyMap(4)  # only 4 slots
    km.intern([b"a", b"b", b"c", b"d", b"e", b"f"])
    em = np.full(6, 1_000_000, np.int64)
    ids = np.array([0, 1, 2, 3, 4, 5, -1, 0], np.int32)
    packed, n_full = km.assemble(ids, len(ids), em, em, quantity=1)
    assert n_full == 2  # e, f dropped: table full
    valid = (packed[:, 2] & 2) != 0
    np.testing.assert_array_equal(
        valid, [True, True, True, True, False, False, False, True]
    )
    assert packed[4, 0] == -1 and packed[6, 0] == -1
    # id 0 re-used after padding: same slot as its first occurrence,
    # rank 1, and the first occurrence lost its is_last flag.
    assert packed[7, 0] == packed[0, 0]
    assert packed[7, 1] == 1
    assert (packed[0, 2] & 1) == 0 and (packed[7, 2] & 1) == 1
    # Un-interned (out-of-range) ids are counted as failures, not padding.
    packed2, n_full2 = km.assemble(
        np.array([0, 99], np.int32), 2, em, em, quantity=1
    )
    assert n_full2 == 1 and packed2[1, 0] == -1 and packed2[1, 2] == 0


@needs_native
def test_native_assemble_multiple_intern_calls():
    from throttlecrab_tpu.native import NativeKeyMap

    km = NativeKeyMap(64)
    assert km.intern([b"x", b"y"]) == 0
    assert km.intern([b"z"]) == 2
    em = np.array([10, 20, 30], np.int64) * 1_000_000
    packed, n_full = km.assemble(
        np.array([2, 0, 1], np.int32), 3, em, em * 2
    )
    assert n_full == 0
    # Params follow the id, not the slot.
    lo = packed[:, 3].view(np.uint32).astype(np.int64)
    hi = packed[:, 4].astype(np.int64)
    np.testing.assert_array_equal((hi << 32) | lo, em[[2, 0, 1]])
    # Same keys through resolve agree on slots.
    slots, _, _, _ = km.resolve([b"z", b"x", b"y"], np.ones(3, bool))
    np.testing.assert_array_equal(packed[:, 0], slots)


class TestByIdPath:
    """The 8 B/request by-id launch path (tk_assemble_ids +
    gcra_scan_byid + tk_finish_ids) must match the packed path exactly."""

    @pytest.fixture
    def native_km(self):
        from throttlecrab_tpu.native import toolchain_available

        if not toolchain_available():
            pytest.skip("no C++ toolchain")
        from throttlecrab_tpu.native import NativeKeyMap

        return NativeKeyMap(256)

    def test_words_match_packed_rows(self, native_km):
        """assemble_ids emits the same slot/rank/is_last/valid structure
        as assemble, in 8 bytes instead of 36."""
        km = native_km
        n = 64
        km.intern([b"key:%d" % i for i in range(n)])
        em = np.arange(1, n + 1, dtype=np.int64) * 1000
        tol = np.arange(1, n + 1, dtype=np.int64) * 7000
        rng = np.random.RandomState(3)
        ids = rng.randint(0, n, 96).astype(np.int32)
        ids[5] = -1  # padding
        packed, n_full = km.assemble(ids, 32, em, tol, 1)
        assert n_full == 0
        words, n_bad = km.assemble_ids(ids, 32)
        assert n_bad == 0
        slots = km.resolve_all()

        meta = words >> 32
        w_rank = (meta & 0x3FFF).astype(np.int32)
        w_last = (meta & (1 << 14)) != 0
        w_valid = (meta & (1 << 15)) != 0
        w_id = (words & 0xFFFFFFFF).astype(np.int64)

        p_valid = (packed[:, 2] & 2) != 0
        np.testing.assert_array_equal(w_valid, p_valid)
        np.testing.assert_array_equal(w_rank[w_valid], packed[p_valid, 1])
        np.testing.assert_array_equal(
            w_last[w_valid], (packed[p_valid, 2] & 1) != 0
        )
        # The id in each word resolves to the packed row's slot.
        np.testing.assert_array_equal(
            slots[w_id[w_valid]], packed[p_valid, 0]
        )

    def test_end_to_end_matches_packed(self, native_km):
        """Same workload through check_many_byid + finish_ids and through
        check_many_packed + finish: identical wire values and identical
        table state."""
        from throttlecrab_tpu.tpu.kernel import PACK_WIDTH
        from throttlecrab_tpu.tpu.table import BucketTable

        km = native_km
        n, B, K = 40, 32, 4
        km.intern([b"k:%d" % i for i in range(n)])
        em = (np.arange(n, dtype=np.int64) % 7 + 1) * 250_000_000
        tol = em * (np.arange(n, dtype=np.int64) % 5 + 2)
        rng = np.random.RandomState(11)
        ids = rng.randint(0, n, K * B).astype(np.int32)
        now = np.full(K, 1_753_000_000_000_000_000, np.int64)

        packed, n_full = km.assemble(ids, B, em, tol, 1)
        assert not n_full
        words, n_bad = km.assemble_ids(ids, B)
        assert not n_bad

        t1 = BucketTable(128)
        out_p = np.asarray(
            t1.check_many_packed(
                packed.reshape(K, B, PACK_WIDTH), now,
                with_degen=False, compact="cur",
            )
        )
        wire_p = km.finish(packed, out_p.reshape(-1), int(now[0]))

        t2 = BucketTable(128)
        rows = t2.upload_id_rows(km.resolve_all(), em, tol)
        out_w = np.asarray(
            t2.check_many_byid(
                rows, words.reshape(K, B), now,
                quantity=1, with_degen=False, compact="cur",
            )
        )
        wire_w = km.finish_ids(
            words, em, tol, 1, out_w.reshape(-1), int(now[0])
        )

        np.testing.assert_array_equal(out_p, out_w)
        np.testing.assert_array_equal(wire_p, wire_w)
        np.testing.assert_array_equal(
            np.asarray(t1.state)[:64], np.asarray(t2.state)[:64]
        )

    def test_assemble_ids_rejects_oversized_batch(self, native_km):
        with pytest.raises(ValueError):
            native_km.assemble_ids(np.zeros(4, np.int32), 1 << 15)

    def test_uninterned_id_reported_bad(self, native_km):
        km = native_km
        km.intern([b"a", b"b"])
        words, n_bad = km.assemble_ids(
            np.array([0, 1, 7, -1], np.int32), 4
        )
        assert n_bad == 1
        meta = words >> 32
        valid = (meta & (1 << 15)) != 0
        np.testing.assert_array_equal(valid, [True, True, False, False])

    def test_stale_id_rows_guarded(self, native_km):
        """A sweep or growth remaps slots; the ResidentIdRows guard must
        refuse to launch against the stale device rows."""
        from throttlecrab_tpu.tpu.table import (
            BucketTable,
            StaleIdRowsError,
        )

        km = native_km
        km.intern([b"g:%d" % i for i in range(8)])
        slots = km.resolve_all()
        em = np.full(8, 10**9, np.int64)
        tol = em * 4
        table = BucketTable(64)
        rows = table.upload_id_rows(slots, em, tol, keymap=km)
        words, bad = km.assemble_ids(np.arange(8, dtype=np.int32), 8)
        assert not bad
        now = np.array([1_753_000_000_000_000_000], np.int64)
        table.check_many_byid(
            rows, words.reshape(1, 8), now, 1,
            with_degen=False, compact="cur",
        )  # fresh rows serve fine

        km.free_slots(slots[:2])  # sweep analog: slots recycled
        with pytest.raises(StaleIdRowsError):
            table.check_many_byid(
                rows, words.reshape(1, 8), now, 1,
                with_degen=False, compact="cur",
            )
        # Re-upload refreshes the guard.
        rows2 = table.upload_id_rows(km.resolve_all(), em, tol, keymap=km)
        words2, bad2 = km.assemble_ids(np.arange(8, dtype=np.int32), 8)
        assert not bad2
        table.check_many_byid(
            rows2, words2.reshape(1, 8), now, 1,
            with_degen=False, compact="cur",
        )

    def test_raw_ids_matches_host_words(self, native_km):
        """gcra_scan_ids (4 B raw ids, on-device segmenting) must match
        gcra_scan_byid (host-built words) on duplicate-heavy traffic
        with padding holes: same cur words, same wire values, same
        table state."""
        from throttlecrab_tpu.tpu.table import BucketTable

        km = native_km
        n, B, K = 40, 32, 4
        km.intern([b"k:%d" % i for i in range(n)])
        em = (np.arange(n, dtype=np.int64) % 7 + 1) * 250_000_000
        tol = em * (np.arange(n, dtype=np.int64) % 5 + 2)
        rng = np.random.RandomState(11)
        ids = rng.randint(0, n, K * B).astype(np.int32)
        ids[[3, 17, 40, 100]] = -1  # padding holes mid-batch
        now = np.full(K, 1_753_000_000_000_000_000, np.int64)

        words, bad = km.assemble_ids(ids, B)
        assert not bad
        slots = km.resolve_all()

        t1 = BucketTable(128)
        r1 = t1.upload_id_rows(slots, em, tol)
        out_w = np.asarray(
            t1.check_many_byid(
                r1, words.reshape(K, B), now, 1,
                with_degen=False, compact="cur",
            )
        ).reshape(-1)
        wire_w = km.finish_ids(words, em, tol, 1, out_w, int(now[0]))

        t2 = BucketTable(128)
        r2 = t2.upload_id_rows(slots, em, tol)
        out_r = np.asarray(
            t2.check_many_ids(
                r2, ids.reshape(K, B), now, 1,
                with_degen=False, compact="cur",
            )
        ).reshape(-1)
        wire_r = km.finish_raw(ids, em, tol, 1, out_r, int(now[0]))

        valid = ids >= 0
        np.testing.assert_array_equal(out_w[valid], out_r[valid])
        np.testing.assert_array_equal(wire_w[valid], wire_r[valid])
        # Allowed bit masked off on padding lanes in both paths.
        assert not (out_r[~valid] & 1).any()
        np.testing.assert_array_equal(
            np.asarray(t1.state)[:64], np.asarray(t2.state)[:64]
        )

    def test_raw_ids_hot_key_burst_semantics(self, native_km):
        """One key duplicated across a whole raw-ids batch must admit
        exactly `burst` requests in rank order — the on-device segment
        derivation reproducing the reference's sequential semantics."""
        from throttlecrab_tpu.tpu.table import BucketTable

        km = native_km
        km.intern([b"hot", b"cold"])
        slots = km.resolve_all()
        burst = 10
        em = np.full(2, 6_000_000_000, np.int64)  # period/count = 6s
        tol = em * (burst - 1)
        table = BucketTable(64)
        rows = table.upload_id_rows(slots, em, tol)
        ids = np.zeros(64, np.int32)  # 63x hot + 1 cold in the middle
        ids[31] = 1
        now = np.array([1_753_000_000_000_000_000], np.int64)
        out = np.asarray(
            table.check_many_ids(
                rows, ids.reshape(1, 64), now, 1,
                with_degen=False, compact="cur",
            )
        ).reshape(-1)
        allowed = (out & 1) != 0
        hot = ids == 0
        assert int(allowed[hot].sum()) == burst
        # Prefix property: the first `burst` hot occurrences are the
        # allowed ones (arrival order preserved through the sort).
        assert allowed[hot][:burst].all() and not allowed[hot][burst:].any()
        assert allowed[31]  # the cold key is its own segment

    def test_finish_raw_rejects_out_of_table_ids(self, native_km):
        km = native_km
        km.intern([b"a", b"b"])
        em = np.array([10**9, 10**9], np.int64)
        tol = em * 3
        cur2 = np.zeros(3, np.int64)
        with pytest.raises(ValueError):
            km.finish_raw(
                np.array([0, 1, 2], np.int32), em, tol, 1, cur2, 0
            )

    def test_intern_after_upload_invalidates_rows(self, native_km):
        """Ids interned after upload are not covered by the resident
        rows; the guard must force a re-upload rather than let the
        kernel clip the new id onto another key's row."""
        from throttlecrab_tpu.tpu.table import (
            BucketTable,
            StaleIdRowsError,
        )

        km = native_km
        km.intern([b"old"])
        em = np.array([10**9], np.int64)
        tol = em * 3
        table = BucketTable(64)
        rows = table.upload_id_rows(km.resolve_all(), em, tol, keymap=km)
        km.intern([b"new"])
        now = np.array([1_753_000_000_000_000_000], np.int64)
        with pytest.raises(StaleIdRowsError):
            table.check_many_ids(
                rows, np.array([[1]], np.int32), now, 1,
                with_degen=False, compact="cur",
            )


class TestIds20Stream:
    def test_pack_ids20_layout_and_guards(self):
        from throttlecrab_tpu.tpu.kernel import (
            IDS20_SENTINEL,
            pack_ids20,
        )

        ids = np.array([[0, 1, 0xFFFF, 0x9FFFE, -1, 7, 8, 9]], np.int32)
        buf = pack_ids20(ids)
        assert buf.dtype == np.uint16 and buf.shape == (1, 8 + 2)
        # Low 16 bits verbatim; padding becomes the all-ones sentinel.
        assert buf[0, 2] == 0xFFFF and buf[0, 3] == 0xFFFE
        assert buf[0, 4] == IDS20_SENTINEL & 0xFFFF
        # High nibbles packed 4-per-u16 in lane order.
        assert buf[0, 8] == (0x9 << 12)  # lanes 0..3: 0,0,0,0x9
        assert buf[0, 9] == 0xF          # lane 4 (sentinel hi) in slot 0
        with pytest.raises(ValueError):
            pack_ids20(np.full((1, 8), IDS20_SENTINEL, np.int32))
        with pytest.raises(ValueError):
            pack_ids20(np.zeros((1, 6), np.int32))  # width % 4 != 0

    @pytest.mark.parametrize("compact", [False, "cur", "w32"])
    def test_ids20_matches_raw_ids(self, compact):
        """The 2.5 B/request stream must decide identically to the raw
        i32 ids path — same outputs, same table state — on
        duplicate-heavy traffic with padding holes."""
        from throttlecrab_tpu.tpu.table import BucketTable

        rng = np.random.RandomState(17)
        n, B, K = 600, 32, 4
        em = (np.arange(n, dtype=np.int64) % 7 + 1) * 250_000_000
        tol = em * (np.arange(n, dtype=np.int64) % 5 + 2)
        slots = np.arange(n, dtype=np.int32)
        ids = rng.randint(0, n, (K, B)).astype(np.int32)
        ids[0, 3] = ids[1, 8] = ids[3, 31] = -1  # padding holes
        now = np.full(K, 1_753_000_000_000_000_000, np.int64)
        wd = compact is False  # exact path exercises degen machinery too

        from throttlecrab_tpu.tpu.kernel import pack_ids20

        t1 = BucketTable(1024)
        r1 = t1.upload_id_rows(slots, em, tol)
        out_raw = np.asarray(
            t1.check_many_ids(r1, ids, now, 1, with_degen=wd, compact=compact)
        )
        t2 = BucketTable(1024)
        r2 = t2.upload_id_rows(slots, em, tol)
        out_20 = np.asarray(
            t2.check_many_ids20(
                r2, pack_ids20(ids), now, 1, with_degen=wd, compact=compact
            )
        )
        # Padding lanes are don't-care (the two paths clip them onto
        # different rows before masking); every VALID lane must match,
        # and the allowed bit must be off on padding in both.
        valid = ids >= 0
        if compact is False:
            np.testing.assert_array_equal(
                out_raw[:, :, :][np.broadcast_to(valid[:, None, :],
                                                 out_raw.shape)],
                out_20[np.broadcast_to(valid[:, None, :], out_20.shape)],
            )
            assert not out_raw[:, 0, :][~valid].any()
            assert not out_20[:, 0, :][~valid].any()
        else:
            np.testing.assert_array_equal(out_raw[valid], out_20[valid])
            assert not (out_raw[~valid] & 1).any()
            assert not (out_20[~valid] & 1).any()
        np.testing.assert_array_equal(
            np.asarray(t1.state)[:700], np.asarray(t2.state)[:700]
        )

    def test_ids20_plain_entry_matches_acc_twin(self):
        """The public non-accumulating gcra_scan_ids20 must decide
        identically to the _acc twin the table routes through (same
        pinning the other plain/acc pairs get)."""
        from throttlecrab_tpu.tpu.kernel import (
            EMPTY_EXPIRY,
            gcra_scan_ids20,
            gcra_scan_ids20_acc,
            pack_id_rows,
            pack_ids20,
            pack_state,
        )

        n, B, K = 40, 16, 3
        em = np.full(n, 400_000_000, np.int64)
        tol = em * 5
        rows = jnp.asarray(pack_id_rows(np.arange(n, dtype=np.int32), em, tol))
        rng = np.random.RandomState(3)
        ids = rng.randint(0, n, (K, B)).astype(np.int32)
        buf = pack_ids20(ids)
        now = np.full(K, 1_753_000_000_000_000_000, np.int64)

        def fresh():
            return pack_state(
                jnp.zeros((256,), jnp.int64),
                jnp.full((256,), EMPTY_EXPIRY, jnp.int64),
            )

        st1, out1 = gcra_scan_ids20(
            fresh(), rows, jnp.asarray(buf), now, 1,
            with_degen=False, compact="cur",
        )
        st2, acc, out2 = gcra_scan_ids20_acc(
            fresh(), jnp.zeros((), jnp.int64), rows, jnp.asarray(buf),
            now, 1, with_degen=False, compact="cur",
        )
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        np.testing.assert_array_equal(np.asarray(st1), np.asarray(st2))
        assert int(acc) == 0  # fresh table: no expired hits

    def test_ids20_rejects_malformed_buffer(self):
        from throttlecrab_tpu.tpu.kernel import pack_id_rows
        from throttlecrab_tpu.tpu.table import BucketTable

        t = BucketTable(64)
        rows = t.upload_id_rows(
            np.arange(4, dtype=np.int32),
            np.full(4, NS, np.int64),
            np.full(4, 2 * NS, np.int64),
        )
        # Raw i32 ids where the u16 stream belongs: loud, not garbage.
        with pytest.raises(ValueError, match="pack_ids20"):
            t.check_many_ids20(
                rows, np.zeros((1, 5), np.int32), np.array([1], np.int64)
            )
        # Wrong width (not a multiple of 5 lanes).
        with pytest.raises(ValueError, match="pack_ids20"):
            t.check_many_ids20(
                rows, np.zeros((1, 8), np.uint16), np.array([1], np.int64)
            )

    def test_ids20_kernels_reject_misaligned_width(self):
        """Regression (ADVICE low): the raw kernels derived B = W*4//5
        without validating W % 5 == 0, so a direct caller handing a
        misaligned buffer (e.g. a raw id stream) got its high-nibble
        plane mis-split into plausible-but-wrong ids and decided against
        the wrong buckets.  Both entry points must fail loudly instead."""
        from throttlecrab_tpu.tpu.kernel import (
            EMPTY_EXPIRY,
            gcra_scan_ids20,
            gcra_scan_ids20_acc,
            pack_id_rows,
            pack_state,
        )

        n = 4
        em = np.full(n, NS, np.int64)
        rows = jnp.asarray(
            pack_id_rows(np.arange(n, dtype=np.int32), em, em * 2)
        )
        state = pack_state(
            jnp.zeros((64,), jnp.int64),
            jnp.full((64,), EMPTY_EXPIRY, jnp.int64),
        )
        bad = jnp.zeros((1, 8), jnp.uint16)  # 8 % 5 != 0
        now = np.array([NS], np.int64)
        with pytest.raises(ValueError, match="multiple of 5"):
            gcra_scan_ids20(state, rows, bad, now, 1)
        with pytest.raises(ValueError, match="multiple of 5"):
            gcra_scan_ids20_acc(
                state, jnp.zeros((), jnp.int64), rows, bad, now, 1
            )

    def test_ids20_rejects_oversized_table(self):
        from throttlecrab_tpu.tpu.kernel import pack_id_rows, pack_ids20
        from throttlecrab_tpu.tpu.table import BucketTable

        t = BucketTable(64)
        n = (1 << 20)  # one past the sentinel bound
        rows = np.zeros((n, 8), np.int32)
        with pytest.raises(ValueError, match="sentinel"):
            t.check_many_ids20(
                jnp.asarray(rows),
                pack_ids20(np.zeros((1, 4), np.int32)),
                np.array([1], np.int64),
            )
