"""Fused Pallas decision kernel (tpu/pallas_fused.py) edges.

The oracle differential lives in the tier fuzzer
(test_tier_fuzz.py::test_tier_ladder_fuzz_fused_alternation); this file
pins the kernel-specific contracts: the i32-pair arithmetic against the
i64 originals, the fused window against the composed-XLA twins across
widths / output tiers / ring-vs-batch shapes, shard_map composition,
the insight coexistence that retires the downgrade warning, and the
kill switch (THROTTLECRAB_PALLAS_FUSED unset = byte-identical current
behavior, fused code never invoked).
"""

import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from throttlecrab_tpu.tpu import pallas_fused as pf
from throttlecrab_tpu.tpu import sat
from throttlecrab_tpu.tpu.kernel import (
    EMPTY_EXPIRY,
    INS_WIDTH,
    gcra_scan_packed_acc,
    gcra_scan_packed_ins,
    pack_requests,
    pack_state,
)

NS = 1_000_000_000
T0 = 1_753_700_000 * NS

I64_EDGES = np.array(
    [
        0, 1, -1, 2, -2, (1 << 31) - 1, 1 << 31, -(1 << 31),
        (1 << 32) - 1, 1 << 32, (1 << 62), -(1 << 62),
        (1 << 63) - 1, -(1 << 63), NS, -NS, (1 << 61), 977,
    ],
    dtype=np.int64,
)


def _pairs(x):
    x = np.asarray(x, np.int64)
    lo = (x & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    hi = (x >> 32).astype(np.int32)
    return jnp.asarray(lo), jnp.asarray(hi)


def _join(p):
    return (np.asarray(p[1]).astype(np.int64) << 32) | (
        np.asarray(p[0]).astype(np.int64) & 0xFFFFFFFF
    )


def _rand_i64(rng, n):
    vals = rng.integers(-(1 << 63), 1 << 63, n, dtype=np.int64)
    # splice the edge values in so every run covers them
    idx = rng.choice(n, size=min(len(I64_EDGES), n), replace=False)
    vals[idx] = I64_EDGES[: len(idx)]
    return vals


def test_pair_math_matches_i64():
    """Every pair helper bit-identical to its i64 original (sat.py /
    numpy wrapping semantics) over random values spliced with the
    2^31/2^32/2^63 boundary edges."""
    rng = np.random.default_rng(42)
    n = 512
    a = _rand_i64(rng, n)
    b = _rand_i64(rng, n)
    pa, pb = _pairs(a), _pairs(b)

    with np.errstate(over="ignore"):
        assert (_join(pf._add64(pa, pb)) == a + b).all()
        assert (_join(pf._sub64(pa, pb)) == a - b).all()
        assert (_join(pf._mul64_lo(pa, pb)) == a * b).all()
    assert (np.asarray(pf._lt64(pa, pb)) == (a < b)).all()
    assert (np.asarray(pf._le64(pa, pb)) == (a <= b)).all()
    assert (np.asarray(pf._eq64(pa, pa)) == np.ones(n, bool)).all()
    assert (
        np.asarray(pf._ult64(pa, pb))
        == (a.view(np.uint64) < b.view(np.uint64))
    ).all()
    assert (_join(pf._max64(pa, pb)) == np.maximum(a, b)).all()
    assert (_join(pf._min64(pa, pb)) == np.minimum(a, b)).all()

    assert (
        _join(pf._sat_add64(pa, pb))
        == np.asarray(sat.sat_add(jnp.asarray(a), jnp.asarray(b)))
    ).all()
    assert (
        _join(pf._sat_sub64(pa, pb))
        == np.asarray(sat.sat_sub(jnp.asarray(a), jnp.asarray(b)))
    ).all()
    bn = np.abs(b) % (1 << 62)  # nn forms: b >= 0 contract
    assert (
        _join(pf._sat_add_nn64(pa, _pairs(bn)))
        == np.asarray(sat.sat_add_nn(jnp.asarray(a), jnp.asarray(bn)))
    ).all()
    assert (
        _join(pf._sat_sub_nn64(pa, _pairs(bn)))
        == np.asarray(sat.sat_sub_nn(jnp.asarray(a), jnp.asarray(bn)))
    ).all()
    an = np.abs(a) % ((1 << 63) - 1)  # nonneg-mul contract
    assert (
        _join(pf._sat_mul_nonneg64(_pairs(an), _pairs(bn)))
        == np.asarray(
            sat.sat_mul_nonneg(jnp.asarray(an), jnp.asarray(bn))
        )
    ).all()
    den = np.maximum(bn, 1)
    assert (
        _join(pf._udiv64(_pairs(an), _pairs(den))) == an // den
    ).all(), "unsigned long division"


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


def _rand_window(rng, K, B, cap, degen):
    """A hostile packed window: duplicate segments, degenerate params
    (when `degen`), invalid lanes, saturating-scale values."""
    slots = rng.integers(0, cap, (K, B)).astype(np.int32)
    em = rng.choice([0, 1, 1000, NS, 7 * NS, 1 << 62], (K, B)).astype(
        np.int64
    )
    tol = rng.choice(
        [0, 5, NS, 100 * NS, (1 << 61) + 7, -(3 * NS)], (K, B)
    ).astype(np.int64)
    q = rng.choice([0, 1, 2, 50], (K, B)).astype(np.int64)
    if not degen:
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
    now = T0 + np.sort(rng.integers(0, 100 * NS, K)).astype(np.int64)
    return pack_requests(slots, rank, is_last, em, tol, q, valid), now, valid


def _run_pair(seed, K, B, cap, width, compact, with_degen, steps=2):
    """Drive the fused and XLA packed-scan twins over the same windows;
    assert valid-lane outputs, real-slot state, and both accumulators
    stay bit-identical at every step."""
    rng = np.random.default_rng(seed)
    N = cap + B
    st_x, st_f = _fresh_state(N, width), _fresh_state(N, width)
    exp_x, exp_f = jnp.zeros((), jnp.int64), jnp.zeros((), jnp.int64)
    ic_x, ic_f = jnp.zeros((2,), jnp.int64), jnp.zeros((2,), jnp.int64)
    for step in range(steps):
        packed, now, valid = _rand_window(rng, K, B, cap, with_degen)
        now = now + step * 200 * NS
        if width > 4:
            st_x, exp_x, ic_x, out_x = gcra_scan_packed_ins(
                st_x, exp_x, ic_x, jnp.asarray(packed), jnp.asarray(now),
                with_degen=with_degen, compact=compact,
            )
            st_f, exp_f, ic_f, out_f = pf.gcra_scan_packed_fused_ins(
                st_f, exp_f, ic_f, packed, now,
                with_degen=with_degen, compact=compact,
            )
            assert (np.asarray(ic_x) == np.asarray(ic_f)).all()
        else:
            st_x, exp_x, out_x = gcra_scan_packed_acc(
                st_x, exp_x, jnp.asarray(packed), jnp.asarray(now),
                with_degen=with_degen, compact=compact,
            )
            st_f, exp_f, out_f = pf.gcra_scan_packed_fused_acc(
                st_f, exp_f, packed, now,
                with_degen=with_degen, compact=compact,
            )
        ox, of = np.asarray(out_x), np.asarray(out_f)
        mask = valid if compact in ("cur", "w32") else valid[:, None, :]
        bad = (ox != of) & mask
        assert not bad.any(), (
            f"out diverged ({compact=}, {with_degen=}, {width=}): "
            f"{np.argwhere(bad)[:4]}"
        )
        assert (
            np.asarray(st_x)[:cap] == np.asarray(st_f)[:cap]
        ).all(), "stored state diverged"
        assert int(exp_x) == int(exp_f), "expired-hit accumulator"


@pytest.mark.parametrize("width", [4, INS_WIDTH])
@pytest.mark.parametrize(
    "compact,with_degen",
    [(False, True), (True, True), (True, False), ("cur", False),
     ("w32", False)],
)
def test_fused_window_bit_identical_to_xla(width, compact, with_degen):
    """The fused window against the composed-XLA twin on hostile random
    windows: every output tier, both row widths, exact and certified
    paths, duplicate segments + degenerate orbits + invalid lanes,
    state carried across consecutive windows."""
    _run_pair(
        7 * width + len(str(compact)), K=2, B=16, cap=32,
        width=width, compact=compact, with_degen=with_degen,
    )


@pytest.mark.parametrize("K,B", [(1, 4), (1, 16), (3, 8), (2, 48)])
def test_ring_and_shape_edges(K, B):
    """Batch widths below / at / above the DMA ring depth (RING=16) and
    non-power-of-two lane counts all pipeline correctly — the fused
    grid walks any K, and the rings degrade to whatever depth B
    allows."""
    _run_pair(99 + K * B, K=K, B=B, cap=64, width=4,
              compact=True, with_degen=True, steps=1)


def test_scratch_tail_takes_suppressed_writes():
    """A denied-everywhere window must leave the real rows bit-identical
    under both dispatches AND land its redirects inside the scratch
    tail, never on a real slot (the unique-index contract)."""
    B, cap = 16, 8
    st = _fresh_state(cap + B, 4)
    # one key, burst 1 (tol 0), quantity 2: every request denied after
    # the orbit's first write
    slots = np.zeros((1, B), np.int32)
    rank = np.arange(B, dtype=np.int32)[None]
    is_last = np.zeros((1, B), bool)
    is_last[0, -1] = True
    em = np.full((1, B), NS, np.int64)
    tol = np.zeros((1, B), np.int64)
    q = np.full((1, B), 2, np.int64)
    valid = np.ones((1, B), bool)
    packed = pack_requests(slots, rank, is_last, em, tol, q, valid)
    now = np.array([T0], np.int64)
    st_f, _, out_f = pf.gcra_scan_packed_fused_acc(
        st, jnp.zeros((), jnp.int64), packed, now,
        with_degen=True, compact=True,
    )
    st_x, _, out_x = gcra_scan_packed_acc(
        _fresh_state(cap + B, 4), jnp.zeros((), jnp.int64),
        jnp.asarray(packed), jnp.asarray(now),
        with_degen=True, compact=True,
    )
    assert (np.asarray(out_f) == np.asarray(out_x)).all()
    assert (np.asarray(st_f)[:cap] == np.asarray(st_x)[:cap]).all()


def test_insight_coexists_no_downgrade_warning(monkeypatch, caplog):
    """THROTTLECRAB_PALLAS_FUSED=1 + insight: the width-polymorphic
    kernel carries the 6-wide rows natively, so enable_insight warns
    about nothing."""
    from throttlecrab_tpu.tpu.table import BucketTable

    monkeypatch.setenv("THROTTLECRAB_PALLAS_FUSED", "1")
    with caplog.at_level(logging.WARNING, logger="throttlecrab.table"):
        table = BucketTable(64, insight=True)
    assert not caplog.records
    assert table._packed_launch()[0] is pf.gcra_scan_packed_fused_ins


def test_env_parse_matches_config_bool(monkeypatch):
    """kernel.pallas_fused_enabled and config._env_bool must never
    disagree about the kill switch: THROTTLECRAB_PALLAS_FUSED=off/
    false/no must be OFF everywhere (a lax 'not in (\"\", \"0\")' parse
    once ran the fused kernel while every config surface reported it
    disabled)."""
    from throttlecrab_tpu.server.config import _env_bool
    from throttlecrab_tpu.tpu.kernel import pallas_fused_enabled

    for v in ("", "0", "1", "true", "false", "yes", "no", "on", "off",
              "TRUE", "oFF", "2"):
        monkeypatch.setenv("THROTTLECRAB_PALLAS_FUSED", v)
        assert pallas_fused_enabled() == _env_bool(v), v
    monkeypatch.delenv("THROTTLECRAB_PALLAS_FUSED")
    assert pallas_fused_enabled() is False


def test_create_limiter_arms_env_both_directions(monkeypatch):
    """store.create_limiter writes the RESOLVED config value to the env
    in both directions — a stale '1' from an earlier limiter in the
    same process must not defeat a later config's kill switch."""
    from throttlecrab_tpu.server.config import Config
    from throttlecrab_tpu.server.store import create_limiter

    monkeypatch.setenv("THROTTLECRAB_PALLAS_FUSED", "1")
    create_limiter(Config(http=True, store_capacity=1024))
    assert os.environ["THROTTLECRAB_PALLAS_FUSED"] == "0"
    create_limiter(
        Config(http=True, store_capacity=1024, pallas_fused=True)
    )
    assert os.environ["THROTTLECRAB_PALLAS_FUSED"] == "1"


@pytest.mark.slow
def test_flag_unset_never_imports_fused_module():
    """With the knob unset, a serving dispatch must not import
    tpu.pallas_fused at all — the default composed-XLA path stays
    isolated from the experimental pallas stack (fresh process, since
    this suite imports the module itself)."""
    code = (
        "import sys\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from throttlecrab_tpu.tpu.limiter import TpuRateLimiter\n"
        "lim = TpuRateLimiter(capacity=64, keymap='python')\n"
        f"lim.rate_limit_batch(['a', 'b'], 5, 10, 60, 1, {T0}, wire=True)\n"
        "assert 'throttlecrab_tpu.tpu.pallas_fused' not in sys.modules\n"
        "print('isolated')\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "THROTTLECRAB_PALLAS_FUSED"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0 and "isolated" in r.stdout, r.stderr[-2000:]


def test_kill_switch_fused_never_invoked(monkeypatch):
    """With THROTTLECRAB_PALLAS_FUSED unset the fused module must never
    be entered — current behavior stays byte-identical by construction."""
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    monkeypatch.delenv("THROTTLECRAB_PALLAS_FUSED", raising=False)

    def boom(*a, **k):  # pragma: no cover - fails the test if reached
        raise AssertionError("fused kernel invoked with the flag unset")

    monkeypatch.setattr(pf, "fused_window", boom)
    lim = TpuRateLimiter(capacity=256, keymap="python")
    res = lim.rate_limit_batch(
        ["a", "b", "a"], 5, 10, 60, 1, T0, wire=True
    )
    assert res.status.tolist() == [0, 0, 0]
    h = lim.dispatch_many(
        [(["a", "c"], 5, 10, 60, 1, T0 + NS)], wire=True
    )
    h.fetch()


def test_limiter_end_to_end_fused_equals_xla(monkeypatch):
    """Whole-limiter equality across the serving dispatchers
    (rate_limit_batch, dispatch_many incl. the w32/cur tier ladder)
    with the fused kernel on vs off, including stored state."""
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    rng = np.random.default_rng(11)
    monkeypatch.setenv("THROTTLECRAB_PALLAS_FUSED", "0")
    lims = {}
    for fused in (False, True):
        lims[fused] = TpuRateLimiter(capacity=256, keymap="python")
    keys = [f"k{i}" for i in range(24)]
    now = T0
    for step in range(5):
        n = int(rng.integers(2, 20))
        ks = [keys[rng.integers(len(keys))] for _ in range(n)]
        b = rng.integers(1, 2500, n)
        c = rng.integers(1, 100, n)
        p = rng.integers(1, 60, n)
        q = np.where(rng.random(n) < 0.15, 0, 1)
        batches = [(ks, b, c, p, q, now + j * NS // 5) for j in range(2)]
        got = {}
        for fused in (False, True):
            monkeypatch.setenv(
                "THROTTLECRAB_PALLAS_FUSED", "1" if fused else "0"
            )
            got[fused] = lims[fused].dispatch_many(
                batches, wire=True
            ).fetch()
        for g0, g1 in zip(got[False], got[True]):
            for f in ("allowed", "remaining", "reset_after_s",
                      "retry_after_s", "status"):
                assert (
                    np.asarray(getattr(g0, f))
                    == np.asarray(getattr(g1, f))
                ).all(), (step, f)
        assert (
            np.asarray(lims[False].table.state)[:256]
            == np.asarray(lims[True].table.state)[:256]
        ).all(), "table state diverged between dispatches"
        now += int(rng.integers(1, 3 * NS))


def test_shard_map_tenant_counters_ride_fused_launch(monkeypatch):
    """Tenant-armed mesh: the in-launch per-tenant [T, 2] psum fold
    reads the fused kernel's output planes — counters and decisions
    must match the XLA mesh exactly."""
    from conftest import require_devices

    require_devices(2)
    from throttlecrab_tpu.parallel.sharded import (
        ShardedTpuRateLimiter,
        make_mesh,
    )
    from throttlecrab_tpu.parallel.tenants import TenantRegistry

    rng = np.random.default_rng(31)
    monkeypatch.setenv("THROTTLECRAB_PALLAS_FUSED", "0")
    lims = {}
    for fused in (False, True):
        lims[fused] = ShardedTpuRateLimiter(
            capacity_per_shard=128, mesh=make_mesh(2), insight=True,
            tenants=TenantRegistry(max_tenants=4, delim=":"),
        )
    keys = [f"t{i % 3}:k{i}" for i in range(30)]
    now = T0
    for step in range(3):
        n = int(rng.integers(4, 20))
        ks = [keys[rng.integers(len(keys))] for _ in range(n)]
        b = rng.integers(1, 30, n)
        c = rng.integers(1, 80, n)
        p = rng.integers(1, 50, n)
        batches = [(ks, b, c, p, 1, now + j * NS // 10) for j in range(2)]
        got = {}
        for fused in (False, True):
            monkeypatch.setenv(
                "THROTTLECRAB_PALLAS_FUSED", "1" if fused else "0"
            )
            got[fused] = lims[fused].dispatch_many(
                batches, wire=True
            ).fetch()
        for g0, g1 in zip(got[False], got[True]):
            for f in ("allowed", "remaining", "status"):
                assert (
                    np.asarray(getattr(g0, f))
                    == np.asarray(getattr(g1, f))
                ).all(), (step, f)
        assert lims[False].tenant_stats() == lims[True].tenant_stats()
        now += NS


def test_shard_map_composition(monkeypatch):
    """ShardedBucketTable runs the identical fused program per shard:
    decisions, per-shard stored state, and the psum'd insight totals
    all bit-identical to the composed-XLA mesh, at both row widths."""
    from conftest import require_devices

    require_devices(2)
    from throttlecrab_tpu.parallel.sharded import (
        ShardedTpuRateLimiter,
        make_mesh,
    )

    rng = np.random.default_rng(23)
    for insight in (False, True):
        monkeypatch.setenv("THROTTLECRAB_PALLAS_FUSED", "0")
        lims = {}
        for fused in (False, True):
            lims[fused] = ShardedTpuRateLimiter(
                capacity_per_shard=128, mesh=make_mesh(2), insight=insight
            )
        keys = [f"k{i}" for i in range(32)]
        now = T0
        for step in range(3):
            n = int(rng.integers(3, 22))
            ks = [keys[rng.integers(len(keys))] for _ in range(n)]
            b = rng.integers(1, 40, n)
            c = rng.integers(1, 100, n)
            p = rng.integers(1, 60, n)
            q = np.where(rng.random(n) < 0.1, 0, 1)
            batches = [
                (ks, b, c, p, q, now + j * NS // 10) for j in range(2)
            ]
            got = {}
            for fused in (False, True):
                monkeypatch.setenv(
                    "THROTTLECRAB_PALLAS_FUSED", "1" if fused else "0"
                )
                got[fused] = lims[fused].dispatch_many(
                    batches, wire=True
                ).fetch()
            for g0, g1 in zip(got[False], got[True]):
                for f in ("allowed", "remaining", "reset_after_s",
                          "retry_after_s", "status"):
                    assert (
                        np.asarray(getattr(g0, f))
                        == np.asarray(getattr(g1, f))
                    ).all(), (insight, step, f)
            assert (
                np.asarray(lims[False].table.state)[:, :128]
                == np.asarray(lims[True].table.state)[:, :128]
            ).all(), (insight, step, "shard state")
            if insight:
                assert (
                    lims[False].table.insight_counts()
                    == lims[True].table.insight_counts()
                ), "psum'd mesh insight totals"
            now += int(rng.integers(1, 2 * NS))
