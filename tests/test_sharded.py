"""Multi-device sharded limiter tests (8 virtual CPU devices, see conftest).

The sharded engine must be observationally identical to the scalar oracle
(core.RateLimiter over a dict store): same allow/deny stream, same
remaining/reset/retry accounting, regardless of how keys hash across the
mesh.  Mirrors the reference's store-agnostic shared suite
(`store_test_suite.rs`) at the cluster level.
"""

import jax
import numpy as np

from conftest import require_devices
import pytest

from throttlecrab_tpu.core.rate_limiter import RateLimiter
from throttlecrab_tpu.core.store.periodic import PeriodicStore
from throttlecrab_tpu.parallel import ShardedTpuRateLimiter, shard_of_key
from throttlecrab_tpu.parallel.sharded import make_mesh

NS = 1_000_000_000
T0 = 1_700_000_000 * NS


@pytest.fixture(scope="module")
def mesh():
    require_devices(8)  # single-chip THROTTLECRAB_TPU_TEST_REAL runs skip
    return make_mesh(8)


@pytest.fixture()
def limiter(mesh):
    return ShardedTpuRateLimiter(capacity_per_shard=256, mesh=mesh)


def oracle():
    return RateLimiter(PeriodicStore())


def test_keys_spread_across_shards():
    ids = {shard_of_key(f"key-{i}".encode(), 8) for i in range(256)}
    assert len(ids) == 8  # CRC32 routing actually uses the whole mesh


def test_scalar_parity_across_shards(limiter):
    ora = oracle()
    for i in range(40):
        key = f"user-{i % 7}"
        now = T0 + i * 137_000_000
        got = limiter.rate_limit(key, 3, 10, 60, 1, now)
        want = ora.rate_limit(key, 3, 10, 60, 1, now)
        assert got == want, f"step {i} key {key}"


def test_batch_parity_uniform_params(limiter):
    ora = oracle()
    rng = np.random.default_rng(42)
    keys = [f"k{int(x)}" for x in rng.integers(0, 50, 300)]
    now = T0
    res = limiter.rate_limit_batch(keys, 5, 100, 60, 1, now)
    for i, key in enumerate(keys):
        allowed, r = ora.rate_limit(key, 5, 100, 60, 1, now)
        assert bool(res.allowed[i]) == allowed, f"req {i} key {key}"
        assert int(res.remaining[i]) == r.remaining
        assert int(res.reset_after_ns[i]) == r.reset_after_ns
        assert int(res.retry_after_ns[i]) == r.retry_after_ns


def test_batch_parity_heterogeneous_params(limiter):
    ora = oracle()
    rng = np.random.default_rng(7)
    n = 200
    keys = [f"k{int(x)}" for x in rng.integers(0, 30, n)]
    burst = rng.integers(1, 6, n)
    count = rng.integers(1, 50, n)
    period = rng.integers(1, 120, n)
    qty = rng.integers(0, 3, n)
    now = T0
    res = limiter.rate_limit_batch(keys, burst, count, period, qty, now)
    for i, key in enumerate(keys):
        allowed, r = ora.rate_limit(
            key, int(burst[i]), int(count[i]), int(period[i]), int(qty[i]), now
        )
        assert bool(res.allowed[i]) == allowed, f"req {i}"
        assert int(res.remaining[i]) == r.remaining, f"req {i}"


def test_psum_counters_are_global(limiter):
    keys = [f"c{i}" for i in range(64)]
    res = limiter.rate_limit_batch(keys, 1, 1, 60, 2, T0)
    # quantity 2 > burst 1: every request denied.
    assert not res.allowed.any()
    assert limiter.total_allowed == 0
    assert limiter.total_denied == 64
    res = limiter.rate_limit_batch(keys, 10, 10, 60, 1, T0)
    assert res.allowed.all()
    assert limiter.total_allowed == 64


def test_sweep_frees_across_all_shards(limiter):
    keys = [f"s{i}" for i in range(80)]
    limiter.rate_limit_batch(keys, 2, 10, 1, 1, T0)
    assert len(limiter) == 80
    freed = limiter.sweep(T0 + 3600 * NS)
    assert freed == 80
    assert len(limiter) == 0


def test_duplicate_keys_serialize_within_batch(limiter):
    # 20 hits on one key with burst 10 in a single batch: exactly 10 allowed.
    keys = ["dup"] * 20
    res = limiter.rate_limit_batch(keys, 10, 100, 3600, 1, T0)
    assert int(res.allowed.sum()) == 10
    assert res.allowed[:10].all() and not res.allowed[10:].any()


def test_param_change_mid_batch(limiter):
    ora = oracle()
    keys = ["p", "p", "p", "p"]
    burst = [5, 5, 2, 2]
    count = [10, 10, 10, 10]
    period = [60, 60, 60, 60]
    qty = [1, 1, 1, 1]
    res = limiter.rate_limit_batch(keys, burst, count, period, qty, T0)
    for i in range(4):
        allowed, r = ora.rate_limit(
            "p", burst[i], count[i], period[i], qty[i], T0
        )
        assert bool(res.allowed[i]) == allowed, f"req {i}"
        assert int(res.remaining[i]) == r.remaining, f"req {i}"


def test_invalid_requests_do_not_poison_batch(limiter):
    keys = ["a", "b", "c"]
    res = limiter.rate_limit_batch(keys, [5, -1, 5], 10, 60, [1, 1, -2], T0)
    assert res.status[0] == 0
    assert res.status[1] != 0
    assert res.status[2] != 0
    assert res.allowed[0] and not res.allowed[1] and not res.allowed[2]


def test_table_grow_preserves_state(mesh):
    lim = ShardedTpuRateLimiter(capacity_per_shard=4, mesh=mesh)
    # Exhaust burst for one key, then overflow capacity to force growth.
    for _ in range(3):
        lim.rate_limit("grow-key", 3, 10, 3600, 1, T0)
    keys = [f"g{i}" for i in range(200)]
    lim.rate_limit_batch(keys, 3, 10, 3600, 1, T0)
    # State must survive the reallocation: the key is still exhausted.
    allowed, _ = lim.rate_limit("grow-key", 3, 10, 3600, 1, T0 + 1)
    assert not allowed


def test_shard_inputs_land_on_their_own_devices(limiter):
    """Each shard's [1, ...] slice of a stacked input goes straight to
    that shard's device, and the table spans every mesh device."""
    table = limiter.table
    D = table.n_shards
    (x,) = table._put_shards((np.arange(D * 16).reshape(D, 16), np.int32))
    mesh_devices = set(table.mesh.devices.flat)
    assert x.sharding.device_set == mesh_devices
    for shard in x.addressable_shards:
        assert shard.data.shape == (1, 16)
        row = shard.index[0].start
        np.testing.assert_array_equal(
            np.asarray(shard.data)[0], np.arange(16) + 16 * row
        )
    assert table.state.sharding.device_set == mesh_devices
