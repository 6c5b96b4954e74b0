"""The main-path kernels compiled ahead of time for a described TPU v5e.

Nothing runs: each test lowers a kernel at the server's real launch
shapes (a 1,048,576-slot table with the default insight-widened rows,
K = 16 sub-batches of B = 4096, the engine's defaults) and compiles it
with the TPU compiler for a v5e that is described, not attached.  What
the chip's compiler refuses fails here, at no chip time.  The served
launches go through the tables' own `compile_launch`, the method the
fused knob's boot gate calls.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and test workers import
every test file.  A topology that cannot be described fails the tests
(the TPU compiler is installed with JAX).  The persistent compile cache
is off around these compiles (a cache entry written for a described
chip cannot be read back without one).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from throttlecrab_tpu.tpu.kernel import IDROW_WIDTH, INS_WIDTH
from throttlecrab_tpu.tpu.pallas_fused import SERVED_VARIANTS

CAPACITY = 1 << 20
SCRATCH = 1 << 16  # BucketTable.SCRATCH
K, B = 16, 4096  # config max_scan_depth and batch_size defaults
VARIANTS = pytest.mark.parametrize(
    "with_degen,compact", SERVED_VARIANTS, ids=["w32", "general"]
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def shape(dims, dtype, sharding):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


def served_table(one_chip):
    """The server's default single-device table (insight on), built from
    the attributes compile_launch reads: a described chip holds no
    arrays."""
    from throttlecrab_tpu.tpu.table import BucketTable

    table = object.__new__(BucketTable)
    table.insight = True
    table.state = shape((CAPACITY + SCRATCH, INS_WIDTH), jnp.int32, one_chip)
    table.exp_acc = shape((), jnp.int64, one_chip)
    table.ins_counts = shape((2,), jnp.int64, one_chip)
    return table


def sharded_table(topo, D=4, T=64):
    """The --shards 4 table with tenant counters, from its attributes."""
    from throttlecrab_tpu.parallel.sharded import AXIS, ShardedBucketTable
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices[:D]), (AXIS,))
    table = object.__new__(ShardedBucketTable)
    table.mesh, table.n_shards, table.tenant_slots = mesh, D, T
    table.sharding = NamedSharding(mesh, P(AXIS, None, None))
    table.row_sharding = NamedSharding(mesh, P(AXIS))
    table.replicated = NamedSharding(mesh, P())
    table.state = shape(
        (D, CAPACITY // D + ShardedBucketTable.SCRATCH, INS_WIDTH),
        jnp.int32, table.sharding,
    )
    table._step_cache = {}
    return table


@VARIANTS
def test_served_packed_scan_compiles(one_chip, with_degen, compact):
    """The served path's launch: K packed sub-batches into the
    insight-widened table (TpuRateLimiter.dispatch_wire_window ->
    BucketTable.check_many_packed), in each served output tier."""
    compiled = served_table(one_chip).compile_launch(
        K, B, with_degen=with_degen, compact=compact
    )
    assert compiled.output_shardings[-1].device_set == one_chip.device_set
    mem = compiled.memory_analysis()
    # The 24 MiB table is donated: updated in place, not copied.
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 21
    assert "tpu_custom_call" not in compiled.as_text()


def test_byid_scan_compiles(one_chip):
    """The by-id K-deep scan over 20-bit ids with device-resident
    parameter rows (bench.py's default path)."""
    from throttlecrab_tpu.tpu.kernel import gcra_scan_ids20_acc

    compiled = gcra_scan_ids20_acc.lower(
        shape((CAPACITY + SCRATCH, 4), jnp.int32, one_chip),
        shape((), jnp.int64, one_chip),
        shape((1_000_000, IDROW_WIDTH), jnp.int32, one_chip),
        shape((K, B + B // 4), jnp.uint16, one_chip),
        shape((K,), jnp.int64, one_chip),
        1,
        with_degen=False, compact="w32",
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@VARIANTS
def test_sharded_scan_step_compiles_with_psum(topo, with_degen, compact):
    """The --shards 4 scan step with tenant counters on a 4-chip mesh:
    the table is sharded over all four chips and the counters are
    all-reduced over ICI."""
    compiled = sharded_table(topo).compile_launch(
        K, B, with_degen=with_degen, compact=compact
    )
    assert "all-reduce" in compiled.as_text()
    assert compiled.output_shardings[0].device_set == set(topo.devices[:4])


def test_fused_knob_refused_for_the_chip(one_chip, monkeypatch):
    """THROTTLECRAB_PALLAS_FUSED=1's boot gate on a v5e: Mosaic still
    refuses the fused kernel (1-D column-vector relayouts and 4-wide row
    DMAs; ROADMAP S5), so a server with the knob on fails at boot.
    When the kernel's layout rewrite lands this test turns into the
    compile test: the gate passes and `tpu_custom_call` is in the text."""
    from throttlecrab_tpu.tpu import pallas_fused

    monkeypatch.setattr(pallas_fused, "INTERPRET", False)
    monkeypatch.setenv("THROTTLECRAB_PALLAS_FUSED", "1")
    with pytest.raises(RuntimeError, match="TPU v5 lite.*Mosaic"):
        pallas_fused.require_compiles(served_table(one_chip), K, B)
