"""Mesh-sharded engine: key-shard data parallelism over all devices.

Run CPU-hermetic with:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/sharded_mesh.py --cpu
"""

import os.path as _p, sys as _s
_s.path.insert(0, _p.dirname(_p.dirname(_p.abspath(__file__))))

import jax

if "--cpu" in _s.argv:
    # Pin the CPU platform before the first device query.
    jax.config.update("jax_platforms", "cpu")

import time

from throttlecrab_tpu.parallel import ShardedTpuRateLimiter
from throttlecrab_tpu.parallel.sharded import make_mesh


def main() -> None:
    mesh = make_mesh()  # every visible device
    print(f"mesh: {mesh.shape}")
    limiter = ShardedTpuRateLimiter(capacity_per_shard=1 << 14, mesh=mesh)
    now = time.time_ns()

    keys = [f"user:{i}" for i in range(8192)]
    result = limiter.rate_limit_batch(
        keys, max_burst=10, count_per_period=100, period=60,
        quantity=1, now_ns=now,
    )
    print(f"{int(result.allowed.sum())}/{len(keys)} allowed")
    # psum-reduced global counters (one collective over the mesh):
    print(f"global allowed={limiter.total_allowed} "
          f"denied={limiter.total_denied}")


if __name__ == "__main__":
    main()
