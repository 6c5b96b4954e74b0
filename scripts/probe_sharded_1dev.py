"""Re-check the round-4 known issue: sharded scan on a 1-device REAL mesh.

One real-v5e run of the cross-batch state-carry scenario (round 4) failed
its assertion on a silently-degraded 1-device TPU mesh (TESTING.md
"Known issue"), while CPU meshes of every size pass.  This script runs
the exact scenario on whatever real backend the environment provides
(mesh of 1) plus the non-sharded twin, and prints a verdict:

    python scripts/probe_sharded_1dev.py [--cpu]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import throttlecrab_tpu  # noqa: F401
import jax

if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")

from throttlecrab_tpu.parallel.sharded import ShardedTpuRateLimiter, make_mesh
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

T0 = 1_700_000_000 * 10**9


def scenario(lim):
    batches = [(["hot"] * 4, 10, 100, 3600, 1, T0 + k) for k in range(4)]
    results = lim.rate_limit_many(batches)
    return [bool(a) for r in results for a in r.allowed]


def main() -> int:
    dev = jax.devices()[0]
    print(f"device: {dev}", file=sys.stderr, flush=True)
    want = [True] * 10 + [False] * 6

    sharded = ShardedTpuRateLimiter(
        capacity_per_shard=64, mesh=make_mesh(1)
    )
    got_sharded = scenario(sharded)

    plain = TpuRateLimiter(capacity=64)
    got_plain = scenario(plain)

    print(json.dumps({
        "platform": dev.platform,
        "sharded_1dev_ok": got_sharded == want,
        "plain_ok": got_plain == want,
        "sharded_allowed": got_sharded,
        "sharded_counters": [sharded.total_allowed, sharded.total_denied],
    }))
    return 0 if got_sharded == want and got_plain == want else 1


if __name__ == "__main__":
    sys.exit(main())
