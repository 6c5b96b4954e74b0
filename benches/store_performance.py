"""In-process engine micro-benchmarks.

The criterion-suite equivalent (`throttlecrab-server/benches/
store_performance.rs:7-366`): single hot key, hot/cold 80/20, uniform
random, sequential, zipfian, high-cardinality sweeps, and the three cleanup
policies compared — but measured against the batched device engine, since
that is this framework's hot path.  Prints one JSON line per scenario.

Usage:
  python benches/store_performance.py [--cpu] [--batch 4096] [--iters 64]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def bench_scenario(limiter, name, key_ids, batch, iters, params, now0):
    """Time `iters` batches drawn from key_ids; returns decisions/s."""
    n = len(key_ids)
    burst, count, period = params
    keys = [f"bench:{i}" for i in range(int(key_ids.max()) + 1)]
    # warmup / compile (wire=True: the serving fast path — compact i32
    # outputs + certified kernel — is what every transport runs).
    limiter.rate_limit_batch(
        [keys[i] for i in key_ids[:batch]], burst, count, period, 1, now0,
        wire=True,
    )
    t0 = time.perf_counter()
    for it in range(iters):
        sel = key_ids[(it * batch) % n : (it * batch) % n + batch]
        if len(sel) < batch:
            sel = np.concatenate([sel, key_ids[: batch - len(sel)]])
        limiter.rate_limit_batch(
            [keys[i] for i in sel], burst, count, period, 1,
            now0 + it * 1_000_000, wire=True,
        )
    dt = time.perf_counter() - t0
    rate = iters * batch / dt
    print(json.dumps({
        "scenario": name,
        "decisions_per_sec": round(rate),
        "batch": batch,
        "iters": iters,
    }))
    return rate


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=64)
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    import throttlecrab_tpu  # noqa: F401

    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    rng = np.random.default_rng(3)
    B, iters = args.batch, args.iters
    now0 = 1_753_000_000 * 1_000_000_000
    total = B * iters
    params = (100, 10_000, 60)

    # Key distributions (store_performance.rs groups).
    scenarios = {
        "single_hot_key": np.zeros(total, np.int64),
        "hot_keys_80_20": np.where(
            rng.random(total) < 0.8,
            rng.integers(0, 20, total),  # 80% of traffic on 20 keys
            rng.integers(20, 2000, total),
        ),
        "uniform_random_2k": rng.integers(0, 2000, total),
        "sequential_2k": np.arange(total, dtype=np.int64) % 2000,
        "zipfian_100k": None,  # built below
        "high_cardinality_100k": rng.permutation(
            np.arange(total, dtype=np.int64) % 100_000
        ),
    }
    ranks = np.arange(1, 100_001, dtype=np.float64)
    p = ranks**-1.1
    p /= p.sum()
    scenarios["zipfian_100k"] = rng.choice(100_000, size=total, p=p)

    for name, ids in scenarios.items():
        limiter = TpuRateLimiter(capacity=1 << 18, keymap="auto")
        bench_scenario(limiter, name, ids, B, iters, params, now0)

    # Workload-pattern rps sweep: the configured request-rate knob
    # (count_per_period = 100/1000/10000) cycled sequentially over 100
    # hot keys, like the reference's workload_patterns rps_* group
    # (store_performance.rs:263-291).
    rps_ids = np.arange(total, dtype=np.int64) % 100
    for rate in (100, 1000, 10_000):
        limiter = TpuRateLimiter(capacity=1 << 12, keymap="auto")
        bench_scenario(
            limiter, f"workload_rps_{rate}", rps_ids, B, iters,
            (100, rate, 60), now0,
        )

    # Cleanup-policy comparison on the zipfian workload
    # (store comparison group in the reference bench).
    from throttlecrab_tpu.server.engine import BatchingEngine  # noqa: F401
    from throttlecrab_tpu.tpu.cleanup import make_policy

    for policy_name in ("periodic", "probabilistic", "adaptive"):
        limiter = TpuRateLimiter(capacity=1 << 18, keymap="auto")
        policy = make_policy(policy_name)
        ids = scenarios["zipfian_100k"]
        keys = [f"bench:{i}" for i in range(100_000)]
        limiter.rate_limit_batch(
            [keys[i] for i in ids[:B]], *params, 1, now0
        )
        t0 = time.perf_counter()
        for it in range(iters):
            sel = ids[(it * B) % total : (it * B) % total + B]
            if len(sel) < B:
                sel = np.concatenate([sel, ids[: B - len(sel)]])
            now = now0 + it * 1_000_000
            limiter.rate_limit_batch(
                [keys[i] for i in sel], *params, 1, now, wire=True
            )
            policy.record_ops(B)
            if policy.should_clean(now, len(limiter), limiter.total_capacity):
                freed = limiter.sweep(now)
                policy.after_sweep(now, freed, len(limiter) + freed)
        dt = time.perf_counter() - t0
        print(json.dumps({
            "scenario": f"policy_{policy_name}_zipfian",
            "decisions_per_sec": round(iters * B / dt),
            "batch": B,
            "iters": iters,
        }))

    # Concurrent-contention group: 1/2/4/8 clients hammering the engine
    # (store_performance.rs:87-115 sweeps tokio threads the same way).
    # Measures coalescing: throughput plus requests-per-launch.
    bench_contention(B, max(iters * B // 8, 2000))
    return 0


def bench_contention(batch_size: int, total_requests: int) -> None:
    """N concurrent clients issue single requests through the batching
    engine; the engine coalesces them into device launches.  Reports
    decisions/s and the achieved requests-per-launch (coalescing
    efficiency) per client count."""
    import asyncio

    from throttlecrab_tpu.server.engine import BatchingEngine
    from throttlecrab_tpu.server.metrics import Metrics
    from throttlecrab_tpu.server.types import ThrottleRequest
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    for n_clients in (1, 2, 4, 8):
        limiter = TpuRateLimiter(capacity=1 << 16, keymap="auto")
        metrics = Metrics.builder().max_denied_keys(0).build()
        engine = BatchingEngine(
            limiter,
            batch_size=batch_size,
            max_linger_us=200,
            metrics=metrics,
        )
        per_client = total_requests // n_clients

        async def run() -> float:
            # Warm the compile outside the timed window.
            await engine.throttle(ThrottleRequest("warm", 10, 100, 60, 1))

            async def client(c: int) -> None:
                for i in range(per_client):
                    await engine.throttle(
                        ThrottleRequest(
                            f"c{c}:k{i % 512}", 1 << 30, 1 << 30, 3600, 1
                        )
                    )

            t0 = time.perf_counter()
            await asyncio.gather(
                *(client(c) for c in range(n_clients))
            )
            return time.perf_counter() - t0

        dt = asyncio.run(run())
        decided = per_client * n_clients
        launches = max(metrics.device_launches - 1, 1)  # minus warmup
        print(json.dumps({
            "scenario": f"contention_{n_clients}_clients",
            "decisions_per_sec": round(decided / dt),
            "requests_per_launch": round(
                (metrics.batched_requests - 1) / launches, 1
            ),
            "clients": n_clients,
            "requests": decided,
        }))


if __name__ == "__main__":
    sys.exit(main())
