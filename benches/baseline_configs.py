"""The five BASELINE.json benchmark configs, one JSON line each.

SURVEY §7.2 step 6's obligation: the driver's north-star config list,
measured against this framework's engine.

  1. single key, burst 10, 100/60s, 10k sequential checks
  2. 10k unique keys, uniform, batch=256, shared (10,100,60) params
  3. 1M keys, Zipf-1.1, batch=4096, heterogeneous params
     (the headline — bench.py owns it; a scaled-down pass runs here)
  4. 1M keys + 20% expired, periodic sweep interleaved every 1k batches
  5. multi-tenant: 64 tenants x 100k keys, psum-reduced allowed/denied
     counters across an 8-device mesh

Config 5 needs 8 devices: on a v5e-8 it uses the real mesh; elsewhere it
runs on 8 virtual CPU devices (set before JAX initializes), which
validates the collective layout but not ICI bandwidth.

Usage:
  python benches/baseline_configs.py [--cpu] [--quick] [--config N]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import numpy as np

NS = 1_000_000_000
T0 = 1_753_000_000 * NS


def out(config, name, rate, extra=None):
    line = {
        "config": config,
        "scenario": name,
        "decisions_per_sec": round(rate),
    }
    if extra:
        line.update(extra)
    print(json.dumps(line), flush=True)


def config1(quick):
    """Single key, burst 10, 100/60s, sequential scalar checks (the
    reference's CPU AdaptiveStore baseline shape)."""
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    lim = TpuRateLimiter(capacity=1024, keymap="auto")
    n = 1_000 if quick else 10_000
    lim.rate_limit("cfg1", 10, 100, 60, 1, T0)  # compile
    t0 = time.perf_counter()
    for i in range(n):
        lim.rate_limit("cfg1", 10, 100, 60, 1, T0 + i * 1_000_000)
    out("1", f"single key, {n} sequential scalar checks",
        n / (time.perf_counter() - t0))


def config2(quick):
    """10k unique keys, uniform, batch=256, shared params."""
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    lim = TpuRateLimiter(capacity=1 << 15, keymap="auto")
    n_keys, batch = 10_000, 256
    iters = 64 if quick else 512
    keys = [f"cfg2:{i}" for i in range(n_keys)]
    rng = np.random.default_rng(2)
    sel = rng.integers(0, n_keys, (iters + 1, batch))
    lim.rate_limit_batch([keys[i] for i in sel[0]], 10, 100, 60, 1, T0)
    t0 = time.perf_counter()
    for it in range(1, iters + 1):
        lim.rate_limit_batch(
            [keys[i] for i in sel[it]], 10, 100, 60, 1,
            T0 + it * 1_000_000,
        )
    out("2", f"10k keys uniform, batch={batch}",
        iters * batch / (time.perf_counter() - t0))


def config3(quick):
    """Headline shape, scaled down — `python bench.py` is the real run.
    In this process: a child would need the chip this process holds."""
    import bench
    import jax

    argv = ["--quick"] + (["--cpu"] if "--cpu" in sys.argv else [])
    line = bench.run_headline(
        bench.build_parser().parse_args(argv), jax.devices()[0]
    )
    out("3", "headline (bench.py --quick)", line["value"],
        {"note": "full run: python bench.py"})


def config4(quick):
    """Keys with 20% short-TTL traffic, periodic sweep every 1k batches."""
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    n_keys = 20_000 if quick else 200_000
    batch = 4096
    n_batches = 200 if quick else 1000
    sweep_every = 100 if quick else 1000
    lim = TpuRateLimiter(capacity=1 << (16 if quick else 19), keymap="auto")
    keys = [f"cfg4:{i}" for i in range(n_keys)]
    rng = np.random.default_rng(4)
    # 20% of traffic hits keys whose period makes them expire within the
    # run (short TTL); sweeps reclaim them.
    short = rng.random(n_keys) < 0.2
    periods = np.where(short, 1, 3600).astype(np.int64)
    sel = rng.integers(0, n_keys, (n_batches + 1, batch))
    lim.rate_limit_batch(
        [keys[i] for i in sel[0]], 10, 100, periods[sel[0]], 1, T0
    )
    swept = 0
    t0 = time.perf_counter()
    for it in range(1, n_batches + 1):
        now = T0 + it * 50_000_000  # 50ms per batch of virtual time
        lim.rate_limit_batch(
            [keys[i] for i in sel[it]], 10, 100, periods[sel[it]], 1, now
        )
        if it % sweep_every == 0:
            swept += lim.sweep(now)
    out("4", "20% expiring keys, periodic sweep interleaved",
        n_batches * batch / (time.perf_counter() - t0),
        {"slots_swept": int(swept)})


def config5(quick):
    """64 tenants x 100k keys over an 8-device mesh; allowed/denied
    totals are the kernel's psum-reduced global counters."""
    from throttlecrab_tpu.parallel.sharded import (
        ShardedTpuRateLimiter,
        make_mesh,
    )

    import jax

    n_dev = min(8, len(jax.devices()))
    tenants = 64
    keys_per_tenant = 1_000 if quick else 10_000
    batch = 4096
    iters = 32 if quick else 128
    lim = ShardedTpuRateLimiter(
        capacity_per_shard=1 << (15 if quick else 18),
        mesh=make_mesh(n_dev), keymap="auto", auto_grow=False,
    )
    rng = np.random.default_rng(5)
    t_sel = rng.integers(0, tenants, (iters + 1, batch))
    k_sel = rng.integers(0, keys_per_tenant, (iters + 1, batch))
    def batch_keys(it):
        return [
            f"t{t_sel[it, j]}:k{k_sel[it, j]}" for j in range(batch)
        ]
    lim.rate_limit_batch(batch_keys(0), 5, 100, 60, 1, T0)
    t0 = time.perf_counter()
    for it in range(1, iters + 1):
        lim.rate_limit_batch(
            batch_keys(it), 5, 100, 60, 1, T0 + it * 1_000_000
        )
    dt = time.perf_counter() - t0
    out("5", f"64 tenants x {keys_per_tenant} keys, {n_dev}-device mesh",
        iters * batch / dt,
        {"psum_allowed": lim.total_allowed,
         "psum_denied": lim.total_denied,
         "devices": n_dev})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--config", type=int, default=0,
                    help="run one config (1-5); default all")
    args = ap.parse_args()

    # Config 5 needs >= 8 devices; request virtual CPU devices before
    # JAX initializes when the host has fewer.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import throttlecrab_tpu  # noqa: F401

    configs = {1: config1, 2: config2, 3: config3, 4: config4, 5: config5}
    todo = [args.config] if args.config else [1, 2, 3, 4, 5]
    for c in todo:
        configs[c](args.quick)
    return 0


if __name__ == "__main__":
    sys.exit(main())
