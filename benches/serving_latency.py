"""Decision-latency percentiles at batch 4096 — the second clause of the
BASELINE north star (p99 decision latency < 1 ms at batch = 4096,
BASELINE.md:49-53).

Measures, per window of `--batch` requests against 1 M interned keys:

  engine path   — host prepare (C++ tk_prepare_batch when available) +
                  one device launch + result fetch, the exact path every
                  transport runs (dispatch_wire_window round trip).
  kernel only   — the device-resident by-id scan step alone (what a
                  PCIe-attached deployment pays once inputs are
                  resident): one launch + 8 B/request fetch.

Each window's wall time IS the decision latency of every request in it
(requests are answered together when the window's fetch completes), so
the per-window distribution is the per-request latency distribution.

Prints one JSON line per path with p50/p90/p99/max in ms plus the
implied decisions/s.  Run with --cpu off-TPU (a CPU rate is not a
device number).

Usage: python benches/serving_latency.py [--cpu] [--batch 4096]
       [--windows 64] [--keys 1000000]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def percentiles(samples_ms):
    s = np.sort(np.asarray(samples_ms))
    return {
        "p50_ms": round(float(np.percentile(s, 50)), 3),
        "p90_ms": round(float(np.percentile(s, 90)), 3),
        "p99_ms": round(float(np.percentile(s, 99)), 3),
        "max_ms": round(float(s[-1]), 3),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--windows", type=int, default=64)
    ap.add_argument("--keys", type=int, default=1_000_000)
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    import throttlecrab_tpu  # noqa: F401

    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    B, W, NK = args.batch, args.windows, args.keys
    now0 = 1_753_000_000 * 1_000_000_000
    rng = np.random.default_rng(5)

    lim = TpuRateLimiter(capacity=max(NK * 2, 1 << 16), keymap="auto")
    km = lim.keymap
    native = hasattr(km, "prepare_batch")
    print(
        f"keymap={'native' if native else 'python'} batch={B} "
        f"windows={W} keys={NK}",
        file=sys.stderr,
    )

    # Zipf-1.1 traffic over NK keys, params matching the headline bench.
    ranks = np.arange(1, NK + 1, dtype=np.float64)
    p = ranks**-1.1
    p /= p.sum()
    draws = rng.choice(NK, size=(W + 8) * B, p=p).astype(np.int64)

    keys = [b"lat:%d" % i for i in range(NK)]
    params = np.array([[100, 10_000, 60, 1]], np.int64).repeat(B, 0)

    def frame(ids):
        sel = [keys[i] for i in ids]
        blob = b"".join(sel)
        offs = np.cumsum([0] + [len(k) for k in sel]).astype(np.int64)
        return (blob, offs, params)

    # --- engine path: dispatch_wire_window round trips ------------------
    samples = []
    for w in range(W + 8):
        ids = draws[w * B : (w + 1) * B]
        now = now0 + w * 1_000_000
        t0 = time.perf_counter()
        if native:
            h = lim.dispatch_wire_window([frame(ids)], now)
            h.fetch()
        else:
            lim.rate_limit_batch(
                [keys[i] for i in ids], 100, 10_000, 60, 1, now, wire=True
            )
        dt = (time.perf_counter() - t0) * 1e3
        if w >= 8:  # first windows include compile
            samples.append(dt)
    stats = percentiles(samples)
    print(json.dumps({
        "path": "engine (prepare+launch+fetch)",
        "batch": B,
        **stats,
        "decisions_per_sec": round(B / (np.mean(samples) / 1e3)),
    }))

    # --- kernel-only: device-resident by-id scan ------------------------
    if native:
        # Fresh limiter so id i == key i (the engine run above interned
        # keys in traffic order).
        lim = TpuRateLimiter(capacity=max(NK * 2, 1 << 16), keymap="auto")
        km = lim.keymap
        km.intern(keys)  # host-only registration, untimed
        em = np.full(NK, 6_000_000, np.int64)
        tol = em * 100
        rows = lim.table.upload_id_rows(km.resolve_all(), em, tol)
        samples_k = []
        for w in range(W + 8):
            ids = draws[w * B : (w + 1) * B]
            now = np.array([now0 + w * 1_000_000], np.int64)
            t0 = time.perf_counter()
            out = lim.table.check_many_ids(
                rows, ids.astype(np.int32).reshape(1, B), now, 1,
                with_degen=False, compact="cur",
            )
            np.asarray(out)  # fetch = decision delivery
            dt = (time.perf_counter() - t0) * 1e3
            if w >= 8:
                samples_k.append(dt)
        stats_k = percentiles(samples_k)
        print(json.dumps({
            "path": "kernel (resident launch+fetch)",
            "batch": B,
            **stats_k,
            "decisions_per_sec": round(B / (np.mean(samples_k) / 1e3)),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
