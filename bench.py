"""Headline benchmark: rate-limit decisions/sec on the TPU engine.

BASELINE.json config 3 — 1M distinct keys, Zipf-1.1 hot-key skew,
batch = 4096, per-key heterogeneous (burst, count, period) — measured
end-to-end through the host path (key→slot resolution + segment structure +
device launch + result fetch), i.e. what a serving deployment pays per
decision.

Launch architecture:

  - per-key (slot, emission, tolerance) rows live DEVICE-resident
    (uploaded once at setup); each request then crosses to the device
    as its bare 4-byte id and the device derives the duplicate-segment
    structure itself with a stable sort (kernel.gcra_scan_ids).
    `--segment host` instead ships 8-byte words built by C++
    tk_assemble_ids; `--path packed` the 36-byte self-contained rows;
  - results come back as ONE i64 per request (compact="cur"), finished
    to the exact i32 wire values by C++ tk_finish_raw/tk_finish_ids;
  - launches are K-deep scans with PIPE in flight, fetched on a small
    thread pool.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "decisions/s", "vs_baseline": N}

vs_baseline compares against the reference's best in-process library number
(AdaptiveStore, 12.5M req/s on Apple M3 Max over 2k keys —
docs/benchmark-results.md:28-32); this benchmark carries 500x that key
cardinality.

Flags: --cpu (force CPU backend), --quick (fewer batches), --depth K
(micro-batches per launch), --pipe P (launches in flight), --profile DIR
(capture an xprof trace of trial 0's timed region), --path
{auto,byid,packed,legacy} (launch path; --legacy is shorthand),
--segment {auto,device,host} (where the duplicate-segment structure is
derived on the byid path), --no-resident (skip the kernel-ceiling
measurement), --control
(control-plane A/B: kill-switch bit-identity, static defaults vs
controller on the declared objective, rank x2 determinism).

Without --cpu the benchmark needs a TPU: it exits non-zero when JAX
finds none, and never falls back to the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import deque

import numpy as np

REFERENCE_BASELINE = 12_500_000.0  # req/s, reference library AdaptiveStore

N_KEYS = 1_000_000
BATCH = 4096
ZIPF_A = 1.1
NS = 1_000_000_000
T0 = 1_753_000_000 * NS


def zipf_indices(rng, n_keys, size, a=ZIPF_A):
    """Bounded Zipf(a) ranks in [0, n_keys) via explicit probabilities."""
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    p = ranks ** -a
    p /= p.sum()
    return rng.choice(n_keys, size=size, p=p)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--depth", type=int, default=None,
                    help="micro-batches per device launch (default: 256 "
                         "on TPU, where a deeper launch amortizes the "
                         "fixed per-launch cost, else 64)")
    ap.add_argument("--pipe", type=int, default=4,
                    help="launches kept in flight")
    ap.add_argument("--profile", default=None,
                    help="capture an xprof trace of the timed region here")
    ap.add_argument("--legacy", action="store_true",
                    help="unpacked per-sub-batch resolve path")
    ap.add_argument("--path", choices=("auto", "byid", "packed", "legacy"),
                    default="auto",
                    help="launch path: byid = 8 B/request words + "
                         "device-resident parameter rows (default with "
                         "the native keymap); packed = 36 B/request "
                         "rows; legacy = per-sub-batch Python resolve")
    ap.add_argument("--no-resident", action="store_true",
                    help="skip the device-resident kernel-ceiling "
                         "measurement")
    ap.add_argument("--segment",
                    choices=("auto", "device20", "device", "host"),
                    default="auto",
                    help="byid path: device20 = 20-bit packed ids "
                         "(2.5 B/request, tables < 2^20-1 keys) with "
                         "on-device segment derivation; device = raw "
                         "4 B ids, segments on-device; host = 8 B words "
                         "built by C++ tk_assemble_ids.  auto = device20 "
                         "on TPU when the table fits (fewest bytes per "
                         "request), host elsewhere (the 1-vCPU XLA sort "
                         "costs more than it saves)")
    ap.add_argument("--wire", choices=("auto", "cur", "w32"),
                    default="auto",
                    help="by-id device output tier: w32 = 4 B/request "
                         "(device-packed wire values; wins whenever the "
                         "link is the bottleneck), cur = 8 B/request "
                         "(host-finished; wins on the CPU backend where "
                         "the extra device divisions cost more than "
                         "bytes).  auto = w32 on accelerators, cur on "
                         "cpu")
    ap.add_argument("--front", action="store_true",
                    help="front-tier benchmark instead: the hot-key "
                         "abuse workload (harness `hotkey-abuse`, ~90%% "
                         "of traffic hammering saturated keys) measured "
                         "with the exact deny cache on vs off; prints "
                         "both rates and the speedup")
    ap.add_argument("--insight", action="store_true",
                    help="insight-tier A/B instead: decisions/s with "
                         "the device analytics accumulators on vs off "
                         "(same workload shape as the serving engine's "
                         "scan path), plus the measured overhead "
                         "fraction — budget <= 2%%")
    ap.add_argument("--cluster", action="store_true",
                    help="elastic-cluster A/B instead: the 2-node "
                         "mixed workload under legacy modulo routing "
                         "vs the consistent-hash ring (must be within "
                         "session noise) vs ring+replication, same "
                         "session; benches/cluster_throughput.py owns "
                         "the full join/kill/rejoin timeline")
    ap.add_argument("--mesh", action="store_true",
                    help="sharded-mesh A/B instead: the BASELINE "
                         "config-5 multi-tenant shape on the widest "
                         "available mesh (8 virtual CPU devices off-"
                         "hardware), insight+tenants ON vs OFF, same "
                         "session; benches/mesh_scaling.py owns the "
                         "full D=1/2/4/8 sweep")
    ap.add_argument("--replay", action="store_true",
                    help="record/replay A/B instead: one synthetic "
                         "flash-crowd trace (throttlecrab_tpu/replay) "
                         "replayed against two limiter configs in THIS "
                         "session — the exact same-session A/B shape "
                         "docs/benchmark-results.md prescribes against "
                         "the ±2x session-variance caveat; verifies "
                         "the two configs' outcome vectors are "
                         "bit-identical before timing them")
    ap.add_argument("--replay-trace", default="",
                    help="with --replay: replay this trace file "
                         "instead of synthesizing one")
    ap.add_argument("--checkpoint", action="store_true",
                    help="crash-durability A/B instead (ISSUE 19): one "
                         "flash-crowd trace replayed against the same "
                         "limiter config with checkpointing OFF vs a "
                         "Checkpointer marking every decided window "
                         "dirty and writing a durable generation every "
                         "8 windows; verifies the outcome vectors are "
                         "bit-identical first (persistence rides the "
                         "observe path only), then reports the "
                         "decision-throughput overhead and bytes "
                         "written")
    ap.add_argument("--control", action="store_true",
                    help="control-plane A/B instead (ISSUE 16): one "
                         "flash-crowd trace simulated under virtual "
                         "time against static defaults vs the feedback "
                         "controller (throttlecrab_tpu/control), same "
                         "session; verifies the controller-off run is "
                         "bit-identical to a plain oracle replay first, "
                         "then compares the declared multi-objective "
                         "score and ranks the default candidate grid")
    return ap


def main() -> int:
    args = build_parser().parse_args()

    if args.mesh:
        # The mesh A/B needs up to 8 devices; request virtual CPU
        # devices before JAX initializes when the host has fewer
        # (harmless on real multi-chip hardware: the flag only affects
        # the host platform).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    if args.cluster:
        # The cluster A/B boots node processes that each need the
        # backend: this process must not hold the chip when they start.
        if not args.cpu:
            print(
                "error: --cluster starts server processes that each need "
                "the chip, and a chip serves one process; run it with "
                "--cpu",
                file=sys.stderr,
            )
            return 2
        return run_cluster_bench(args)

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")

    import throttlecrab_tpu  # noqa: F401  (enables x64)
    import jax

    from throttlecrab_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    print(f"bench device: {device}", file=sys.stderr)
    if not args.cpu and device.platform != "tpu":
        print(
            f"error: no TPU found (JAX runs on {device.platform}); pass "
            "--cpu for a CPU correctness run",
            file=sys.stderr,
        )
        return 1
    if args.front:
        return run_front_bench(args, device)
    if args.insight:
        return run_insight_bench(args, device)
    if args.mesh:
        return run_mesh_bench(args, device)
    if args.replay:
        return run_replay_bench(args, device)
    if args.checkpoint:
        return run_checkpoint_bench(args, device)
    if args.control:
        return run_control_bench(args, device)
    print(json.dumps(run_headline(args, device)))
    return 0


def run_headline(args, device) -> dict:
    """BASELINE config 3 through the host path; returns the headline
    line (extra detail goes to stderr)."""
    import jax

    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter, derive_params

    rng = np.random.default_rng(7)
    n_keys = 100_000 if args.quick else N_KEYS
    depth = args.depth
    if depth is None:
        depth = 256 if device.platform == "tpu" else 64
    if args.quick:
        depth = min(depth, 16)
    # Hold the timed workload near ~8M decisions regardless of depth.
    warm_launches = 2 if args.quick else 4
    timed_launches = 4 if args.quick else max(8, 2048 // depth)

    limiter = TpuRateLimiter(capacity=1 << 21, keymap="auto", auto_grow=False)
    keymap_kind = type(limiter.keymap).__name__
    path = args.path
    if args.legacy:
        path = "legacy"
    if path == "auto":
        path = (
            "byid" if hasattr(limiter.keymap, "assemble_ids") else "legacy"
        )
    if path in ("byid", "packed") and not hasattr(
        limiter.keymap, "assemble"
    ):
        print(
            f"{path} path needs the native keymap; falling back to legacy",
            file=sys.stderr,
        )
        path = "legacy"
    print(f"keymap: {keymap_kind}  path: {path}", file=sys.stderr)

    # Per-key heterogeneous parameters (BASELINE config 3), derived
    # deterministically from the key id.
    kid = np.arange(n_keys, dtype=np.int64)
    burst_all = 5 + (kid % 60)
    count_all = 50 + (kid % 1000)
    period_all = 30 + (kid % 120)
    keys = [b"bench:key:%d" % i for i in range(n_keys)]

    em_all, tol_all, _ = derive_params(burst_all, count_all, period_all)

    extra = {
        "scan_depth": depth,
        "pipe": args.pipe,
        "batch": BATCH,
        "n_keys": n_keys,
        "keymap": keymap_kind,
        "device": str(device),
        "platform": device.platform,
        "path": path,
        "wire_pref": args.wire,
    }

    if path == "byid":
        from throttlecrab_tpu.tpu.kernel import IDS20_SENTINEL

        segment = args.segment
        if segment == "auto":
            segment = (
                ("device20" if n_keys < IDS20_SENTINEL else "device")
                if device.platform == "tpu"
                else "host"
            )
        if segment == "device20" and n_keys >= IDS20_SENTINEL:
            print(
                "table too large for 20-bit ids; using raw 4 B ids",
                file=sys.stderr,
            )
            segment = "device"
        extra["segment"] = segment
        rate = run_byid(
            limiter, keys, em_all, tol_all, rng, n_keys, depth,
            args.pipe, warm_launches, timed_launches, args.profile,
            not args.no_resident, segment, extra,
        )
    elif path == "packed":
        rate = run_packed(
            limiter, keys, em_all, tol_all, rng, n_keys, depth,
            args.pipe, warm_launches, timed_launches, args.profile, extra,
        )
    else:
        rate = run_legacy(
            limiter, keys, em_all, tol_all, rng, n_keys, depth,
            warm_launches, timed_launches, extra,
        )

    print(json.dumps(extra), file=sys.stderr)
    return {
        "metric": (
            "rate-limit decisions/sec "
            f"({n_keys // 1000}k keys, Zipf-1.1, batch={BATCH})"
        ),
        "value": round(rate),
        "unit": "decisions/s",
        "vs_baseline": round(rate / REFERENCE_BASELINE, 3),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }


def run_front_bench(args, device) -> int:
    """Hot-key abuse decisions/s with the front tier's deny cache on vs
    off (ISSUE 1 acceptance: >= 2x with the cache on, CPU acceptable).

    Models the batching engine's saturation semantics faithfully: cache
    hits are answered at lookup time and never occupy the pending queue
    (engine.throttle returns before enqueueing), so under sustained
    abuse the engine launches once per `batch_size` accumulated MISSES,
    not once per batch_size arrivals — the launch's fixed cost amortizes
    over every arrival the cache absorbed in between.  The cache path is
    the bulk window flow (FrontTier.lookup_window / observe_window: one
    native call per window).  With the cache off, every arrival queues and launches
    ride batch_size-request windows.  Time is virtual (1 ms per arrival
    window): the hot keys saturate in the first windows and then stay
    inside their proven deny windows — the regime this traffic shape
    produces in production (a denied attacker retries long before
    retry_after expires)."""
    from itertools import repeat

    from throttlecrab_tpu.front import DenyCache, FrontTier
    from throttlecrab_tpu.harness.workload import make_keys
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    chunk = 4096          # arrivals per virtual-time step
    batch_size = 4096     # engine flush threshold (server default)
    warm = 4
    n_windows = (12 if args.quick else 50) + warm
    key_space = 10_000
    burst, count, period = 5, 10, 60  # em 6 s: hot keys stay denied
    keys = make_keys("hotkey-abuse", chunk * n_windows, key_space, seed=11)
    windows = [
        keys[i * chunk : (i + 1) * chunk] for i in range(n_windows)
    ]
    b_col = [burst] * chunk
    c_col = [count] * chunk
    p_col = [period] * chunk
    ones = [1] * chunk

    def launch(limiter, front, pend_keys, pend_now):
        """One engine flush: decide the pending requests (collect_cur so
        denials can certify) and observe them back into the cache."""
        m = len(pend_keys)
        seq = front.next_seq()
        res = limiter.rate_limit_batch(
            pend_keys, burst, count, period, [1] * m, pend_now,
            wire=True, collect_cur=True,
        )
        if res.cur_ns is None:
            # The launch committed but can't certify: conservative drop.
            front.fail_window(pend_keys)
            return
        # C-level row assembly: tolist() the planes once, zip with
        # repeat() for the constant columns — no per-row Python frame.
        front.observe_window(
            zip(pend_keys, repeat(burst), repeat(count), repeat(period),
                repeat(1), res.allowed.tolist(), res.cur_ns.tolist()),
            pend_now, seq,
        )

    def measure(with_front):
        limiter = TpuRateLimiter(capacity=1 << 15, keymap="python")
        front = (
            FrontTier(DenyCache(1 << 16), None) if with_front else None
        )
        now = T0
        t0 = None
        hits = 0
        pend: list = []
        for i, ks in enumerate(windows):
            if i == warm:
                t0 = time.perf_counter()
                hits = 0
            if front is None:
                limiter.rate_limit_batch(
                    ks, b_col, c_col, p_col, ones, now, wire=True
                )
            else:
                rows, n_hits = front.lookup_window(
                    ks, b_col, c_col, p_col, ones, now
                )
                hits += n_hits
                pend.extend(k for k, r in zip(ks, rows) if r is None)
                # Engine semantics: flush once batch_size misses queued
                # (the linger would flush the tail; steady-state abuse
                # is size-bound).
                while len(pend) >= batch_size:
                    launch(limiter, front, pend[:batch_size], now)
                    del pend[:batch_size]
            now += NS // 1000
        elapsed = time.perf_counter() - t0
        # The tail flush rides an odd-sized (fresh-compile) batch; it is
        # bookkeeping for reuse, not steady-state throughput: untimed.
        if front is not None and pend:
            launch(limiter, front, pend, now)
            pend.clear()
        rate = (n_windows - warm) * chunk / elapsed
        return rate, hits

    # Best of 2 per mode (the repo bench idiom): container scheduling
    # noise swings single runs several-fold either way.
    rate_off = max(measure(with_front=False)[0] for _ in range(2))
    rate_on, hits = max(
        (measure(with_front=True) for _ in range(2)),
        key=lambda rh: rh[0],
    )
    print(
        json.dumps(
            {
                "metric": (
                    "front-tier hot-key abuse decisions/s "
                    f"(hotkey-abuse, {key_space // 1000}k key space, "
                    f"batch={batch_size})"
                ),
                "front_off": round(rate_off),
                "front_on": round(rate_on),
                "unit": "decisions/s",
                "speedup": round(rate_on / rate_off, 2),
                "deny_cache_hit_rate": round(
                    hits / ((n_windows - warm) * chunk), 3
                ),
                "platform": device.platform,
            }
        )
    )
    return 0


def run_insight_bench(args, device) -> int:
    """Decisions/s with the insight accumulators on vs off (ISSUE 5
    acceptance: <= 2% overhead on the device-resident path).

    Both sides run the exact serving shape — K-deep wire-mode scan
    launches (rate_limit_many, the engine's backlog path) over a
    Zipf-skewed key stream with per-key heterogeneous params — so the
    measured delta is precisely what a production deployment pays for
    per-launch analytics: one scatter-add + two reductions riding each
    decision launch.  The throttled poll (accumulator fetch + top-K
    launch) happens ~1/s in production and is measured separately as
    poll_ms so its cost is visible but not smeared into the per-decision
    rate."""
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    rng = np.random.default_rng(13)
    n_keys = 20_000 if args.quick else 100_000
    batch = BATCH
    depth = 4 if args.quick else 8
    warm = 2
    timed = 6 if args.quick else 16
    kid = np.arange(n_keys, dtype=np.int64)
    burst_all = 5 + (kid % 60)
    count_all = 50 + (kid % 1000)
    period_all = 30 + (kid % 120)
    keys = [f"bench:key:{i}" for i in range(n_keys)]

    n_launches = warm + timed
    draws = zipf_indices(rng, n_keys, n_launches * batch * depth).astype(
        np.int64
    )

    def measure(insight):
        limiter = TpuRateLimiter(
            capacity=1 << 17, keymap="python", insight=insight
        )
        t0 = None
        for li in range(n_launches):
            if li == warm:
                t0 = time.perf_counter()
            base = li * batch * depth
            windows = []
            for j in range(depth):
                sel = draws[base + j * batch : base + (j + 1) * batch]
                windows.append(
                    (
                        [keys[i] for i in sel],
                        burst_all[sel],
                        count_all[sel],
                        period_all[sel],
                        1,
                        T0 + li * 50_000_000,
                    )
                )
            limiter.rate_limit_many(windows, wire=True)
        elapsed = time.perf_counter() - t0
        rate = timed * batch * depth / elapsed
        poll_ms = 0.0
        if insight:
            # One production poll: the scalar fetch + top-K launch.
            t1 = time.perf_counter()
            limiter.table.insight_counts()
            tk = limiter.table.insight_topk(64)
            np.asarray(tk[0]), np.asarray(tk[1])
            poll_ms = (time.perf_counter() - t1) * 1e3
        return rate, poll_ms

    # Best of 2 per mode (the repo bench idiom): container scheduling
    # noise swings single runs several-fold either way.
    rate_off = max(measure(False)[0] for _ in range(2))
    rate_on, poll_ms = max(
        (measure(True) for _ in range(2)), key=lambda rp: rp[0]
    )
    print(
        json.dumps(
            {
                "metric": (
                    "insight-tier A/B decisions/s "
                    f"({n_keys // 1000}k keys, Zipf-1.1, "
                    f"batch={batch}, depth={depth})"
                ),
                "insight_off": round(rate_off),
                "insight_on": round(rate_on),
                "unit": "decisions/s",
                "overhead_frac": round(1.0 - rate_on / rate_off, 4),
                "poll_ms": round(poll_ms, 3),
                "platform": device.platform,
            }
        )
    )
    return 0


def run_replay_bench(args, device) -> int:
    """Record/replay same-session A/B (ISSUE 14): one trace — synthetic
    flash-crowd by default, or any recorded trace via --replay-trace —
    replayed against two limiter configs in one session.

    The two configs here are the insight kill-switch pair (analytics
    accumulators on vs off): replay first PROVES their outcome vectors
    are bit-identical (the kill-switch contract, now checked under a
    replayable workload instead of a bespoke test harness), then times
    each side over the identical decision stream.  Unlike the live A/B
    benches, both sides consume the same keys, params and timestamps by
    construction — the trace is the controlled variable the ±2x
    session-variance caveat in docs/benchmark-results.md asks for."""
    from throttlecrab_tpu.replay.generators import synthesize
    from throttlecrab_tpu.replay.player import outcome_vector, replay
    from throttlecrab_tpu.replay.trace import Trace
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    if args.replay_trace:
        trace = Trace.load(args.replay_trace)
        source = args.replay_trace
    else:
        trace = synthesize(
            "flash-crowd",
            windows=24 if args.quick else 96,
            batch=512 if args.quick else 2048,
            key_space=4096 if args.quick else 32768,
            seed=17,
        )
        source = "synthetic flash-crowd"
    cap = 1 << 17

    def measure(insight: bool):
        limiter = TpuRateLimiter(
            capacity=cap, keymap="python", insight=insight
        )
        outcomes = replay(trace, limiter)  # warm pass: compiles + grows
        vec = outcome_vector(outcomes)
        limiter2 = TpuRateLimiter(
            capacity=cap, keymap="python", insight=insight
        )
        t0 = time.perf_counter()
        replay(trace, limiter2)
        elapsed = time.perf_counter() - t0
        return trace.n_rows() / elapsed, vec

    # Best of 2 per mode (the repo bench idiom), same trace both sides.
    rate_off, vec_off = max(
        (measure(False) for _ in range(2)), key=lambda rv: rv[0]
    )
    rate_on, vec_on = max(
        (measure(True) for _ in range(2)), key=lambda rv: rv[0]
    )
    identical = vec_off == vec_on
    print(
        json.dumps(
            {
                "metric": (
                    "replay A/B decisions/s (one trace, two configs, "
                    f"same session; {source}, "
                    f"{len(trace.windows)} windows, "
                    f"{trace.n_rows()} rows)"
                ),
                "insight_off": round(rate_off),
                "insight_on": round(rate_on),
                "unit": "decisions/s",
                "overhead_frac": round(1.0 - rate_on / rate_off, 4),
                "outcomes_bit_identical": identical,
                "platform": device.platform,
            }
        )
    )
    return 0 if identical else 1


def run_checkpoint_bench(args, device) -> int:
    """Crash-durability same-session A/B (ISSUE 19): one trace —
    synthetic flash-crowd by default, or any recorded trace via
    --replay-trace — replayed with checkpointing off vs on in one
    session.

    The on side mirrors the server wiring: every decided window's keys
    are marked dirty (the engine's post-decision observe path) and a
    durable generation — encode, fsync, rename, directory fsync — is
    written every 8 windows.  Replay first PROVES the outcome vectors
    bit-identical (persistence only ever exports; it cannot shift a
    decision), then times each side over the identical stream, so the
    reported overhead isolates dirty-marking + the periodic durable
    write.  Same-session, same trace: the controlled-variable shape
    docs/benchmark-results.md prescribes."""
    import shutil
    import tempfile
    from pathlib import Path

    from throttlecrab_tpu.persist import Checkpointer
    from throttlecrab_tpu.replay.generators import synthesize
    from throttlecrab_tpu.replay.player import (
        _decode_keys,
        outcome_vector,
    )
    from throttlecrab_tpu.replay.trace import Trace
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    if args.replay_trace:
        trace = Trace.load(args.replay_trace)
        source = args.replay_trace
    else:
        trace = synthesize(
            "flash-crowd",
            windows=24 if args.quick else 96,
            batch=512 if args.quick else 2048,
            key_space=4096 if args.quick else 32768,
            seed=17,
        )
        source = "synthetic flash-crowd"
    cap = 1 << 17
    every = 8

    def _replay(limiter, ck):
        """replay/player.replay with the server's persistence hooks:
        the same loop for both sides so the A/B isolates the hooks."""
        out = []
        for i, w in enumerate(trace.windows):
            keys = _decode_keys(w.keys, limiter)
            res = limiter.rate_limit_batch(
                keys,
                w.params[:, 0], w.params[:, 1], w.params[:, 2],
                w.params[:, 3], w.now_ns,
            )
            out.append((
                np.asarray(res.allowed, np.uint8).copy(),
                np.asarray(res.status, np.uint8).copy(),
            ))
            if ck is not None:
                ck.note_keys(keys)
                if (i + 1) % every == 0:
                    ck.checkpoint_now(w.now_ns)
        return out

    def measure(checkpoint: bool):
        ckdir = tempfile.mkdtemp(prefix="tc-bench-ck-")
        try:
            def build():
                limiter = TpuRateLimiter(capacity=cap, keymap="python")
                ck = None
                if checkpoint:
                    ck = Checkpointer(
                        limiter, ckdir, interval_ns=1 << 62
                    )
                return limiter, ck

            limiter, ck = build()
            vec = outcome_vector(_replay(limiter, ck))  # warm pass
            shutil.rmtree(ckdir, ignore_errors=True)
            limiter2, ck2 = build()
            t0 = time.perf_counter()
            _replay(limiter2, ck2)
            elapsed = time.perf_counter() - t0
            stats = {"generations": 0, "bytes": 0}
            if ck2 is not None:
                stats["generations"] = ck2.checkpoints_total
                stats["bytes"] = sum(
                    p.stat().st_size
                    for p in Path(ckdir).glob("*.tck")
                )
            return trace.n_rows() / elapsed, vec, stats
        finally:
            shutil.rmtree(ckdir, ignore_errors=True)

    rate_off, vec_off, _ = max(
        (measure(False) for _ in range(2)), key=lambda rv: rv[0]
    )
    rate_on, vec_on, ck_stats = max(
        (measure(True) for _ in range(2)), key=lambda rv: rv[0]
    )
    identical = vec_off == vec_on
    print(
        json.dumps(
            {
                "metric": (
                    "checkpoint A/B decisions/s (one trace, durability "
                    f"off vs on, same session; {source}, "
                    f"{len(trace.windows)} windows, "
                    f"{trace.n_rows()} rows, one generation per "
                    f"{every} windows)"
                ),
                "checkpoint_off": round(rate_off),
                "checkpoint_on": round(rate_on),
                "unit": "decisions/s",
                "overhead_frac": round(1.0 - rate_on / rate_off, 4),
                "generations_written": ck_stats["generations"],
                "checkpoint_bytes": ck_stats["bytes"],
                "outcomes_bit_identical": identical,
                "platform": device.platform,
            }
        )
    )
    return 0 if identical else 1


def run_control_bench(args, device) -> int:
    """Control-plane same-session A/B (ISSUE 16): one flash-crowd
    trace — synthetic by default, or any recorded trace via
    --replay-trace — simulated under virtual time (2x overload: the
    virtual device drains half the offered rate) twice in this
    session: once with static default knobs, once with the feedback
    controller armed.

    Order of proof mirrors run_replay_bench: FIRST the kill-switch
    contract — the controller-off simulation's outcome planes must be
    byte-identical to a plain scalar-oracle replay of the same trace
    (no shed, no knob moved, the subsystem invisible) — THEN the A/B
    on the declared multi-objective score (served throughput / queue
    wait / fairness), plus a `control rank` pass over the default
    candidate grid run twice to pin ranking determinism."""
    from throttlecrab_tpu.control import (
        ControlReplayer,
        Policy,
        default_candidates,
        rank,
        rank_json,
    )
    from throttlecrab_tpu.replay.generators import synthesize
    from throttlecrab_tpu.replay.player import (
        make_target,
        outcome_vector,
        replay,
    )
    from throttlecrab_tpu.replay.trace import Trace

    if args.replay_trace:
        trace = Trace.load(args.replay_trace)
        source = args.replay_trace
    else:
        # One fixed shape regardless of --quick: the A/B is only
        # meaningful in the overload regime where shedding pays — the
        # static side's virtual backlog must climb well past the 5 ms
        # AIMD setpoint (it peaks near 100 ms here) while still staying
        # under the DEFAULT 100k admission bound, so the static side
        # never sheds and the kill-switch bit-identity proof below
        # compares stock knobs exactly as a default boot would.  Milder
        # traces make "do nothing" the correct policy (the log-scaled
        # objective forgives modest queueing), which tests nothing.
        trace = synthesize(
            "flash-crowd",
            windows=96,
            batch=2048,
            key_space=32768,
            seed=17,
        )
        source = "synthetic flash-crowd"

    off = ControlReplayer(
        trace, Policy(name="static", mode="off")
    ).run()
    plain = outcome_vector(replay(trace, make_target("oracle", trace)))
    identical = off.vector() == plain

    on = ControlReplayer(
        trace, Policy(name="both", mode="both")
    ).run()

    ranking = [
        rank_json(rank(trace, default_candidates(8)))
        for _ in range(2)
    ]
    top = json.loads(ranking[0])[0]
    print(
        json.dumps(
            {
                "metric": (
                    "control A/B objective score (one trace, virtual "
                    f"time, 2x overload, same session; {source}, "
                    f"{len(trace.windows)} windows, "
                    f"{trace.n_rows()} rows)"
                ),
                "static_score": round(off.score, 6),
                "controller_score": round(on.score, 6),
                "controller_beats_static": on.score > off.score,
                "static_max_wait_us": round(off.max_wait_us_seen, 1),
                "controller_max_wait_us": round(on.max_wait_us_seen, 1),
                "controller_shed": on.shed,
                "controller_actuations": on.actuations,
                "off_bit_identical_to_plain_replay": identical,
                "rank_top": {
                    "name": top["policy"]["name"],
                    "score": top["score"],
                },
                "rank_deterministic": ranking[0] == ranking[1],
                "platform": device.platform,
            }
        )
    )
    ok = identical and on.score > off.score and ranking[0] == ranking[1]
    return 0 if ok else 1


def run_cluster_bench(args) -> int:
    """Elastic-cluster A/B: delegate to benches/cluster_throughput.py's
    2-node legacy-vs-ring scenarios (a subprocess keeps this process
    free of node event-loop threads).  The ring must be within session
    noise of the legacy modulo path — the lookup is one vectorized
    searchsorted either way."""
    import subprocess

    cmd = [
        sys.executable,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "benches", "cluster_throughput.py"),
        "--ab-only",
    ]
    if args.quick:
        cmd.append("--quick")
    return subprocess.call(cmd)


def run_mesh_bench(args, device) -> int:
    """Sharded-mesh serving A/B (ISSUE 6): the BASELINE config-5
    multi-tenant shape (64 tenants, tenant-prefixed keys, batch 4096)
    on the widest available mesh, measured with the full mesh-native
    stack ON (insight-widened shard rows + psum'd per-tenant counters)
    vs the bare sharded limiter — the per-decision price of serving
    analytics and tenant accounting from the mesh.  Same session, best
    of 2 per mode (the repo bench idiom); benches/mesh_scaling.py owns
    the D=1/2/4/8 width sweep."""
    import jax

    from throttlecrab_tpu.parallel.sharded import (
        ShardedTpuRateLimiter,
        make_mesh,
    )
    from throttlecrab_tpu.parallel.tenants import TenantRegistry

    n_dev = min(8, len(jax.devices()))
    tenants = 64
    per_tenant = 400 if args.quick else 1562  # ~config-5: 64 x ~1.5k
    batch = BATCH
    depth = 4  # engine-shaped: K wire-mode windows per mesh launch
    warm = 2
    iters = 4 if args.quick else 12
    keys = [
        f"t{t}:k{i}" for t in range(tenants) for i in range(per_tenant)
    ]
    rng = np.random.default_rng(17)
    sel = rng.integers(0, len(keys), ((warm + iters) * depth, batch))

    def measure(tenants_on, insight):
        lim = ShardedTpuRateLimiter(
            capacity_per_shard=max(2 * len(keys) // n_dev, 4096),
            mesh=make_mesh(n_dev),
            keymap="auto",
            auto_grow=False,
            insight=insight,
            tenants=(
                TenantRegistry(max_tenants=tenants + 4)
                if tenants_on
                else None
            ),
        )
        now = T0
        t0 = None
        for it in range(warm + iters):
            if it == warm:
                t0 = time.perf_counter()
            windows = []
            for j in range(depth):
                now += 1_000_000_000
                windows.append((
                    [keys[i] for i in sel[it * depth + j]],
                    5, 100, 60, 1, now,
                ))
            lim.rate_limit_many(windows, wire=True)
        return iters * depth * batch / (time.perf_counter() - t0)

    # Three points, best of 2 each: the bare sharded limiter (the
    # pre-tenant baseline path), + the tenant layer (per-tenant psum'd
    # counters + host tid attribution), + insight on top.  The insight
    # A/B at FIXED tenant config is the acceptance number; the tenant
    # delta is priced separately so neither hides in the other.
    rate_bare = max(measure(False, False) for _ in range(2))
    rate_tenants = max(measure(True, False) for _ in range(2))
    rate_full = max(measure(True, True) for _ in range(2))
    print(
        json.dumps(
            {
                "metric": (
                    "sharded-mesh multi-tenant decisions/s "
                    f"(config-5 shape, {tenants} tenants x "
                    f"{per_tenant} keys, batch={batch}, "
                    f"{n_dev}-device mesh)"
                ),
                "mesh_bare": round(rate_bare),
                "mesh_tenants": round(rate_tenants),
                "mesh_full": round(rate_full),
                "unit": "decisions/s",
                "tenant_overhead_frac": round(
                    1.0 - rate_tenants / rate_bare, 4
                ),
                "insight_overhead_frac": round(
                    1.0 - rate_full / rate_tenants, 4
                ),
                "devices": n_dev,
                "platform": device.platform,
            }
        )
    )
    return 0


def _populate(dispatch, rng, n_keys, per_launch, pipe, limiter, extra):
    """Touch every key once through `dispatch`, pipelined, fetching only
    to bound the in-flight window (outputs are discarded)."""
    t_pop = time.perf_counter()
    pop_order = rng.permutation(n_keys).astype(np.int32)
    pending = deque()
    for start in range(0, n_keys, per_launch):
        chunk = pop_order[start : start + per_launch]
        ids = np.full(per_launch, -1, np.int32)
        ids[: len(chunk)] = chunk
        pending.append(dispatch(ids, T0)[1])
        if len(pending) > pipe:
            np.asarray(pending.popleft())
    while pending:
        np.asarray(pending.popleft())
    extra["populate_s"] = round(time.perf_counter() - t_pop, 2)
    print(
        f"populated {len(limiter)} keys in {extra['populate_s']}s",
        file=sys.stderr,
    )


def _timed_trials(
    dispatch, complete, rng, n_keys, per_launch, pipe,
    warm_launches, timed_launches, profile_dir, extra,
):
    """The shared timed phase: Zipf-skewed launches, PIPE in flight,
    fetch+finish on a 3-worker pool, TWO independent trials reporting
    the better one (both trial rates land in the JSON).  --profile
    captures exactly trial 0's timed launches."""
    from concurrent.futures import ThreadPoolExecutor

    import contextlib

    n_launches = warm_launches + timed_launches
    draws = zipf_indices(rng, n_keys, n_launches * per_launch).astype(
        np.int32
    )
    chunks = [
        draws[i * per_launch : (i + 1) * per_launch]
        for i in range(n_launches)
    ]

    pool = ThreadPoolExecutor(max_workers=3)
    trial_rates = []
    best = None
    for trial in range(2):
        pending = deque()
        for li in range(warm_launches):
            pending.append(pool.submit(complete, *dispatch(
                chunks[li], T0 + (trial * n_launches + li) * 50_000_000
            )))
        while pending:
            pending.popleft().result()

        if profile_dir and trial == 0:
            from throttlecrab_tpu.tpu.profiling import trace

            profiler = trace(profile_dir)
            extra["trace_dir"] = profile_dir
            extra["trace_trial"] = 0
        else:
            profiler = contextlib.nullcontext()

        with profiler:
            t_dispatch = {}
            latencies = []
            t_start = time.perf_counter()
            for li in range(warm_launches, n_launches):
                t_dispatch[li] = time.perf_counter()
                now_ns = T0 + (trial * n_launches + li) * 50_000_000
                pending.append(
                    (li, pool.submit(complete, *dispatch(
                        chunks[li], now_ns
                    )))
                )
                if len(pending) > pipe:
                    j, fut = pending.popleft()
                    fut.result()
                    latencies.append(time.perf_counter() - t_dispatch[j])
            while pending:
                j, fut = pending.popleft()
                fut.result()
                latencies.append(time.perf_counter() - t_dispatch[j])
            elapsed = time.perf_counter() - t_start
            trial_rates.append(
                round(timed_launches * per_launch / elapsed)
            )
            if best is None or elapsed < best[0]:
                best = (elapsed, latencies)
    pool.shutdown()

    elapsed, latencies = best
    decided = timed_launches * per_launch
    lat = np.sort(np.asarray(latencies))
    extra.update(
        {
            "elapsed_s": round(elapsed, 3),
            "decisions": decided,
            "trial_rates": trial_rates,
            "fetch_latency_p50_ms": round(
                float(lat[int(0.50 * len(lat))]) * 1e3, 3
            ),
            "fetch_latency_p99_ms": round(
                float(lat[min(int(0.99 * len(lat)), len(lat) - 1)]) * 1e3, 3
            ),
            "launch_wall_ms": round(elapsed / timed_launches * 1e3, 3),
        }
    )
    return decided / elapsed


def run_byid(
    limiter, keys, em_all, tol_all, rng, n_keys, depth, pipe,
    warm_launches, timed_launches, profile_dir, resident, segment,
    extra,
):
    """The minimum-wire-bytes path: resident per-key parameter rows +
    8 B/request compact="cur" outputs, fed by either

      - raw 4 B/request key ids with the duplicate-segment structure
        derived ON-DEVICE by a stable sort (`--segment device`, the
        default: kernel.gcra_scan_ids — nothing but the id stream
        crosses the wire, and no C++ assembly runs at dispatch), or
      - 8 B/request i64 words built by C++ tk_assemble_ids
        (`--segment host`: kernel.gcra_scan_byid).

    The on-device sort trades device work for fewer upload bytes.  The
    fetch returns one i64 per request, finished to exact i32 wire
    values by C++ tk_finish_raw / tk_finish_ids on a thread pool.
    """
    from concurrent.futures import ThreadPoolExecutor

    km = limiter.keymap
    table = limiter.table
    per_launch = BATCH * depth
    dev_segment = segment in ("device", "device20")
    ids20 = segment == "device20"
    if ids20:
        from throttlecrab_tpu.tpu.kernel import pack_ids20

    # Untimed setup: intern the key universe, resolve slots, upload the
    # per-id parameter rows (config state, resident across launches).
    km.intern(keys)
    slots = km.resolve_all()
    assert (slots >= 0).all(), "table full during setup"
    id_rows = table.upload_id_rows(slots, em_all, tol_all, keymap=km)

    # Output tier: w32 (4 B/request — the device packs the exact wire
    # values into one i32) when the bench params fit its field widths,
    # else cur (8 B/request, host-finished).
    from throttlecrab_tpu.tpu.kernel import finish_w32, fits_w32_wire

    n_ids = len(em_all)
    wire_pref = extra.get("wire_pref", "auto")
    if wire_pref == "auto":
        # w32's halved fetch only pays where the link is the bottleneck;
        # the CPU backend has no link and pays the divisions instead.
        wire_pref = "cur" if extra.get("platform") == "cpu" else "w32"
    use_w32 = wire_pref == "w32" and fits_w32_wire(
        np.ones(n_ids, bool), em_all, tol_all,
        np.ones(n_ids, np.int64), T0, table.tol_hwm, table.now_hwm,
    )
    extra["wire_mode"] = "w32" if use_w32 else "cur"
    print(f"device output tier: {extra['wire_mode']}", file=sys.stderr)

    common = dict(
        quantity=1,
        with_degen=False,  # certified: qty=1, burst>1, emission>0,
        # tol>0, now/tol < 2**61 (fits_cur_wire / fits_w32_wire)
        compact="w32" if use_w32 else "cur",
    )

    def dispatch(ids, now_ns):
        now_arr = np.full(depth, now_ns, np.int64)
        if ids20:
            out = table.check_many_ids20(
                id_rows, pack_ids20(ids.reshape(depth, BATCH)), now_arr,
                **common,
            )
            return ids, out, now_ns
        if dev_segment:
            out = table.check_many_ids(
                id_rows, ids.reshape(depth, BATCH), now_arr, **common
            )
            return ids, out, now_ns
        words, n_bad = km.assemble_ids(ids, BATCH)
        assert not n_bad
        out = table.check_many_byid(
            id_rows, words.reshape(depth, BATCH), now_arr, **common
        )
        return words, out, now_ns

    def complete(carrier, out, now_ns):
        """Fetch the device words and finish the exact i32 wire values
        (allowed, remaining, reset_s, retry_s): w32 fetches 4 B/request
        and unpacks with numpy shifts; cur fetches 8 B/request and
        reconstructs in C++ (tk_finish_raw / tk_finish_ids)."""
        cur2 = np.asarray(out)
        if use_w32:
            return finish_w32(cur2)
        if dev_segment:
            return km.finish_raw(carrier, em_all, tol_all, 1, cur2, now_ns)
        return km.finish_ids(carrier, em_all, tol_all, 1, cur2, now_ns)

    _populate(dispatch, rng, n_keys, per_launch, pipe, limiter, extra)

    # ---- host-assembly-only throughput -----------------------------------
    probe_ids = zipf_indices(rng, n_keys, per_launch).astype(np.int32)
    km.assemble_ids(probe_ids, BATCH)
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        km.assemble_ids(probe_ids, BATCH)
    host_rate = reps * per_launch / (time.perf_counter() - t0)
    extra["host_assemble_slots_per_s"] = round(host_rate)
    print(
        f"host assembly alone: {host_rate / 1e6:.1f} M slots/s",
        file=sys.stderr,
    )

    # ---- device-resident kernel ceiling ----------------------------------
    # What the same kernel sustains when requests are already device-side
    # (i.e. what a PCIe-attached deployment's device half would do): R
    # launches over pre-staged word buffers, outputs reduced to one
    # scalar on device, one fetch at the end.  Shows how much of the
    # end-to-end gap is transfer rather than the kernel.
    if resident:
        import jax

        _sum = jax.jit(lambda x: x.sum())
        R = 8

        def measure(use_devseg):
            """Best-of-2 resident rate for one kernel variant (the first
            timing block after a compile/idle period reads ~2x slow on
            this platform — docs/tpu-launch-profile.md)."""
            staged = []
            for _ in range(R):
                ids_r = zipf_indices(
                    rng, n_keys, per_launch
                ).astype(np.int32)
                if use_devseg:
                    wd = jax.device_put(ids_r.reshape(depth, BATCH))
                else:
                    w, n_bad = km.assemble_ids(ids_r, BATCH)
                    assert not n_bad
                    wd = jax.device_put(w.reshape(depth, BATCH))
                np.asarray(_sum(wd))  # settle the upload (untimed)
                staged.append(wd)
            check = (
                table.check_many_ids
                if use_devseg
                else table.check_many_byid
            )
            best_dt = None
            for _round in range(2):
                t0 = time.perf_counter()
                checks = []
                for r, wd in enumerate(staged):
                    out = check(
                        id_rows, wd,
                        np.full(depth, T0 + r * 50_000_000, np.int64),
                        quantity=1, with_degen=False, compact="cur",
                    )
                    checks.append(_sum(out))
                np.asarray(sum(checks))  # one scalar fetch drains all
                dt = time.perf_counter() - t0
                best_dt = dt if best_dt is None else min(best_dt, dt)
            return R * per_launch / best_dt

        # The deployment ceiling: host-built words, no on-device sort —
        # what a PCIe-attached single chip sustains end-to-end (host
        # assembly at 48-84 M slots/s is not the limiter there).
        rate_words = measure(False)
        extra["device_resident_decisions_per_s"] = round(rate_words)
        print(
            f"device-resident kernel: {rate_words / 1e6:.1f} M dec/s "
            "(host-words variant, best of 2)",
            file=sys.stderr,
        )
        if dev_segment:
            # The kernel the fewest-bytes end-to-end path actually
            # runs (adds the on-device segment sort).
            rate_seg = measure(True)
            extra["device_resident_devseg_decisions_per_s"] = round(
                rate_seg
            )
            print(
                f"device-resident kernel: {rate_seg / 1e6:.1f} M dec/s "
                "(device-segment variant, best of 2)",
                file=sys.stderr,
            )

    return _timed_trials(
        dispatch, complete, rng, n_keys, per_launch, pipe,
        warm_launches, timed_launches, profile_dir, extra,
    )


def run_packed(
    limiter, keys, em_all, tol_all, rng, n_keys, depth, pipe,
    warm_launches, timed_launches, profile_dir, extra,
):
    """36 B/request packed-row path (C++ tk_assemble + pipelined packed
    dispatch + compact="cur" fetch).  Superseded as the default by
    run_byid — kept as the A/B reference for the wire-bytes model and
    for workloads whose parameters change per request.

    Both paths fetch on a 3-thread pool, without copy_to_host_async()
    at dispatch time."""
    from throttlecrab_tpu.tpu.kernel import PACK_WIDTH as W

    km = limiter.keymap
    table = limiter.table
    per_launch = BATCH * depth

    km.intern(keys)  # id i == key i (host-only registration, untimed)

    def dispatch(ids, now_ns):
        packed, n_full = km.assemble(ids, BATCH, em_all, tol_all, 1)
        assert not n_full
        out = table.check_many_packed(
            packed.reshape(depth, BATCH, W),
            np.full(depth, now_ns, np.int64),
            with_degen=False,  # certified: qty=1, burst>1, emission>0,
            compact="cur",     # tol>0, now/tol < 2**61 (fits_cur_wire)
        )
        return packed, out, now_ns

    def complete(packed, out, now_ns):
        """Fetch the 8 B/request device words and finish the exact i32
        wire values (allowed, remaining, reset_s, retry_s) in C++."""
        cur2 = np.asarray(out)
        return km.finish(packed, cur2, now_ns)

    _populate(dispatch, rng, n_keys, per_launch, pipe, limiter, extra)

    # ---- host-assembly-only throughput (VERDICT r3 #2 deliverable) -------
    probe_ids = zipf_indices(rng, n_keys, per_launch).astype(np.int32)
    km.assemble(probe_ids, BATCH, em_all, tol_all, 1)  # warm caches
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        km.assemble(probe_ids, BATCH, em_all, tol_all, 1)
    host_rate = reps * per_launch / (time.perf_counter() - t0)
    extra["host_assemble_slots_per_s"] = round(host_rate)
    print(
        f"host assembly alone: {host_rate / 1e6:.1f} M slots/s",
        file=sys.stderr,
    )

    return _timed_trials(
        dispatch, complete, rng, n_keys, per_launch, pipe,
        warm_launches, timed_launches, profile_dir, extra,
    )


def run_legacy(
    limiter, keys, em_all, tol_all, rng, n_keys, depth,
    warm_launches, timed_launches, extra,
):
    """Pre-round-4 path: per-sub-batch Python resolve, blocking fetches."""
    bytes_keys = getattr(limiter.keymap, "BYTES_KEYS", False)
    key_src = keys if bytes_keys else [k.decode() for k in keys]
    per_launch = BATCH * depth

    t_pop = time.perf_counter()
    pop_order = rng.permutation(n_keys)
    for start in range(0, n_keys, per_launch):
        chunk = pop_order[start : start + per_launch]
        run_launch(limiter, key_src, chunk, em_all, tol_all, T0, depth)
    extra["populate_s"] = round(time.perf_counter() - t_pop, 2)
    print(
        f"populated {len(limiter)} keys in {extra['populate_s']}s",
        file=sys.stderr,
    )

    n_launches = warm_launches + timed_launches
    draws = zipf_indices(rng, n_keys, n_launches * per_launch)

    launch_times = []
    decided = 0
    t_start = None
    for li in range(n_launches):
        chunk = draws[li * per_launch : (li + 1) * per_launch]
        t0 = time.perf_counter()
        run_launch(
            limiter, key_src, chunk, em_all, tol_all,
            T0 + li * 50_000_000, depth,
        )
        dt = time.perf_counter() - t0
        if li == warm_launches - 1:
            t_start = time.perf_counter()
        elif li >= warm_launches:
            launch_times.append(dt)
            decided += per_launch
    elapsed = time.perf_counter() - t_start

    lat = np.sort(np.asarray(launch_times))
    extra.update(
        {
            "elapsed_s": round(elapsed, 3),
            "decisions": decided,
            "launch_p50_ms": round(
                float(lat[int(0.50 * len(lat))]) * 1e3, 3
            ),
            "launch_p99_ms": round(
                float(lat[min(int(0.99 * len(lat)), len(lat) - 1)]) * 1e3, 3
            ),
        }
    )
    return decided / elapsed


def run_launch(limiter, key_src, idx_chunk, em_all, tol_all, now_ns, depth):
    """One K-deep device launch over `idx_chunk` key ids (host path incl.
    key resolution and segment structure, like the serving engine)."""
    n = len(idx_chunk)
    k = max(n // BATCH, 1)
    n = k * BATCH  # truncate ragged tail
    idx = idx_chunk[:n]

    slots = np.empty(n, np.int32)
    rank = np.empty(n, np.int32)
    is_last = np.empty(n, bool)
    valid = np.ones(BATCH, bool)
    for j in range(k):
        sel = idx[j * BATCH : (j + 1) * BATCH]
        batch_keys = [key_src[i] for i in sel]
        sl, rk, il, n_full = limiter.keymap.resolve(batch_keys, valid)
        assert not n_full
        slots[j * BATCH : (j + 1) * BATCH] = sl
        rank[j * BATCH : (j + 1) * BATCH] = rk
        is_last[j * BATCH : (j + 1) * BATCH] = il

    shape = (k, BATCH)
    out = limiter.table.check_many(
        slots.reshape(shape),
        rank.reshape(shape),
        is_last.reshape(shape),
        em_all[idx].reshape(shape),
        tol_all[idx].reshape(shape),
        np.ones(shape, np.int64),
        np.ones(shape, bool),
        np.full(k, now_ns, np.int64),
        with_degen=False,  # host-certified: qty=1, burst>1, emission>0, tol>0
        compact=True,  # i32 wire outputs, half the fetch bytes
    )
    return np.asarray(out)


if __name__ == "__main__":
    sys.exit(main())
