"""One run of one cell: the server under test in this process, the load
clients in child processes, the window, the trace, the check.

This process is the only one that touches JAX.  Its main thread runs the
server's own entry, `run_server(config)`, which installs its signal
handlers there; the harness's own work runs on a second thread, which
ends the server with SIGTERM once the window is over and every reply is
in.  Then, on the main thread again, the plain reference decides every
request the server decided, in the order and at the clock readings of
the server's flight recorder, and each client's replies are compared
with it; every recorded clock reading has to fall while a request of its
key was in flight, by the clients' own clocks.  Where the configuration's
keys belong to tenants, the server's per-tenant allowed and denied
counters have to have grown by the reference's decisions, tenant by
tenant.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np

from . import client, spec, stats, trace_reduce, workload
from .reference import clock, gcra

ROOT = Path(__file__).resolve().parent.parent
BOOT_TIMEOUT_S = 900
TIME_LIMIT_S = 1150   # a first run compiles; the driver allows 1200 s
RING_WINDOWS = 100_000_000  # the recorder keeps every window of a run
CLOCK_SLACK_NS = 100_000  # on top of the clocks' drift over the run


class RunError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(port: int, path: str) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as r:
        return r.read().decode()


def scrape(port: int) -> dict:
    """Unlabelled samples of GET /metrics."""
    out = {}
    for line in http_get(port, "/metrics").splitlines():
        name, _, value = line.partition(" ")
        if name and not name.startswith("#") and "{" not in name:
            try:
                out[name] = float(value)
            except ValueError:
                pass
    return out


TENANT_COUNTERS = ("throttlecrab_tpu_tenant_allowed",
                   "throttlecrab_tpu_tenant_denied")


def scrape_tenants(port: int) -> dict:
    """{tenant: [allowed, denied]}: the labelled per-tenant samples of
    GET /metrics (`name{tenant="t07"} 123`); a tenant the server has not
    counted yet is absent."""
    out = {}
    for line in http_get(port, "/metrics").splitlines():
        name, brace, rest = line.partition('{tenant="')
        if brace and name in TENANT_COUNTERS:
            label, _, value = rest.rpartition('"} ')
            out.setdefault(label, [0, 0])[TENANT_COUNTERS.index(name)] = \
                int(float(value))
    return out


def tenant_totals(config: dict, keys, allowed) -> np.ndarray:
    """[T, 2] (allowed, denied) decisions per tenant of the requests of
    key indices `keys` (-1, a key of another form, left out)."""
    t = workload.tenant_of(config, keys)
    allowed = np.asarray(allowed) != 0
    known = np.asarray(keys) >= 0
    T = workload.n_tenants(config)
    return np.stack([np.bincount(t[known & allowed], minlength=T),
                     np.bincount(t[known & ~allowed], minlength=T)], axis=1)


def tenant_count_gap(config: dict, before: dict, after: dict,
                     ref: np.ndarray) -> int:
    """Sum over tenants of |program's counted (allowed, denied) - the
    reference's|: the program's counts are the growth of its per-tenant
    samples from `before` to `after`.  The server's registry names at
    most THROTTLECRAB_TENANT_MAX - 1 tenants and counts the rest in one
    overflow bucket, so the tenants that no sample names are compared,
    summed, with the samples of every other label summed."""
    grown = {k: np.subtract(after.get(k, (0, 0)), before.get(k, (0, 0)))
             for k in set(before) | set(after)}
    gap = 0
    unnamed = np.zeros(2, np.int64)
    for t, name in enumerate(workload.tenant_names(config)):
        if name in grown:
            gap += int(np.abs(grown.pop(name) - ref[t]).sum())
        else:
            unnamed += ref[t]
    rest = sum(grown.values(), np.zeros(2, np.int64))
    return gap + int(np.abs(rest - unnamed).sum())


# --------------------------------------------------------------------- #


class Context:
    """What a metric's reader reads: counters over the window, the
    clients' record, the trace's reduction, the device's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def delta(self, name: str) -> float:
        return self.counters_after[name] - self.counters_before[name]

    def peaks(self) -> dict:
        return spec.peaks(self.device["kind"])


def apply_rehearsal(cell: spec.Cell) -> None:
    """A tiny size for the CPU: the files' `rehearsal` overrides."""
    cell.config = {**cell.config, **cell.config.get("rehearsal", {})}
    cell.traffic = {**cell.traffic, **cell.traffic.get("rehearsal", {})}


def server_argv(config: dict, resp_port: int, http_port: int) -> list:
    return [str(a) for a in config["server_args"]] + [
        "--store-capacity", str(config["table_slots"]),
        "--redis-host", "127.0.0.1", "--redis-port", str(resp_port),
        "--http", "--http-host", "127.0.0.1", "--http-port", str(http_port),
    ]


class Clients:
    """The load-client processes; none imports JAX."""

    def __init__(self, cell, seed, seconds, port, workdir):
        traffic = cell.traffic
        nproc = int(traffic["processes"])
        nconn = int(traffic["connections"])
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = str(ROOT)
        self.procs, self.errs = [], []
        for p in range(nproc):
            job = {
                "config": cell.config, "traffic": traffic,
                "seconds": seconds, "seed": seed, "port": port,
                "connections": list(range(p, nconn, nproc)),
            }
            err = open(Path(workdir) / f"client{p}.err", "w+b")
            proc = subprocess.Popen(
                [sys.executable, "-m", "benchmark.client"], cwd=ROOT,
                env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=err,
            )
            proc.stdin.write(json.dumps(job).encode() + b"\n")
            proc.stdin.flush()
            self.procs.append(proc)
            self.errs.append(err)

    def _fail(self, what: str):
        tails = []
        for e in self.errs:
            e.seek(0)
            tails.append(e.read()[-1500:].decode(errors="replace"))
        raise RunError(f"{what}; client stderr: {tails}")

    def wait_ready(self) -> None:
        for proc in self.procs:
            if proc.stdout.readline().strip() != b"ready":
                self._fail("a client did not get ready")

    def send(self, line: str) -> None:
        for proc in self.procs:
            proc.stdin.write(line.encode() + b"\n")
            proc.stdin.flush()

    def results(self) -> list:
        """One dict of arrays per connection."""
        conns = []
        for proc in self.procs:
            while True:
                head = proc.stdout.readline()
                if not head:
                    break
                meta = json.loads(head)
                arrays = [client.read_array(proc.stdout)
                          for _ in range(meta["arrays"])]
                names = ["keys", "replies", "ok", "arrival", "send", "due"]
                conns.append(dict(zip(names, arrays),
                                  cpu_busy=meta["cpu_busy"]))
            if proc.wait(timeout=120) != 0:
                self._fail(f"a client exited rc={proc.returncode}")
        return conns

    def stop(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        for e in self.errs:
            e.close()


class CompileWatch:
    """Every compile in this process, from JAX's own monitoring: a
    program's compile (or fetch from the persistent cache) opens with a
    scalar event and closes with a duration event of the same name."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.lock = threading.Lock()
        self.open = 0
        self.events = []  # (monotonic ns, program name)
        jax.monitoring.register_scalar_listener(self._opened)
        jax.monitoring.register_event_duration_secs_listener(self._closed)

    def _opened(self, event, value, **kw):
        if event == self.EVENT:
            with self.lock:
                self.open += 1
                self.events.append((time.monotonic_ns(), kw.get("fun_name")))

    def _closed(self, event, secs, **kw):
        if event == self.EVENT:
            with self.lock:
                self.open -= 1
                self.events.append((time.monotonic_ns(), kw.get("fun_name")))

    def last(self) -> tuple:
        """(compiles in flight, ns of the latest event or 0)."""
        with self.lock:
            return self.open, (self.events[-1][0] if self.events else 0)

    def since(self, t_ns: int) -> list:
        """Programs that opened or closed a compile at or after t_ns."""
        with self.lock:
            return sorted({str(f) for t, f in self.events if t >= t_ns})


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _pow2_range(lo: int, hi: int) -> list:
    """The powers of two from `lo` to `hi`, both powers of two."""
    return [1 << i for i in range(lo.bit_length() - 1, hi.bit_length())]


def prewarm_launches(config) -> int:
    """Compile every launch the native driver can make for this
    single-device server configuration -- K_pad sub-batches (powers of
    two up to the scan depth) of W lanes (powers of two from the
    limiter's MIN_PAD up to the batch size), in the two output tiers a
    window with the deny cache takes (`cur`, and the compact planes) --
    by running each once on a scratch limiter built from the same
    configuration.  The single-device programs are jitted once per
    process, so the jit cache they fill serves the server's own launches
    too; the server's table is untouched.  Which (K_pad, W) a
    window makes depends on backlog and deny-cache hits, so traffic alone
    reaches the rare ones only after minutes.  Returns the launches."""
    import jax

    from throttlecrab_tpu.server.store import create_limiter
    from throttlecrab_tpu.tpu.kernel import PACK_WIDTH

    limiter = create_limiter(config)
    now = time.time_ns()
    n = 0
    for depth in _pow2_range(1, _pow2(config.max_scan_depth)):
        for width in _pow2_range(_pow2(limiter.MIN_PAD),
                                 _pow2(config.batch_size)):
            for compact in ("cur", True):
                jax.block_until_ready(limiter.table.check_many_packed(
                    np.zeros((depth, width, PACK_WIDTH), np.int32),
                    np.full(depth, now, np.int64), with_degen=False,
                    compact=compact, params_cur_safe=True, max_tolerance=0,
                ))
                n += 1
    del limiter
    gc.collect()
    return n


# The output tiers a sharded launch may take (ShardedTpuRateLimiter's
# w32 -> cur -> 4-plane ladder, chosen per launch from its lanes and the
# table's high-water marks).  A configuration whose limits certify one
# tier for every launch names it under "sharded_tiers", and only that
# tier is compiled: each tier costs a program per launch shape.
SHARDED_TIERS = ("w32", "cur", True)


def sharded_launch_shapes(config, n_shards: int, min_pad: int) -> list:
    """(K, B) of every launch the native driver can make on a sharded
    table: a window of one batch is one `check_batch` of B lanes per
    shard (K 1), a window of K > 1 batches one `check_many` scan of K
    padded to a power of two.  B is max(min_pad, the next power of two
    of the widest shard's rows): any power of two up to the batch size
    for one batch; for a scan at least the widest shard of a full batch,
    since the driver scans only behind a full first batch (whose rows
    the benchmark's traffic makes all valid)."""
    top = max(_pow2(config.batch_size), min_pad)
    shapes = [(1, b) for b in _pow2_range(min_pad, top)]
    full = max(_pow2(-(-config.batch_size // n_shards)), min_pad)
    for k in _pow2_range(2, _pow2(config.max_scan_depth)):
        shapes += [(k, b) for b in _pow2_range(full, top)]
    return shapes


def prewarm_sharded(limiter, config, tiers=SHARDED_TIERS) -> int:
    """Compile every launch of a sharded limiter (`sharded_launch_shapes`,
    in each of `tiers`) by running each once on the SERVER'S
    OWN table, with every lane idle: the sharded table builds its jitted
    steps per instance, so a scratch limiter's compiles would not reach
    the server's.  An idle lane is decided by nothing and written
    nowhere, and the table-level launch folds no counter, so the state,
    the global and the per-tenant counters stay as they were.  Returns
    the launches."""
    import jax

    table = limiter.table
    n = 0
    for k, b in sharded_launch_shapes(config, table.n_shards,
                                      limiter.MIN_PAD):
        lanes = (table.n_shards, b) if k == 1 else (table.n_shards, k, b)
        zeros = np.zeros(lanes, np.int64)
        idle = np.zeros(lanes, bool)
        launch, now = ((table.check_batch, 0) if k == 1 else
                       (table.check_many, np.zeros(k, np.int64)))
        for tier in tiers:
            out = launch(zeros, zeros, ~idle, zeros, zeros, zeros, idle,
                         now, with_degen=False, compact=tier,
                         params_cur_safe=True)
            jax.block_until_ready([x for x in out if x is not None])
            n += 1
    return n


# --------------------------------------------------------------------- #


class Run:
    def __init__(self, args, t_start: float):
        self.args = args
        self.t_start = t_start
        self.cell = spec.Cell(ROOT, args.workload)
        if args.rehearse:
            apply_rehearsal(self.cell)
        for item in args.set or ():
            name, _, value = item.partition("=")
            if name not in self.cell.traffic:
                raise spec.SpecError(f"--set {item}: no such traffic key")
            self.cell.traffic[name] = float(value)
        self.config = self.cell.config
        self.traffic = self.cell.traffic
        self.workdir = Path(tempfile.mkdtemp(prefix="tcbench-"))
        self.error = None
        self.stopping = threading.Event()
        self.tenants_before = None

    # -- set-up on the main thread ------------------------------------- #

    def prepare(self):
        args = self.args
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        # No eviction in the checkout's own cache: its bookkeeping (an
        # "-atime" file per entry) fails on entries written without it.
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["THROTTLECRAB_PLATFORM"] = "cpu"
            os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
            flags = os.environ.get("XLA_FLAGS", "")
            if self.cell.chips > 1 and \
                    "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count="
                    f"{self.cell.chips}"
                ).strip()
        for k, v in self.config.get("server_env", {}).items():
            os.environ[k] = str(v)
        import jax

        devices = jax.devices()
        platform = devices[0].platform
        if not args.rehearse and platform != "tpu":
            raise RunError(f"no TPU: JAX finds {platform} devices")
        if len(devices) < self.cell.chips:
            raise RunError(
                f"{self.cell.chips} chips wanted, JAX finds {len(devices)}"
            )
        from throttlecrab_tpu import native
        from throttlecrab_tpu.runtime import enable_compile_cache
        from throttlecrab_tpu.server.config import Config
        from throttlecrab_tpu.tpu.snapshot import write_snapshot_payload

        enable_compile_cache()
        self.compiles = CompileWatch()
        if not native.native_available():
            raise RunError(f"native keymap: {native.keymap_build_error()}")
        if not native.wire_available():
            raise RunError(f"native wire: {native.wire_build_error()}")
        self.resp_port, self.http_port = free_port(), free_port()
        argv = server_argv(self.config, self.resp_port, self.http_port)
        t = time.monotonic()
        boot = Config.from_env_and_args(argv)
        # A sharded table is prewarmed as the server builds it (_building).
        self.prewarmed = 0 if boot.shards > 1 else prewarm_launches(boot)
        self.prewarm_s = time.monotonic() - t
        # The keys reach the table as users restore theirs: a snapshot
        # read at boot.
        self.state = workload.initial_state(self.config, time.time_ns())
        n = workload.n_keys(self.config)
        snap = self.workdir / "state.npz"
        write_snapshot_payload({
            "keys": workload.key_bytes(self.config, range(n)),
            "slots": np.arange(n, dtype=np.int64),
            "shard": np.zeros(n, np.int32),
            "tat": self.state["tat"], "expiry": self.state["expiry"],
            "capacity": int(self.config["table_slots"]), "n_shards": 1,
            "source_bytes_keys": True,
        }, snap)
        os.environ["THROTTLECRAB_SNAPSHOT_PATH"] = str(snap)
        if not args.no_recorder:
            os.environ.update({
                "THROTTLECRAB_TRACE_DIR": str(self.workdir / "recorder"),
                "THROTTLECRAB_TRACE_MODE": "ring",
                "THROTTLECRAB_TRACE_WINDOWS": str(RING_WINDOWS),
                "THROTTLECRAB_TRACE_DUMP_ON_DEGRADE": "false",
            })
        self.server_config = Config.from_env_and_args(argv)
        logging.basicConfig(
            level=logging.WARNING,
            format="%(asctime)s %(levelname)s %(name)s: %(message)s",
        )
        # Frames are built while the server boots.
        self.clients = Clients(self.cell, args.seed, args.seconds,
                               self.resp_port, self.workdir)

    def serve(self):
        """The server, on this (the main) thread, until SIGINT."""
        import asyncio

        import throttlecrab_tpu.server.__main__ as server_main

        create = server_main.create_limiter
        server_main.create_limiter = self._building(create)
        worker = threading.Thread(target=self._drive, daemon=True)
        worker.start()
        try:
            asyncio.run(server_main.run_server(self.server_config))
        except KeyboardInterrupt:  # SIGINT before the server's handler
            pass
        finally:
            server_main.create_limiter = create
            self.stopping.set()
            if worker.is_alive():
                self.clients.stop()  # unblocks a worker waiting on them
            worker.join(timeout=120)
            self.clients.stop()

    def _building(self, create):
        """The server's own limiter factory, wrapped: the harness notes the
        devices the table spans and compiles a sharded table's launches
        on it (prewarm_sharded), before the server restores its keys."""

        def build(config):
            limiter = create(config)
            self.devices = list(limiter.table.state.devices())
            if config.shards > 1:
                t = time.monotonic()
                self.prewarmed = prewarm_sharded(
                    limiter, config,
                    self.config.get("sharded_tiers", SHARDED_TIERS))
                self.prewarm_s += time.monotonic() - t
            return limiter

        return build

    # -- the harness thread -------------------------------------------- #

    def _drive(self):
        try:
            self._drive_inner()
        except BaseException as e:  # reported on the main thread
            self.error = e
        finally:
            # SIGINT: the server's abrupt stop (every reply is in), or a
            # KeyboardInterrupt where its handler is not installed yet.
            os.kill(os.getpid(), signal.SIGINT)

    def _wait_health(self) -> dict:
        from throttlecrab_tpu.runtime import parse_health

        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while True:
            try:
                body = http_get(self.http_port, "/health")
                device = parse_health(body)
                if body.startswith("OK ") and device:
                    return device
            except OSError:
                pass
            if time.monotonic() > deadline or self.stopping.is_set():
                raise RunError("the server never answered /health")
            time.sleep(0.2)

    def _drive_inner(self):
        from throttlecrab_tpu.replay.recorder import active_recorder

        args, traffic = self.args, self.traffic
        device = self._wait_health()
        if not args.rehearse and device["platform"] != "tpu":
            raise RunError(f"the server computes on {device}")
        if device["count"] < self.cell.chips:
            raise RunError(f"{self.cell.chips} chips wanted: {device}")
        self.device = device
        # This server, having restored the snapshot, serves now: the
        # recorder holds every window from here on.
        self.recorder = active_recorder()
        if self.recorder is None and not args.no_recorder:
            raise RunError("the flight recorder is not armed")
        self.server_config.snapshot_path = ""  # no write-back at exit
        # The per-tenant counters before any request: their growth is
        # what the run decided.
        self.tenants_before = (scrape_tenants(self.http_port)
                               if workload.n_tenants(self.config) else None)
        self.offsets = [clock.offset_ns()]
        self.clients.wait_ready()
        start = time.monotonic_ns() + 50_000_000
        cap = start + int((float(traffic["warmup_max_s"]) + args.seconds)
                          * 1e9)
        self.clients.send(f"go {start} {cap}")
        t0 = self._warm_up(start)
        t1 = t0 + int(args.seconds * 1e9)
        self.clients.send(f"window {t0} {t1}")
        _sleep_until(t0)
        m0 = scrape(self.http_port)
        trace_dir = self.workdir / "profile"
        if args.trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            t_trace = time.monotonic()
        _sleep_until(t1)
        if args.trace:
            self.trace_window_s = time.monotonic() - t_trace
            import jax

            jax.profiler.stop_trace()
        m1 = scrape(self.http_port)
        self.conns = self.clients.results()
        if self.tenants_before is not None:  # every reply is in
            self.tenants_after = scrape_tenants(self.http_port)
        self.offsets.append(clock.offset_ns())
        # A compile that opened in the window may close after it.
        self.compiled_in_window = self.compiles.since(t0)
        self.counters = (m0, m1)
        self.setup_s = t0 / 1e9 - self.t_start
        self.window_ns = (t0, t1)
        if args.trace:
            paths = sorted(trace_dir.glob("**/*.xplane.pb"))
            if not paths:
                raise RunError("the profiler wrote no trace")
            self.trace_path = paths[-1]

    def _warm_up(self, start: int) -> int:
        """The cell's own traffic until every shape it makes has compiled
        and the server keeps pace: at least `warmup_s`, then until, over
        the last `warmup_settle_s`, the compile counter has not moved
        and none is in flight (CompileWatch), and the server has decided
        requests -- an open loop's at its offered rate, within a tenth,
        so that no backlog is left -- and is deciding still; at most
        `warmup_max_s`.  Returns t0."""
        traffic = self.traffic
        least = start + int(float(traffic["warmup_s"]) * 1e9)
        most = start + int(float(traffic["warmup_max_s"]) * 1e9)
        settle = int(float(traffic["warmup_settle_s"]) * 1e9)
        rate = float(traffic["rate"]) if traffic["loop"] == "open" else None
        seen = []  # (ns, requests decided)
        while True:
            now = time.monotonic_ns()
            seen.append((now, scrape(self.http_port)[
                "throttlecrab_requests_total"]))
            old = [x for x in seen if x[0] <= now - settle]
            in_flight, last = self.compiles.last()
            if old and now >= least and not in_flight \
                    and last <= now - settle:
                t, r = old[-1]
                decided = (seen[-1][1] - r) / ((now - t) / 1e9)
                deciding_now = seen[-1][1] > seen[-2][1]
                if deciding_now and (
                    rate is None or abs(decided - rate) <= 0.1 * rate
                ):
                    return now + 100_000_000
            if now >= most:
                return now + 100_000_000
            time.sleep(0.25)

    # -- after the server has stopped ---------------------------------- #

    def check(self) -> dict:
        """The reference over every recorded decision, compared with
        every reply the clients received."""
        keys_c = np.concatenate([c["keys"][: len(c["ok"])]
                                 for c in self.conns])
        replies_c = np.concatenate([c["replies"] for c in self.conns])
        ok_c = np.concatenate([c["ok"] for c in self.conns])
        sent = sum(len(c["keys"]) for c in self.conns)
        unanswered = sent - len(ok_c)
        errors = int((~ok_c).sum())
        self.attempted = sent
        self.failed = errors + unanswered
        if self.recorder is None:  # --no-recorder: nothing to check
            self.reference_s, self.decisions = 0.0, 0
            return {"recorder_off": {"value": 1, "limit": 0}}
        rec_keys, rec_now = recorded(self.recorder, self.config)
        n = workload.n_keys(self.config)
        burst, count, period = workload.limits(self.config, np.arange(n))
        qty = np.ones(len(rec_keys), np.int64)
        unknown = int((rec_keys < 0).sum())
        rk = np.where(rec_keys < 0, 0, rec_keys)
        t = time.monotonic()
        ref = gcra.decide(rk, rec_now, qty, burst, count, period,
                          self.state["tat"], self.state["expiry"])
        ref_s = time.monotonic() - t
        if self.args.control:
            # The control in the program's place: the reference with its
            # clock read in whole milliseconds answers every request the
            # server decided, and those answers are judged as the
            # server's would be.
            keys_c, replies_c = rk, gcra.decide(
                rk, rec_now, qty, burst, count, period,
                self.state["tat"], self.state["expiry"],
                clock_ns=1_000_000,
            )
            ok_c = np.ones(len(rk), bool)
        wrong, unmatched = gcra.compare(keys_c[ok_c], replies_c[ok_c],
                                        rk, ref)
        tenants = None
        if self.tenants_before is not None:
            # The per-tenant counters against the reference's decisions,
            # tenant by tenant: those the server counted, or the
            # control's own where it stands in the program's place.
            before, after = self.tenants_before, self.tenants_after
            if self.args.control:
                names = workload.tenant_names(self.config)
                totals = tenant_totals(self.config, rec_keys,
                                       replies_c[:, 0])
                before, after = {}, dict(zip(names, totals.tolist()))
            tenants = tenant_count_gap(
                self.config, before, after,
                tenant_totals(self.config, rec_keys, ref[:, 0]),
            )
        checks = {
            "wrong_replies": {"value": wrong, "limit": 0},
            "unmatched_decisions": {"value": unmatched + unknown,
                                    "limit": 0},
            "failed_requests": {"value": errors + unanswered, "limit": 0},
            "clock_outside": {"value": self.clock_outside(rec_keys,
                                                          rec_now),
                              "limit": 0},
        }
        if tenants is not None:
            checks["tenant_counts"] = {"value": tenants, "limit": 0}
        self.reference_s = ref_s
        self.decisions = len(rk)
        return checks

    def clock_outside(self, rec_keys, rec_now) -> int:
        """Recorded decisions whose clock reading no request of their key
        brackets (reference/clock.py), the server's realtime clock moved
        onto the clients' monotonic one."""
        (off0, err0), (off1, err1) = self.offsets
        slack = CLOCK_SLACK_NS + abs(off1 - off0) + err0 + err1
        req_keys, send, arrival = [], [], []
        for c in self.conns:
            n = len(c["send"])
            arr = np.full(n, np.iinfo(np.int64).max // 4, np.int64)
            arr[: len(c["arrival"])] = c["arrival"][:n]
            req_keys.append(c["keys"][:n])
            send.append(c["send"])
            arrival.append(arr)
        known = rec_keys >= 0
        return clock.outside(
            np.concatenate(req_keys), np.concatenate(send),
            np.concatenate(arrival), rec_keys[known],
            rec_now[known] - off0, slack,
        )

    def context(self) -> Context:
        m0, m1 = self.counters
        t0, t1 = self.window_ns
        in_window = 0
        lat = None
        lateness = None
        due_in_window = None
        for c in self.conns:
            ok = c["ok"]
            arr = c["arrival"]
            in_window += int(((arr >= t0) & (arr <= t1) & ok).sum())
        if self.traffic["loop"] == "open":
            due = np.concatenate([c["due"] for c in self.conns])
            send = np.concatenate([
                _pad(c["send"], len(c["due"])) for c in self.conns
            ])
            was_sent = np.concatenate([
                np.arange(len(c["due"])) < len(c["send"])
                for c in self.conns
            ])
            arrival = np.concatenate([
                _pad(c["arrival"], len(c["due"])) for c in self.conns
            ])
            answered = np.concatenate([
                _pad(c["ok"], len(c["due"])) for c in self.conns
            ])
            sel = (due >= t0) & (due < t1)
            lat = stats.latencies_ns(due[sel], arrival[sel], answered[sel])
            lateness = (send - due)[sel & was_sent]
            due_in_window = due[sel]
        reduced = None
        if self.args.trace:
            reduced = trace_reduce.reduce(
                trace_reduce.load(self.trace_path)
            )
        return Context(
            counters_before=m0, counters_after=m1,
            window_s=(t1 - t0) / 1e9, replies_in_window=in_window,
            latencies_ns=lat, lateness_ns=lateness, trace=reduced,
            due_in_window=due_in_window,
            trace_window_s=getattr(self, "trace_window_s", None),
            setup_s=self.setup_s, device=self.device,
            config=self.config, traffic=self.traffic, cell=self.cell,
        )


def _pad(a, n):
    out = np.zeros(n, a.dtype)
    out[: len(a)] = a[:n]
    return out


def _sleep_until(t_ns: int) -> None:
    while True:
        wait = t_ns - time.monotonic_ns()
        if wait <= 0:
            return
        time.sleep(min(wait / 1e9, 0.5))


def recorded(recorder, config) -> tuple:
    """(key index, clock reading) of every decision the server recorded,
    in its order, from the ring the recorder keeps in memory: tuples
    (seq, now_ns, source, keys, params, allowed, status)."""
    keys, now = [], []
    for w in recorder._ring:
        keys.extend(w[3])
        now.append(np.full(len(w[3]), w[1], np.int64))
    idx = workload.parse_keys(config, keys)
    return idx, (np.concatenate(now) if now else np.zeros(0, np.int64))


def memory_peak(devices) -> int:
    """The peak bytes in use of the fullest of `devices`."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def main(argv=None, t_start: float = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="a tiny size on the CPU; never a result")
    ap.add_argument("--control", action="store_true",
                    help="put the control in the program's place: the "
                         "reference on a millisecond clock; never correct")
    ap.add_argument("--set", action="append", metavar="KEY=NUMBER",
                    help="builder sweeps: override a number of the "
                         "cell's traffic file (rate, depth)")
    ap.add_argument("--no-recorder", action="store_true",
                    help="serve without the flight recorder, to measure "
                         "its cost; such a run is never correct")
    ap.add_argument("--save-trace", default=None,
                    help="copy the traced window's .xplane.pb here")
    args = ap.parse_args(argv)
    signal.signal(signal.SIGALRM, _time_limit)
    signal.alarm(TIME_LIMIT_S)
    run = None
    try:
        run = Run(args, t_start)
        run.prepare()
        run.serve()
        if run.error is not None:
            raise run.error
        peak = memory_peak(run.devices)
        gc.collect()
        checks = run.check()
        ctx = run.context()
        result = report(run, ctx, checks, peak)
    except (RunError, spec.SpecError, ImportError, OSError, ValueError) as e:
        log(f"benchmark run FAILED: {type(e).__name__}: {e}")
        return 1
    finally:
        signal.alarm(0)
        if run is not None:
            shutil.rmtree(run.workdir, ignore_errors=True)
    print(json.dumps(result))
    for name, v in result["checks"].items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    return 0


def _time_limit(signum, frame):
    raise RunError(f"time limit of {TIME_LIMIT_S} s reached")


def report(run, ctx, checks, peak) -> dict:
    args = run.args
    # Compiling inside the window makes the run no measurement.
    m0, m1 = run.counters
    if run.compiled_in_window:
        raise RunError(f"compiles inside the window: {run.compiled_in_window}")
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in run.cell.metrics(kind):
        value = run.cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    device = {
        "platform": run.device["platform"], "kind": run.device["kind"],
        "count": run.device["count"], "memory_peak_bytes": peak,
    }
    info = {
        "setup_s": ctx.setup_s,
        "decisions_recorded": run.decisions,
        "reference_s": run.reference_s,
        "compile_s": m1["throttlecrab_tpu_compile_seconds"],
        "prewarmed_launches": run.prewarmed,
        "prewarm_s": run.prewarm_s,
        "compile_cache_hits": m1["throttlecrab_tpu_compile_cache_hits"],
        "replies_in_window": ctx.replies_in_window,
        "client_cpu_busy_max": max(c["cpu_busy"] for c in run.conns),
    }
    if ctx.latencies_ns is not None:
        late = ctx.lateness_ns
        info["samples"] = len(ctx.latencies_ns)
        # Not a bounded metric: a ~120 ms stall of the whole host in
        # about half of the windows moves it by 2-8x (PERF.md).
        info["p99_ms"] = (stats.percentile(ctx.latencies_ns, 99) / 1e6
                          if len(ctx.latencies_ns) else None)
        info["lateness_p99_ms"] = (
            stats.percentile(late, 99) / 1e6 if len(late) else None
        )
        info["lateness_max_ms"] = (
            float(late.max()) / 1e6 if len(late) else None
        )
        # A growing backlog: the last quarter of the window waits longer
        # than the first.
        q = len(ctx.latencies_ns) // 4
        if q:
            order = np.argsort(ctx.due_in_window, kind="stable")
            lat = ctx.latencies_ns[order]
            info["p50_first_quarter_ms"] = stats.percentile(lat[:q], 50) / 1e6
            info["p50_last_quarter_ms"] = stats.percentile(lat[-q:], 50) / 1e6
    if args.set:
        info["set"] = args.set
    line = "run: " + json.dumps(info)
    print(line)
    log(line)
    result = {}
    if args.rehearse:
        result["rehearsal"] = True
        result["rehearsal_correct"] = correct
    else:
        result["correct"] = correct
    result.update({
        "attempted": run.attempted, "failed": run.failed,
        "metrics": metrics, "device": device,
    })
    if args.trace and ctx.trace is not None:
        if ctx.trace["busy_s"] is not None:
            device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace_window_s
        result["breakdown"] = {
            "device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"],
        }
        if args.save_trace:
            shutil.copy(run.trace_path, args.save_trace)
    result["checks"] = checks
    return result
