#!/usr/bin/env python3
"""The served GCRA path, end to end, on one TPU chip.

Drives ``python -m throttlecrab_tpu.server`` (native RESP backend plus
HTTP) through the entry points a user calls, at BASELINE config 3's
size: a 1,048,576-slot table, 1,000,000 distinct keys with per-key
(burst, period), each sent once, then Zipf-1.1 traffic over them.

Phases:
  1. build the native libraries on this host (keymap + wire server);
  2. boot the server as a child process -- the only process that touches
     the chip: this one never starts a JAX backend (it imports the
     package for the oracle with JAX_PLATFORMS=cpu) -- with
     THROTTLECRAB_SUPERVISOR_MODE=fail and the flight recorder in full
     mode; wait for /health, which names the device;
  3. send every key once over pipelined RESP, then Zipf-1.1 requests
     drawn from --seed, then a few POST /throttle over HTTP;
  4. check every reply, /metrics (launches > 0, degrades == 0, errors
     == 0), SIGTERM the server and replay its full trace against the
     scalar oracle (replay/player.py): 0 outcome mismatches;
  5. report; the last line is ``{"ok": true, "device": {...}}`` with the
     device the server reported.

``--chips 4`` runs only the mesh path instead: ``--shards 4`` with the
tenant layer (BASELINE config 5, 64 tenants x 100k keys), the same
checks and replay.  ``--rehearse`` runs the same phases at a small size
on the CPU; its last line is never ``"ok": true``.  Any failure exits
non-zero and prints no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
WORK = ROOT / ".chip_smoke"  # the trace: too large to bring back

#: (table slots, tenants, keys per tenant, Zipf requests); tenants 0 =
#: plain keys.  The chip sizes are BASELINE configs 3 and 5.
SIZES = {
    (1, False): (1 << 20, 0, 1_000_000, 200_000),
    (4, False): (1 << 23, 64, 100_000, 200_000),
    (1, True): (1 << 15, 0, 20_000, 10_000),
    (4, True): (1 << 16, 64, 300, 10_000),
}
ZIPF_A = 1.1
CONNECTIONS = 8
WINDOW = 2048  # requests in flight per connection
BOOT_TIMEOUT_S = 600
IO_TIMEOUT_S = 300
TIME_LIMIT_S = 1100  # the whole run, inside the driver's 1200 s


class SmokeError(RuntimeError):
    pass


#: Report lines: shown on stderr as they come, and on stdout only once
#: the run has passed (a failed run prints no result).
_REPORT: list = []


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def report(name: str, value) -> None:
    _REPORT.append(f"{name}: {value}")
    log(_REPORT[-1])


def _time_limit(signum, frame):
    raise SmokeError(f"time limit of {TIME_LIMIT_S}s reached")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# --------------------------------------------------------------------- #
# Keys, per-key limits and traffic (all from the seed).


def key_of(i: int, per_tenant: int, tenants: int) -> bytes:
    if tenants:
        return b"t%d:k%d" % (i // per_tenant, i % per_tenant)
    return b"smoke:%d" % i


def limits_of(i: int):
    """BASELINE config 3's heterogeneous (burst, count, period)."""
    return 5 + i % 60, 50 + i % 1000, 30 + i % 120


def zipf_indices(rng, n_keys: int, size: int):
    import numpy as np

    p = np.arange(1, n_keys + 1, dtype=np.float64) ** -ZIPF_A
    p /= p.sum()
    return rng.choice(n_keys, size=size, p=p)


# --------------------------------------------------------------------- #
# RESP client: CONNECTIONS sockets, WINDOW frames in flight on each.

_REPLY = rb"\*5\r\n:[01]\r\n:\d+\r\n:\d+\r\n:\d+\r\n:\d+\r\n"


def _frames(indices, per_tenant, tenants) -> bytes:
    out = []
    for i in indices:
        i = int(i)
        key = key_of(i, per_tenant, tenants)
        burst, count, period = limits_of(i)
        args = (b"THROTTLE", key, b"%d" % burst, b"%d" % count,
                b"%d" % period)
        out.append(b"*5\r\n" + b"".join(
            b"$%d\r\n%s\r\n" % (len(a), a) for a in args
        ))
    return b"".join(out)


def _check_replies(buf: bytes, indices) -> tuple:
    """(allowed, denied) of one window's replies; raises if any reply is
    malformed or disagrees with the request's limit."""
    import numpy as np

    n = len(indices)
    if not re.fullmatch(b"(?:" + _REPLY + b"){%d}" % n, buf):
        bad = buf[:200]
        raise SmokeError(f"malformed RESP replies (first bytes {bad!r})")
    vals = np.array(re.findall(rb":(\d+)\r\n", buf), np.int64).reshape(n, 5)
    burst = 5 + np.asarray(indices, np.int64) % 60
    if not (vals[:, 1] == burst).all():
        raise SmokeError("a reply's limit is not its request's max_burst")
    allowed = vals[:, 0] == 1
    if (vals[allowed, 4] != 0).any():
        raise SmokeError("an allowed reply carries a retry_after")
    return int(allowed.sum()), int((~allowed).sum())


def _drive_connection(port, indices, per_tenant, tenants, totals, lock):
    with socket.create_connection(("127.0.0.1", port)) as sock:
        sock.settimeout(IO_TIMEOUT_S)
        for start in range(0, len(indices), WINDOW):
            chunk = indices[start:start + WINDOW]
            sock.sendall(_frames(chunk, per_tenant, tenants))
            need = 6 * len(chunk)  # CRLF-terminated lines per window
            buf = b""
            while buf.count(b"\r\n") < need:
                if buf.startswith(b"-") or b"\r\n-" in buf:
                    raise SmokeError(f"RESP error reply: {buf[-200:]!r}")
                data = sock.recv(1 << 20)
                if not data:
                    raise SmokeError("server closed the RESP connection")
                buf += data
            allowed, denied = _check_replies(buf, chunk)
            with lock:
                totals["allowed"] += allowed
                totals["denied"] += denied


def drive_resp(port, indices, per_tenant, tenants) -> dict:
    """Send `indices` (key ids) over CONNECTIONS pipelined sockets."""
    totals = {"allowed": 0, "denied": 0}
    lock = threading.Lock()
    errors = []
    shares = [indices[c::CONNECTIONS] for c in range(CONNECTIONS)]

    def run(share):
        try:
            _drive_connection(port, share, per_tenant, tenants, totals,
                              lock)
        except Exception as e:  # re-raised below, in this thread
            errors.append(e)

    threads = [
        threading.Thread(target=run, args=(s,), daemon=True) for s in shares
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise SmokeError(f"RESP traffic failed: {errors[0]!r}")
    return totals


def http(port: int, path: str, body=None) -> str:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        method="GET" if body is None else "POST",
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        if r.status != 200:
            raise SmokeError(f"{path}: HTTP {r.status}")
        return r.read().decode()


def scrape(text: str) -> dict:
    """Unlabelled samples of a Prometheus text page."""
    out = {}
    for line in text.splitlines():
        m = re.fullmatch(r"([a-z_]+) (\S+)", line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


# --------------------------------------------------------------------- #


def build_native() -> None:
    """Phase 1: the keymap and wire-server libraries, built on (and for)
    this host."""
    from throttlecrab_tpu import native

    t0 = time.perf_counter()
    if not native.native_available():
        raise SmokeError(f"native keymap: {native.keymap_build_error()}")
    if not native.wire_available():
        raise SmokeError(f"native wire server: {native.wire_build_error()}")
    report("native_build_s", round(time.perf_counter() - t0, 3))
    report("keymap", "native (NativeKeyMap, native/keymap.cpp)")
    report("wire_backend", "native RESP (native/wire_server.cpp)")


def boot(chips: int, rehearse: bool, capacity: int, env0: dict):
    """Phase 2: the server child; returns (proc, ports, device, log)."""
    resp_port, http_port = free_port(), free_port()
    trace_dir = WORK / "trace"
    for old in trace_dir.glob("*.tctr"):
        old.unlink()
    env = dict(env0)
    env.update({
        "PYTHONPATH": str(ROOT) + os.pathsep + env0.get("PYTHONPATH", ""),
        "THROTTLECRAB_STORE_CAPACITY": str(capacity),
        "THROTTLECRAB_KEYMAP": "native",
        "THROTTLECRAB_SUPERVISOR_MODE": "fail",
        "THROTTLECRAB_TRACE_DIR": str(trace_dir),
        "THROTTLECRAB_TRACE_MODE": "full",
    })
    if rehearse:
        env["THROTTLECRAB_PLATFORM"] = "cpu"
        env["XLA_FLAGS"] = (
            env0.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}"
        ).strip()
    cmd = [
        sys.executable, "-m", "throttlecrab_tpu.server",
        "--redis", "--redis-backend", "native",
        "--redis-port", str(resp_port),
        "--http", "--http-port", str(http_port),
        "--log-level", "info",
    ]
    if chips > 1:
        cmd += ["--shards", str(chips)]
    server_log = OUT / f"server-{chips}chip.log"
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        cmd, cwd=ROOT, env=env, stdout=open(server_log, "wb"),
        stderr=subprocess.STDOUT, start_new_session=True,
    )
    deadline = time.monotonic() + BOOT_TIMEOUT_S
    while True:
        if proc.poll() is not None:
            raise SmokeError(
                f"server exited during boot rc={proc.returncode}:\n"
                + server_log.read_text()[-3000:]
            )
        try:
            body = http(http_port, "/health")
            break
        except (OSError, SmokeError):
            if time.monotonic() > deadline:
                raise SmokeError("server never answered /health")
            time.sleep(0.5)
    from throttlecrab_tpu.runtime import parse_health

    device = parse_health(body)
    report("boot_s", round(time.perf_counter() - t0, 3))
    report("health", body)
    if device is None or not body.startswith("OK "):
        raise SmokeError(f"/health does not report an ok device: {body!r}")
    return proc, (resp_port, http_port), device, server_log


def stop(proc, timeout: float = 300) -> int:
    """SIGTERM (drain, trace flush), then SIGKILL past `timeout`."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            raise SmokeError("server did not stop on SIGTERM")
    return proc.returncode


def check_trace(sent: int) -> dict:
    """Phase 4: the server's full trace replayed against the scalar
    oracle; every decision must be in it and agree."""
    from throttlecrab_tpu.replay.player import differential_replay
    from throttlecrab_tpu.replay.trace import Trace

    paths = sorted((WORK / "trace").glob("*.tctr"))
    if len(paths) != 1:
        raise SmokeError(f"expected one trace file, found {paths}")
    t0 = time.perf_counter()
    trace = Trace.load(str(paths[0]))
    summary = differential_replay(trace, target="oracle").summary()
    summary["replay_s"] = round(time.perf_counter() - t0, 3)
    report("trace", json.dumps(summary))
    if summary["rows"] != sent:
        raise SmokeError(
            f"trace holds {summary['rows']} decisions, {sent} were sent"
        )
    if summary["compared"] != sent:
        raise SmokeError(
            f"only {summary['compared']} of {sent} traced decisions are "
            "comparable (status internal/overloaded/deadline in trace)"
        )
    if summary["recorded_mismatches"] or summary["oracle_mismatches"]:
        raise SmokeError(f"oracle mismatches: {summary}")
    paths[0].unlink()
    return summary


def run(args) -> dict:
    import numpy as np

    capacity, tenants, per_tenant, n_zipf = SIZES[(args.chips,
                                                    args.rehearse)]
    n_keys = tenants * per_tenant if tenants else per_tenant
    report("chips", args.chips)
    report("table_slots", capacity)
    report("distinct_keys", n_keys)
    if tenants:
        report("tenants", f"{tenants} x {per_tenant} keys")
    build_native()

    env0 = dict(os.environ)
    env0.pop("JAX_PLATFORMS", None)
    if args.platforms is not None:
        env0["JAX_PLATFORMS"] = args.platforms
    proc, (resp_port, http_port), device, server_log = boot(
        args.chips, args.rehearse, capacity, env0
    )
    try:
        if not args.rehearse and device["platform"] != "tpu":
            raise SmokeError(
                f"no TPU: the server computes on {device}"
            )
        if device["count"] < args.chips:
            raise SmokeError(f"{args.chips} chips wanted, have {device}")
        if args.chips > 1:
            devs = re.search(r"limiter devices: (.*)", server_log.read_text())
            spans = devs.group(1).split(", ") if devs else []
            report("table_devices", spans)
            if len(set(spans)) != args.chips:
                raise SmokeError(f"the table spans {spans}")

        rng = np.random.default_rng(args.seed)
        t0 = time.perf_counter()
        first = drive_resp(resp_port, rng.permutation(n_keys), per_tenant,
                           tenants)
        t_keys = time.perf_counter() - t0
        report("keys_loaded", n_keys)
        report("keys_pass_s", round(t_keys, 3))
        t0 = time.perf_counter()
        zipf = drive_resp(resp_port, zipf_indices(rng, n_keys, n_zipf),
                          per_tenant, tenants)
        report("zipf_requests", n_zipf)
        report("zipf_pass_s", round(time.perf_counter() - t0, 3))
        # Keys the RESP passes touched once or never since: the HTTP
        # path reaches the same buckets, and no denial of it is answered
        # from the engine's deny cache (the flight recorder does not
        # capture those, so the trace would miss them).
        cold = [n_keys - 1 - j for j in range(6)]
        for i in cold:
            burst, count, period = limits_of(i)
            reply = json.loads(http(http_port, "/throttle", {
                "key": key_of(i, per_tenant, tenants).decode(),
                "max_burst": burst, "count_per_period": count,
                "period": period,
            }))
            if reply["limit"] != burst or reply["allowed"] not in (
                True, False
            ):
                raise SmokeError(f"bad HTTP reply {reply}")
        sent = n_keys + n_zipf + len(cold)
        allowed = first["allowed"] + zipf["allowed"]
        report("decisions", sent)
        report("allowed_resp", allowed)
        report("denied_resp", first["denied"] + zipf["denied"])

        m = scrape(http(http_port, "/metrics"))
        report("device_launches", int(m["throttlecrab_tpu_device_launches"]))
        report("compile_s", m["throttlecrab_tpu_compile_seconds"])
        report("compile_cache_hits",
               int(m["throttlecrab_tpu_compile_cache_hits"]))
        failed = int(m["throttlecrab_requests_errors"])
        report("failed", failed)
        degrades = int(m["throttlecrab_tpu_supervisor_degrades"])
        report("supervisor_degrades", degrades)
        if m["throttlecrab_tpu_device_launches"] <= 0:
            raise SmokeError("no device launch was counted")
        if failed or degrades:
            raise SmokeError(f"failed={failed} degrades={degrades}")
        if int(m["throttlecrab_requests_total"]) != sent:
            raise SmokeError(
                f"server counted {m['throttlecrab_requests_total']} "
                f"requests, {sent} were sent"
            )
    finally:
        rc = stop(proc)
    if rc != 0:
        raise SmokeError(f"server exited rc={rc} after SIGTERM")
    summary = check_trace(sent)
    report("mismatches", summary["recorded_mismatches"])
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served single-chip path (default); "
                         "4: only the --shards 4 mesh path with tenants")
    ap.add_argument("--rehearse", action="store_true",
                    help="the same phases at a small size on the CPU")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # The server child gets the environment as it came; this process
    # stays off the chip even if something here touched JAX.
    args.platforms = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    if not (ROOT / "throttlecrab_tpu").is_dir():
        log("error: chip_smoke.py runs from the root of a checkout")
        return 2
    sys.path.insert(0, str(ROOT))
    OUT.mkdir(parents=True, exist_ok=True)
    (WORK / "trace").mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    signal.signal(signal.SIGALRM, _time_limit)
    signal.alarm(TIME_LIMIT_S)
    try:
        device = run(args)
    except SmokeError as e:
        log(f"chip_smoke FAILED: {e}")
        return 1
    finally:
        signal.alarm(0)
    report("total_s", round(time.perf_counter() - t0, 3))
    print("\n".join(_REPORT))
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
