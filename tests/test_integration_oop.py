"""Out-of-process integration tests: spawn the real server module as a
subprocess and drive it with real clients over all three transports.

The reference's equivalent spawns the server binary with `cargo run` and
asserts allow/deny counts through a real Redis client
(integration-tests/tests/redis_integration_test.rs:8-23, 140-160: burst 3
→ 3 allowed / 2 denied).  One server process serves the whole module.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from throttlecrab_tpu.runtime import parse_health

HTTP_PORT = 28080
GRPC_PORT = 28070
REDIS_PORT = 28060


def spawn_server(*extra_args):
    """Spawn the real server module on the CPU backend (shared by the
    module fixture and the restart tests)."""
    env = dict(os.environ)
    env["THROTTLECRAB_PLATFORM"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [
            sys.executable, "-m", "throttlecrab_tpu.server",
            "--store", "adaptive", "--log-level", "warn", *extra_args,
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )


def wait_health(proc, http_port, deadline_s=120):
    deadline = time.time() + deadline_s
    last_err = None
    while time.time() < deadline:
        if proc.poll() is not None:
            out, _ = proc.communicate()
            pytest.fail(f"server exited early rc={proc.returncode}:\n{out}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/health", timeout=1
            ) as r:
                body = r.read().decode()
            assert body.startswith("OK ")
            # The server names the device it computes on.
            assert parse_health(body)["platform"] == "cpu"
            return
        except Exception as e:  # noqa: BLE001 - retry until deadline
            last_err = e
            time.sleep(0.5)
    proc.terminate()
    pytest.fail(f"server never became healthy: {last_err}")


@pytest.fixture(scope="module")
def server():
    proc = spawn_server(
        "--http", "--http-port", str(HTTP_PORT),
        "--grpc", "--grpc-port", str(GRPC_PORT),
        "--redis", "--redis-port", str(REDIS_PORT),
    )
    wait_health(proc, HTTP_PORT)
    yield proc
    proc.terminate()
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
        pytest.fail("server did not shut down gracefully within 30s")


def resp_frame(*parts: bytes) -> bytes:
    out = b"*%d\r\n" % len(parts)
    for p in parts:
        out += b"$%d\r\n%s\r\n" % (len(p), p)
    return out


def read_resp_reply(sock: socket.socket) -> bytes:
    """One RESP reply (integer-array, simple string, or error)."""
    data = b""
    sock.settimeout(10)
    while True:
        data += sock.recv(4096)
        if data.startswith((b"+", b"-")):
            if data.endswith(b"\r\n"):
                return data
        elif data.startswith(b"*"):
            # 5-integer array: 6 CRLF-terminated lines total.
            if data.count(b"\r\n") >= 6:
                return data
        else:
            raise AssertionError(f"unexpected reply: {data!r}")


def test_redis_burst3_three_allowed_two_denied(server):
    """redis_integration_test.rs:140-160, byte for byte over a real socket."""
    with socket.create_connection(("127.0.0.1", REDIS_PORT), 10) as s:
        allowed = []
        for _ in range(5):
            s.sendall(
                resp_frame(b"THROTTLE", b"oop:redis", b"3", b"10", b"60")
            )
            reply = read_resp_reply(s)
            assert reply.startswith(b"*5\r\n")
            allowed.append(reply.split(b"\r\n")[1] == b":1")
        assert allowed == [True, True, True, False, False]
        # PING still answers on the same connection.
        s.sendall(resp_frame(b"PING"))
        assert read_resp_reply(s) == b"+PONG\r\n"


def test_http_burst3_three_allowed_two_denied(server):
    body = json.dumps(
        {"key": "oop:http", "max_burst": 3, "count_per_period": 10,
         "period": 60}
    ).encode()
    results = []
    for _ in range(5):
        req = urllib.request.Request(
            f"http://127.0.0.1:{HTTP_PORT}/throttle",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            results.append(json.loads(r.read())["allowed"])
    assert results == [True, True, True, False, False]


def test_grpc_burst3_three_allowed_two_denied(server):
    grpc = pytest.importorskip("grpc")
    from throttlecrab_tpu.server.proto import throttlecrab_pb2 as pb

    channel = grpc.insecure_channel(f"127.0.0.1:{GRPC_PORT}")
    throttle = channel.unary_unary(
        "/throttlecrab.RateLimiter/Throttle",
        request_serializer=pb.ThrottleRequest.SerializeToString,
        response_deserializer=pb.ThrottleResponse.FromString,
    )
    results = []
    for _ in range(5):
        reply = throttle(
            pb.ThrottleRequest(
                key="oop:grpc", max_burst=3, count_per_period=10, period=60,
                quantity=1,
            ),
            timeout=10,
        )
        results.append(reply.allowed)
    channel.close()
    assert results == [True, True, True, False, False]


def test_limits_shared_across_transports(server):
    """One key hit over HTTP then RESP shares one bucket
    (multi_transport.rs:159-225, but across a process boundary)."""
    body = json.dumps(
        {"key": "oop:shared", "max_burst": 2, "count_per_period": 10,
         "period": 60}
    ).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{HTTP_PORT}/throttle",
        data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        assert json.loads(r.read())["allowed"] is True
    with socket.create_connection(("127.0.0.1", REDIS_PORT), 10) as s:
        s.sendall(resp_frame(b"THROTTLE", b"oop:shared", b"2", b"10", b"60"))
        assert read_resp_reply(s).split(b"\r\n")[1] == b":1"
        s.sendall(resp_frame(b"THROTTLE", b"oop:shared", b"2", b"10", b"60"))
        assert read_resp_reply(s).split(b"\r\n")[1] == b":0"  # exhausted


def test_metrics_visible_after_traffic(server):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{HTTP_PORT}/metrics", timeout=10
    ) as r:
        text = r.read().decode()
    assert "throttlecrab_requests_total" in text
    assert "throttlecrab_requests_by_transport" in text


def test_snapshot_survives_restart(tmp_path):
    """--snapshot-path: exhaust a burst, SIGTERM the server, restart with
    the same path — the key must still be exhausted (state restored).
    Uses a suffix-less path on purpose: numpy appends .npz on save, and
    the restore side must normalize identically or silently start cold."""
    snap = str(tmp_path / "state")  # note: no .npz suffix
    port = 28085

    def throttle():
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/throttle",
            data=json.dumps(
                {"key": "snap:k", "max_burst": 3,
                 "count_per_period": 10, "period": 3600}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())["allowed"]

    args = ("--http", "--http-port", str(port), "--snapshot-path", snap)
    proc = spawn_server(*args)
    try:
        wait_health(proc, port)
        assert [throttle() for _ in range(4)] == [True, True, True, False]
    finally:
        proc.terminate()
    assert proc.wait(timeout=60) == 0
    assert os.path.exists(snap + ".npz")

    proc = spawn_server(*args)
    try:
        wait_health(proc, port)
        # Still exhausted across the restart.
        assert throttle() is False
    finally:
        proc.terminate()
        proc.wait(timeout=60)
