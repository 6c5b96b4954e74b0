"""Cross-process cluster sharding: limits must hold across process
boundaries (SURVEY §2.4's DCN obligation; the reference's answer was
client-side sharding, README.md:247-249 — here the server does it).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from throttlecrab_tpu.parallel.cluster import (
    ClusterLimiter,
    decode_batch,
    decode_reply,
    encode_batch,
    encode_reply,
    node_of_key,
)
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

NS = 1_000_000_000
T0 = 1_700_000_000 * NS


# ------------------------------------------------------------- protocol #


def test_frame_roundtrip():
    keys = [b"alpha", b"b" * 300, b"", "ünïcode".encode()]
    params = [(10, 100, 60, 1), (5, 50, 30, 2), (1, 1, 1, 0),
              (2 ** 40, 2 ** 41, 2 ** 42, 2 ** 43)]
    frame = encode_batch(keys, params, T0)
    # strip header
    body = frame[5:]
    dkeys, dparams, dnow = decode_batch(body)
    assert dkeys == keys
    assert dparams.tolist() == [list(p) for p in params]
    assert dnow == T0


def test_reply_roundtrip():
    frame = encode_reply(
        np.array([0, 2, 0], np.uint8),
        np.array([True, False, False]),
        np.array([10, 0, 5], np.int64),
        np.array([9, 0, 0], np.int64),
        np.array([6 * NS, 0, 2 ** 62], np.int64),
        np.array([0, 0, 3 * NS], np.int64),
    )
    rep = decode_reply(frame[5:])
    assert rep["status"].tolist() == [0, 2, 0]
    assert rep["allowed"].tolist() == [1, 0, 0]
    assert rep["reset_ns"][2] == 2 ** 62


def test_malformed_frames_rejected():
    from throttlecrab_tpu.parallel.cluster import (
        ClusterProtocolError,
        _HDR,
        _REP_HEAD,
        _REQ_HEAD,
    )
    import struct

    # Attacker-controlled count must not size an allocation: n=2^32-1 in a
    # tiny frame.
    with pytest.raises(ClusterProtocolError):
        decode_batch(_REQ_HEAD.pack(0xFFFFFFFF, T0))
    with pytest.raises(ClusterProtocolError):
        decode_reply(_REP_HEAD.pack(0xFFFFFFFF))
    # Truncated reply body.
    with pytest.raises(ClusterProtocolError):
        decode_reply(_REP_HEAD.pack(2) + b"\x00" * 10)
    # Item overrunning the frame.
    bad = _REQ_HEAD.pack(1, T0) + struct.pack("<H", 500) + b"k"
    with pytest.raises(ClusterProtocolError):
        decode_batch(bad)
    assert _HDR.size == 5


def test_migrate_replica_frames_hardened():
    """OP_MIGRATE/OP_REPLICA frames carry the same malformed-frame
    contract as OP_THROTTLE_BATCH: attacker-controlled counts cannot
    size allocations, truncation raises the typed error, trailing
    garbage is rejected."""
    import struct

    from throttlecrab_tpu.parallel.cluster import (
        OP_MIGRATE,
        ClusterProtocolError,
        _ROWS_HEAD,
        decode_ring,
        decode_route,
        decode_rows,
        encode_ring,
        encode_rows,
    )

    # Round trip.
    f = encode_rows(OP_MIGRATE, 1, 9, [b"k1", b""], [10, -5], [20, 1 << 61])
    origin, epoch, keys, tats, exps = decode_rows(f[5:])
    assert (origin, epoch, keys) == (1, 9, [b"k1", b""])
    assert tats.tolist() == [10, -5] and exps.tolist() == [20, 1 << 61]
    # Oversized count in a tiny frame.
    with pytest.raises(ClusterProtocolError):
        decode_rows(_ROWS_HEAD.pack(0, 0, 0xFFFFFFFF))
    # Truncated item.
    bad = _ROWS_HEAD.pack(0, 0, 1) + struct.pack("<H", 500) + b"k"
    with pytest.raises(ClusterProtocolError):
        decode_rows(bad)
    # Trailing garbage after a valid frame.
    with pytest.raises(ClusterProtocolError):
        decode_rows(f[5:] + b"\x00")
    # Short/mismatched ring frames.
    with pytest.raises(ClusterProtocolError):
        decode_ring(b"\x01")
    with pytest.raises(ClusterProtocolError):
        decode_ring(encode_ring(5, 3, [1.0, 1.0])[5:] + b"\x00\x00")
    # Route frame: too short for even the hop byte.
    with pytest.raises(ClusterProtocolError):
        decode_route(b"")


def test_ring_vectorized_matches_oracle_and_excludes():
    from throttlecrab_tpu.parallel.ring import HashRing, batch_crc32

    nodes = [f"10.0.0.{i}:9000" for i in range(5)]
    ring = HashRing(nodes, 128)
    keys = [b"rk:%d" % i for i in range(3000)]
    owners = ring.owners_of(batch_crc32(keys))
    # Vectorized lookup is bit-identical to the per-key oracle.
    for i in (0, 1, 7, 100, 999, 2999):
        assert ring.owner_of(keys[i]) == owners[i]
    # Roughly balanced (5 nodes x 128 vnodes).
    counts = np.bincount(owners, minlength=5)
    assert counts.min() > 300, counts
    # Excluding a node moves ONLY its keys, each to its successor.
    o2 = ring.owners_of(batch_crc32(keys), exclude=frozenset({2}))
    moved = owners != o2
    assert (owners[moved] == 2).all() and (o2 != 2).all()
    # successor_of agrees with exclusion routing.
    for i in np.flatnonzero(moved)[:50]:
        assert ring.successor_of(keys[int(i)], 2) == o2[int(i)]
    # Weights scale ownership monotonically; weight 0 owns nothing.
    half = HashRing(nodes, 128, weights={0: 0.5}).owners_of(
        batch_crc32(keys)
    )
    zero = HashRing(nodes, 128, weights={0: 0.0}).owners_of(
        batch_crc32(keys)
    )
    full0 = int((owners == 0).sum())
    assert int((half == 0).sum()) < full0
    assert int((zero == 0).sum()) == 0
    # A membership change moves ~1/N of the space, not ~all of it (the
    # modulo failure mode the ring exists to fix).
    o4 = HashRing(nodes[:4], 128).owners_of(batch_crc32(keys))
    stayed = o4 == owners
    assert stayed.mean() > 0.70, stayed.mean()


def test_oversized_key_fails_only_itself():
    local = TpuRateLimiter(capacity=64)
    cl = ClusterLimiter(local, ["127.0.0.1:1"], 0)
    keys = ["ok1", "x" * 70_000, "ok2"]
    res = cl.rate_limit_batch(keys, 5, 100, 60, 1, T0)
    assert res.allowed.tolist() == [True, False, True]
    assert res.status[1] != 0 and res.status[0] == 0 and res.status[2] == 0


def test_node_routing_stable_and_decorrelated():
    keys = [b"user:%d" % i for i in range(2000)]
    owners = [node_of_key(k, 4) for k in keys]
    # Deterministic.
    assert owners == [node_of_key(k, 4) for k in keys]
    # Roughly balanced.
    counts = np.bincount(owners, minlength=4)
    assert counts.min() > 300
    # Decorrelated from the intra-node device-shard hash: keys owned by
    # node 0 of 2 must still spread over 2 local shards.
    from throttlecrab_tpu.parallel.sharded import shard_of_key

    node0 = [k for k in keys if node_of_key(k, 2) == 0]
    local = np.bincount([shard_of_key(k, 2) for k in node0], minlength=2)
    assert local.min() > len(node0) // 4


# -------------------------------------------------- single-node passthru #


def test_single_node_cluster_is_passthrough():
    plain = TpuRateLimiter(capacity=256)
    local = TpuRateLimiter(capacity=256)
    cl = ClusterLimiter(local, ["127.0.0.1:1"], 0)  # only node: no RPC
    keys = [f"k{i % 20}" for i in range(64)]
    a = plain.rate_limit_batch(keys, 5, 100, 60, 1, T0)
    b = cl.rate_limit_batch(keys, 5, 100, 60, 1, T0)
    assert a.allowed.tolist() == b.allowed.tolist()
    assert a.remaining.tolist() == b.remaining.tolist()
    assert a.reset_after_ns.tolist() == b.reset_after_ns.tolist()
    # wire path too
    w = cl.rate_limit_batch(keys, 5, 100, 60, 1, T0 + NS, wire=True)
    assert w.reset_after_s.dtype == np.int64


# ------------------------------------------------------- two processes #

HTTP_A, HTTP_B = 28180, 28181
RPC_A, RPC_B = 28190, 28191
NODES = f"127.0.0.1:{RPC_A},127.0.0.1:{RPC_B}"


def spawn_node(index: int, http_port: int):
    env = dict(os.environ)
    env["THROTTLECRAB_PLATFORM"] = "cpu"
    # First-touch jit compiles on the CPU backend take 10-40 s; the
    # serving-grade 250 ms forward deadline would expire mid-compile.
    env["THROTTLECRAB_CLUSTER_TIMEOUT_MS"] = "60000"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [
            sys.executable, "-m", "throttlecrab_tpu.server",
            "--http", "--http-port", str(http_port),
            "--cluster-nodes", NODES, "--cluster-index", str(index),
            "--store", "adaptive", "--log-level", "warn",
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )


def wait_healthy(proc, port, deadline_s=120):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if proc.poll() is not None:
            out, _ = proc.communicate()
            pytest.fail(f"node exited early rc={proc.returncode}:\n{out}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=1
            ) as r:
                assert r.read().startswith(b"OK device=")
                return
        except Exception:
            time.sleep(0.5)
    pytest.fail("node never became healthy")


def throttle_via(port, key, burst=3):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/throttle",
        data=json.dumps(
            {"key": key, "max_burst": burst, "count_per_period": 10,
             "period": 60}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=15) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def two_nodes():
    a = spawn_node(0, HTTP_A)
    b = spawn_node(1, HTTP_B)
    try:
        wait_healthy(a, HTTP_A)
        wait_healthy(b, HTTP_B)
        # Warm every decide path (first-touch jit compiles take 10-40 s
        # on this host): local decides on each node AND the cross-node
        # forward in both directions.  Without this, a starved host can
        # push the first forwarded decide past the 60 s deadline and
        # ring failover masks it as a fresh local decision — an
        # over-allow the real assertions below would misattribute.
        warm_a = key_owned_by(0, "warm0")
        warm_b = key_owned_by(1, "warm1")
        for port in (HTTP_A, HTTP_B):
            for k in (warm_a, warm_b):
                throttle_via(port, k, burst=100)
        yield a, b
    finally:
        for p in (a, b):
            if p.poll() is None:
                p.terminate()
        for p in (a, b):
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()


#: Servers spawned with the default config route on the consistent-hash
#: ring (THROTTLECRAB_CLUSTER_VNODES=128), so ownership probes must use
#: the same ring the servers build from the same node list.
def _default_ring(n_nodes: int = 2):
    from throttlecrab_tpu.parallel.ring import HashRing

    return HashRing(NODES.split(",")[:n_nodes], 128)


def key_owned_by(node_idx: int, prefix: str) -> str:
    ring = _default_ring()
    for i in range(10_000):
        k = f"{prefix}:{i}"
        if ring.owner_of(k.encode()) == node_idx:
            return k
    raise AssertionError("no key found")


def test_limits_hold_across_processes(two_nodes):
    """Burst 3 on one key, driven through BOTH nodes' HTTP frontends:
    exactly 3 allowed in total — the owner decides no matter which node
    the client hit."""
    key = key_owned_by(1, "xproc")  # owned by node B
    results = [
        throttle_via(HTTP_A, key)["allowed"],  # A forwards to B
        throttle_via(HTTP_A, key)["allowed"],
        throttle_via(HTTP_B, key)["allowed"],  # B decides locally
        throttle_via(HTTP_A, key)["allowed"],
        throttle_via(HTTP_B, key)["allowed"],
    ]
    assert results == [True, True, True, False, False]


def test_both_directions_route(two_nodes):
    """A key owned by node A driven via node B (reverse forwarding)."""
    key = key_owned_by(0, "revproc")
    results = [throttle_via(HTTP_B, key, burst=2)["allowed"]
               for _ in range(3)]
    assert results == [True, True, False]


def test_remaining_consistent_across_frontends(two_nodes):
    key = key_owned_by(1, "remproc")
    r1 = throttle_via(HTTP_A, key, burst=5)
    r2 = throttle_via(HTTP_B, key, burst=5)
    r3 = throttle_via(HTTP_A, key, burst=5)
    assert (r1["remaining"], r2["remaining"], r3["remaining"]) == (4, 3, 2)


def test_bidirectional_concurrent_traffic_no_deadlock(two_nodes):
    """Both frontends forwarding to each other simultaneously must not
    deadlock: each node's reply production (its ClusterServer) only needs
    the device lock, never the engine lock held across outbound RPCs.
    Regression for the cross-node lock cycle."""
    import concurrent.futures

    key_a = key_owned_by(0, "bidiA")  # A-owned, driven via B
    key_b = key_owned_by(1, "bidiB")  # B-owned, driven via A

    t0 = time.time()
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        futs = []
        for i in range(12):
            futs.append(
                pool.submit(throttle_via, HTTP_A, f"{key_b}:{i}", 100)
            )
            futs.append(
                pool.submit(throttle_via, HTTP_B, f"{key_a}:{i}", 100)
            )
        results = [f.result(timeout=60) for f in futs]
    elapsed = time.time() - t0
    assert all(r["allowed"] for r in results)
    # Well under the 30s RPC timeout a deadlock would burn per round.
    assert elapsed < 20, f"bidirectional traffic took {elapsed:.1f}s"


def test_peer_failure_successor_takes_over(two_nodes):
    """Killing node B no longer costs its key range: the ring routes
    B-owned keys to their successor (A, in a 2-node ring), which
    absorbs the warm replica and keeps deciding — zero client-visible
    failures, the elastic upgrade over the legacy modulo tier's
    STATUS_INTERNAL (that behavior is pinned separately in-process with
    vnodes=0)."""
    a, b = two_nodes
    key_b = key_owned_by(1, "failproc")
    key_a = key_owned_by(0, "okproc")
    # SIGKILL: this test pins *unplanned* death (SIGTERM now runs the
    # graceful drain + planned leave, which hands off without a
    # takeover — that path is pinned in test_cluster_chaos.py).
    b.kill()
    b.wait(timeout=30)
    # B-owned key via A: decided by A as B's ring successor (no 500).
    results = [throttle_via(HTTP_A, key_b)["allowed"] for _ in range(5)]
    assert results == [True, True, True, False, False]
    # A-owned key unaffected.
    assert throttle_via(HTTP_A, key_a)["allowed"] is True
    # The takeover is observable on the cluster view.
    with urllib.request.urlopen(
        f"http://127.0.0.1:{HTTP_A}/health/cluster", timeout=10
    ) as r:
        view = json.loads(r.read())
    assert view["mode"] == "ring"
    assert view["takeovers"] >= 1
    assert f"127.0.0.1:{RPC_B}" in view["absorbed"]


def test_legacy_modulo_dead_peer_fails_only_its_range():
    """vnodes=0 (the kill switch) keeps the pre-ring contract: a dead
    peer's keys fail with STATUS_INTERNAL, everything else decides."""
    from throttlecrab_tpu.tpu.limiter import STATUS_INTERNAL

    local = TpuRateLimiter(capacity=256)
    cl = ClusterLimiter(
        local, ["127.0.0.1:1", "127.0.0.1:2"], 1,
        io_timeout_s=0.2, connect_timeout_s=0.2,
    )
    assert cl.ring is None and cl._pump is None
    key_remote = next(
        f"lm:{i}" for i in range(10_000)
        if node_of_key(f"lm:{i}".encode(), 2) == 0
    )
    key_local = next(
        f"ll:{i}" for i in range(10_000)
        if node_of_key(f"ll:{i}".encode(), 2) == 1
    )
    res = cl.rate_limit_batch([key_remote, key_local], 5, 100, 60, 1, T0)
    assert res.allowed.tolist() == [False, True]
    assert res.status[0] == STATUS_INTERNAL and res.status[1] == 0


def test_unencodable_key_fails_only_itself():
    """A lone surrogate outside U+DC80-DCFF (JSON can deliver one) cannot
    cross the wire; it must fail individually, not 500 its batchmates."""
    local = TpuRateLimiter(capacity=64)
    cl = ClusterLimiter(local, ["127.0.0.1:1"], 0)
    keys = ["good1", "\ud800bad", "good2"]
    res = cl.rate_limit_batch(keys, 5, 100, 60, 1, T0)
    assert res.allowed.tolist() == [True, False, True]
    assert res.status[1] != 0 and res.status[0] == 0 and res.status[2] == 0


# ------------------------------------------------ failure containment #


def _silent_listener():
    """A TCP listener that accepts and then never replies (a hung peer —
    worse than a dead one, because connect succeeds)."""
    import socket as _socket
    import threading as _threading

    srv = _socket.socket()
    srv.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    conns = []
    stop = _threading.Event()

    def loop():
        srv.settimeout(0.2)
        while not stop.is_set():
            try:
                c, _ = srv.accept()
                conns.append(c)
            except OSError:
                continue

    t = _threading.Thread(target=loop, daemon=True)
    t.start()

    def close():
        stop.set()
        t.join(timeout=2)
        for c in conns:
            c.close()
        srv.close()

    return srv.getsockname()[1], close


def test_silent_peer_fails_within_deadline_local_keys_unaffected():
    """An accepted-but-silent peer must cost at most the configured
    forward deadline, fail ONLY its own keys, and leave local keys
    deciding at full speed (round-3 weakness #6: the old 30 s IO timeout
    stalled every batch)."""
    port, close = _silent_listener()
    try:
        local = TpuRateLimiter(capacity=256)
        cl = ClusterLimiter(
            local, [f"127.0.0.1:{port}", "127.0.0.1:1"], 1,
            io_timeout_s=0.3, breaker_failures=99,  # breaker off: pure deadline
        )
        key_remote = next(
            f"sp:{i}" for i in range(10_000)
            if node_of_key(f"sp:{i}".encode(), 2) == 0
        )
        key_local = next(
            f"sl:{i}" for i in range(10_000)
            if node_of_key(f"sl:{i}".encode(), 2) == 1
        )
        # Warm the local compile outside the timed window.
        cl.rate_limit_batch([key_local], 5, 100, 60, 1, T0)

        t0 = time.monotonic()
        res = cl.rate_limit_batch(
            [key_remote, key_local], 5, 100, 60, 1, T0 + NS
        )
        elapsed = time.monotonic() - t0
        assert elapsed < 2.0, f"hung peer stalled the batch {elapsed:.1f}s"
        assert res.allowed.tolist() == [False, True]
        assert res.status[0] != 0 and res.status[1] == 0
    finally:
        close()


def test_circuit_breaker_opens_and_recovers():
    """After N consecutive failures the breaker opens (fail-fast, no
    network touch); after the cooldown one probe goes through again."""
    from throttlecrab_tpu.parallel.cluster import PeerConnection, PeerUnavailable

    fake_now = [0.0]
    peer = PeerConnection(
        "127.0.0.1", 1, io_timeout_s=0.1, connect_timeout_s=0.1,
        breaker_failures=3, breaker_cooldown_s=5.0,
        clock=lambda: fake_now[0],
    )
    # Three real failures arm the breaker (connection refused each time).
    for i in range(3):
        fake_now[0] += 10.0  # clear any backoff between attempts
        with pytest.raises(OSError):
            peer.send_frame(b"x")
        peer.record_failure()
    # Inside the cooldown: fail-fast without touching the network.
    with pytest.raises(PeerUnavailable):
        peer.send_frame(b"x")
    # After the cooldown a probe attempt is allowed through again (and
    # hits the real refused connection, not the gate).
    fake_now[0] += 5.1
    with pytest.raises(OSError) as exc:
        peer.send_frame(b"x")
    assert not isinstance(exc.value, PeerUnavailable)


def test_reconnect_backoff_gates_attempts():
    from throttlecrab_tpu.parallel.cluster import PeerConnection, PeerUnavailable

    fake_now = [100.0]
    peer = PeerConnection(
        "127.0.0.1", 1, connect_timeout_s=0.1,
        breaker_failures=99, clock=lambda: fake_now[0],
    )
    with pytest.raises(OSError):
        peer.send_frame(b"x")
    peer.record_failure()
    # Immediately after the failure: gated, no network touch.
    with pytest.raises(PeerUnavailable):
        peer.send_frame(b"x")
    # Past the first backoff window (50 ms): real attempt again.
    fake_now[0] += 0.06
    with pytest.raises(OSError) as exc:
        peer.send_frame(b"x")
    assert not isinstance(exc.value, PeerUnavailable)


def test_cluster_batch_failfast_when_breaker_open():
    """A whole batch with a breaker-open peer resolves instantly: remote
    keys STATUS_INTERNAL, local keys decided."""
    local = TpuRateLimiter(capacity=256)
    cl = ClusterLimiter(
        local, ["127.0.0.1:1", "127.0.0.1:2"], 1,
        io_timeout_s=0.1, connect_timeout_s=0.1,
        breaker_failures=1, breaker_cooldown_s=60.0,
    )
    key_remote = next(
        f"bf:{i}" for i in range(10_000)
        if node_of_key(f"bf:{i}".encode(), 2) == 0
    )
    key_local = next(
        f"bl:{i}" for i in range(10_000)
        if node_of_key(f"bl:{i}".encode(), 2) == 1
    )
    cl.rate_limit_batch([key_local], 5, 100, 60, 1, T0)  # warm compile
    cl.rate_limit_batch([key_remote], 5, 100, 60, 1, T0)  # arms breaker
    t0 = time.monotonic()
    res = cl.rate_limit_batch(
        [key_remote, key_local], 5, 100, 60, 1, T0 + NS
    )
    assert time.monotonic() - t0 < 0.5
    assert res.allowed.tolist() == [False, True]
    stats = cl.peer_stats()
    assert stats["127.0.0.1:1"]["failed"] >= 2


def test_cluster_wire_window_delegates_when_local():
    """Single-node clusters (and all-local windows) keep the fully-native
    wire path; a window containing a remote-owned key returns None and
    routes through the forwarding path instead."""
    from throttlecrab_tpu.native import native_available

    if not native_available():
        pytest.skip("no C++ keymap")

    def make_frames(keys):
        blob = b"".join(keys)
        offsets = np.zeros(len(keys) + 1, np.int64)
        np.cumsum([len(k) for k in keys], out=offsets[1:])
        params = np.array([[3, 10, 3600, 1]] * len(keys), np.int64)
        return [(blob, offsets, params)]

    # Single node: always delegates.
    cl1 = ClusterLimiter(
        TpuRateLimiter(capacity=128, keymap="native"), ["127.0.0.1:1"], 0
    )
    handle = cl1.dispatch_wire_window(make_frames([b"w:a", b"w:b"]), T0)
    assert handle is not None
    res = handle.fetch()[0]
    assert res.allowed.tolist() == [True, True]

    # Two nodes: all-local window delegates, remote-containing one won't.
    local_key = next(
        b"wl:%d" % i for i in range(10_000)
        if node_of_key(b"wl:%d" % i, 2) == 0
    )
    remote_key = next(
        b"wr:%d" % i for i in range(10_000)
        if node_of_key(b"wr:%d" % i, 2) == 1
    )
    cl2 = ClusterLimiter(
        TpuRateLimiter(capacity=128, keymap="native"),
        ["127.0.0.1:1", "127.0.0.1:2"], 0,
    )
    assert cl2.dispatch_wire_window(make_frames([local_key]), T0) is not None
    assert (
        cl2.dispatch_wire_window(make_frames([local_key, remote_key]), T0)
        is None
    )


def test_cluster_differential_vs_oracle():
    """Random traffic (incl. wild parameter draws) through an in-process
    ClusterLimiter with a real spawned peer must match the scalar oracle
    value-for-value — the RPC encode/decode path carries exact i64
    params and exact wire results for keys owned by either node."""
    import numpy as np

    from test_tpu_batch import oracle_batch
    from throttlecrab_tpu.core.rate_limiter import RateLimiter
    from throttlecrab_tpu.core.store.periodic import PeriodicStore
    from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

    I32 = (1 << 31) - 1
    b_proc = spawn_node(1, HTTP_B)
    try:
        wait_healthy(b_proc, HTTP_B)
        local = TpuRateLimiter(capacity=1 << 12, keymap="auto")
        cl = ClusterLimiter(local, NODES.split(","), 0, io_timeout_s=60.0)
        for seed in range(3):
            rng = np.random.RandomState(9000 + seed)
            oracle = RateLimiter(PeriodicStore())
            pool = [b"cd%dk%d" % (seed, i) for i in range(8)]
            params = {}
            for k in pool:
                wild = rng.rand() < 0.2
                params[k] = (
                    int(rng.randint(1, 1 << 40)) if wild
                    else int(rng.randint(1, 30)),
                    int(rng.randint(1, 1 << 20)) if wild
                    else int(rng.randint(1, 3000)),
                    int(rng.choice([1, 10, 3600, 1 << 25])) if wild
                    else int(rng.choice([1, 10, 60, 3600])),
                )
            now = 1_753_700_000 * 10**9 + seed * 3600 * 10**9
            for step in range(5):
                n = int(rng.randint(1, 20))
                keys = [pool[rng.randint(len(pool))] for _ in range(n)]
                b = np.array([params[k][0] for k in keys], np.int64)
                c = np.array([params[k][1] for k in keys], np.int64)
                p = np.array([params[k][2] for k in keys], np.int64)
                q = np.array(
                    [int(rng.randint(0, 5)) for _ in keys], np.int64
                )
                qm: dict = {}
                for i, k in enumerate(keys):
                    q[i] = qm.setdefault(k, int(q[i]))
                res = cl.rate_limit_many(
                    [(keys, b, c, p, q, now)], wire=True
                )[0]
                exp = oracle_batch(oracle, keys, b, c, p, q, now)
                ok = exp["status"] == 0
                ctx = f"seed{seed} step{step}"
                np.testing.assert_array_equal(
                    res.status, exp["status"], err_msg=ctx
                )
                np.testing.assert_array_equal(
                    res.allowed[ok], exp["allowed"][ok], err_msg=ctx
                )
                np.testing.assert_array_equal(
                    res.remaining[ok],
                    np.minimum(exp["remaining"], I32)[ok], err_msg=ctx,
                )
                np.testing.assert_array_equal(
                    res.reset_after_s[ok],
                    np.minimum(exp["reset"] // 10**9, I32)[ok],
                    err_msg=ctx,
                )
                now += int(rng.randint(0, 10**9))
        stats = cl.peer_stats()[NODES.split(",")[1]]
        assert stats["forwarded"] > 0 and stats["failed"] == 0
    finally:
        b_proc.terminate()
        try:
            b_proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            b_proc.kill()
            b_proc.wait()
