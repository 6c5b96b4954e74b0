"""Elastic-cluster chaos: join, kill, rejoin, reweight, partition heal.

The contracts under test (ISSUE 8 / ROADMAP item 4):

- **Join exactness** — a node joining under load moves key ranges via
  OP_MIGRATE with a handoff gate: zero lost or double-counted decisions
  across the migration epoch, pinned differentially against the scalar
  single-node oracle.
- **Warm-standby failover** — killing a node costs no client-visible
  failures on replicated ranges: its ring successor absorbs the
  OP_REPLICA rows and continues from the replicated TATs (stale by at
  most the replication lag + 1 s wire truncation; GCRA's clamp-against-
  now makes a low TAT strictly more permissive, never wrong-denying).
- **Rejoin** — the recovered node re-enters via the same OP_JOIN path:
  successors migrate the freshest absorbed state back, overwriting its
  stale table.
- **Reweight** — a degraded node announces a reduced ring weight; the
  lost vnode ranges migrate out before the flip, so decisions stay
  exact.
- **Migration chaos** — injected `migrate` faults lose the handoff;
  the joiner's gate deadline unblocks loudly and serving continues.

All in-process tests drive real TCP sockets between in-process nodes
(one event loop thread per node) with explicit timestamps, so runs are
deterministic up to thread scheduling.  The 3-process acceptance soak
(join -> kill -> rejoin against spawned servers) is `slow` and also run
as an explicit CI step.
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from throttlecrab_tpu.parallel.cluster import ClusterLimiter, ClusterServer
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

NS = 1_000_000_000
T0 = 1_760_000_000 * NS
CAP = 2048


def free_ports(n: int):
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


class Node:
    """One in-process cluster node: device limiter + ring cluster tier +
    RPC listener on its own event-loop thread."""

    def __init__(self, index, nodes, **kw):
        kw.setdefault("vnodes", 64)
        kw.setdefault("replicate", True)
        # The reply timeout must stay ABOVE the handoff gate's worst
        # case or a peer legitimately blocked waiting for an inbound
        # migrate is falsely declared dead and its range re-decided
        # from the warm replica (a double count the exactness tests
        # catch).  Tests that inject a 20x-slowed gate clock stretch
        # the 4 s gate to 80 real seconds, so give the reply wait 3x
        # that; genuinely dead nodes refuse connections instantly, so
        # the long timeout never runs in a healthy teardown.
        kw.setdefault("io_timeout_s", 240.0)
        kw.setdefault("handoff_timeout_s", 4.0)
        self.index = index
        self.limiter = TpuRateLimiter(capacity=CAP)
        # First-touch jit compile outside any cluster deadline.
        self.limiter.rate_limit_batch(["__warm__"], 5, 100, 60, 1, T0 - NS)
        self.cl = ClusterLimiter(self.limiter, nodes, index, **kw)
        port = int(nodes[index].rpartition(":")[2])
        self.srv = ClusterServer(
            "127.0.0.1", port, self.cl.local, self.cl.device_lock,
            cluster=self.cl,
        )
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._run, name=f"node{index}-loop", daemon=True
        )
        self._thread.start()
        asyncio.run_coroutine_threadsafe(
            self.srv.start(), self.loop
        ).result(timeout=10)

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def join_cluster(self):
        self.cl.announce_join_all()

    def kill(self):
        """Hard stop: RPC listener down, pump stopped, sockets dropped.
        Idempotent — test teardowns may race an in-test kill."""
        if getattr(self, "_dead", False):
            return
        self._dead = True
        asyncio.run_coroutine_threadsafe(
            self.srv.stop(), self.loop
        ).result(timeout=10)
        self.cl.close()
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=5)


@pytest.fixture
def two_ring_nodes():
    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes)
    b = Node(1, nodes)
    a.join_cluster()
    b.join_cluster()
    try:
        yield a, b
    finally:
        for n in (a, b):
            try:
                n.kill()
            except Exception:
                pass


def settle_handoffs(*nodes_, deadline_s=300.0):
    """Block (real time) until every node's inbound-handoff gate has
    drained.  `apply_migrate` pops a pending entry whenever the rows
    land — only a decide thread inside `_wait_handoff` can abandon one
    at the gate deadline — so polling here instead of deciding makes a
    join exact no matter how long the joiner's JIT-compiling bulk
    inserts take on a loaded CI box.  A migrate that never lands
    (genuinely lost) still fails loudly at `deadline_s`."""
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        if all(not n.cl._pending_from for n in nodes_):
            return
        time.sleep(0.01)
    pytest.fail(
        "handoff never settled: "
        + repr([dict(n.cl._pending_from) for n in nodes_])
    )


def oracle_check(oracle, node, keys, burst, count, period, now, ctx):
    """One batch through the cluster vs the scalar oracle, exact."""
    from test_tpu_batch import oracle_batch

    n = len(keys)
    b = np.full(n, burst, np.int64)
    c = np.full(n, count, np.int64)
    p = np.full(n, period, np.int64)
    q = np.ones(n, np.int64)
    res = node.cl.rate_limit_batch(keys, b, c, p, q, now)
    exp = oracle_batch(oracle, keys, b, c, p, q, now)
    np.testing.assert_array_equal(res.status, exp["status"], err_msg=ctx)
    np.testing.assert_array_equal(res.allowed, exp["allowed"], err_msg=ctx)
    np.testing.assert_array_equal(
        res.remaining, exp["remaining"], err_msg=ctx
    )
    return res


# ------------------------------------------------------------- join #


def test_join_under_load_zero_lost_or_double_counted():
    """A third node joins mid-stream: every decision before, during and
    after the migration epoch matches the single-node scalar oracle
    value-for-value — nothing lost (a key's state survives the range
    handoff) and nothing double-decided (old owner stops exactly when
    the new owner starts)."""
    from throttlecrab_tpu.core.rate_limiter import RateLimiter
    from throttlecrab_tpu.core.store.periodic import PeriodicStore

    ports = free_ports(3)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    # The handoff gate measures its deadline on the injectable cluster
    # clock: slow it 20x so a loaded CI box can never expire the 4 s
    # gate while the migrate is genuinely in flight (the flake this
    # replaces), while a genuinely lost handoff still unblocks eventually.
    t_base = time.monotonic()
    slow_clock = lambda: t_base + (time.monotonic() - t_base) * 0.05  # noqa: E731
    a = Node(0, nodes, clock=slow_clock)
    b = Node(1, nodes, clock=slow_clock)
    c = None
    try:
        a.join_cluster()
        b.join_cluster()
        settle_handoffs(a, b)
        oracle = RateLimiter(PeriodicStore())
        pool = [f"jn:{i}" for i in range(48)]
        now = T0
        frontends = [a, b]
        for step in range(24):
            if step == 8:
                # Join under load: node 2 boots and announces (same
                # slowed gate clock — it is the joiner whose handoff
                # deadline the flake used to race).  The settle makes
                # the exactness claim load-proof: the gate clears when
                # the migrates LAND, not when a decide polls it, so
                # waiting here cannot mask an abandoned handoff (that
                # would hang the gate and trip the settle deadline).
                c = Node(2, nodes, clock=slow_clock)
                c.join_cluster()
                settle_handoffs(a, b, c)
                frontends = [a, b, c]
            via = frontends[step % len(frontends)]
            oracle_check(
                oracle, via, pool, 4, 10, 60, now, f"step{step}"
            )
            now += NS // 4
        # The joiner actually took over ranges: it received migrated
        # keys and now decides its share locally (peers forward to it).
        assert c.cl.migrated_in > 0
        assert any(
            p is not None and p.forwarded > 0
            for p in (a.cl.peers[2], b.cl.peers[2])
        )
        # And the handoff gate never abandoned a migration.
        assert c.cl.handoff_timeouts == 0
    finally:
        for n in (a, b, c):
            if n is not None:
                try:
                    n.kill()
                except Exception:
                    pass


def test_migrate_fault_abandons_handoff_loudly():
    """Injected `migrate` faults lose the handoff: the joiner's gate
    deadline unblocks (handoff_timeouts counts it) and serving
    continues without client-visible failures."""
    from throttlecrab_tpu.faults import FaultInjector, arm, disarm, parse_spec

    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes, handoff_timeout_s=0.8)
    b = None
    try:
        # Seed state on A for keys B will own, so B's join has ranges
        # to (fail to) migrate.
        keys = [f"mf:{i}" for i in range(64)]
        a.cl.rate_limit_batch(keys, 4, 10, 60, 1, T0)
        arm(FaultInjector(parse_spec("migrate:persistent"), seed=7))
        b = Node(1, nodes, handoff_timeout_s=0.8)
        b.join_cluster()
        res = b.cl.rate_limit_batch(keys, 4, 10, 60, 1, T0 + NS)
        assert (res.status == 0).all()
        assert b.cl.handoff_timeouts >= 1
    finally:
        disarm()
        for n in (a, b):
            if n is not None:
                try:
                    n.kill()
                except Exception:
                    pass


# ------------------------------------------------- kill / failover #


def exhaust_key(node, key, now, burst=2):
    """Drive one key to denial; returns the now used last."""
    for i in range(burst + 2):
        node.cl.rate_limit_batch([key], burst, 2, 600, 1, now + i)
    return now + burst + 2


def test_node_kill_replica_takeover_no_client_failures(two_ring_nodes):
    """Killing a node costs zero client-visible failures on its range:
    the successor absorbs the warm replica and — the warm-standby
    point — an exhausted key STAYS denied after takeover (the replica
    carried its TAT; a fresh table would wrongly re-allow it)."""
    a, b = two_ring_nodes
    ring = a.cl.ring
    b_keys = [
        k for k in (f"kv:{i}" for i in range(4000))
        if ring.owner_of(k.encode()) == 1
    ]
    hot, fresh = b_keys[0], b_keys[1]
    now = T0
    # Decide on the owner so replicas flow B -> A.
    now = exhaust_key(b, hot, now)
    res = b.cl.rate_limit_batch([hot], 2, 2, 600, 1, now)
    assert not res.allowed[0], "precondition: key exhausted on B"
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and hot.encode() not in a.cl.replica_store:
        time.sleep(0.1)
    assert hot.encode() in a.cl.replica_store, "replica never reached A"

    b.kill()
    # Exhausted key: served by A from the replica, still denied.
    res = a.cl.rate_limit_batch([hot, fresh], 2, 2, 600, 1, now + 1)
    assert (res.status == 0).all(), "client-visible failure on failover"
    assert not res.allowed[0], "replica TAT lost: takeover re-allowed"
    assert res.allowed[1], "fresh key on dead range must serve"
    assert a.cl.takeover_count >= 1
    stats = a.cl.peer_stats()[a.cl.nodes[1]]
    assert stats["breaker_open"] in (0, 1)  # breaker state surfaced
    view = a.cl.cluster_view()
    assert view["mode"] == "ring" and view["takeovers"] >= 1


def test_breaker_open_failover_is_fast(two_ring_nodes):
    """Once the breaker opens, a dead peer's keys cost ~nothing: the
    partition routes them straight to the successor without touching
    the network."""
    a, b = two_ring_nodes
    ring = a.cl.ring
    b_key = next(
        k for k in (f"bf:{i}" for i in range(4000))
        if ring.owner_of(k.encode()) == 1
    )
    b.kill()
    # Open the breaker (default 3 consecutive failures).  Attempts
    # inside the reconnect backoff don't count (by design), so space
    # them out until it trips.
    deadline = time.monotonic() + 10
    i = 0
    while (
        not a.cl.peers[1].breaker_open and time.monotonic() < deadline
    ):
        a.cl.rate_limit_batch([b_key], 5, 100, 60, 1, T0 + i)
        i += 1
        time.sleep(0.15)
    assert a.cl.peers[1].breaker_open
    t0 = time.monotonic()
    res = a.cl.rate_limit_batch([b_key], 5, 100, 60, 1, T0 + 10)
    assert res.status[0] == 0
    assert time.monotonic() - t0 < 0.5, "breaker-open path touched the net"


def test_rejoin_migrates_absorbed_state_back():
    """Kill -> serve via the successor -> rejoin: the successor
    migrates the absorbed (freshest) rows back, so the rejoined node
    continues from the state decided during its absence — its stale
    table is overwritten, not trusted."""
    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes)
    b = Node(1, nodes)
    b2 = None
    try:
        a.join_cluster()
        b.join_cluster()
        ring = a.cl.ring
        hot = next(
            k for k in (f"rj:{i}" for i in range(4000))
            if ring.owner_of(k.encode()) == 1
        )
        now = T0
        # B owns the key and has replicated it; then B dies.
        now = exhaust_key(b, hot, now)
        deadline = time.monotonic() + 5
        while (
            time.monotonic() < deadline
            and hot.encode() not in a.cl.replica_store
        ):
            time.sleep(0.1)
        b.kill()
        # A serves the range during the outage (takeover).
        res = a.cl.rate_limit_batch([hot], 2, 2, 600, 1, now)
        assert res.status[0] == 0 and not res.allowed[0]
        # B restarts fresh (empty table) and rejoins.
        b2 = Node(1, nodes)
        b2.join_cluster()
        # The rejoined node decides from the migrated state: still
        # denied, not re-allowed from an empty row.
        res = b2.cl.rate_limit_batch([hot], 2, 2, 600, 1, now + 1)
        assert res.status[0] == 0
        assert not res.allowed[0], "rejoin lost the absorbed state"
        assert b2.cl.migrated_in >= 1
        # A routes to B again (absorbed flag cleared).
        assert 1 not in a.cl._absorbed or not a.cl.peers[1].breaker_open
        res = a.cl.rate_limit_batch([hot], 2, 2, 600, 1, now + 2)
        assert res.status[0] == 0 and not res.allowed[0]
    finally:
        for n in (a, b2):
            if n is not None:
                try:
                    n.kill()
                except Exception:
                    pass


def test_crash_rejoin_restores_checkpoint_then_reconciles(tmp_path):
    """Crash-rejoin with durability: the restarted node restores its
    local checkpoint BEFORE announcing, then the successor's
    migrate-back reconciles per key newest-wins — inbound rows that are
    not newer than the restored local row are counted and dropped, and
    a key only the checkpoint knew (never replicated, never absorbed)
    keeps its spent budget across the crash."""
    from throttlecrab_tpu.persist import Checkpointer, recover_into
    from throttlecrab_tpu.tpu.snapshot import export_state

    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes)
    b = Node(1, nodes)
    b2 = None
    try:
        a.join_cluster()
        b.join_cluster()
        ring = a.cl.ring
        gen = (k for k in (f"cj:{i}" for i in range(8000))
               if ring.owner_of(k.encode()) == 1)
        hot, cold = next(gen), next(gen)
        now = T0
        # hot: exhausted on B and replicated to A (the takeover path).
        now = exhaust_key(b, hot, now)
        deadline = time.monotonic() + 5
        while (
            time.monotonic() < deadline
            and hot.encode() not in a.cl.replica_store
        ):
            time.sleep(0.1)
        # cold: 1 of burst 2 spent on B, then checkpointed.  Replication
        # may or may not have pushed it by the kill — the checkpoint is
        # what guarantees the spend survives.
        res = b.cl.rate_limit_batch([cold], 2, 2, 600, 1, now)
        assert res.status[0] == 0 and res.allowed[0]
        ck = Checkpointer(b.limiter, tmp_path, interval_ns=1 << 62)
        assert ck.checkpoint_now(now, force_base=True) >= 2
        b.kill()
        # A serves hot during the outage from the absorbed replica.
        res = a.cl.rate_limit_batch([hot], 2, 2, 600, 1, now + 1)
        assert res.status[0] == 0 and not res.allowed[0]
        # B restarts on the same disk: restore the chain FIRST (into a
        # swept-empty table), then announce.
        b2 = Node(1, nodes)
        b2.limiter.sweep(1 << 62)  # clear the constructor's warm-up row
        rres = recover_into(b2.cl, tmp_path, now + 2)
        assert rres is not None and rres.restored >= 2
        b2.join_cluster()
        settle_handoffs(a, b2)
        # hot: migrate-back (same-or-newer than the checkpoint) kept it
        # denied — no re-allow from the crash.
        res = b2.cl.rate_limit_batch([hot], 2, 2, 600, 1, now + 3)
        assert res.status[0] == 0 and not res.allowed[0]
        # cold: the checkpointed spend survived — exactly one token
        # left, not a fresh bucket.
        res = b2.cl.rate_limit_batch([cold], 2, 2, 600, 1, now + 3)
        assert res.status[0] == 0 and res.allowed[0]
        res = b2.cl.rate_limit_batch([cold], 2, 2, 600, 1, now + 4)
        assert res.status[0] == 0 and not res.allowed[0]
        # Newest-wins reconcile, directly: replay a STALE inbound row
        # for cold (older TAT than the live local row).  It must be
        # counted + dropped, never clobber the newer local state.
        k_col, _s, _sh, t_col, _e, _c, _d = export_state(b2.cl.local)
        rows = {k: int(t_col[i]) for i, k in enumerate(k_col)}
        cold_local = rows[
            cold if cold in rows else cold.encode()
        ]
        stale_before = b2.cl.reconciled_stale
        b2.cl.apply_migrate(
            0, b2.cl.epoch, [cold.encode()], [cold_local - 1], [now + 600 * NS]
        )
        assert b2.cl.reconciled_stale == stale_before + 1
        assert b2.cl.cluster_view()["reconciled_stale"] >= 1
        res = b2.cl.rate_limit_batch([cold], 2, 2, 600, 1, now + 5)
        assert res.status[0] == 0 and not res.allowed[0], (
            "stale migrate-back clobbered the newer restored row"
        )
    finally:
        for n in (a, b, b2):
            if n is not None:
                try:
                    n.kill()
                except Exception:
                    pass


def test_wire_window_fast_path_feeds_replication():
    """The native transports' dispatch_wire_window fast path decides
    exactly the locally-owned rows warm replication exists to protect;
    its decisions must reach the successor's replica store like every
    other path (regression: the fast path silently skipped the pump)."""
    from throttlecrab_tpu.native import native_available

    if not native_available():
        pytest.skip("no C++ keymap")

    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]

    class NativeNode(Node):
        def __init__(self, index):
            from throttlecrab_tpu.parallel.cluster import (
                ClusterLimiter,
                ClusterServer,
            )

            self.index = index
            self.limiter = TpuRateLimiter(capacity=CAP, keymap="native")
            self.limiter.rate_limit_batch(
                ["__warm__"], 5, 100, 60, 1, T0 - NS
            )
            self.cl = ClusterLimiter(
                self.limiter, nodes, index, vnodes=64, replicate=True,
                io_timeout_s=60.0, handoff_timeout_s=4.0,
            )
            self.srv = ClusterServer(
                "127.0.0.1", int(nodes[index].rpartition(":")[2]),
                self.cl.local, self.cl.device_lock, cluster=self.cl,
            )
            self.loop = asyncio.new_event_loop()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            asyncio.run_coroutine_threadsafe(
                self.srv.start(), self.loop
            ).result(timeout=10)

    a = NativeNode(0)
    b = NativeNode(1)
    try:
        a.join_cluster()
        b.join_cluster()
        # The fast path refuses a window while a join's handoff is
        # still pending.
        settle_handoffs(a, b)
        ring = a.cl.ring
        keys = [
            b"ww:%d" % i for i in range(6000)
            if ring.owner_of(b"ww:%d" % i) == 0
        ][:32]
        blob = b"".join(keys)
        offsets = np.zeros(len(keys) + 1, np.int64)
        np.cumsum([len(k) for k in keys], out=offsets[1:])
        params = np.array([[3, 10, 3600, 1]] * len(keys), np.int64)
        handle = a.cl.dispatch_wire_window([(blob, offsets, params)], T0)
        assert handle is not None, "all-local window must take fast path"
        res = handle.fetch()[0]
        assert res.allowed.all()
        # The decided rows must reach B's replica store via the pump.
        deadline = time.monotonic() + 8
        while (
            time.monotonic() < deadline
            and keys[0] not in b.cl.replica_store
        ):
            time.sleep(0.1)
        assert keys[0] in b.cl.replica_store, (
            "wire fast path bypassed warm replication"
        )
    finally:
        for n in (a, b):
            try:
                n.kill()
            except Exception:
                pass


def test_takeover_traffic_replicates_to_live_successor():
    """Keys decided during a takeover must keep a second copy: their
    ring successor-excluding-self is the DEAD node, so the replica
    pump must route them to the next LIVE node instead of dropping
    them (regression: during an outage the absorbed range was
    single-copy, and a second failure would have lost it)."""
    ports = free_ports(3)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes)
    b = Node(1, nodes)
    c = Node(2, nodes)
    try:
        for n in (a, b, c):
            n.join_cluster()
        settle_handoffs(a, b, c)
        ring = a.cl.ring
        # A key owned by C whose failover target (exclude C) is A.
        hot = next(
            k for k in (f"ts:{i}" for i in range(8000))
            if ring.owner_of(k.encode()) == 2
            and ring.owner_of(k.encode(), exclude=frozenset({2})) == 0
        )
        c.kill()
        # Drive it through A: breaker opens, A takes over and decides.
        for i in range(6):
            res = a.cl.rate_limit_batch([hot], 5, 100, 60, 1, T0 + i)
            assert res.status[0] == 0
        # The replica of the absorbed key must reach the live third
        # node (B), not be dropped toward dead C.
        deadline = time.monotonic() + 8
        while (
            time.monotonic() < deadline
            and hot.encode() not in b.cl.replica_store
        ):
            time.sleep(0.1)
        assert hot.encode() in b.cl.replica_store, (
            "takeover traffic left the absorbed range single-copy"
        )
    finally:
        for n in (a, b, c):
            try:
                n.kill()
            except Exception:
                pass


# --------------------------------------------------------- reweight #


def test_reweight_migrates_ranges_and_stays_exact():
    """announce_weight (the supervisor's degraded-capacity hook target)
    moves vnode ranges out before the flip: decisions across the
    reweight stay oracle-exact and the peer adopts the new weights."""
    from throttlecrab_tpu.core.rate_limiter import RateLimiter
    from throttlecrab_tpu.core.store.periodic import PeriodicStore

    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes)
    b = Node(1, nodes)
    try:
        a.join_cluster()
        b.join_cluster()
        oracle = RateLimiter(PeriodicStore())
        pool = [f"rw:{i}" for i in range(64)]
        now = T0
        for step in range(6):
            oracle_check(oracle, (a, b)[step % 2], pool, 4, 10, 60, now,
                         f"pre{step}")
            now += NS // 4
        owned_before = int(
            (a.cl.ring.owners_of(
                np.asarray([__import__("zlib").crc32(k.encode())
                            for k in pool], np.uint32)
            ) == 0).sum()
        )
        a.cl.announce_weight(0.5)
        # Peer adopts the broadcast weights.
        deadline = time.monotonic() + 5
        while (
            time.monotonic() < deadline
            and b.cl.ring.weights.get(0) != 0.5
        ):
            time.sleep(0.05)
        assert b.cl.ring.weights.get(0) == 0.5
        owned_after = int(
            (a.cl.ring.owners_of(
                np.asarray([__import__("zlib").crc32(k.encode())
                            for k in pool], np.uint32)
            ) == 0).sum()
        )
        assert owned_after < owned_before
        assert a.cl.peers[1].migrated > 0 or owned_before == owned_after
        for step in range(8):
            oracle_check(oracle, (a, b)[step % 2], pool, 4, 10, 60, now,
                         f"post{step}")
            now += NS // 4
        # Restore: ranges migrate back, still exact.
        a.cl.announce_weight(1.0)
        for step in range(6):
            oracle_check(oracle, (a, b)[step % 2], pool, 4, 10, 60, now,
                         f"back{step}")
            now += NS // 4
    finally:
        for n in (a, b):
            try:
                n.kill()
            except Exception:
                pass


def test_supervisor_degrade_calls_capacity_hooks():
    """The supervisor's degrade/re-promote paths fire the capacity
    hooks run_server wires to the cluster's schedule_reweight."""
    from throttlecrab_tpu.faults import FaultInjector, arm, disarm, parse_spec
    from throttlecrab_tpu.server.supervisor import SupervisedLimiter

    calls = []
    lim = TpuRateLimiter(capacity=256)
    lim.rate_limit_batch(["__warm__"], 5, 100, 60, 1, T0 - NS)
    sup = SupervisedLimiter(
        lim, retries=0, probe_interval_ms=1, sleep_fn=lambda s: None
    )
    sup.on_degrade = lambda: calls.append("degrade")
    sup.on_repromote = lambda: calls.append("repromote")
    try:
        arm(FaultInjector(parse_spec("launch:count:1"), seed=3))
        res = sup.rate_limit_batch(["k"], 5, 100, 60, 1, T0)
        assert res.allowed[0]
        assert sup.state == "degraded"
        assert calls == ["degrade"]
        # Device heals; the next decide past the probe interval
        # re-promotes and fires the restore hook.
        res = sup.rate_limit_batch(["k"], 5, 100, 60, 1, T0 + 10**9)
        assert sup.state == "ok"
        assert calls == ["degrade", "repromote"]
    finally:
        disarm()


# ---------------------------------------------- partition heal (slow) #


@pytest.mark.slow
def test_partition_heal_reannounce_converges():
    """A 'partitioned' node (listener down, process alive) is declared
    dead and its range absorbed; when its listener returns, the pump's
    periodic re-announce heals the link and both sides converge back to
    single-owner routing."""
    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes, breaker_cooldown_s=0.3)
    b = Node(1, nodes, breaker_cooldown_s=0.3)
    try:
        a.join_cluster()
        b.join_cluster()
        ring = a.cl.ring
        hot = next(
            k for k in (f"ph:{i}" for i in range(4000))
            if ring.owner_of(k.encode()) == 1
        )
        now = exhaust_key(b, hot, T0)
        # Partition: B's listener goes away (sockets drop), B itself
        # keeps running (its pump will later re-announce).
        asyncio.run_coroutine_threadsafe(b.srv.stop(), b.loop).result(10)
        # Attempts inside the reconnect backoff don't count toward the
        # breaker (by design); space them out until it trips.
        deadline = time.monotonic() + 10
        i = 0
        while (
            not a.cl.peers[1].breaker_open
            and time.monotonic() < deadline
        ):
            a.cl.rate_limit_batch([hot], 2, 2, 600, 1, now + i)
            i += 1
            time.sleep(0.15)
        assert a.cl.peers[1].breaker_open
        # Heal: the listener returns on the same port.
        b.srv = ClusterServer(
            "127.0.0.1", ports[1], b.cl.local, b.cl.device_lock,
            cluster=b.cl,
        )
        asyncio.run_coroutine_threadsafe(b.srv.start(), b.loop).result(10)
        # The pumps' re-announce probes run on the breaker cooldown
        # cadence; wait for the link to heal in both directions.
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and (
            a.cl.peers[1].breaker_open or 1 in a.cl._absorbed
        ):
            time.sleep(0.2)
        assert not a.cl.peers[1].breaker_open, "link never healed"
        # Routing restored: A forwards to B and the state converged
        # (the key is still denied wherever it is decided).
        res = a.cl.rate_limit_batch([hot], 2, 2, 600, 1, now + 10)
        assert res.status[0] == 0 and not res.allowed[0]
        res = b.cl.rate_limit_batch([hot], 2, 2, 600, 1, now + 11)
        assert res.status[0] == 0 and not res.allowed[0]
    finally:
        for n in (a, b):
            try:
                n.kill()
            except Exception:
                pass


# --------------------------------------- 3-process acceptance (slow) #

HTTP_PORTS = (28480, 28481, 28482)
RPC_PORTS = (28490, 28491, 28492)
NODES3 = ",".join(f"127.0.0.1:{p}" for p in RPC_PORTS)


def spawn_node3(index: int, trace_dir: str = ""):
    env = dict(os.environ)
    env["THROTTLECRAB_PLATFORM"] = "cpu"
    env["THROTTLECRAB_CLUSTER_TIMEOUT_MS"] = "60000"
    if trace_dir:
        # Full-capture flight recorder: every decided window lands in
        # this node's trace file (finalized on graceful shutdown), so
        # the soak's timeline is replayable after the fact.
        env["THROTTLECRAB_TRACE_DIR"] = trace_dir
        env["THROTTLECRAB_TRACE_MODE"] = "full"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [
            sys.executable, "-m", "throttlecrab_tpu.server",
            "--http", "--http-port", str(HTTP_PORTS[index]),
            "--cluster-nodes", NODES3, "--cluster-index", str(index),
            "--store", "adaptive", "--log-level", "warn",
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )


def wait_healthy3(proc, port, deadline_s=180):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if proc.poll() is not None:
            out, _ = proc.communicate()
            pytest.fail(f"node exited early rc={proc.returncode}:\n{out}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=1
            ) as r:
                if r.status == 200:
                    return
        except Exception:
            time.sleep(0.5)
    pytest.fail("node never became healthy")


def throttle3(port, key, burst=3, count=2, period=600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/throttle",
        data=json.dumps(
            {"key": key, "max_burst": burst, "count_per_period": count,
             "period": period}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def cluster_view3(port):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/health/cluster", timeout=10
    ) as r:
        return json.loads(r.read())


@pytest.mark.slow
def test_three_node_join_kill_rejoin_acceptance(tmp_path):
    """The end-to-end elastic lifecycle on three real server processes:
    sustained load survives a node join (zero failed requests, ranges
    migrate) and a node exit via SIGTERM — now the graceful drain +
    planned leave, with the kill-path takeover as its bounded fallback
    (zero failed requests on the range either way — an exhausted key
    stays denied through the handoff), and the departed node rejoins
    with the state migrated back.  This is the CI acceptance gate for
    the elastic path.

    Record -> replay pass (ISSUE 14): every node runs with the
    full-capture flight recorder armed; after the soak, the three
    nodes' traces are merged by server timestamp and checked for
    conservation against the client's own observation — every decision
    the client saw appears in the recorded timeline exactly once, with
    the same outcome, in the same per-key order (zero lost or
    double-counted decisions across join, kill and rejoin)."""
    from collections import defaultdict

    from throttlecrab_tpu.parallel.ring import HashRing

    trace_dirs = [str(tmp_path / f"node{i}") for i in range(3)]
    for d in trace_dirs:
        os.makedirs(d, exist_ok=True)
    #: Client ground truth: key -> [allowed, ...] in request order.
    client_log = defaultdict(list)

    def throttle3t(port, key, **kw):
        doc = throttle3(port, key, **kw)
        client_log[key].append(bool(doc["allowed"]))
        return doc

    ring3 = HashRing(NODES3.split(","), 128)
    procs = [
        spawn_node3(0, trace_dirs[0]), spawn_node3(1, trace_dirs[1]),
        None,
    ]
    try:
        wait_healthy3(procs[0], HTTP_PORTS[0])
        wait_healthy3(procs[1], HTTP_PORTS[1])

        pool = [f"acc:{i}" for i in range(60)]
        failures = 0
        # Steady state through both frontends (also warms compiles).
        for step in range(4):
            for k in pool:
                throttle3t(HTTP_PORTS[step % 2], k, burst=50, count=100,
                          period=60)

        # ---- JOIN under load ---------------------------------------- #
        procs[2] = spawn_node3(2, trace_dirs[2])
        join_allowed = []
        deadline = time.time() + 180
        joined = False
        while time.time() < deadline:
            for k in pool:
                try:
                    join_allowed.append(
                        throttle3t(HTTP_PORTS[0], k, burst=50, count=100,
                                  period=60)["allowed"]
                    )
                except urllib.error.HTTPError:
                    failures += 1
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{HTTP_PORTS[2]}/health", timeout=1
                ) as r:
                    if r.status == 200:
                        joined = True
            except Exception:
                pass
            if joined:
                break
        assert joined, "node 2 never became healthy"
        assert failures == 0, f"{failures} client failures during join"
        # One more pass so traffic flows through the 3-node ring.
        for k in pool:
            throttle3t(HTTP_PORTS[2], k, burst=50, count=100, period=60)
        view = cluster_view3(HTTP_PORTS[0])
        assert view["mode"] == "ring"

        # ---- LEAVE (SIGTERM drain) with warm replica ----------------- #
        hot = next(
            k for k in (f"hotacc:{i}" for i in range(10_000))
            if ring3.owner_of(k.encode()) == 2
        )
        # Exhaust it on the 3-node cluster (burst 2): 2 allowed, rest
        # denied; replica deltas flow to the successor.
        seq = [throttle3t(HTTP_PORTS[2], hot, burst=2)["allowed"]
               for _ in range(4)]
        assert seq == [True, True, False, False]
        time.sleep(2.0)  # replica pump cadence
        # SIGTERM now drains gracefully: planned leave (zero-staleness
        # handoff) with the kill-path takeover as its bounded fallback;
        # either way the exit must cost zero client-visible failures.
        procs[2].terminate()
        procs[2].wait(timeout=30)
        # Zero client-visible failures on the departed range, and the
        # exhausted key STAYS denied — the leave handoff (or, on the
        # fallback path, the warm replica) carried its TAT.
        for i in range(3):
            r = throttle3t(HTTP_PORTS[i % 2], hot, burst=2)
            assert r["allowed"] is False, (
                "node exit lost the handed-off state"
            )
        fresh = next(
            k for k in (f"freshacc:{i}" for i in range(10_000))
            if ring3.owner_of(k.encode()) == 2
        )
        assert throttle3t(HTTP_PORTS[0], fresh, burst=5)["allowed"] is True
        views = [cluster_view3(HTTP_PORTS[i]) for i in range(2)]
        # The survivors observed the exit: a planned leave (the SIGTERM
        # drain's normal path) or a takeover (its bounded fallback).
        assert any(
            v["leaves"] >= 1 or v["takeovers"] >= 1 for v in views
        ), views

        # ---- REJOIN ------------------------------------------------- #
        procs[2] = spawn_node3(2, trace_dirs[2])
        wait_healthy3(procs[2], HTTP_PORTS[2])
        time.sleep(1.0)
        # The rejoined node serves its range from the migrated-back
        # state: still denied on its own frontend.
        assert throttle3t(HTTP_PORTS[2], hot, burst=2)["allowed"] is False
        assert throttle3t(HTTP_PORTS[0], hot, burst=2)["allowed"] is False

        # ---- RECORD -> REPLAY: conservation over the merged traces -- #
        # Graceful shutdown finalizes each node's full-capture trace
        # file (incl. node 2's pre-kill file: SIGTERM closed it).
        for p in procs:
            if p is not None and p.poll() is None:
                p.terminate()
        for p in procs:
            if p is not None:
                p.wait(timeout=60)
        import glob as _glob

        from throttlecrab_tpu.replay.trace import Trace

        rows = []
        for d in trace_dirs:
            for path in _glob.glob(os.path.join(d, "*.tctr")):
                for w in Trace.load(path).windows:
                    for j in range(len(w)):
                        rows.append((
                            w.now_ns,
                            w.keys[j].decode(),
                            bool(w.allowed[j]),
                            int(w.status[j]),
                        ))
        # Merge the three nodes' timelines by the server-side window
        # timestamp (one wall clock: same host).  The client is serial,
        # so per-key order is total.
        rows.sort(key=lambda r: r[0])
        recorded = defaultdict(list)
        for _t, key, was_allowed, status in rows:
            assert status == 0, (key, status)
            recorded[key].append(was_allowed)
        # Conservation: every decision the client observed appears in
        # the recorded timeline exactly once (nothing lost to the kill
        # or the migrations, nothing double-counted by forwarding),
        # with the same outcome, in the same per-key order.
        assert set(recorded) == set(client_log), (
            set(recorded) ^ set(client_log)
        )
        for key, seq_client in client_log.items():
            assert recorded[key] == seq_client, (
                f"replayed timeline for {key!r} diverged: "
                f"{recorded[key]} != {seq_client}"
            )
    finally:
        for p in procs:
            if p is not None and p.poll() is None:
                p.terminate()
        for p in procs:
            if p is not None:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()


# --------------------------------------------- record -> replay #


def test_cluster_record_replay_join_kill_rejoin():
    """Record/replay over the elastic lifecycle (ISSUE 14): a 3-node
    in-process cluster captures its client-visible decisions and
    membership timeline into one trace (join -> kill -> rejoin), and a
    ClusterReplayer reconstructs the membership from the recorded
    events and replays the identical outcome vector — zero lost or
    double-counted decisions from the replayed timeline (an exhausted
    key must stay denied across the takeover in the replay too)."""
    from throttlecrab_tpu.replay.player import (
        ClusterReplayer,
        outcome_vector,
    )
    from throttlecrab_tpu.replay.recorder import FlightRecorder, arm, disarm
    from throttlecrab_tpu.replay.trace import Trace

    ports = free_ports(3)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    recorder = FlightRecorder(capacity=4096, out_dir="/tmp")
    arm(recorder)
    a = Node(0, nodes)
    b = Node(1, nodes)
    c = None
    b2 = None
    replayer = None
    try:
        a.join_cluster()
        b.join_cluster()
        for n in (a, b):
            n.cl.capture = True
        ring = a.cl.ring
        pool = [f"rr:{i}" for i in range(32)]
        hot = next(
            k for k in (f"rrhot:{i}" for i in range(4000))
            if ring.owner_of(k.encode()) == 1
        )
        now = T0
        frontends = [a, b]
        for step in range(6):
            via = frontends[step % len(frontends)]
            via.cl.rate_limit_batch(pool, 8, 100, 60, 1, now)
            now += NS // 4

        # JOIN under load: node 2 boots, announces, serves.
        c = Node(2, nodes)
        c.cl.capture = True
        c.join_cluster()
        frontends = [a, b, c]
        for step in range(6):
            via = frontends[step % len(frontends)]
            via.cl.rate_limit_batch(pool, 8, 100, 60, 1, now)
            now += NS // 4

        # Exhaust the hot key on its owner; replica flows to successor.
        for i in range(4):
            res = b.cl.rate_limit_batch([hot], 2, 2, 600, 1, now + i)
        now += 4
        assert not res.allowed[0], "precondition: hot key exhausted"
        successor = ring.owner_of(hot.encode(), exclude=frozenset({1}))
        succ_node = {0: a, 2: c}[successor]
        deadline = time.monotonic() + 8
        while (
            time.monotonic() < deadline
            and hot.encode() not in succ_node.cl.replica_store
        ):
            time.sleep(0.1)
        assert hot.encode() in succ_node.cl.replica_store

        # KILL: the successor absorbs; exhausted key stays denied.
        b.kill()
        for i in range(3):
            res = a.cl.rate_limit_batch([hot], 2, 2, 600, 1, now)
            assert res.status[0] == 0 and not res.allowed[0]
            now += NS // 4
        a.cl.rate_limit_batch(pool, 8, 100, 60, 1, now)
        now += NS // 4

        # REJOIN: fresh node 1, state migrated back, still denied.
        b2 = Node(1, nodes)
        b2.cl.capture = True
        b2.join_cluster()
        res = b2.cl.rate_limit_batch([hot], 2, 2, 600, 1, now)
        assert res.status[0] == 0 and not res.allowed[0]
        now += NS // 4
        b2.cl.rate_limit_batch(pool, 8, 100, 60, 1, now)

        path, _n = recorder.dump()
        disarm()
        trace = Trace.load(path)
        kinds = [e.kind for e in trace.events]
        assert "cluster-join" in kinds and "cluster-takeover" in kinds

        # Replay the whole timeline on a fresh in-process cluster.
        replayer = ClusterReplayer(3, capacity=CAP)
        replayed = replayer.replay(trace, settle_s=1.0)
        assert outcome_vector(replayed) == trace.outcome_vector(), (
            "replayed cluster timeline drifted from the recorded "
            "outcomes (lost or double-counted decisions)"
        )
    finally:
        disarm()
        if replayer is not None:
            replayer.close()
        for n in (a, b, c, b2):
            if n is not None:
                try:
                    n.kill()
                except Exception:
                    pass


# ------------------------------------------------------------------ #
# Lifecycle ops off the event loop (PR 11 async-boundary fix)


def test_ring_and_join_ops_adopt_through_server():
    """OP_RING and the OP_JOIN ack now run apply_ring / ring_state on
    the lifecycle executor instead of the server's event loop (the
    async-boundary checker pins the static half; this pins behavior):
    per-connection ordering must survive the move — a ring broadcast
    followed by a join on the SAME connection must see the adopted
    weights in the ack."""
    from throttlecrab_tpu.parallel.cluster import (
        _HDR,
        OP_RING,
        OP_RING_STATE,
        decode_ring,
        encode_join,
        encode_ring,
    )

    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes)  # peer 1 never starts: only the frames matter
    try:
        with a.cl._mu:
            epoch0 = a.cl.epoch
        s = socket.create_connection(("127.0.0.1", ports[0]), 5)
        s.settimeout(30)
        try:
            # Weight broadcast, then a join announcement, pipelined on
            # one connection.  The server must apply the ring BEFORE
            # answering the join (op order == reply order).
            s.sendall(encode_ring(OP_RING, epoch0 + 7, [1.0, 0.25]))
            s.sendall(encode_join(1))
            head = b""
            while len(head) < _HDR.size:
                head += s.recv(_HDR.size - len(head))
            body_len, op = _HDR.unpack(head)
            assert op == OP_RING_STATE
            body = b""
            while len(body) < body_len:
                body += s.recv(body_len - len(body))
            epoch, weights = decode_ring(body)
            assert epoch >= epoch0 + 7
            # Peer 1's announced weight was adopted; node 0 stays the
            # authority for its own (1.0).
            assert weights == [1.0, 0.25]
        finally:
            s.close()
        with a.cl._mu:
            assert a.cl.ring.weights[1] == 0.25
    finally:
        a.kill()


def test_replica_push_failure_retries_next_live_successor():
    """A replica push that fails (successor just died, or a stale
    OP_JOIN heal re-closed its breaker before re-detection) must retry
    once on the NEXT live successor instead of dropping the rows —
    otherwise the absorbed range stays single-copy for the whole
    re-detection window (the deterministic twin of the timing-
    sensitive takeover test above)."""
    ports = free_ports(3)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    lim = TpuRateLimiter(capacity=CAP)
    cl = ClusterLimiter(lim, nodes, 0, vnodes=64, replicate=True)
    try:
        ring = cl.ring
        # A key whose first successor (excluding self) is node 2 and
        # whose next successor is node 1.
        hot = next(
            k for k in (f"rt:{i}".encode() for i in range(8000))
            if ring.owner_of(k, exclude=frozenset({0})) == 2
            and ring.owner_of(k, exclude=frozenset({0, 2})) == 1
        )
        sent = {1: [], 2: []}

        class _P:
            def __init__(self, idx, fail):
                self.idx = idx
                self.fail = fail
                self.lock = threading.Lock()
                self.breaker_open = False
                self.failed = 0

            def send_frame(self, frame):
                if self.fail:
                    raise ConnectionRefusedError(111, "refused")
                sent[self.idx].append(frame)

            def record_failure(self):
                self.failed += 1

            def close(self):
                pass

        cl.peers[1] = _P(1, fail=False)
        cl.peers[2] = _P(2, fail=True)  # dies on the push
        entry = (
            [hot],
            np.asarray([5], np.int64), np.asarray([100], np.int64),
            np.asarray([60], np.int64), T0,
            np.asarray([6 * NS], np.int64),
            np.asarray([0], np.uint8), np.asarray([True], bool),
            False,
        )
        cl._flush_replicas([entry])
        assert sent[2] == []  # the first successor's push failed...
        assert len(sent[1]) == 1  # ...and the rows landed on the next
        from throttlecrab_tpu.parallel.cluster import decode_rows

        _origin, _epoch, keys, _tats, _exps = decode_rows(
            sent[1][0][5:]
        )
        assert keys == [hot]
    finally:
        cl.close()

# ------------------------------------------------------------------ #
# Planned leave / rolling restart (PR 17 graceful lifecycle)


def test_leave_under_load_exact_differential():
    """A node leaves mid-stream (planned departure): every decision
    before, during and after the handoff matches the single-node
    scalar oracle value-for-value.  The leave path is OP_JOIN run in
    reverse, so the join test's zero-lost / zero-double-counted
    contract holds — with zero staleness, unlike the kill path whose
    replica handoff tolerates the replication lag."""
    from throttlecrab_tpu.core.rate_limiter import RateLimiter
    from throttlecrab_tpu.core.store.periodic import PeriodicStore

    ports = free_ports(3)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    # Same slowed gate clock as the join test: the receivers' handoff
    # deadlines must not expire under CI load while the leave stream
    # is genuinely in flight.
    t_base = time.monotonic()
    slow_clock = lambda: t_base + (time.monotonic() - t_base) * 0.05  # noqa: E731
    a = Node(0, nodes, clock=slow_clock)
    b = Node(1, nodes, clock=slow_clock)
    c = Node(2, nodes, clock=slow_clock)
    try:
        for n in (a, b, c):
            n.join_cluster()
        settle_handoffs(a, b, c)
        oracle = RateLimiter(PeriodicStore())
        pool = [f"lv:{i}" for i in range(48)]
        now = T0
        frontends = [a, b, c]
        for step in range(24):
            if step == 10:
                # Planned leave under load: B hands its whole table
                # off and goes lame-duck; A and C keep the stream
                # exact through the flip (B stays up, so any frontend
                # racing the announcement still reaches it and B
                # re-forwards — decisions never fork).  leave() returns
                # once every range was SENT; settle until the receivers
                # APPLIED them, so a loaded box can't expire a gate on
                # rows that are genuinely in flight.
                assert b.cl.leave(), "leave with live peers must ack"
                settle_handoffs(a, c)
                frontends = [a, c]
            via = frontends[step % len(frontends)]
            oracle_check(
                oracle, via, pool, 4, 10, 60, now, f"step{step}"
            )
            now += NS // 4
        # The departing node's state actually moved: receivers
        # installed its migrated rows, and no handoff gate expired
        # (an expired gate means the exactness above was luck).
        assert b.cl.leave_count >= 1
        assert a.cl.leave_count >= 1 and c.cl.leave_count >= 1
        assert a.cl.migrated_in + c.cl.migrated_in > 0
        assert a.cl.handoff_timeouts == 0
        assert c.cl.handoff_timeouts == 0
    finally:
        for n in (a, b, c):
            try:
                n.kill()
            except Exception:
                pass


def test_lame_duck_forwards_not_decides(two_ring_nodes):
    """After leave() the departed node still answers every request —
    lame-duck mode forwards to the new owner instead of deciding from
    its exported (now-authoritative-elsewhere) table."""
    a, b = two_ring_nodes
    keys = [f"ld:{i}" for i in range(16)]
    res = a.cl.rate_limit_batch(keys, 4, 10, 60, 1, T0)
    assert (res.status == 0).all() and res.allowed.all()
    assert a.cl.leave(), "leave with a live peer must ack"
    assert a.cl._lame_duck
    fwd0 = a.cl.peers[1].forwarded
    res = a.cl.rate_limit_batch(keys, 4, 10, 60, 1, T0 + NS)
    assert (res.status == 0).all() and res.allowed.all()
    # The batch went over the wire: nothing decides locally on a
    # weight-0 lame duck (forwarded counts forward RPCs).
    assert a.cl.peers[1].forwarded > fwd0
    # And the handoff carried the pre-leave TATs: the second hit on a
    # burst-4 key sees the first one (remaining 2, not a fresh 3).
    assert (res.remaining == 2).all(), "leave handoff lost state"


def test_leave_fault_falls_back_to_kill_path():
    """Injected `leave` faults break the announcement: leave() reports
    the partial handoff (returns False) instead of pretending, and the
    ordinary kill-path takeover still covers the exit — the survivor
    serves the departed range from its warm replica with zero
    client-visible failures (bounded staleness, not lost decisions)."""
    from throttlecrab_tpu.faults import FaultInjector, arm, disarm, parse_spec

    ports = free_ports(2)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    a = Node(0, nodes)
    b = Node(1, nodes)
    try:
        a.join_cluster()
        b.join_cluster()
        ring = a.cl.ring
        hot = next(
            k for k in (f"lf:{i}" for i in range(4000))
            if ring.owner_of(k.encode()) == 1
        )
        now = T0
        now = exhaust_key(b, hot, now)
        # Wait for the warm replica so the fallback has state to serve.
        deadline = time.monotonic() + 5
        while (
            time.monotonic() < deadline
            and hot.encode() not in a.cl.replica_store
        ):
            time.sleep(0.1)
        assert hot.encode() in a.cl.replica_store
        arm(FaultInjector(parse_spec("leave:persistent"), seed=3))
        assert b.cl.leave() is False, "broken announce must not ack"
        disarm()
        b.kill()
        # Kill path: the survivor absorbs the range and an exhausted
        # key STAYS denied (the replica carried its TAT).
        res = a.cl.rate_limit_batch([hot], 2, 2, 600, 1, now)
        assert res.status[0] == 0 and not res.allowed[0]
    finally:
        disarm()
        for n in (a, b):
            try:
                n.kill()
            except Exception:
                pass


def test_deadline_shed_differential(two_ring_nodes):
    """Rows already past their client deadline shed with
    STATUS_DEADLINE before any device dispatch or forward — and a shed
    row must NOT consume quota: the batchmates and every later
    decision match an oracle that never saw the shed requests."""
    from test_tpu_batch import oracle_batch

    from throttlecrab_tpu.core.rate_limiter import RateLimiter
    from throttlecrab_tpu.core.store.periodic import PeriodicStore
    from throttlecrab_tpu.tpu.limiter import STATUS_DEADLINE

    a, b = two_ring_nodes
    oracle = RateLimiter(PeriodicStore())
    pool = [f"dl:{i}" for i in range(32)]
    now = T0
    oracle_check(oracle, a, pool, 4, 10, 60, now, "warm")
    now += NS
    # Half the batch arrives already expired (even rows); the live
    # half must still decide exactly, locally and across forwards.
    dl = np.zeros(len(pool), np.int64)
    dl[::2] = now - 1
    dl[1::2] = now + 5 * NS
    res = a.cl.rate_limit_batch(pool, 4, 10, 60, 1, now, deadlines_ns=dl)
    assert (res.status[::2] == STATUS_DEADLINE).all()
    assert not res.allowed[::2].any()
    live_ix = np.arange(1, len(pool), 2)
    live_keys = [pool[i] for i in live_ix]
    nl = len(live_keys)
    exp = oracle_batch(
        oracle, live_keys,
        np.full(nl, 4, np.int64), np.full(nl, 10, np.int64),
        np.full(nl, 60, np.int64), np.ones(nl, np.int64), now,
    )
    np.testing.assert_array_equal(res.status[live_ix], exp["status"])
    np.testing.assert_array_equal(res.allowed[live_ix], exp["allowed"])
    np.testing.assert_array_equal(
        res.remaining[live_ix], exp["remaining"]
    )
    # The shed rows left no trace: the full pool keeps matching an
    # oracle that never saw them, from either frontend.
    now += NS
    oracle_check(oracle, b, pool, 4, 10, 60, now, "post-shed-b")
    now += NS
    oracle_check(oracle, a, pool, 4, 10, 60, now, "post-shed-a")


def test_rolling_restart_soak():
    """Zero-staleness rolling restart: each node in turn leaves
    (planned handoff), dies, restarts empty and rejoins — under a
    continuous oracle-pinned stream.  Every decision across all three
    restart epochs matches the scalar oracle value-for-value, so a
    full fleet roll costs zero staleness and zero lost decisions."""
    from throttlecrab_tpu.core.rate_limiter import RateLimiter
    from throttlecrab_tpu.core.store.periodic import PeriodicStore

    ports = free_ports(3)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    t_base = time.monotonic()
    slow_clock = lambda: t_base + (time.monotonic() - t_base) * 0.05  # noqa: E731
    ns = [Node(i, nodes, clock=slow_clock) for i in range(3)]
    try:
        for n in ns:
            n.join_cluster()
        settle_handoffs(*ns)
        oracle = RateLimiter(PeriodicStore())
        pool = [f"rr:{i}" for i in range(48)]
        state = {"now": T0, "step": 0}

        def drive(k_steps):
            for _ in range(k_steps):
                live = [n for n in ns if n is not None]
                via = live[state["step"] % len(live)]
                oracle_check(
                    oracle, via, pool, 4, 10, 60, state["now"],
                    f"step{state['step']}",
                )
                state["now"] += NS // 4
                state["step"] += 1

        drive(3)
        for victim in range(3):
            assert ns[victim].cl.leave(), f"node {victim} leave must ack"
            # The kill below only stays invisible once both survivors
            # have processed the OP_LEAVE announcement (before that
            # they would route at a corpse and fail over to replicas —
            # the kill path, not the one under test here).
            others = [
                n for i, n in enumerate(ns)
                if n is not None and i != victim
            ]
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not all(
                victim in n.cl._departed for n in others
            ):
                time.sleep(0.05)
            assert all(victim in n.cl._departed for n in others)
            settle_handoffs(*others)
            ns[victim].kill()
            ns[victim] = None
            drive(3)
            ns[victim] = Node(victim, nodes, clock=slow_clock)
            ns[victim].join_cluster()
            settle_handoffs(*[n for n in ns if n is not None])
            drive(3)
        for n in ns:
            assert n.cl.handoff_timeouts == 0
    finally:
        for n in ns:
            if n is not None:
                try:
                    n.kill()
                except Exception:
                    pass


def test_cluster_record_replay_planned_leave():
    """The rolling-restart soak's trace ingredient: a planned leave is
    captured as a `cluster-leave` event and the ClusterReplayer
    reconstructs it — the replayed outcome vector matches the recorded
    one exactly, because the replay runs the same state-preserving
    handoff the live node did (not the kill path's replica fallback)."""
    from throttlecrab_tpu.replay.player import (
        ClusterReplayer,
        outcome_vector,
    )
    from throttlecrab_tpu.replay.recorder import FlightRecorder, arm, disarm
    from throttlecrab_tpu.replay.trace import Trace

    ports = free_ports(3)
    nodes = [f"127.0.0.1:{p}" for p in ports]
    recorder = FlightRecorder(capacity=4096, out_dir="/tmp")
    arm(recorder)
    a = Node(0, nodes)
    b = Node(1, nodes)
    c = Node(2, nodes)
    replayer = None
    try:
        for n in (a, b, c):
            n.join_cluster()
            n.cl.capture = True
        settle_handoffs(a, b, c)
        pool = [f"rl:{i}" for i in range(32)]
        now = T0
        frontends = [a, b, c]
        for step in range(6):
            frontends[step % 3].cl.rate_limit_batch(
                pool, 4, 10, 60, 1, now
            )
            now += NS // 4
        # Planned leave under load; the lame duck then goes away for
        # good (burst-4 keys driven past their limit, so any replayed
        # staleness would flip a deny to an allow).
        assert b.cl.leave()
        settle_handoffs(a, c)
        frontends = [a, c]
        for step in range(6):
            frontends[step % 2].cl.rate_limit_batch(
                pool, 4, 10, 60, 1, now
            )
            now += NS // 4
        b.kill()
        for step in range(4):
            frontends[step % 2].cl.rate_limit_batch(
                pool, 4, 10, 60, 1, now
            )
            now += NS // 4

        path, _n = recorder.dump()
        disarm()
        trace = Trace.load(path)
        assert "cluster-leave" in [e.kind for e in trace.events]
        replayer = ClusterReplayer(3, capacity=CAP)
        replayed = replayer.replay(trace, settle_s=1.0)
        assert outcome_vector(replayed) == trace.outcome_vector(), (
            "replayed planned-leave timeline drifted from the "
            "recorded outcomes"
        )
    finally:
        disarm()
        if replayer is not None:
            replayer.close()
        for n in (a, b, c):
            try:
                n.kill()
            except Exception:
                pass


def test_leave_and_droute_codecs_roundtrip_and_harden():
    """The two PR 17 wire frames follow the cluster codec contract:
    exact roundtrip, and truncated/corrupt bodies raise the typed
    protocol error instead of mis-decoding."""
    from throttlecrab_tpu.parallel.cluster import (
        ClusterProtocolError,
        _HDR,
        decode_droute,
        decode_leave,
        encode_droute,
        encode_leave,
    )

    frame = encode_leave(3, 17)
    assert decode_leave(frame[_HDR.size:]) == (3, 17)
    with pytest.raises(ClusterProtocolError):
        decode_leave(frame[_HDR.size:-1])

    keys = [b"a", b"bb", b"ccc"]
    params = np.array(
        [[4, 10, 60, 1], [5, 11, 61, 2], [6, 12, 62, 3]], np.int64
    )
    budgets = np.array([7 * NS, 0, 3 * NS], np.int64)
    frame = encode_droute(keys, params, T0, 2, budgets)
    hops, k2, p2, now2, b2 = decode_droute(frame[_HDR.size:])
    assert hops == 2 and k2 == keys and now2 == T0
    np.testing.assert_array_equal(p2, params)
    np.testing.assert_array_equal(b2, budgets)
    # Truncation anywhere in the budget column or batch body raises.
    for cut in (1, 10, 30):
        with pytest.raises(ClusterProtocolError):
            decode_droute(frame[_HDR.size:-cut])
