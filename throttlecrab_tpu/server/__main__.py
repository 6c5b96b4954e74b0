"""Server entry point: `python -m throttlecrab_tpu.server --http ...`.

Lifecycle mirrors the reference's `main.rs:49-184`: parse config → init
logging → build metrics → build limiter + micro-batching engine (the actor
replacement) → start every enabled transport → wait for SIGINT/SIGTERM →
graceful shutdown (flush the engine, stop transports).
"""

from __future__ import annotations

import asyncio
import logging
import os
import signal
import sys

from ..runtime import device_info, enable_compile_cache
from .config import Config, ConfigError
from .engine import BatchingEngine
from .metrics import Metrics
from .store import (
    create_cleanup_policy,
    create_control,
    create_front_tier,
    create_insight,
    create_limiter,
    create_supervised_limiter,
)

log = logging.getLogger("throttlecrab")

LOG_LEVELS = {
    "error": logging.ERROR,
    "warn": logging.WARNING,
    "info": logging.INFO,
    "debug": logging.DEBUG,
    "trace": logging.DEBUG,
}


def build_transports(config: Config, engine, metrics):
    """One instance per enabled transport (main.rs:74-116)."""
    transports = []
    if config.http:
        if config.http_backend == "native":
            from .native_http import NativeHttpTransport

            transports.append(
                NativeHttpTransport(
                    config.http_host,
                    config.http_port,
                    engine.limiter,
                    metrics,
                    batch_size=config.batch_size,
                    max_linger_us=config.max_linger_us,
                    max_scan_depth=config.max_scan_depth,
                    cleanup_policy=engine.cleanup_policy,
                    limiter_lock=engine.limiter_lock,
                    now_fn=engine.now_fn,
                    front=engine.front,
                    insight=engine.insight,
                    control=engine.control,
                    checkpointer=engine.checkpointer,
                )
            )
        else:
            from .http import HttpTransport

            transports.append(
                HttpTransport(
                    config.http_host, config.http_port, engine, metrics
                )
            )
    if config.grpc:
        from .grpc import GrpcTransport

        transports.append(
            GrpcTransport(config.grpc_host, config.grpc_port, engine, metrics)
        )
    if config.redis:
        if config.redis_backend == "native":
            from .native_redis import NativeRedisTransport
            from .store import create_cleanup_policy

            # One policy instance is shared by the engine and the native
            # driver (both consult it under engine.limiter_lock), so ops
            # accounting sees all traffic and sweeps never double-fire.
            native_policy = engine.cleanup_policy
            transports.append(
                NativeRedisTransport(
                    config.redis_host,
                    config.redis_port,
                    engine.limiter,
                    metrics,
                    batch_size=config.batch_size,
                    max_linger_us=config.max_linger_us,
                    max_scan_depth=config.max_scan_depth,
                    cleanup_policy=native_policy,
                    limiter_lock=engine.limiter_lock,
                    now_fn=engine.now_fn,
                    front=engine.front,
                    insight=engine.insight,
                    control=engine.control,
                    checkpointer=engine.checkpointer,
                )
            )
        else:
            from .redis import RedisTransport

            transports.append(
                RedisTransport(
                    config.redis_host, config.redis_port, engine, metrics
                )
            )
    return transports


class SnapshotRefused(RuntimeError):
    """Boot refused: the snapshot is corrupt and strict mode is on."""


def restore_snapshot_on_boot(limiter, config: Config) -> int:
    """Restore-on-boot with the THROTTLECRAB_SNAPSHOT_STRICT policy.

    A corrupt/truncated snapshot must never crash the server with a
    raw traceback: strict mode (the default) refuses to start with a
    clear SnapshotRefused, non-strict logs the corruption and starts
    with an empty table.  Returns the number of keys restored (0 when
    no snapshot exists or the non-strict path started cold)."""
    import os as _os
    import time as _time

    from ..tpu.snapshot import SnapshotError, _normalize, load_snapshot

    if not config.snapshot_path:
        return 0
    if not _os.path.exists(_normalize(config.snapshot_path)):
        return 0
    try:
        restored = load_snapshot(
            limiter, config.snapshot_path, _time.time_ns()
        )
        log.info(
            "restored %d keys from snapshot %s",
            restored, config.snapshot_path,
        )
        return restored
    except SnapshotError as e:
        if config.snapshot_strict:
            raise SnapshotRefused(
                f"refusing to start: {e} (set "
                "THROTTLECRAB_SNAPSHOT_STRICT=0 to log and start with "
                "an empty table instead)"
            ) from e
        log.error(
            "snapshot %s is corrupt; starting with an empty table "
            "(THROTTLECRAB_SNAPSHOT_STRICT=0): %s",
            config.snapshot_path, e,
        )
    except Exception:
        # Non-corruption failure (e.g. capacity): soft state — a bad
        # snapshot degrades to a cold start, never to a refused boot
        # or wrong decisions.
        log.exception(
            "snapshot restore failed; starting cold (%s)",
            config.snapshot_path,
        )
    # A partial restore may have populated the keymap (no rollback in
    # bulk insert) — sweep everything so "cold" is real, not a table
    # full of dead entries rejecting new keys.
    try:
        limiter.sweep(1 << 62)
    except Exception:
        log.exception("post-restore-failure sweep failed")
    return 0


def restore_on_boot(limiter, config: Config, checkpointer) -> int:
    """Boot restore precedence: checkpoint chain first, snapshot second.

    The checkpoint directory is best-effort durable state, so its
    recovery never refuses boot (torn/corrupt generations narrow what
    gets restored — persist/recovery.py).  Only when no usable chain
    exists does boot fall through to the explicitly-named snapshot,
    which keeps its THROTTLECRAB_SNAPSHOT_STRICT refuse-on-corrupt
    policy."""
    import time as _time

    if checkpointer is not None:
        from ..persist import recover_into

        try:
            res = recover_into(
                limiter, checkpointer.directory, _time.time_ns()
            )
        except Exception:
            # Non-corruption failure (e.g. capacity): same soft policy
            # as the snapshot path — sweep to a real cold start and
            # fall through.
            log.exception(
                "checkpoint recovery failed; falling back to "
                "snapshot restore (%s)", checkpointer.directory,
            )
            try:
                limiter.sweep(1 << 62)
            except Exception:
                log.exception("post-recovery-failure sweep failed")
            res = None
        if res is not None:
            checkpointer.note_recovery(
                res.restored, res.corrupt_skipped, res.chains
            )
            log.info(
                "recovered %d keys from checkpoint chain gen=%d "
                "(%d corrupt generation(s) skipped, manifest=%s)",
                res.restored, res.generation, res.corrupt_skipped,
                "used" if res.used_manifest else "rebuilt",
            )
            return res.restored
    return restore_snapshot_on_boot(limiter, config)


async def run_server(config: Config) -> None:
    metrics = (
        Metrics.builder().max_denied_keys(config.max_denied_keys).build()
    )
    log.info("starting rate limiter with %s store", config.store)
    if config.faults:
        # Chaos arming: deterministic injected faults at the five real
        # failure surfaces (throttlecrab_tpu/faults/).
        from ..faults import FaultInjector, arm, parse_spec

        arm(FaultInjector(parse_spec(config.faults),
                          seed=config.faults_seed))
        log.warning("fault injection armed: %s", config.faults)
    recorder = None
    if config.trace_dir:
        # Flight recorder (throttlecrab_tpu/replay/): per-batch capture
        # hooks on the engine flush path, the native driver and the
        # supervisor's degrade path all feed this one process-wide
        # recorder; GET /trace/dump and persistent degrade dump it.
        from ..replay import recorder as replay_recorder

        recorder = replay_recorder.from_config(config)
        replay_recorder.arm(recorder)
        log.info(
            "trace recorder armed: dir=%s mode=%s windows=%d",
            config.trace_dir, config.trace_mode, config.trace_windows,
        )
    device_limiter = create_limiter(config)
    device = device_info()
    log.info(
        "device: platform=%s kind=%s count=%d; limiter devices: %s",
        device["platform"], device["kind"], device["count"],
        ", ".join(
            str(d)
            for d in sorted(
                device_limiter.table.state.devices(), key=lambda d: d.id
            )
        ),
    )
    if device["platform"] == "cpu" and not os.environ.get(
        "THROTTLECRAB_PLATFORM"
    ):
        log.warning(
            "no accelerator found: serving from XLA:CPU (set "
            "THROTTLECRAB_PLATFORM=cpu to say this is intended)"
        )
    if getattr(device_limiter, "tenants", None) is not None:
        # Sharded mesh with the tenant layer armed: export the
        # psum-reduced per-tenant counters on GET /metrics.
        metrics.set_tenant_stats_provider(device_limiter.tenant_stats)
    # Failure-domain supervision (L3.75): every transport drives the
    # same supervised limiter, so retry/degrade/re-promote decisions
    # are made once, under the shared limiter lock.
    limiter = create_supervised_limiter(config, device_limiter, metrics)
    supervisor = limiter
    metrics.set_engine_state_provider(lambda: supervisor.state)
    cluster_nodes = config.cluster_node_list()
    if cluster_nodes:
        # Multi-node deployment: every key has one owner node (salted
        # stable hash); remote keys forward over the cluster RPC and
        # limits hold globally (parallel/cluster.py).
        from ..parallel.cluster import ClusterLimiter

        log.info(
            "cluster mode: node %d of %d (%s)",
            config.cluster_index, len(cluster_nodes),
            cluster_nodes[config.cluster_index],
        )
        limiter = ClusterLimiter(
            limiter, cluster_nodes, config.cluster_index,
            io_timeout_s=config.cluster_timeout_ms / 1000.0,
            breaker_failures=config.cluster_breaker_failures,
            breaker_cooldown_s=config.cluster_breaker_cooldown_ms / 1000.0,
            connect_timeout_s=config.cluster_connect_timeout_ms / 1000.0,
            vnodes=config.cluster_vnodes,
            replicate=config.cluster_replicate,
            handoff_timeout_s=config.cluster_handoff_timeout_ms / 1000.0,
            replica_cap=config.cluster_replica_cap,
        )
        metrics.set_cluster_stats_provider(limiter.peer_stats)
        metrics.set_cluster_view_provider(limiter.cluster_view)
        if config.cluster_vnodes > 0:
            # Elastic capacity announcements: a degraded node shrinks
            # its ring weight so neighbours absorb load; re-promotion
            # restores it.  schedule-only (the hooks run under the
            # limiter lock; the cluster pump applies them outside it).
            cluster = limiter
            supervisor.on_degrade = (
                lambda: cluster.schedule_reweight(0.5)
            )
            supervisor.on_repromote = (
                lambda: cluster.schedule_reweight(1.0)
            )
    checkpointer = None
    if config.checkpoint_dir:
        # Crash durability (persist/): background generation-chain
        # checkpoints plus boot-time recovery.  With interval 0 the
        # subsystem is recovery + shutdown-flush only (no ticks, no
        # dirty tracking).
        from ..persist import Checkpointer

        checkpointer = Checkpointer(
            limiter,
            config.checkpoint_dir,
            interval_ns=config.checkpoint_interval_ms * 1_000_000,
            retain=config.checkpoint_retain,
            mode=config.checkpoint_mode,
        )
        metrics.set_checkpoint_stats_provider(checkpointer.metric_stats)
        log.info(
            "checkpointing armed: dir=%s interval=%dms retain=%d mode=%s",
            config.checkpoint_dir, config.checkpoint_interval_ms,
            config.checkpoint_retain, config.checkpoint_mode,
        )
    loop = asyncio.get_running_loop()
    # The restore is a device bulk-insert (and, on a corrupt snapshot,
    # a full sweep): executor, not the event loop — by the time the
    # cluster RPC listener starts serving below, the loop must be free.
    await loop.run_in_executor(
        None, restore_on_boot, limiter, config, checkpointer
    )
    # Front tier (L3.5): exact deny cache + admission control, shared
    # by the asyncio engine and the native transports.  Built after the
    # snapshot restore on purpose — the cache must start empty against
    # restored foreign state.
    front = create_front_tier(config, metrics, limiter)
    # Re-promotion rewrites bucket state out from under cached denials:
    # the supervisor needs the front's on_restore hook.
    supervisor.front = front
    # Insight tier (L3.75): device-resident analytics + the deny-cache
    # and admission feedback loop.  The supervisor feeds it from the
    # host oracle while degraded so /stats stays truthful.
    insight = create_insight(config, metrics, device_limiter, front)
    supervisor.insight = insight
    if cluster_nodes and insight is not None:
        # In cluster mode the device is serialized by the cluster's
        # device lock (the RPC listener decides under it, bypassing
        # engine.limiter_lock); the insight poll must use the same one
        # or it races the RPC path's donated state buffers.
        insight.poll_lock = limiter.device_lock
    cleanup_policy = create_cleanup_policy(config)
    # Control plane (L3.9): adaptive feedback over the knob surface the
    # tiers above just built.  Off by default (THROTTLECRAB_CONTROL=0):
    # create_control returns None, nothing ticks, no knob ever moves.
    control = create_control(
        config, metrics, limiter, front, insight, cleanup_policy
    )
    if cluster_nodes and control is not None:
        # Same reasoning as the insight poll_lock override above: in
        # cluster mode the device is serialized by the cluster's device
        # lock, and the control tick's sensor reads ride that hold.
        control.tick_lock = limiter.device_lock
    engine = BatchingEngine(
        limiter,
        batch_size=config.batch_size,
        max_linger_us=config.max_linger_us,
        max_scan_depth=config.max_scan_depth,
        cleanup_policy=cleanup_policy,
        metrics=metrics,
        profile_dir=config.profile_dir or None,
        front=front,
        insight=insight,
        control=control,
        deadline_default_ms=config.deadline_default_ms,
        checkpointer=checkpointer,
    )
    transports = build_transports(config, engine, metrics)
    if cluster_nodes:
        from ..parallel.cluster import ClusterServer

        rpc_port = int(
            cluster_nodes[config.cluster_index].rpartition(":")[2]
        )
        # The RPC listener decides on the local limiter under the
        # cluster's device lock — NOT the engine's limiter_lock, which is
        # held across outbound peer RPCs; sharing it would deadlock two
        # nodes forwarding to each other.
        transports.append(
            ClusterServer(
                config.cluster_bind_host,
                rpc_port,
                limiter.local,
                limiter.device_lock,
                cluster=limiter,
            )
        )

    for transport in transports:
        await transport.start()

    if cluster_nodes and config.cluster_vnodes > 0:
        # Announce membership only once the RPC listener is up, so
        # peers can stream our key range back (join/rejoin path).
        limiter.start_membership()

    stop = asyncio.Event()
    drain_requested = False

    def _signal_handler(graceful: bool) -> None:
        nonlocal drain_requested
        log.info(
            "shutdown signal received (%s)",
            "drain" if graceful else "kill",
        )
        if graceful:
            drain_requested = True
        stop.set()

    # SIGTERM (the orchestrator's planned-stop signal) drains: stop
    # accepting, flush queued requests with real decisions, planned
    # cluster leave, snapshot.  SIGINT keeps today's abrupt kill path.
    for sig, graceful in (
        (signal.SIGINT, False),
        (signal.SIGTERM, True),
    ):
        try:
            loop.add_signal_handler(sig, _signal_handler, graceful)
        except NotImplementedError:  # pragma: no cover - non-unix
            pass

    serve_tasks = [
        asyncio.create_task(t.serve_forever(), name=f"transport-{t.name}")
        for t in transports
    ]
    stop_task = asyncio.create_task(stop.wait())
    # A transport crashing ends the process with an error, like the
    # reference's JoinSet select (main.rs:143-171).
    done, _pending = await asyncio.wait(
        serve_tasks + [stop_task], return_when=asyncio.FIRST_COMPLETED
    )
    failed = False
    for task in done:
        if task is not stop_task and task.exception() is not None:
            log.error("transport failed: %r", task.exception())
            failed = True

    log.info("shutting down")
    stop_task.cancel()
    if drain_requested and config.drain_timeout_ms > 0 and not failed:
        # Graceful drain, bounded: past the budget the node degrades to
        # the abrupt kill path below (cluster peers' replica takeover
        # bounds the damage exactly as for a crash).
        async def _drain() -> None:
            # 1. De-route: health answers "draining", listeners stop
            #    accepting new connections (established ones keep
            #    serving until stop() below).
            engine.begin_drain()
            for transport in transports:
                drain_hook = getattr(transport, "drain", None)
                if drain_hook is not None:
                    await drain_hook()
            # 2. Flush everything already queued with real decisions.
            await engine.drain()
            # 3. Planned cluster leave: stream our key range to the new
            #    owners (zero lost decisions, zero replica staleness) —
            #    blocking socket work, so on the executor.
            if cluster_nodes and config.cluster_vnodes > 0:
                left = await loop.run_in_executor(None, limiter.leave)
                if not left:
                    log.warning(
                        "planned leave unavailable; peers take over "
                        "via the kill path"
                    )

        try:
            await asyncio.wait_for(
                _drain(), config.drain_timeout_ms / 1000.0
            )
            log.info("drain complete")
        except asyncio.TimeoutError:
            log.warning(
                "drain timed out after %dms; falling back to the "
                "kill path", config.drain_timeout_ms,
            )
        except Exception:
            log.exception("drain failed; falling back to the kill path")
    await engine.shutdown()
    if recorder is not None:
        # Finalize the trace: full mode flushes + closes its incremental
        # file so a recorded workload replays after a clean stop (ring
        # mode persists nothing unless dumped — by design).
        from ..replay import recorder as replay_recorder

        await loop.run_in_executor(None, recorder.close)
        replay_recorder.disarm()
    if cluster_nodes:
        # Stop the replica/membership pump and drop peer sockets before
        # the snapshot, so no migration mutates the table under it.
        limiter.close()
    for transport in transports:
        await transport.stop()
    if checkpointer is not None:
        # Final generation flush: transports are stopped, so the bare
        # (lockless) export races nothing.  Best-effort — a failed
        # flush leaves the previous durable chain intact.
        await loop.run_in_executor(None, checkpointer.stop)
    if config.snapshot_path:
        from ..tpu.snapshot import (
            export_snapshot_payload,
            write_snapshot_payload,
        )

        def locked_export() -> dict:
            # The lock serializes against any straggling native driver
            # thread, but only the device export rides the hold — the
            # .npz compression and file/fsync work below run with it
            # released.
            with engine.limiter_lock:
                return export_snapshot_payload(limiter)

        try:
            # Device export + .npz write: executor, not the event loop.
            payload = await loop.run_in_executor(None, locked_export)
            saved = await loop.run_in_executor(
                None, write_snapshot_payload, payload,
                config.snapshot_path,
            )
            log.info(
                "saved %d keys to snapshot %s",
                saved, config.snapshot_path,
            )
        except Exception:
            log.exception(
                "snapshot save failed (%s)", config.snapshot_path
            )
    for task in serve_tasks:
        task.cancel()
    await asyncio.gather(*serve_tasks, stop_task, return_exceptions=True)
    if failed:
        raise TransportFailure("a transport task ended with an error")


class TransportFailure(RuntimeError):
    pass


def main(argv=None) -> int:
    # THROTTLECRAB_PLATFORM pins the jax backend (e.g. "cpu" for CPU-only
    # deployments and the out-of-process tests).  Must happen before any
    # device query.
    platform = os.environ.get("THROTTLECRAB_PLATFORM")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    enable_compile_cache()
    try:
        config = Config.from_env_and_args(argv)
    except ConfigError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    logging.basicConfig(
        level=LOG_LEVELS.get(config.log_level.lower(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    try:
        asyncio.run(run_server(config))
    except KeyboardInterrupt:
        pass
    except SnapshotRefused as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    except TransportFailure:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
