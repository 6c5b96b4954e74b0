"""Limiter/policy factory: config → engine parts (reference: store.rs:57-87).

The reference's factory picks one of three store types and spawns the
matching actor; here the "store" choice selects the cleanup policy (the
bucket table itself is always the TPU SoA table), and `shards` selects
between the single-device and mesh-sharded limiter.
"""

from __future__ import annotations

import logging

from ..tpu.cleanup import CleanupPolicy, make_policy
from ..tpu.limiter import TpuRateLimiter

log = logging.getLogger("throttlecrab.store")


def create_limiter(config):
    """Build the device limiter the engine will drive."""
    if config.shards > 1:
        from ..parallel.sharded import ShardedTpuRateLimiter, make_mesh
        from ..parallel.tenants import TenantRegistry

        mesh = make_mesh(config.shards)
        tenants = None
        if getattr(config, "tenant_max", 0) > 0:
            tenants = TenantRegistry(
                max_tenants=config.tenant_max,
                delim=config.tenant_delim,
                quota_frac=config.tenant_quota,
                affinity=config.tenant_affinity,
            )
        return ShardedTpuRateLimiter(
            capacity_per_shard=max(
                config.store_capacity // config.shards, 1024
            ),
            mesh=mesh,
            keymap=config.keymap,
            # Insight tier (L3.75) is mesh-native: widened shard rows,
            # psum'd totals, one-launch mesh-global top-K.
            insight=getattr(config, "insight", False),
            tenants=tenants,
        )
    return TpuRateLimiter(
        capacity=config.store_capacity,
        keymap=config.keymap,
        # Insight tier (L3.75): arm the device analytics accumulators
        # at build time — they ride every decision launch.
        insight=getattr(config, "insight", False),
    )


def create_supervised_limiter(config, limiter, metrics=None):
    """Wrap the device limiter in the failure-domain supervisor
    (server/supervisor.py): transient launch/fetch faults retry with
    bounded backoff, persistent device failure degrades to the host
    scalar oracle (THROTTLECRAB_SUPERVISOR_MODE=degrade), and recovery
    re-promotes.  One wrapper supervises every transport, because they
    all share the same limiter."""
    from .supervisor import SupervisedLimiter

    return SupervisedLimiter(
        limiter,
        retries=config.supervisor_retries,
        backoff_us=config.supervisor_backoff_us,
        backoff_max_us=config.supervisor_backoff_max_us,
        probe_interval_ms=config.supervisor_probe_interval_ms,
        mode=config.supervisor_mode,
        metrics=metrics,
    )


def create_front_tier(config, metrics, limiter):
    """Build the front tier (L3.5: exact deny cache + admission
    control) from the THROTTLECRAB_FRONT_* knobs, or None when both
    halves are disabled.  One instance is shared by the asyncio engine
    and every native transport driving the same limiter."""
    import inspect

    from ..front import AdmissionController, DenyCache, FrontTier
    from ..tpu.limiter import limiter_uses_bytes_keys

    # Capability-probe the DEVICE limiter, not a supervision wrapper:
    # the wrapper's uniform signatures would make a cur-less limiter
    # look certifiable and resurrect the permanently-empty-cache trap
    # this probe exists to avoid.
    limiter = getattr(limiter, "inner", limiter)

    # A deny cache can only certify entries when the limiter exposes the
    # exact observed TAT: either the cur tier (collect_cur) or, for
    # non-wire limiters, the full-ns result planes.  Sharded/cluster
    # limiters offer neither today — the cache would stay permanently
    # empty while every request still paid its lookup/in-flight
    # bookkeeping, so build only the admission half for them.
    try:
        params = inspect.signature(limiter.rate_limit_batch).parameters
    except (AttributeError, TypeError, ValueError):
        params = {}
    certifiable = "collect_cur" in params or "wire" not in params
    if config.front_deny_cache > 0 and not certifiable:
        # Loud when the operator actually CHOSE a cache size, informative
        # when it is just the default riding a sharded/cluster config (a
        # WARNING about a choice never made would train operators to
        # ignore the line that matters when the cache was configured).
        import dataclasses

        from .config import Config

        default = next(
            f.default
            for f in dataclasses.fields(Config)
            if f.name == "front_deny_cache"
        )
        emit = (
            log.info
            if config.front_deny_cache == default
            else log.warning
        )
        emit(
            "front-tier deny cache configured "
            "(THROTTLECRAB_FRONT_DENY_CACHE=%d) but this limiter "
            "cannot certify entries (no exact observed-TAT surface); "
            "building admission control only — set "
            "THROTTLECRAB_FRONT_DENY_CACHE=0 to silence",
            config.front_deny_cache,
        )
    deny = None
    if config.front_deny_cache > 0 and certifiable:
        from ..native import deny_available, deny_build_error

        if deny_available():
            deny = DenyCache(config.front_deny_cache)
        else:
            log.warning(
                "front-tier deny cache configured "
                "(THROTTLECRAB_FRONT_DENY_CACHE=%d) but its native library "
                "is unavailable (%s); building admission control only",
                config.front_deny_cache,
                (deny_build_error() or "no build error").splitlines()[0],
            )
    admission = None
    if config.front_max_pending or config.front_max_wait_us:
        admission = AdmissionController(
            max_pending=config.front_max_pending,
            max_wait_us=config.front_max_wait_us,
            peek_frac=config.front_peek_frac,
        )
    if deny is None and admission is None:
        return None
    front = FrontTier(
        deny, admission, metrics=metrics,
        bytes_keys=limiter_uses_bytes_keys(limiter),
    )
    if metrics is not None:
        metrics.set_front_stats_provider(front.stats)
    return front


def create_insight(config, metrics, limiter, front):
    """Build the insight tier (L3.75: device-resident traffic
    analytics + the deny-cache/admission feedback loop) from the
    THROTTLECRAB_INSIGHT_* knobs, or None when disabled or the limiter
    cannot carry it.  Both the single-device and the mesh-sharded
    limiter carry it (the sharded table serves mesh-global results);
    a limiter without an insight-armed table — e.g. a duck-typed
    replacement — drops the tier LOUDLY, never silently.
    """
    if not config.insight:
        return None
    from ..insight import InsightTier

    dev = getattr(limiter, "inner", limiter)
    table = getattr(dev, "table", None)
    if table is None or not getattr(table, "insight", False):
        # Loud, not silent: the operator asked for insight but this
        # limiter cannot carry the widened analytics rows, so /stats,
        # the deny-cache prewarm and the admission feedback loop are all
        # dropped for this boot.
        log.warning(
            "insight tier requested (THROTTLECRAB_INSIGHT=1) but the "
            "%s limiter's table does not carry the insight "
            "accumulators; serving WITHOUT /stats analytics or the "
            "admission/deny-cache feedback loop — set "
            "THROTTLECRAB_INSIGHT=0 to silence",
            type(dev).__name__,
        )
        return None
    insight = InsightTier(
        limiter=dev,
        sketch_capacity=config.insight_sketch,
        topk=config.insight_topk,
        window_s=config.insight_window_s,
        poll_ms=config.insight_poll_ms,
        decay_s=config.insight_decay_s,
        prewarm=config.insight_prewarm,
        hot_denies=config.insight_hot_denies,
        shed_weight=config.insight_shed_weight,
        front=front,
    )
    if metrics is not None:
        metrics.set_insight_stats_provider(insight.metric_stats)
    # Pay the poll ops' jit compiles at boot, not inside the first
    # serving flush (InsightTier.prime docstring has the numbers).
    insight.prime()
    return insight


def create_control(config, metrics, limiter, front, insight,
                   cleanup_policy):
    """Build the control plane (L3.9: adaptive feedback over the knob
    surface) from the THROTTLECRAB_CONTROL_* knobs, or None when
    disabled — the kill switch builds NOTHING, so decisions and every
    knob value are bit-identical to the subsystem absent.  Sensors and
    actuators register only for the subsystems this deployment actually
    built (a front-less boot simply has fewer knobs to move)."""
    from ..control import create_control_plane

    plane = create_control_plane(
        config,
        front=front,
        insight=insight,
        cleanup_policy=cleanup_policy,
        limiter=limiter,
        metrics=metrics,
    )
    if plane is not None:
        log.info(
            "control plane armed: mode=%s tick=%dms actuators=%s",
            config.control_mode, config.control_tick_ms,
            ",".join(plane.registry.names()),
        )
    return plane


def create_cleanup_policy(config) -> CleanupPolicy:
    """store.rs:57-87: the store type decides when cleanup runs."""
    if config.store == "periodic":
        return make_policy(
            "periodic", cleanup_interval_secs=config.store_cleanup_interval
        )
    if config.store == "probabilistic":
        return make_policy(
            "probabilistic",
            cleanup_probability=config.store_cleanup_probability,
        )
    return make_policy(
        "adaptive",
        min_interval_secs=config.store_min_interval,
        max_interval_secs=config.store_max_interval,
        max_operations=config.store_max_operations,
    )
