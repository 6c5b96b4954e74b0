"""Key-sharded bucket table over a `jax.sharding.Mesh`.

The TPU-native replacement for the reference's *only* horizontal-scaling
story — "shard keys across instances client-side" (`README.md:247-249`) —
done inside the framework instead: the table lives sharded over the mesh's
``shard`` axis, every device runs the same batched GCRA kernel on its local
shard (`shard_map`), and the per-batch allowed/denied counters are
``psum``-reduced across the mesh so multi-tenant metrics are global without a
host-side gather (BASELINE.json config 5).

Design notes (TPU-first):
- One launch decides the whole mesh's batch: inputs are stacked ``[D, B]``
  arrays sharded on axis 0, so each device sees only its ``[1, B]`` slice.
  No cross-device traffic on the hot path — a key's state lives on exactly
  one shard (hash routing on the host), so the kernel body is embarrassingly
  parallel; the only collectives are the tiny counter ``psum``s over ICI.
- The host routes keys to shards with a stable CRC32 hash — one vectorized
  numpy pass per batch (parallel/tenants.py), bit-identical to the
  ``zlib.crc32`` the per-key form uses — and keeps one keymap per shard,
  mirroring how a multi-instance deployment of the reference would
  partition its HashMaps.
- The insight tier (L3.75) is mesh-native: with ``insight=True`` the shard
  rows widen to ``kernel.INS_WIDTH`` so the per-slot denied-hit counter
  rides the SAME per-shard row gather/scatter the decision path already
  pays (the fuse-into-the-row design PR 4 measured at ~0.8%% overhead),
  totals ride the existing counter ``psum``, and the top-K poll is ONE
  mesh launch: each shard computes its device-side partial top-K and an
  ``all_gather`` over the ``shard`` axis merges the partials, so
  ``InsightTier`` polls one mesh-global result.
- Tenants/namespaces (the prefix before the first delimiter) are a
  first-class dimension (parallel/tenants.py): optional tenant-affine
  routing makes a tenant's keys shard-local, per-tenant allowed/denied
  counters are psum-reduced in-launch, and per-tenant slot quotas keep one
  abusive tenant from filling every shard's keymap.
"""

from __future__ import annotations

import threading
import zlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from ..core.errors import InternalError
from ..tpu.kernel import (
    EMPTY_EXPIRY,
    INS_WIDTH,
    _gcra_body,
    _split_cols,
    cur_wire_safe,
    finish_cur,
    finish_w32,
    fits_w32_wire,
    pack_state,
    unpack_deny,
    unpack_state,
)
from ..tpu.table import (
    HwmMarksMixin,
    _host_max_now,
    _host_max_tol,
    track_cur_safety,
)
from ..tpu.keymap import PyKeyMap
from ..tpu.limiter import (
    STATUS_TENANT_QUOTA,
    BatchResult,
    _ReadyLaunch,
    ScalarCompatMixin,
    TpuRateLimiter,
    WireBatchResult,
    has_degenerate,
    param_rounds,
    prepare_batch,
    segment_info,
    sequential_fallback,
)
from ..tpu.profiling import span
from .tenants import (
    KeyTooLong,
    TenantRegistry,
    crc32_rows,
    key_matrix,
    prefix_lens,
)

AXIS = "shard"


def shard_of_key(key: bytes, n_shards: int) -> int:
    """Stable key→shard routing (host-side, CRC32 — C speed via zlib).

    The single-key form; batches route through the vectorized
    numpy CRC32 twin (tenants.crc32_rows), pinned bit-identical."""
    return zlib.crc32(key) % n_shards


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D ``(shard,)`` mesh over the first ``n_devices`` devices.

    Raises when fewer devices exist than requested — silently shrinking
    the mesh would give the caller fewer shards (and less capacity/
    throughput) than they provisioned for."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"requested a {n_devices}-device mesh but the backend "
                    f"exposes {len(devices)}"
                )
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (AXIS,))


class ShardedBucketTable(HwmMarksMixin):
    """Per-slot GCRA state sharded ``[D, rows, W]`` over the mesh.

    ``W`` is 4 (packed tat/expiry halves), or ``kernel.INS_WIDTH`` when
    the table carries the insight tier's per-slot denied-hit counter —
    the exact same row layouts as the single-device ``BucketTable``, so
    the shard-mapped kernel body is byte-for-byte the same program per
    shard (``THROTTLECRAB_INSIGHT=0`` compiles the identical pre-insight
    graph, not a traced branch).

    ``tenant_slots`` > 0 adds a per-lane tenant-id input to the decision
    launches and a psum-reduced ``[T, 2]`` (allowed, denied) per-tenant
    counter output riding the existing global-counter fetch.
    """

    SCRATCH = 1 << 16

    def __init__(
        self,
        capacity_per_shard: int,
        mesh: Mesh,
        insight: bool = False,
        tenant_slots: int = 0,
    ) -> None:
        self.mesh = mesh
        self.n_shards = mesh.shape[AXIS]
        self.capacity = capacity_per_shard
        self.insight = bool(insight)
        self.tenant_slots = int(tenant_slots)
        self.width = INS_WIDTH if self.insight else 4
        self.sharding = NamedSharding(mesh, P(AXIS, None, None))
        self.row_sharding = NamedSharding(mesh, P(AXIS))
        self.replicated = NamedSharding(mesh, P())
        rows = capacity_per_shard + self.SCRATCH
        self.state = jax.device_put(
            self._host_empty(self.n_shards, rows, self.width), self.sharding
        )
        self._step_cache: dict = {}
        # Mesh-global [allowed, denied] totals for the insight tier:
        # the decision launches already psum these per batch, and the
        # per-launch fetch already lands them on the host — so unlike
        # the single-device table there is nothing device-resident to
        # poll; the limiter folds each launch's counters in here
        # (note_insight_counts) and insight_counts() is free.
        self.ins_allowed = 0
        self.ins_denied = 0
        # Cross-launch compact="cur" certificate, same contract as
        # BucketTable.cur_safe (tpu/table.py track_cur_safety).
        self.cur_safe = True
        # High-water marks for the compact="w32" certificate
        # (HwmMarksMixin, shared with BucketTable).
        self.tol_hwm = 0
        self.now_hwm = 0

    @staticmethod
    def _host_empty(d: int, rows: int, width: int = 4):
        st = pack_state(
            jnp.zeros((d, rows), jnp.int64),
            jnp.full((d, rows), EMPTY_EXPIRY, jnp.int64),
        )
        if width > 4:
            st = jnp.concatenate(
                [st, jnp.zeros((d, rows, width - 4), jnp.int32)], axis=-1
            )
        return st

    # ------------------------------------------------------------------ #

    def _tenant_fold(self, tenant, allowed_b, denied_b):
        """One sub-batch's [T, 2] per-tenant (allowed, denied) counts.

        A one-hot compare + two masked reductions — pure VPU work, no
        scatter (a [B]-lane scatter-add would serialize on TPU; the
        separate-counter-column design PR 4 rejected measured +35-50%
        on CPU for exactly that reason).  T is static per trace."""
        trange = jnp.arange(self.tenant_slots, dtype=jnp.int32)
        onehot = tenant[None, :] == trange[:, None]  # [T, B]
        ta = jnp.sum(onehot & allowed_b[None, :], axis=1)
        td = jnp.sum(onehot & denied_b[None, :], axis=1)
        return jnp.stack([ta, td], axis=1).astype(jnp.int64)

    def _step(self, with_degen: bool, compact):
        """Build (and cache) the jitted shard-mapped decision step.

        `compact` may be "cur" (one i64/request off the mesh, see
        kernel._finish) — the output rank and the allowed-counter read
        change with it."""
        T = self.tenant_slots
        key = (with_degen, compact, T)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        # cur AND w32 both emit one word per request with the allowed
        # bit at bit 0 (the w32 field layout starts with it).
        cur = compact in ("cur", "w32")

        def local(state, slots, rank, is_last, em, tol, q, valid, now,
                  *tenant):
            st, out, n_exp = _gcra_body(
                state[0],
                (
                    slots[0],
                    rank[0].astype(jnp.int64),
                    is_last[0],
                    em[0],
                    tol[0],
                    q[0],
                    valid[0],
                    now,
                ),
                with_degen=with_degen,
                compact=compact,
                count_expired=True,
            )
            allowed_b = ((out & 1) != 0) if cur else (out[0] != 0)
            denied_b = valid[0] & ~allowed_b
            n_allowed = jnp.sum(allowed_b.astype(jnp.int64))
            n_valid = jnp.sum(valid[0].astype(jnp.int64))
            # The collectives on the hot path: global allowed/denied/
            # expired-hit totals (BASELINE config 5's psum-reduced
            # counters; expired hits feed the adaptive cleanup trigger)
            # and, with tenants armed, the [T, 2] per-tenant totals —
            # all tiny ICI traffic.
            counters = lax.psum(
                jnp.stack([n_allowed, n_valid - n_allowed, n_exp]), AXIS
            )
            if not T:
                return st[None], out[None], counters
            tcounts = lax.psum(
                self._tenant_fold(tenant[0][0], allowed_b, denied_b), AXIS
            )
            return st[None], out[None], counters, tcounts

        out_spec = P(AXIS, None) if cur else P(AXIS, None, None)
        in_specs = [
            P(AXIS, None, None),
            *([P(AXIS, None)] * 7),
            P(),
        ]
        out_specs = [P(AXIS, None, None), out_spec, P()]
        if T:
            in_specs.append(P(AXIS, None))
            out_specs.append(P())
        mapped = _shard_map(
            local,
            mesh=self.mesh,
            in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
        )
        fn = jax.jit(mapped, donate_argnums=(0,))
        self._step_cache[key] = fn
        return fn

    def _put_shards(self, *arrays):
        """Stacked [D, ...] host inputs, each shard's slice sent straight
        to its own device (not staged on the default device first)."""
        return [
            jax.device_put(np.asarray(x, dtype), self.row_sharding)
            for x, dtype in arrays
        ]

    def check_batch(
        self,
        slots,
        rank,
        is_last,
        emission,
        tolerance,
        quantity,
        valid,
        now_ns: int,
        with_degen: bool = True,
        compact: bool = False,
        params_cur_safe: bool = False,
        tenant=None,
    ):
        """Decide stacked ``[D, B]`` per-shard batches in one launch.

        Returns (out device array, (allowed, denied, expired) global
        counts, per-tenant [T, 2] counts or None); out is [D, 4, B]
        planes, or i64[D, B] `cur*2+allowed` words when compact="cur"
        (host-finish with kernel.finish_cur).
        """
        assert slots.shape[1] <= self.SCRATCH
        track_cur_safety(self, compact, params_cur_safe)
        self.note_max_tolerance(_host_max_tol(valid, tolerance))
        self.note_launch_now(_host_max_now(now_ns))
        step = self._step(with_degen, compact)
        args = [
            self.state,
            *self._put_shards(
                (slots, np.int32), (rank, np.int32), (is_last, bool),
                (emission, np.int64), (tolerance, np.int64),
                (quantity, np.int64), (valid, bool),
            ),
            jax.device_put(np.asarray(now_ns, np.int64), self.replicated),
        ]
        if self.tenant_slots:
            if tenant is None:
                tenant = np.zeros(slots.shape, np.int32)
            args.extend(self._put_shards((tenant, np.int32)))
            self.state, out, counters, tcounts = step(*args)
        else:
            self.state, out, counters = step(*args)
            tcounts = None
        return out, counters, tcounts

    # ------------------------------------------------------------------ #

    def _scan_step(self, with_degen: bool, compact: bool):
        """Build (and cache) the jitted shard-mapped K-deep scan step.

        The backlog-draining analog of kernel.gcra_scan on the mesh: each
        device scans its own K sub-batches against its local shard (the
        lax.scan carry is the shard's state), so one launch decides K×D
        sub-batches; the only collectives are one psum of the summed
        counters (and the summed per-tenant counters) after the scan.
        """
        T = self.tenant_slots
        key = ("scan", with_degen, compact, T)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        cur = compact in ("cur", "w32")  # one word/request, allowed at bit 0

        def local(state, slots, rank, is_last, em, tol, q, valid, now,
                  *tenant):
            def step(st, batch):
                sl, rk, il, e, t, qq, v, nw, *tn = batch
                st, out, n_exp = _gcra_body(
                    st,
                    (sl, rk.astype(jnp.int64), il, e, t, qq, v, nw),
                    with_degen=with_degen,
                    compact=compact,
                    count_expired=True,
                )
                allowed_b = ((out & 1) != 0) if cur else (out[0] != 0)
                denied_b = v & ~allowed_b
                n_allowed = jnp.sum(allowed_b.astype(jnp.int64))
                n_valid = jnp.sum(v.astype(jnp.int64))
                outs = (
                    out,
                    jnp.stack([n_allowed, n_valid - n_allowed, n_exp]),
                )
                if T:
                    outs = outs + (
                        self._tenant_fold(tn[0], allowed_b, denied_b),
                    )
                return st, outs

            xs = [
                slots[0], rank[0], is_last[0], em[0], tol[0], q[0],
                valid[0], now,
            ]
            if T:
                xs.append(tenant[0][0])
            st, scanned = lax.scan(step, state[0], tuple(xs))
            outs, counts = scanned[0], scanned[1]
            counters = lax.psum(counts.sum(axis=0), AXIS)
            if not T:
                return st[None], outs[None], counters
            tcounts = lax.psum(scanned[2].sum(axis=0), AXIS)
            return st[None], outs[None], counters, tcounts

        out_spec = (
            P(AXIS, None, None) if cur else P(AXIS, None, None, None)
        )
        in_specs = [
            P(AXIS, None, None),
            *([P(AXIS, None, None)] * 7),
            P(),
        ]
        out_specs = [P(AXIS, None, None), out_spec, P()]
        if T:
            in_specs.append(P(AXIS, None, None))
            out_specs.append(P())
        mapped = _shard_map(
            local,
            mesh=self.mesh,
            in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
        )
        fn = jax.jit(mapped, donate_argnums=(0,))
        self._step_cache[key] = fn
        return fn

    def compile_launch(self, depth: int, batch: int, *, with_degen, compact):
        """Compile, without running, the scan step check_many launches
        for `depth` sub-batches of `batch` lanes per shard."""
        lanes = (self.n_shards, depth, batch)

        def lane(dtype):
            return jax.ShapeDtypeStruct(lanes, dtype,
                                        sharding=self.row_sharding)

        args = [
            jax.ShapeDtypeStruct(self.state.shape, self.state.dtype,
                                 sharding=self.sharding),
            lane(jnp.int32), lane(jnp.int32), lane(jnp.bool_),
            lane(jnp.int64), lane(jnp.int64), lane(jnp.int64),
            lane(jnp.bool_),
            jax.ShapeDtypeStruct((depth,), jnp.int64,
                                 sharding=self.replicated),
        ]
        if self.tenant_slots:
            args.append(lane(jnp.int32))
        step = self._scan_step(with_degen, compact)
        return step.lower(*args).compile()

    def check_many(
        self,
        slots,
        rank,
        is_last,
        emission,
        tolerance,
        quantity,
        valid,
        now_ns,
        with_degen: bool = True,
        compact: bool = False,
        params_cur_safe: bool = False,
        tenant=None,
    ):
        """K stacked sub-batches per shard (``[D, K, B]`` inputs, i64[K]
        timestamps) in ONE launch.

        Returns (out device array, (allowed, denied, expired) totals,
        per-tenant [T, 2] counts or None); out is [D, K, 4, B] planes,
        or i64[D, K, B] `cur*2+allowed` words when compact="cur"
        (host-finish with kernel.finish_cur).
        """
        assert slots.shape[2] <= self.SCRATCH
        track_cur_safety(self, compact, params_cur_safe)
        self.note_max_tolerance(_host_max_tol(valid, tolerance))
        self.note_launch_now(_host_max_now(now_ns))
        step = self._scan_step(with_degen, compact)
        args = [
            self.state,
            *self._put_shards(
                (slots, np.int32), (rank, np.int32), (is_last, bool),
                (emission, np.int64), (tolerance, np.int64),
                (quantity, np.int64), (valid, bool),
            ),
            jax.device_put(np.asarray(now_ns, np.int64), self.replicated),
        ]
        if self.tenant_slots:
            if tenant is None:
                tenant = np.zeros(slots.shape, np.int32)
            args.extend(self._put_shards((tenant, np.int32)))
            self.state, out, counters, tcounts = step(*args)
        else:
            self.state, out, counters = step(*args)
            tcounts = None
        return out, counters, tcounts

    # ---- insight tier (L3.75) on the mesh ----------------------------- #

    def note_insight_counts(self, allowed: int, denied: int) -> None:
        """Fold one fetched launch's psum'd global counters into the
        insight totals (the limiter calls this under its counter lock)."""
        self.ins_allowed += allowed
        self.ins_denied += denied

    def insight_counts(self) -> tuple:
        """(allowed_total, denied_total) across the whole mesh.  Free:
        the totals ride the per-launch psum'd counter fetch, so unlike
        BucketTable.insight_counts there is no device round trip."""
        return self.ins_allowed, self.ins_denied

    def _topk_fn(self, k: int):
        """Build (and cache) the ONE-launch mesh-global top-K: each
        shard computes its device-side partial top-K over its local
        denied-hit column, an ``all_gather`` over the ``shard`` axis
        merges the D×k partials, and every device reduces the same
        global top-K (the merge lives on the mesh, not the host).  Slot
        ids come back GLOBAL: ``shard * capacity + local_slot``."""
        key = ("topk", k)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        capacity = self.capacity

        def local(state):
            deny = unpack_deny(state[0][:capacity])
            vals, idx = lax.top_k(deny, k)
            d = lax.axis_index(AXIS).astype(jnp.int32)
            gids = d * capacity + idx.astype(jnp.int32)
            # Merge the partials over ICI; every shard then holds the
            # identical global candidate set, so the final top-K below
            # is replicated by construction (the out_specs keep the
            # per-shard copies and the host reads shard 0's — one tiny
            # [D, k] fetch, no replication-inference fragility).
            gv = lax.all_gather(vals, AXIS).reshape(-1)
            gi = lax.all_gather(gids, AXIS).reshape(-1)
            top_v, top_pos = lax.top_k(gv, k)
            return top_v[None], gi[top_pos][None]

        mapped = _shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(AXIS, None, None),),
            out_specs=(P(AXIS, None), P(AXIS, None)),
        )
        fn = jax.jit(mapped)
        self._step_cache[key] = fn
        return fn

    def insight_topk(self, k: int):
        """Mesh-global partial top-K of the denied-hit column:
        (counts i64[k], GLOBAL slot ids i32[k]) device arrays, highest
        first — decode ids as (shard, slot) = divmod(id, capacity)
        (insight.collector.ShardedSlotKeyResolver does).  One tiny mesh
        launch per insight poll (~1/s), never on the decision path."""
        if not self.insight:
            return None
        k = max(1, min(int(k), self.capacity))
        vals, gids = self._topk_fn(k)(self.state)
        return vals[0], gids[0]

    def _decay_fn(self):
        """Build (and cache) the shard-mapped denied-column halving."""
        fn = self._step_cache.get("decay")
        if fn is not None:
            return fn

        def local(state):
            st = state[0]
            st = jnp.concatenate(
                [st[..., :4], _split_cols(unpack_deny(st) // 2)], axis=-1
            )
            return st[None]

        mapped = _shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(AXIS, None, None),),
            out_specs=P(AXIS, None, None),
        )
        fn = jax.jit(mapped, donate_argnums=(0,))
        self._step_cache["decay"] = fn
        return fn

    def insight_decay(self) -> None:
        """Halve every shard's denied-hit counter columns (periodic
        heat decay, same semantics as kernel.insight_decay)."""
        if self.insight:
            self.state = self._decay_fn()(self.state)

    # ------------------------------------------------------------------ #

    def _sweep_fn(self):
        """Build (and cache) the jitted shard-mapped sweep."""
        fn = self._step_cache.get("sweep")
        if fn is not None:
            return fn
        capacity = self.capacity

        def local(now, state):
            st0 = state[0]
            _, expiry = unpack_state(st0)
            expired = expiry <= now
            empty = pack_state(
                jnp.zeros_like(expiry), jnp.full_like(expiry, EMPTY_EXPIRY)
            )
            if st0.shape[-1] > 4:
                # Insight-widened rows: a vacated slot's denied-hit
                # count dies with it (kernel.sweep_expired_ins), or the
                # next key recycled into the slot inherits stale heat.
                empty = jnp.concatenate(
                    [
                        empty,
                        jnp.zeros(
                            st0.shape[:-1] + (st0.shape[-1] - 4,),
                            jnp.int32,
                        ),
                    ],
                    axis=-1,
                )
            st = jnp.where(expired[:, None], empty, st0)
            return st[None], expired[None, :capacity]

        mapped = _shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(), P(AXIS, None, None)),
            out_specs=(P(AXIS, None, None), P(AXIS, None)),
        )
        fn = jax.jit(mapped, donate_argnums=(1,))
        self._step_cache["sweep"] = fn
        return fn

    def sweep(self, now_ns: int) -> np.ndarray:
        """Vacate expired slots on every shard; returns bool[D, capacity]."""
        self.state, expired = self._sweep_fn()(
            jnp.asarray(now_ns, jnp.int64), self.state
        )
        return np.asarray(expired)

    def grow(self, new_capacity: int) -> None:
        if new_capacity <= self.capacity:
            return
        extra = jax.device_put(
            self._host_empty(
                self.n_shards, new_capacity - self.capacity, self.width
            ),
            self.sharding,
        )
        real = self.state[:, : self.capacity]
        scratch = self.state[:, self.capacity :]
        self.state = jax.device_put(
            jnp.concatenate([real, extra, scratch], axis=1), self.sharding
        )
        self.capacity = new_capacity
        self._step_cache.clear()

    @property
    def tat(self):
        """i64[D, capacity] TAT columns (diagnostics/tests)."""
        return unpack_state(self.state)[0][:, : self.capacity]

    @property
    def expiry(self):
        """i64[D, capacity] expiry columns (diagnostics/tests)."""
        return unpack_state(self.state)[1][:, : self.capacity]

    @property
    def deny(self):
        """i64[D, capacity] denied-hit columns (insight tables only;
        diagnostics/tests)."""
        return unpack_deny(self.state)[:, : self.capacity]


class _PreparedWindow:
    """One host-prepared batch: routed, resolved, stacked [D, B] arrays
    plus the request-order bookkeeping fetch() needs to distribute
    per-shard results back to arrival positions."""

    __slots__ = (
        "n", "per_shard", "slots", "rank", "is_last", "em", "tol", "q",
        "vmask", "rounds", "max_burst", "status", "valid", "emission",
        "tolerance", "quantity", "tenant",
    )

    def __init__(self, **kw) -> None:
        for name in self.__slots__:
            setattr(self, name, kw[name])


class _PendingShardedLaunch:
    """An in-flight mesh launch; .fetch() blocks on the stacked output,
    accumulates the psum'd global (and per-tenant) counters, and
    distributes per-batch results.

    `now_list` is set iff the launch used the compact="cur" output
    (i64[D, K, B], 8 B/request off the mesh instead of 16): fetch then
    completes the exact i32 wire values per shard slice with
    kernel.finish_cur, exactly like the single-device path.  `w32` marks
    the 4 B/request device-packed tier (kernel.finish_w32 unpack)."""

    def __init__(
        self, limiter, out_dev, counters, prepared, wire, now_list=None,
        w32=False, tcounts=None,
    ) -> None:
        self._limiter = limiter
        self._out_dev = out_dev
        self._counters = counters
        self._tcounts = tcounts
        self._prepared = prepared
        self._wire = wire
        self._now_list = now_list
        self._w32 = w32

    def fetch(self) -> list:
        out = np.asarray(self._out_dev)
        c = np.asarray(self._counters)
        tc = (
            np.asarray(self._tcounts) if self._tcounts is not None else None
        )
        self._limiter._bump_counters(
            int(c[0]), int(c[1]), int(c[2]), tcounts=tc
        )
        results = []
        for j, prep in enumerate(self._prepared):
            n = prep.n
            allowed = np.zeros(n, bool)
            remaining = np.zeros(n, np.int64)
            reset_after = np.zeros(n, np.int64)
            retry_after = np.zeros(n, np.int64)
            for d, ix in enumerate(prep.per_shard):
                m = len(ix)
                if m == 0:
                    continue
                sel = prep.vmask[d, :m]
                dst = ix[sel]
                if self._w32:
                    al, rem, res, ret = finish_w32(out[d, j, :m][sel])
                    allowed[dst] = al != 0
                    remaining[dst] = rem
                    reset_after[dst] = res
                    retry_after[dst] = ret
                elif self._now_list is not None:
                    al, rem, res, ret = finish_cur(
                        out[d, j, :m][sel], prep.emission[dst],
                        prep.tolerance[dst], prep.quantity[dst],
                        self._now_list[j],
                    )
                    allowed[dst] = al != 0
                    remaining[dst] = rem
                    reset_after[dst] = res
                    retry_after[dst] = ret
                else:
                    allowed[dst] = out[d, j, 0, :m][sel] != 0
                    remaining[dst] = out[d, j, 1, :m][sel]
                    reset_after[dst] = out[d, j, 2, :m][sel]
                    retry_after[dst] = out[d, j, 3, :m][sel]
            results.append(
                self._limiter._make_result(
                    prep.valid, prep.max_burst, prep.status, allowed,
                    remaining, reset_after, retry_after, self._wire,
                )
            )
        return results


class ShardedTpuRateLimiter(ScalarCompatMixin):
    """Batched GCRA with the table sharded over a device mesh.

    Same request semantics as `tpu.limiter.TpuRateLimiter` (arrival-order
    duplicate handling, reference-exact param derivation); keys are routed to
    shards by CRC32 (one vectorized numpy pass per batch) and each shard's
    sub-batch is decided on its own device.

    ``insight=True`` widens the shard rows to the L3.75 layout so the
    insight tier serves mesh deployments; ``tenants`` (a
    tenants.TenantRegistry) arms the namespace layer — tenant-affine
    routing, psum-reduced per-tenant counters, and per-tenant slot
    quotas.
    """

    MIN_PAD = 16

    def __init__(
        self,
        capacity_per_shard: int = 1 << 17,
        mesh: Optional[Mesh] = None,
        keymap="python",
        auto_grow: bool = True,
        insight: bool = False,
        tenants: Optional[TenantRegistry] = None,
    ) -> None:
        """`keymap` selects the per-shard host key→slot backend: "python",
        "native", "auto", or a factory callable `capacity -> keymap`."""
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_shards = self.mesh.shape[AXIS]
        self.tenants = tenants
        self.table = ShardedBucketTable(
            capacity_per_shard,
            self.mesh,
            insight=insight,
            tenant_slots=tenants.max_tenants if tenants is not None else 0,
        )
        if keymap == "auto":
            from ..native import native_available

            keymap = "native" if native_available() else "python"
        if keymap == "native":
            from ..native import NativeKeyMap

            factory = NativeKeyMap
        elif keymap == "python":
            factory = PyKeyMap
        elif callable(keymap):
            factory = keymap
        else:
            raise ValueError(f"unknown keymap backend: {keymap!r}")
        self.keymaps = [factory(capacity_per_shard) for _ in range(self.n_shards)]
        self._bytes_keys = bool(
            getattr(self.keymaps[0], "BYTES_KEYS", False)
        )
        self.auto_grow = auto_grow
        # Per-slot tenant attribution (i32[capacity] per shard, -1 =
        # vacant): filled at slot-ALLOCATION time, so per-request
        # tenant ids in steady state are one numpy gather — no Python
        # prefix extraction on the hot path — and doubles as the
        # slot-quota ledger (`_tenant_used` counts each tenant's live
        # slots per shard; quota enforced when the registry carries
        # one).
        if tenants is not None:
            self._tenant_of_slot = [
                np.full(capacity_per_shard, -1, np.int32)
                for _ in range(self.n_shards)
            ]
            self._tenant_used = [
                np.zeros(tenants.max_tenants, np.int64)
                for _ in range(self.n_shards)
            ]
        else:
            self._tenant_of_slot = None
            self._tenant_used = None
        # psum-reduced global totals, updated per batch.  Fetches can run
        # on an engine executor thread concurrently with a native
        # transport's decide thread, so accumulation takes its own lock.
        self.total_allowed = 0
        self.total_denied = 0
        self.total_expired_hits = 0
        self._counter_lock = threading.Lock()

    def __len__(self) -> int:
        return sum(len(km) for km in self.keymaps)

    def _bump_counters(
        self, allowed: int, denied: int, expired: int = 0, tcounts=None
    ) -> None:
        """Accumulate the psum'd global counters; a launch fetch (engine
        executor thread) can race a native transport's decide thread."""
        with self._counter_lock:
            self.total_allowed += allowed
            self.total_denied += denied
            self.total_expired_hits += expired
            if self.table.insight:
                self.table.note_insight_counts(allowed, denied)
            if tcounts is not None and self.tenants is not None:
                self.tenants.add_counts(tcounts)

    def take_expired_hits(
        self, now_ns: int = 0, min_period_ns: int = 0
    ) -> int:
        """Drain the expired-hit counter for the cleanup policy.  Free:
        the counts ride the already-fetched psum counters (no device
        round trip), so both arguments exist only for signature parity
        with TpuRateLimiter.take_expired_hits (no throttle needed)."""
        with self._counter_lock:
            n = self.total_expired_hits
            self.total_expired_hits = 0
            return n

    def tenant_stats(self) -> dict:
        """Mesh-global per-tenant counters for /stats and metrics
        ({tenant: {"allowed", "denied", "quota_rejections"}}); empty
        when the tenant layer is off."""
        if self.tenants is None:
            return {}
        with self._counter_lock:
            return self.tenants.stats()

    @property
    def total_capacity(self) -> int:
        """Global slot capacity across every shard (len() is also global)."""
        return self.table.capacity * self.n_shards

    # ------------------------------------------------------------------ #

    def shard_of(self, key: bytes) -> int:
        """This limiter's key→shard routing (single-key form): the
        tenant-affine hash when armed, plain full-key CRC32 otherwise.
        Snapshot restore routes through this so restored keys land on
        the shard the serving path will look them up on."""
        reg = self.tenants
        if reg is not None and reg.affinity:
            p = key.find(reg.delim_byte)
            if p > 0:
                return zlib.crc32(key[:p]) % self.n_shards
        return shard_of_key(key, self.n_shards)

    def _route(self, bkeys, n):
        """(shard_ids i32[n], prefix_lens i64[n] or None) for a batch —
        ONE vectorized numpy CRC32 pass over the stacked key bytes
        (tenants.crc32_rows) instead of a per-key Python loop; the
        per-key zlib form survives only as the fallback for exotic
        hashable keys (python keymap) and the routing oracle in tests.
        Tenant IDS are resolved later, at slot-allocation time
        (_attribute_tenants) — steady-state traffic reads them off the
        per-slot cache with one gather, no prefix extraction."""
        D = self.n_shards
        reg = self.tenants
        try:
            mat, lens = key_matrix(bkeys)
        except (TypeError, KeyTooLong):
            # A non-str/bytes hashable key (python keymap only) or an
            # oversized key (the matrix costs O(n × longest key); one
            # huge key must not inflate the whole batch's routing)
            # forces the per-key path for THIS batch — but each bytes
            # key must still route exactly as the vectorized path
            # would (incl. tenant affinity: shard_of is the single-key
            # twin), or a mixed batch would fork a key's bucket across
            # shards.  Exotic keys route via hash() and live in the
            # default namespace (prefix length 0).
            shard_ids = np.fromiter(
                (
                    self.shard_of(bytes(k))
                    if isinstance(k, (bytes, bytearray))
                    else hash(k) % D
                    for k in bkeys
                ),
                np.int32,
                count=n,
            )
            plens = None
            if reg is not None:
                delim = reg.delim_byte
                plens = np.fromiter(
                    (
                        max(bytes(k).find(delim), 0)
                        if isinstance(k, (bytes, bytearray))
                        else 0
                        for k in bkeys
                    ),
                    np.int64,
                    count=n,
                )
            return shard_ids, plens
        crc = crc32_rows(mat, lens)
        if reg is None:
            return (crc % np.uint32(D)).astype(np.int32), None
        plens = prefix_lens(mat, lens, reg.delim_byte)
        if reg.affinity:
            # Tenant-affine: a namespaced key routes by its namespace
            # hash, so one tenant's keys are shard-local; bare keys
            # (no delimiter) keep spreading by full-key hash.
            tcrc = crc32_rows(mat, plens)
            crc = np.where(plens > 0, tcrc, crc)
        return (crc % np.uint32(D)).astype(np.int32), plens

    def _grow_tenant_slots(self, new_capacity: int) -> None:
        if self._tenant_of_slot is None:
            return
        for d in range(self.n_shards):
            old = self._tenant_of_slot[d]
            if new_capacity > len(old):
                grown = np.full(new_capacity, -1, np.int32)
                grown[: len(old)] = old
                self._tenant_of_slot[d] = grown

    def _refuse_over_quota_missing(
        self, d: int, km, sl, ix, bkeys, plens, svalid
    ):
        """Quota-refuse UNRESOLVED fresh keys (table-full lanes) BEFORE
        any growth: an at-quota tenant spraying keys into a full shard
        must never force the table to grow (the guarantee
        parallel/tenants.py documents) — growth is warranted only when
        within-quota keys still need capacity.

        Conservative by construction: usage is counted from the real
        ledger plus this batch's pending acceptances; a key accepted
        here can still be refused by the authoritative post-resolve
        attribution (earlier resolved lanes may consume the quota
        first), costing at most one unnecessary growth — never a wrong
        admission.  Returns a bool[m] rejected mask or None."""
        reg = self.tenants
        if reg.quota_frac <= 0:
            return None
        used = self._tenant_used[d]
        cap = max(int(reg.quota_frac * km.capacity), 1)
        missing = np.flatnonzero(svalid & (sl < 0))
        if not len(missing):
            return None
        pending = np.zeros_like(used)
        decided: dict = {}
        rejected = None
        for lane in missing:
            gi = ix[lane]
            key = bkeys[gi]
            acc = decided.get(key)
            if acc is None:
                p = int(plens[gi]) if plens is not None else 0
                tid = reg.tid_of(
                    bytes(key[:p]) if p else b""
                )
                acc = used[tid] + pending[tid] < cap
                if acc:
                    pending[tid] += 1
                else:
                    reg.quota_rejections[tid] += 1
                decided[key] = acc
            if not acc:
                if rejected is None:
                    rejected = np.zeros(len(sl), bool)
                rejected[lane] = True
        return rejected

    def _attribute_tenants(self, d: int, km, sl, ix, bkeys, plens):
        """Per-lane tenant ids for shard d's resolved lanes, plus quota
        enforcement.

        Steady state is one numpy gather: a slot allocated earlier
        already carries its tenant id in the per-slot cache.  Only
        FRESH allocations (cache miss, tenant id -1) pay a Python
        prefix extraction + registry probe — and, when the registry
        carries a quota, the arrival-order admission decision: each
        fresh key either fits its tenant's quota (the slot is
        attributed) or is refused — the just-allocated slot is freed
        back to the keymap and every lane of that key is rejected with
        STATUS_TENANT_QUOTA.  Existing keys (attributed slots) are
        never touched, so an at-quota tenant keeps deciding on its
        live keys.

        Returns (tenant ids i32[m], rejected bool[m] mask or None)."""
        reg = self.tenants
        tos = self._tenant_of_slot[d]
        used = self._tenant_used[d]
        quota = reg.quota_frac > 0
        cap = max(int(reg.quota_frac * km.capacity), 1)
        tids_lane = tos[np.maximum(sl, 0)].copy()
        tids_lane[sl < 0] = 0
        fresh = np.flatnonzero((sl >= 0) & (tids_lane == -1))
        if not len(fresh):
            return tids_lane, None
        rejected = None
        decided: dict = {}
        freed = []
        for lane in fresh:
            slot = int(sl[lane])
            tid = decided.get(slot)
            if tid is None:
                gi = ix[lane]
                p = int(plens[gi]) if plens is not None else 0
                # p == 0 covers bare keys AND exotic non-bytes keys
                # (the _route fallback): both live in the default
                # namespace without touching the key object.
                tid = reg.tid_of(bytes(bkeys[gi][:p]) if p else b"")
                if quota and used[tid] >= cap:
                    reg.quota_rejections[tid] += 1
                    freed.append(slot)
                    tid = ~tid  # mark refused (recoverable below)
                else:
                    used[tid] += 1
                    tos[slot] = tid
                decided[slot] = tid
            if tid < 0:
                if rejected is None:
                    rejected = np.zeros(len(sl), bool)
                rejected[lane] = True
                tids_lane[lane] = 0
            else:
                tids_lane[lane] = tid
        if freed:
            km.free_slots(np.asarray(freed, np.int64))
        return tids_lane, rejected

    def _prepare_sharded(
        self, keys, max_burst, count_per_period, period, quantity, now_ns
    ) -> _PreparedWindow:
        """Shared per-batch prologue: validate, derive params, route keys
        to shards (one vectorized hash pass), resolve per-shard slots
        (growing on full, enforcing tenant quotas), build the stacked
        [D, B] arrays + conflict rounds.  One implementation for the
        single-batch and scan paths."""
        if now_ns < 0:
            raise ValueError("batch now_ns must be non-negative")
        n = len(keys)
        bkeys = [k.encode() if isinstance(k, str) else k for k in keys]
        max_burst, quantity, emission, tolerance, status, valid = (
            prepare_batch(n, max_burst, count_per_period, period, quantity)
        )

        D = self.n_shards
        shard_ids, plens = self._route(bkeys, n)
        # Per-shard request positions, in arrival order.
        per_shard = [np.flatnonzero(valid & (shard_ids == d)) for d in range(D)]
        width = max((len(ix) for ix in per_shard), default=0)
        B = max(self.MIN_PAD, 1 << max(width - 1, 0).bit_length())

        slots = np.zeros((D, B), np.int32)
        rank = np.zeros((D, B), np.int32)
        is_last = np.ones((D, B), bool)
        em = np.zeros((D, B), np.int64)
        tol = np.zeros((D, B), np.int64)
        q = np.zeros((D, B), np.int64)
        vmask = np.zeros((D, B), bool)
        rounds = np.zeros((D, B), np.int32)
        tenant = (
            np.zeros((D, B), np.int32) if self.table.tenant_slots else None
        )

        key_src = bkeys if self._bytes_keys else keys
        for d, ix in enumerate(per_shard):
            m = len(ix)
            if m == 0:
                continue
            skeys = [key_src[i] for i in ix]
            svalid = np.ones(m, bool)
            km = self.keymaps[d]
            sl, rk, il, n_full = km.resolve(skeys, svalid)
            while n_full:
                if self._tenant_of_slot is not None:
                    # Quota-refuse over-quota fresh keys BEFORE growing:
                    # an at-quota tenant's spray must never force a
                    # (permanent, every-shard) capacity doubling.  Only
                    # within-quota keys still missing slots justify it.
                    rej0 = self._refuse_over_quota_missing(
                        d, km, sl, ix, bkeys, plens, svalid
                    )
                    if rej0 is not None:
                        svalid &= ~rej0
                        status[ix[rej0]] = STATUS_TENANT_QUOTA
                        valid[ix[rej0]] = False
                        rk, il = segment_info(sl, svalid)
                        if not (svalid & (sl < 0)).any():
                            break
                if not self.auto_grow:
                    raise InternalError("bucket table full")
                new_cap = max(km.capacity * 2, 1024)
                for km2 in self.keymaps:
                    km2.grow(new_cap)
                self.table.grow(new_cap)
                self._grow_tenant_slots(new_cap)
                missing = (sl == -1) & svalid
                sl2, _, _, n_full = km.resolve(skeys, missing)
                sl = np.where(missing, sl2, sl)
                rk, il = segment_info(sl, svalid)
            if self._tenant_of_slot is not None:
                tids_lane, rejected = self._attribute_tenants(
                    d, km, sl, ix, bkeys, plens
                )
                if rejected is not None:
                    svalid &= ~rejected
                    status[ix[rejected]] = STATUS_TENANT_QUOTA
                    valid[ix[rejected]] = False
                    rk, il = segment_info(sl, svalid)
                if tenant is not None:
                    tenant[d, :m] = tids_lane
            slots[d, :m] = sl
            rank[d, :m] = rk
            is_last[d, :m] = il
            em[d, :m] = emission[ix]
            tol[d, :m] = tolerance[ix]
            q[d, :m] = quantity[ix]
            vmask[d, :m] = svalid
            pos = np.flatnonzero(svalid)
            if len(np.unique(sl[pos])) != len(pos):
                param_rounds(
                    rounds[d], sl, pos,
                    emission[ix], tolerance[ix], quantity[ix],
                )
        return _PreparedWindow(
            n=n, per_shard=per_shard, slots=slots, rank=rank,
            is_last=is_last, em=em, tol=tol, q=q, vmask=vmask,
            rounds=rounds, max_burst=max_burst, status=status, valid=valid,
            emission=emission, tolerance=tolerance, quantity=quantity,
            tenant=tenant,
        )

    @staticmethod
    def _make_result(valid, max_burst, status, allowed, remaining,
                     reset_after, retry_after, wire):
        fields = dict(
            allowed=allowed,
            limit=np.where(valid, max_burst, 0),
            remaining=remaining,
            status=status,
        )
        if wire:
            return WireBatchResult(
                reset_after_s=reset_after, retry_after_s=retry_after,
                **fields,
            )
        return BatchResult(
            reset_after_ns=reset_after, retry_after_ns=retry_after,
            **fields,
        )

    def rate_limit_batch(
        self,
        keys: Sequence,
        max_burst,
        count_per_period,
        period,
        quantity,
        now_ns: int,
        wire: bool = False,
    ) -> BatchResult:
        prep = self._prepare_sharded(
            keys, max_burst, count_per_period, period, quantity, now_ns
        )
        D = self.n_shards
        B = prep.slots.shape[1]
        valid, emission, tolerance, quantity = (
            prep.valid, prep.emission, prep.tolerance, prep.quantity,
        )
        degen = has_degenerate(valid, emission, tolerance, quantity)
        with_degen = not wire or degen
        # Compact output ladder off the mesh, same tiers as the
        # single-device dispatch: w32 (4 B/request, device-packed) →
        # cur (8 B, host-finished) → 4-plane i32; the table's hwm /
        # cur_safe marks carry the certificates across launches.
        params_cur_safe = cur_wire_safe(valid, tolerance, now_ns)
        use_w32 = (
            wire
            and not degen
            and fits_w32_wire(
                valid, emission, tolerance, quantity, now_ns,
                self.table.tol_hwm, self.table.now_hwm,
            )
        )
        use_cur = (
            not use_w32
            and wire
            and not degen
            and params_cur_safe
            and self.table.cur_safe
        )

        n = prep.n
        allowed = np.zeros(n, bool)
        remaining = np.zeros(n, np.int64)
        reset_after = np.zeros(n, np.int64)
        retry_after = np.zeros(n, np.int64)

        n_rounds = int(prep.rounds.max()) + 1 if n else 1
        for r in range(n_rounds):
            rmask = prep.vmask & (prep.rounds == r)
            if not rmask.any():
                continue
            if n_rounds == 1:
                rk, il = prep.rank, prep.is_last
            else:
                rk = np.zeros((D, B), np.int32)
                il = np.ones((D, B), bool)
                for d in range(D):
                    rk[d], il[d] = segment_info(prep.slots[d], rmask[d])
            out_dev, counters, tcounts = self.table.check_batch(
                prep.slots, rk, il, prep.em, prep.tol, prep.q, rmask,
                now_ns,
                with_degen=with_degen,
                compact="w32" if use_w32 else ("cur" if use_cur else wire),
                params_cur_safe=params_cur_safe,
                tenant=prep.tenant,
            )
            out = np.asarray(out_dev)
            c = np.asarray(counters)
            self._bump_counters(
                int(c[0]), int(c[1]), int(c[2]),
                tcounts=(
                    np.asarray(tcounts) if tcounts is not None else None
                ),
            )
            for d, ix in enumerate(prep.per_shard):
                m = len(ix)
                if m == 0:
                    continue
                sel = rmask[d, :m]
                dst = ix[sel]
                if use_w32:
                    al, rem, res, ret = finish_w32(out[d, :m][sel])
                    allowed[dst] = al != 0
                    remaining[dst] = rem
                    reset_after[dst] = res
                    retry_after[dst] = ret
                elif use_cur:
                    al, rem, res, ret = finish_cur(
                        out[d, :m][sel], emission[dst], tolerance[dst],
                        quantity[dst], now_ns,
                    )
                    allowed[dst] = al != 0
                    remaining[dst] = rem
                    reset_after[dst] = res
                    retry_after[dst] = ret
                else:
                    allowed[dst] = out[d, 0, :m][sel] != 0
                    remaining[dst] = out[d, 1, :m][sel]
                    reset_after[dst] = out[d, 2, :m][sel]
                    retry_after[dst] = out[d, 3, :m][sel]

        return self._make_result(
            valid, prep.max_burst, prep.status, allowed, remaining,
            reset_after, retry_after, wire,
        )

    # ------------------------------------------------------------------ #

    def rate_limit_many(self, batches, wire: bool = False) -> list:
        """Decide K whole batches in ONE mesh launch (scanned shard_map).

        Same contract as TpuRateLimiter.rate_limit_many: `batches` is a
        list of (keys, max_burst, count_per_period, period, quantity,
        now_ns) tuples in arrival order; each sub-batch sees the sharded
        table state left by the previous one.  Batches whose keys change
        parameters mid-batch fall back to the sequential per-batch path
        (rare; exactness beats speed).
        """
        return self.dispatch_many(batches, wire=wire).fetch()

    def dispatch_many(self, batches, wire: bool = False):
        """The dispatch half of rate_limit_many (same split as
        TpuRateLimiter.dispatch_many): host-prepare + mesh-launch the
        window, return a handle whose .fetch() blocks for results — so
        the engine's flush loop can assemble window N+1 while the mesh
        executes window N."""
        if not batches:
            return _ReadyLaunch([])

        prepared = []
        width = self.MIN_PAD
        any_degen = False
        fallback = False
        # Prep mutates tenant-quota state: slot resolution and tenant
        # attribution are idempotent under re-prepare (a re-resolved
        # slot keeps its attribution; a quota-refused key is refused
        # again since its tenant's usage never advanced), but the
        # rejection COUNTER is not — snapshot it so the sequential
        # fallback's re-prepare cannot double-count refusals.
        reg = self.tenants
        rej_snapshot = (
            reg.quota_rejections.copy() if reg is not None else None
        )
        for b in batches:
            prep = self._prepare_sharded(*b)
            if prep.rounds.any():
                fallback = True
                break
            any_degen = any_degen or has_degenerate(
                prep.valid, prep.emission, prep.tolerance, prep.quantity
            )
            prepared.append(prep)
            width = max(width, prep.slots.shape[1])
        if fallback:
            # Re-deciding already-prepared batches is safe: no device
            # writes happened yet, and prep's host mutations are
            # idempotent (see above) once the rejection counters are
            # rolled back to the window's start.
            if rej_snapshot is not None:
                reg.quota_rejections[:] = rej_snapshot
            return _ReadyLaunch(
                sequential_fallback(
                    batches, self.rate_limit_batch,
                    TpuRateLimiter._error_result, wire,
                )
            )

        D = self.n_shards
        K = len(prepared)
        K_pad = 1 << (K - 1).bit_length()
        shape = (D, K_pad, width)
        slots_s = np.zeros(shape, np.int32)
        rank_s = np.zeros(shape, np.int32)
        last_s = np.ones(shape, bool)
        em_s = np.zeros(shape, np.int64)
        tol_s = np.zeros(shape, np.int64)
        q_s = np.zeros(shape, np.int64)
        valid_s = np.zeros(shape, bool)
        tenant_s = (
            np.zeros(shape, np.int32) if self.table.tenant_slots else None
        )
        now_s = np.full(K_pad, batches[-1][5], np.int64)
        for j, prep in enumerate(prepared):
            Bj = prep.slots.shape[1]
            slots_s[:, j, :Bj] = prep.slots
            rank_s[:, j, :Bj] = prep.rank
            last_s[:, j, :Bj] = prep.is_last
            em_s[:, j, :Bj] = prep.em
            tol_s[:, j, :Bj] = prep.tol
            q_s[:, j, :Bj] = prep.q
            valid_s[:, j, :Bj] = prep.vmask
            if tenant_s is not None and prep.tenant is not None:
                tenant_s[:, j, :Bj] = prep.tenant
            now_s[j] = batches[j][5]

        # Compact output ladder off the mesh (w32 → cur → 4-plane),
        # same certificates as the single-device dispatch paths;
        # host-finished in fetch().
        now_max = int(now_s.max(initial=0))
        params_cur_safe = cur_wire_safe(valid_s, tol_s, now_max)
        use_w32 = (
            wire
            and not any_degen
            and now_max < (1 << 61)
            and bool((np.diff(now_s) >= 0).all())
            and fits_w32_wire(
                valid_s, em_s, tol_s, q_s, int(now_s[0]),
                self.table.tol_hwm, self.table.now_hwm,
            )
        )
        use_cur = (
            not use_w32
            and wire
            and not any_degen
            and params_cur_safe
            and self.table.cur_safe
        )
        out_dev, counters, tcounts = self.table.check_many(
            slots_s, rank_s, last_s, em_s, tol_s, q_s, valid_s, now_s,
            with_degen=not wire or any_degen,
            compact="w32" if use_w32 else ("cur" if use_cur else wire),
            params_cur_safe=params_cur_safe,
            tenant=tenant_s,
        )
        return _PendingShardedLaunch(
            self, out_dev, counters, prepared, wire,
            now_list=[int(b[5]) for b in batches] if use_cur else None,
            w32=use_w32,
            tcounts=tcounts,
        )

    # ------------------------------------------------------------------ #

    def sweep(self, now_ns: int) -> int:
        """Sweep every shard; returns total slots freed."""
        with span("cleanup.compact"):
            expired = self.table.sweep(now_ns)
        freed = 0
        with span("cleanup.free"):
            for d in range(self.n_shards):
                idx = np.flatnonzero(expired[d])
                freed += self.keymaps[d].free_slots(idx)
                if self._tenant_of_slot is not None and len(idx):
                    # Release quota attribution for the vacated slots.
                    tos = self._tenant_of_slot[d]
                    tids = tos[idx]
                    live = tids >= 0
                    if live.any():
                        self._tenant_used[d] -= np.bincount(
                            tids[live],
                            minlength=self.tenants.max_tenants,
                        )
                        tos[idx[live]] = -1
        return freed

    def prewarm_sweep(self) -> None:
        """TpuRateLimiter.prewarm_sweep on every shard's table."""
        self.table.sweep(EMPTY_EXPIRY)
