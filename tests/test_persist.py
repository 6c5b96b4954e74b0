"""Crash-durability tests (persist/): checkpoint format, generation
chains, torn-write recovery fallback, fault modes on the snapshot site,
and the SIGKILL-mid-checkpoint soak with an over-allow-only differential
against a scalar oracle.

The safety argument under test everywhere: restored TATs are only ever
*older* than live state was, and GCRA clamps an old TAT up to `now` —
so a stale checkpoint, a torn generation, or a dropped delta is strictly
over-allow-only.  Recovery may forget spends; it must never manufacture
a deny the live server would not have issued.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from conftest import require_devices
from throttlecrab_tpu.persist import (
    Checkpointer,
    CheckpointCorrupt,
    MANIFEST_NAME,
    checkpoint_name,
    decode_checkpoint,
    encode_checkpoint,
    parse_checkpoint_name,
    read_checkpoint,
    read_manifest,
    recover_into,
    scan_chains,
)
from throttlecrab_tpu.tpu.limiter import TpuRateLimiter

NS = 1_000_000_000
T0 = 1_700_000_000 * NS


def _ck(lim, directory, **kw) -> Checkpointer:
    kw.setdefault("interval_ns", 1)  # every explicit tick is due
    kw.setdefault("now_fn", lambda: T0)
    return Checkpointer(lim, directory, **kw)


def _spend(lim, key, n, t=T0, burst=3, period=3600):
    for _ in range(n):
        lim.rate_limit(key, burst, 10, period, 1, t)


# ------------------------------------------------------------------ #
# Format


def test_format_round_trip():
    keys = ["plain", b"\x00raw\xffbytes", "utf8-é"]
    tat = np.array([T0 + 1, T0 + 2, T0 + 3], np.int64)
    exp = np.array([T0 + 10, T0 + 20, T0 + 30], np.int64)
    blob = encode_checkpoint(
        "base", 7, 7, T0, 256, 1, False, keys, tat, exp
    )
    rec = decode_checkpoint(blob)
    assert rec.kind == "base"
    assert rec.generation == 7 and rec.base_generation == 7
    assert rec.created_ns == T0
    assert (rec.capacity, rec.n_shards) == (256, 1)
    assert rec.source_bytes_keys is False
    assert list(rec.tat) == list(tat) and list(rec.expiry) == list(exp)
    # Raw key bytes + flags round-trip (identity decode happens at
    # restore via translate_key, not here).
    assert rec.keys_raw[1] == b"\x00raw\xffbytes"
    assert bool(rec.key_is_bytes[1]) and not bool(rec.key_is_bytes[0])


def test_decode_rejects_every_damage_shape():
    blob = encode_checkpoint(
        "delta", 3, 0, T0, 64, 1, False,
        ["k1", "k2"],
        np.array([1, 2], np.int64), np.array([3, 4], np.int64),
    )
    # Torn prefixes at every interesting boundary.
    for cut in (0, 2, 4, 10, len(blob) // 2, len(blob) - 1):
        with pytest.raises(CheckpointCorrupt):
            decode_checkpoint(blob[:cut])
    # A single flipped body byte trips the CRC.
    flipped = bytearray(blob)
    flipped[len(blob) // 2] ^= 0x40
    with pytest.raises(CheckpointCorrupt, match="CRC"):
        decode_checkpoint(bytes(flipped))
    with pytest.raises(CheckpointCorrupt, match="magic"):
        decode_checkpoint(b"XXXX" + blob[4:])
    # Trailing garbage is torn too (length field disagrees).
    with pytest.raises(CheckpointCorrupt):
        decode_checkpoint(blob + b"junk")


def test_checkpoint_name_round_trip():
    assert checkpoint_name(42, "base") == "ckpt-000000000042-base.tck"
    assert parse_checkpoint_name("ckpt-000000000042-base.tck") == (
        42, "base",
    )
    for bad in (
        "ckpt-12-wat.tck", "snap.npz", "ckpt-xx-base.tck",
        "ckpt-1-base.tmp", "MANIFEST.json",
    ):
        assert parse_checkpoint_name(bad) is None


# ------------------------------------------------------------------ #
# Chain write + recovery


def test_base_delta_chain_round_trips_decisions(tmp_path):
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "hot", 3)  # exhausted
    for i in range(20):
        _spend(lim, f"k{i}", 1)
    ck = _ck(lim, tmp_path)
    assert ck.checkpoint_now(T0) == 21  # base: full table
    _spend(lim, "hot2", 3)  # exhausted after the base
    ck.note_keys(["hot2"])
    assert ck.checkpoint_now(T0) == 1  # delta: just the dirty row
    assert ck.last_generation == 1

    lim2 = TpuRateLimiter(capacity=256)
    res = recover_into(lim2, tmp_path, T0 + NS)
    assert res is not None and res.restored == 22
    assert res.generation == 1 and res.chain == [0, 1]
    assert res.corrupt_skipped == 0 and res.used_manifest
    # Decisions continue where the chain left off: both exhausted keys
    # still deny, a singly-spent key has exactly one token spent.
    assert not lim2.rate_limit("hot", 3, 10, 3600, 1, T0 + NS)[0]
    assert not lim2.rate_limit("hot2", 3, 10, 3600, 1, T0 + NS)[0]
    allowed, r = lim2.rate_limit("k0", 3, 10, 3600, 1, T0 + NS)
    assert allowed and r.remaining == 1


def test_delta_contains_only_dirty_rows(tmp_path):
    lim = TpuRateLimiter(capacity=256)
    for i in range(10):
        _spend(lim, f"k{i}", 1)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    ck.note_keys(["k3", "k7", "never-decided"])
    ck.checkpoint_now(T0)
    rec = read_checkpoint(tmp_path / checkpoint_name(1, "delta"))
    # Dirty ∩ live table: the never-decided key is simply absent.
    assert sorted(k.decode() for k in rec.keys_raw) == ["k3", "k7"]
    assert rec.base_generation == 0


def test_delta_dirty_marks_match_across_key_encodings(tmp_path):
    """Transports note wire (str) keys but a bytes-keyed keymap exports
    bytes — the delta's dirty∩table match is on canonical byte
    identity, never on Python object equality (regression: str marks
    against a native bytes keymap produced only empty deltas, so every
    incremental generation silently carried zero rows)."""
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, b"enc-a", 1)
    _spend(lim, "enc-b", 1)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    # Note each key in the OPPOSITE encoding from how the table holds it.
    ck.note_keys(["enc-a", b"enc-b"])
    ck.checkpoint_now(T0)
    rec = read_checkpoint(tmp_path / checkpoint_name(1, "delta"))
    assert sorted(k.decode() for k in rec.keys_raw) == ["enc-a", "enc-b"]


def test_all_expired_dirty_set_still_writes_empty_delta(tmp_path):
    """No generation holes: an empty delta is a real generation, or a
    later recovery would misread the gap as a torn chain tail."""
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "a", 1)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    ck.note_keys(["gone-key"])  # dirty, but absent from the export
    assert ck.checkpoint_now(T0) == 0
    assert (tmp_path / checkpoint_name(1, "delta")).exists()
    res = recover_into(TpuRateLimiter(capacity=256), tmp_path, T0 + NS)
    assert res.chain == [0, 1] and res.restored == 1


def test_idle_interval_writes_no_file(tmp_path):
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "a", 1)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    assert ck.checkpoint_now(T0) == 0  # nothing dirty, base not due
    assert not (tmp_path / checkpoint_name(1, "delta")).exists()
    assert ck.last_generation == 0


def test_recovery_corrupt_manifest_falls_back_to_scan(tmp_path):
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "hot", 3)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    (tmp_path / MANIFEST_NAME).write_bytes(b'{"chains": [[torn')
    assert read_manifest(tmp_path) is None

    lim2 = TpuRateLimiter(capacity=256)
    res = recover_into(lim2, tmp_path, T0 + NS)
    assert res.restored == 1 and not res.used_manifest
    assert not lim2.rate_limit("hot", 3, 10, 3600, 1, T0 + NS)[0]


def test_recovery_corrupt_newest_delta_drops_one_generation(tmp_path):
    """A torn newest delta costs exactly its generation: the chain
    restores one generation shorter, and the key whose newer row was
    lost comes back with its OLDER row — over-allow-only."""
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "fall", 1)  # one spend in the base
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    _spend(lim, "other", 1)
    ck.note_keys(["other"])
    ck.checkpoint_now(T0)  # delta gen 1, intact
    _spend(lim, "fall", 2)  # now exhausted...
    ck.note_keys(["fall"])
    ck.checkpoint_now(T0)  # ...captured only in delta gen 2
    path2 = tmp_path / checkpoint_name(2, "delta")
    blob = path2.read_bytes()
    path2.write_bytes(blob[: len(blob) // 2])  # torn

    lim2 = TpuRateLimiter(capacity=256)
    res = recover_into(lim2, tmp_path, T0 + NS)
    assert res.generation == 1 and res.chain == [0, 1]
    assert res.corrupt_skipped == 1
    # The lost generation forgot two spends of "fall": the restored row
    # must ALLOW (older TAT = more permissive), never wrongly deny.
    allowed, r = lim2.rate_limit("fall", 3, 10, 3600, 1, T0 + NS)
    assert allowed and r.remaining == 1


def test_recovery_corrupt_base_abandons_chain_for_previous(tmp_path):
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "hot", 3)
    ck = _ck(lim, tmp_path, retain=2)
    ck.checkpoint_now(T0)
    ck.note_keys(["hot"])
    ck.checkpoint_now(T0)  # chain [0, 1]
    _spend(lim, "late", 1)
    ck.checkpoint_now(T0, force_base=True)  # chain [2]
    path2 = tmp_path / checkpoint_name(2, "base")
    path2.write_bytes(b"TCKPgarbage")

    lim2 = TpuRateLimiter(capacity=256)
    res = recover_into(lim2, tmp_path, T0 + NS)
    # The whole newest chain is gone; the previous chain restores.
    assert res.chain == [0, 1] and res.corrupt_skipped == 1
    assert not lim2.rate_limit("hot", 3, 10, 3600, 1, T0 + NS)[0]
    # "late" existed only in the abandoned chain: forgotten → allowed.
    assert lim2.rate_limit("late", 3, 10, 3600, 1, T0 + NS)[0]


def test_recovery_nothing_usable_boots_empty(tmp_path):
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "hot", 3)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    for entry in tmp_path.iterdir():
        if entry.name != MANIFEST_NAME:
            entry.write_bytes(b"\x00" * 16)
    lim2 = TpuRateLimiter(capacity=256)
    assert recover_into(lim2, tmp_path, T0 + NS) is None
    assert len(lim2) == 0


def test_recovery_missing_dir_and_empty_dir(tmp_path):
    assert recover_into(
        TpuRateLimiter(capacity=64), tmp_path / "absent", T0
    ) is None
    assert recover_into(TpuRateLimiter(capacity=64), tmp_path, T0) is None


def test_recovery_requires_empty_limiter(tmp_path):
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "hot", 1)
    _ck(lim, tmp_path).checkpoint_now(T0)
    with pytest.raises(ValueError, match="empty"):
        recover_into(lim, tmp_path, T0 + NS)


def test_restore_time_ttl_sweep_across_chain(tmp_path):
    """Expiry gates restoration per-merged-row across base + deltas."""
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "short", 1, period=2)  # expires ~T0 + 2s
    _spend(lim, "long", 1, period=3600)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    _spend(lim, "short2", 1, t=T0 + NS, period=2)
    ck.note_keys(["short2"])
    ck.checkpoint_now(T0)

    lim2 = TpuRateLimiter(capacity=256)
    res = recover_into(lim2, tmp_path, T0 + 100 * NS)
    assert res.restored == 1  # both short-TTL rows swept at restore
    assert len(lim2) == 1


def test_chain_restores_across_shard_counts(tmp_path):
    """Shard topology is not part of the checkpoint contract: a chain
    written on 4 shards restores onto 2 shards and onto a single
    device — keys re-route through the target's own hash."""
    require_devices(4)
    from throttlecrab_tpu.parallel.sharded import (
        ShardedTpuRateLimiter,
        make_mesh,
    )

    lim = ShardedTpuRateLimiter(capacity_per_shard=128, mesh=make_mesh(4))
    _spend(lim, "hot", 3)
    for i in range(20):
        _spend(lim, f"k{i}", 1)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    _spend(lim, "hot2", 3)
    ck.note_keys(["hot2"])
    ck.checkpoint_now(T0)

    for target in (
        ShardedTpuRateLimiter(capacity_per_shard=128, mesh=make_mesh(2)),
        TpuRateLimiter(capacity=512),
    ):
        res = recover_into(target, tmp_path, T0 + NS)
        assert res.restored == 22
        assert not target.rate_limit("hot", 3, 10, 3600, 1, T0 + NS)[0]
        assert not target.rate_limit("hot2", 3, 10, 3600, 1, T0 + NS)[0]


def test_retention_prunes_to_newest_chains(tmp_path):
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "a", 1)
    ck = _ck(lim, tmp_path, retain=2, mode="full")
    # full mode: every generation is a base → 5 chains written.
    for _ in range(5):
        assert ck.checkpoint_now(T0) == 1
    gens_on_disk = sorted(
        parse_checkpoint_name(e.name)[0]
        for e in tmp_path.iterdir()
        if parse_checkpoint_name(e.name) is not None
    )
    assert gens_on_disk == [3, 4]  # newest 2 chains survive
    assert read_manifest(tmp_path) == [[4], [3]]
    assert scan_chains(tmp_path) == [[4], [3]]


def test_generation_numbering_resumes_past_disk(tmp_path):
    """After recovery the writer must never reuse an on-disk generation
    number, and its first new write is a fresh base (chain re-anchor)."""
    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "a", 1)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)
    ck.note_keys(["a"])
    ck.checkpoint_now(T0)  # chain [0, 1]

    lim2 = TpuRateLimiter(capacity=256)
    res = recover_into(lim2, tmp_path, T0 + NS)
    ck2 = _ck(lim2, tmp_path)
    ck2.note_recovery(res.restored, res.corrupt_skipped, res.chains)
    assert ck2.generation == 2
    ck2.checkpoint_now(T0 + NS)
    assert (tmp_path / checkpoint_name(2, "base")).exists()
    assert recover_into(
        TpuRateLimiter(capacity=256), tmp_path, T0 + NS
    ).chain == [2]


# ------------------------------------------------------------------ #
# Fault modes on the snapshot site


@pytest.fixture
def disarm_faults():
    yield
    from throttlecrab_tpu.faults import disarm

    disarm()


def test_truncate_fault_tears_final_file_and_recovery_survives(
    tmp_path, disarm_faults
):
    """An injected torn write leaves a GENUINELY torn file under the
    final checkpoint name (the rename-journaled-first crash shape); the
    writer re-merges its dirty set, and recovery falls back to the last
    good generation."""
    from throttlecrab_tpu.faults import (
        FaultInjector,
        arm,
        disarm,
        parse_spec,
    )

    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "safe", 1)
    ck = _ck(lim, tmp_path)
    ck.checkpoint_now(T0)  # good base, gen 0
    _spend(lim, "torn-row", 3)
    ck.note_keys(["torn-row"])

    arm(FaultInjector(parse_spec("snapshot:truncate:0.4")))
    with pytest.raises(OSError, match="torn write"):
        ck.checkpoint_now(T0)
    disarm()

    torn = tmp_path / checkpoint_name(1, "delta")
    assert torn.exists()  # promoted into the final path, torn
    with pytest.raises(CheckpointCorrupt):
        read_checkpoint(torn)
    assert ck.write_errors == 1
    assert ck.dirty_count() == 1  # re-merged: nothing lost
    assert ck.last_generation == 0  # generation did not advance

    # The manifest (written before the torn generation) does not name
    # it — recovery via the manifest skips the torn file entirely.
    # Drop the manifest to force the directory scan against the torn
    # file itself: the worst case a real crash leaves behind.
    (tmp_path / MANIFEST_NAME).unlink()
    lim2 = TpuRateLimiter(capacity=256)
    res = recover_into(lim2, tmp_path, T0 + NS)
    assert not res.used_manifest
    assert res.generation == 0 and res.corrupt_skipped == 1
    # Forgotten spends allow; the covered row restored.
    assert lim2.rate_limit("torn-row", 3, 10, 3600, 1, T0 + NS)[0]
    allowed, r = lim2.rate_limit("safe", 3, 10, 3600, 1, T0 + NS)
    assert allowed and r.remaining == 1

    # The next healthy tick retries the SAME generation number with the
    # re-merged dirty set and overwrites the torn file.
    assert ck.checkpoint_now(T0) == 1
    assert read_checkpoint(torn).kind == "delta"
    assert recover_into(
        TpuRateLimiter(capacity=256), tmp_path, T0 + NS
    ).generation == 1


def test_fsyncfail_fault_fails_cleanly_before_rename(
    tmp_path, disarm_faults
):
    from throttlecrab_tpu.faults import (
        FaultInjector,
        arm,
        disarm,
        parse_spec,
    )

    lim = TpuRateLimiter(capacity=256)
    _spend(lim, "a", 1)
    ck = _ck(lim, tmp_path)
    arm(FaultInjector(parse_spec("snapshot:fsyncfail")))
    with pytest.raises(OSError, match="fsync"):
        ck.checkpoint_now(T0)
    disarm()
    # Durability was never promised: no final file, no stray tmp.
    assert list(tmp_path.iterdir()) == []
    assert ck.write_errors == 1
    # Healed, the same state writes durably.
    assert ck.checkpoint_now(T0) == 1
    assert (tmp_path / checkpoint_name(0, "base")).exists()


def test_snapshot_save_fault_modes_degrade_cleanly(
    tmp_path, disarm_faults
):
    """The .npz save path (save_snapshot) has no torn-promote step: both
    new modes surface as a clean OSError with the destination absent."""
    from throttlecrab_tpu.faults import (
        FaultInjector,
        arm,
        disarm,
        parse_spec,
    )
    from throttlecrab_tpu.tpu.snapshot import save_snapshot

    for spec in ("snapshot:truncate:0.5", "snapshot:fsyncfail"):
        lim = TpuRateLimiter(capacity=64)
        _spend(lim, "a", 1)
        path = tmp_path / f"{spec.split(':')[1]}.npz"
        arm(FaultInjector(parse_spec(spec)))
        with pytest.raises(OSError):
            save_snapshot(lim, path)
        disarm()
        assert not path.exists()
        assert not path.with_name(path.name + ".tmp").exists()
        assert save_snapshot(lim, path) == 1  # healed


def test_parse_spec_validates_new_modes():
    from throttlecrab_tpu.faults import parse_spec

    assert parse_spec("snapshot:truncate:0.5")[0].arg == 0.5
    assert parse_spec("snapshot:fsyncfail")[0].mode == "fsyncfail"
    with pytest.raises(ValueError):
        parse_spec("snapshot:truncate")  # frac required
    with pytest.raises(ValueError):
        parse_spec("snapshot:truncate:1.5")  # frac out of range


# ------------------------------------------------------------------ #
# Server wiring


def test_config_checkpoint_knobs_validate():
    from throttlecrab_tpu.server.config import Config, ConfigError

    Config(
        http=True, checkpoint_dir="/tmp/x", checkpoint_interval_ms=100
    ).validate()
    with pytest.raises(ConfigError, match="checkpoint-dir"):
        Config(http=True, checkpoint_interval_ms=100).validate()
    with pytest.raises(ConfigError):
        Config(
            http=True, checkpoint_dir="/tmp/x", checkpoint_interval_ms=-1
        ).validate()
    with pytest.raises(ConfigError):
        Config(
            http=True, checkpoint_dir="/tmp/x", checkpoint_retain=0
        ).validate()
    with pytest.raises(ConfigError):
        Config(
            http=True, checkpoint_dir="/tmp/x", checkpoint_mode="weekly"
        ).validate()


def test_restore_on_boot_prefers_checkpoint_over_snapshot(tmp_path):
    """Boot precedence: the checkpoint chain wins when usable; an
    unusable chain falls through to the snapshot (strict policy and
    all)."""
    import time

    from throttlecrab_tpu.server.__main__ import restore_on_boot
    from throttlecrab_tpu.server.config import Config
    from throttlecrab_tpu.tpu.snapshot import save_snapshot

    now = time.time_ns()
    # Snapshot: 1 key.  Checkpoint chain: 2 keys.
    src = TpuRateLimiter(capacity=256)
    _spend(src, "snap-key", 1, t=now)
    snap = tmp_path / "snap.npz"
    save_snapshot(src, snap)
    src2 = TpuRateLimiter(capacity=256)
    _spend(src2, "ck-a", 1, t=now)
    _spend(src2, "ck-b", 1, t=now)
    ckdir = tmp_path / "ckpt"
    ck = Checkpointer(src2, ckdir, interval_ns=1, now_fn=lambda: now)
    ck.checkpoint_now(now)

    cfg = Config(
        http=True, snapshot_path=str(snap), checkpoint_dir=str(ckdir),
    )
    lim = TpuRateLimiter(capacity=256)
    ck2 = Checkpointer(lim, ckdir, interval_ns=1)
    assert restore_on_boot(lim, cfg, ck2) == 2
    assert ck2.recoveries == 1 and ck2.generation == 1

    # Chain unusable → snapshot path restores instead.
    for entry in ckdir.iterdir():
        entry.write_bytes(b"\x00")
    lim2 = TpuRateLimiter(capacity=256)
    ck3 = Checkpointer(lim2, ckdir, interval_ns=1)
    assert restore_on_boot(lim2, cfg, ck3) == 1
    assert ck3.recoveries == 0


def test_metrics_export_checkpoint_gauges():
    from throttlecrab_tpu.server.metrics import METRIC_NAMES, Metrics

    m = Metrics.builder().build()
    text = m.export_prometheus()
    # Disarmed: the names still emit (registry contract) with defaults.
    assert "throttlecrab_tpu_checkpoint_generation -1" in text

    lim = TpuRateLimiter(capacity=64)
    _spend(lim, "a", 1)
    ck = Checkpointer(
        lim, "/nonexistent-unused", interval_ns=1, now_fn=lambda: T0
    )
    m.set_checkpoint_stats_provider(ck.metric_stats)
    text = m.export_prometheus()
    for name in METRIC_NAMES:
        if name.startswith("throttlecrab_tpu_checkpoint"):
            assert name + " " in text


def test_health_suffix_states():
    lim = TpuRateLimiter(capacity=64)
    clock = {"t": T0}
    ck = Checkpointer(
        lim, "/unused", interval_ns=1, now_fn=lambda: clock["t"]
    )
    assert ck.health_suffix() == "checkpoint_age_s=never"
    ck.last_checkpoint_ns = T0
    clock["t"] = T0 + 2 * NS
    assert ck.health_suffix() == "checkpoint_age_s=2.0"


def test_engine_marks_decided_keys_dirty(tmp_path):
    """The dirty hook rides the engine observe path: decided keys (and
    only decided keys) land in the next delta."""
    import asyncio

    from throttlecrab_tpu.server.engine import BatchingEngine
    from throttlecrab_tpu.server.types import ThrottleRequest

    lim = TpuRateLimiter(capacity=256)
    ck = _ck(lim, tmp_path, interval_ns=1 << 62)  # ticks never due
    engine = BatchingEngine(lim, batch_size=8, checkpointer=ck)

    async def drive():
        reqs = [
            ThrottleRequest(
                key=f"e{i}", max_burst=3, count_per_period=10,
                period=3600, quantity=1,
            )
            for i in range(5)
        ]
        await asyncio.gather(*(engine.throttle(r) for r in reqs))
        await engine.shutdown()

    asyncio.run(drive())
    assert ck.dirty_count() == 5
    ck.checkpoint_now(T0)  # first write: full base
    ck.note_keys(["e0"])
    ck.checkpoint_now(T0)
    rec = read_checkpoint(tmp_path / checkpoint_name(1, "delta"))
    assert [k.decode() for k in rec.keys_raw] == ["e0"]


def test_run_server_checkpoint_lifecycle_off_the_loop(tmp_path):
    """End-to-end run_server lifecycle on the checkpoint path alone (no
    snapshot): serve → SIGINT (final flush) → reboot restores from the
    chain and decisions continue."""
    import asyncio
    import signal
    import socket as _socket

    from throttlecrab_tpu.server.__main__ import run_server
    from throttlecrab_tpu.server.config import Config

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ckdir = tmp_path / "chain"

    async def _post_throttle(key):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        payload = json.dumps(
            {
                "key": key, "max_burst": 3, "count_per_period": 1,
                "period": 3600, "quantity": 1,
            }
        ).encode()
        writer.write(
            (
                "POST /throttle HTTP/1.1\r\nHost: x\r\n"
                f"Content-Length: {len(payload)}\r\n"
                "Connection: close\r\n\r\n"
            ).encode()
            + payload
        )
        await writer.drain()
        raw = await reader.read(-1)
        writer.close()
        return json.loads(raw.partition(b"\r\n\r\n")[2])

    async def _get(path):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(
            f"GET {path} HTTP/1.1\r\nHost: x\r\n"
            "Connection: close\r\n\r\n".encode()
        )
        await writer.drain()
        raw = await reader.read(-1)
        writer.close()
        return raw.partition(b"\r\n\r\n")[2]

    async def lifecycle(expect_remaining):
        cfg = Config(
            http=True,
            http_host="127.0.0.1",
            http_port=port,
            checkpoint_dir=str(ckdir),
            checkpoint_interval_ms=50,
        )
        task = asyncio.create_task(run_server(cfg))
        body = None
        for _ in range(400):
            if task.done():
                task.result()
            try:
                body = await _post_throttle("lifecycle-key")
                break
            except OSError:
                await asyncio.sleep(0.05)
        assert body is not None, "server never came up"
        assert body["allowed"] is True
        assert body["remaining"] == expect_remaining
        # /health carries the checkpoint age only when armed.
        health = await _get("/health")
        assert health.startswith(b"OK device=")
        assert b" checkpoint_age_s=" in health
        os.kill(os.getpid(), signal.SIGINT)
        await asyncio.wait_for(task, timeout=60)

    asyncio.run(lifecycle(expect_remaining=2))
    assert scan_chains(ckdir), "shutdown flush wrote no chain"
    asyncio.run(lifecycle(expect_remaining=1))


# ------------------------------------------------------------------ #
# Harness crash-restart workload + warm-start ledger


def test_crash_restart_workload_and_ledger():
    from throttlecrab_tpu.harness.loadgen import PerfResult
    from throttlecrab_tpu.harness.workload import (
        crash_restart_ledger,
        make_keys,
    )

    ks = make_keys("crash-restart", 2000, 10_000, seed=1)
    assert ks == make_keys("crash-restart", 2000, 10_000, seed=1)
    ledger = crash_restart_ledger(10_000)
    hits = [k for k in ks if k in ledger]
    # Both bands drawn: the audited ledger and the warm tail.
    assert hits and len(hits) < len(ks)
    r = PerfResult("http", 0, 0.0, 0, 0, 0, key_pattern="crash-restart")
    r.ledger_burst = 3
    for k, a in (
        [("key:0", True)] * 5 + [("key:1", True)] * 2 + [("key:1", False)]
    ):
        r.track_ledger(k, a)
    assert r.warm_start_summary() == {
        "ledger_keys": 2,
        "ledger_burst": 3,
        "keys_over_burst": 1,
        "extra_allows_total": 2,
        "max_allows_per_key": 5,
    }


# ------------------------------------------------------------------ #
# SIGKILL soak


BURST = 5


def _spawn_ck_server(port, ckdir):
    import subprocess
    import sys

    env = dict(os.environ)
    env["THROTTLECRAB_PLATFORM"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [
            sys.executable, "-m", "throttlecrab_tpu.server",
            "--http", "--http-port", str(port),
            "--checkpoint-dir", str(ckdir),
            "--checkpoint-interval-ms", "40",
            "--log-level", "warn",
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )


def _http_throttle(port, key, quantity=1):
    import urllib.request

    body = json.dumps(
        {
            "key": key, "max_burst": BURST, "count_per_period": BURST,
            "period": 3600, "quantity": quantity,
        }
    ).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/throttle", data=body, method="POST"
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


def _wait_ck_health(proc, port, deadline_s=120):
    import time
    import urllib.request

    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if proc.poll() is not None:
            out, _ = proc.communicate()
            pytest.fail(f"server exited early rc={proc.returncode}:\n{out}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=1
            ) as r:
                body = r.read()
            # Durability armed: the age suffix rides the OK body.
            assert body.startswith(b"OK device="), body
            assert b" checkpoint_age_s=" in body, body
            return
        except (OSError, AssertionError):
            time.sleep(0.25)
    proc.kill()
    pytest.fail("server never became healthy")


def _metric(port, name) -> float:
    import urllib.request

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ) as r:
        for line in r.read().decode().splitlines():
            if line.startswith(name + " "):
                return float(line.split()[1])
    raise AssertionError(f"metric {name} not exported")


def test_sigkill_mid_checkpoint_soak(tmp_path):
    """SIGKILL a checkpointing server mid-load, restart it on the same
    chain, and differential-check every post-restart decision against
    the scalar GCRA oracle: a warm restore may FORGET spends (restored
    TATs are older → strictly more permissive) but must never
    manufacture a deny the oracle would not issue — zero client-visible
    wrong decisions.

    Kill timing is adversarial by construction: the 40ms checkpoint
    interval keeps a generation write in flight essentially always, and
    a background spender keeps load running at the kill instant."""
    import signal
    import socket as _socket
    import threading
    import time

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ckdir = tmp_path / "chain"
    proc = _spawn_ck_server(port, ckdir)
    try:
        _wait_ck_health(proc, port)
        gen_metric = "throttlecrab_tpu_checkpoint_generation"

        # Phase 1: spend 3 of BURST on each tracked key (all acked).
        keys = [f"soak-{i}" for i in range(12)]
        for key in keys:
            for _ in range(3):
                assert _http_throttle(port, key)["allowed"] is True

        # Phase 2: make those spends durable — wait for TWO generation
        # advances past the post-ack reading.  The first advance may
        # come from a tick whose dirty swap predated some acks; the
        # second advance's swap strictly follows the first's write, so
        # it covers every phase-1 spend.  Fresh sentinel spends keep
        # the dirty set non-empty so ticks keep writing generations.
        g0 = _metric(port, gen_metric)
        deadline = time.time() + 60
        i = 0
        while _metric(port, gen_metric) < g0 + 2:
            _http_throttle(port, f"sentinel-{i}")
            i += 1
            assert time.time() < deadline, "checkpoint ticks stalled"
            time.sleep(0.05)

        # Phase 3: background load at the kill instant ("mid-load"),
        # counting acked allows per key for the oracle bound.
        acked = {}
        stop = threading.Event()

        def pound():
            j = 0
            while not stop.is_set():
                key = f"live-{j % 4}"
                try:
                    if _http_throttle(port, key)["allowed"]:
                        acked[key] = acked.get(key, 0) + 1
                except OSError:
                    return  # the kill landed mid-request
                j += 1

        t = threading.Thread(target=pound)
        t.start()
        time.sleep(0.3)  # several checkpoint intervals of live load
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        stop.set()
        t.join(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    # Phase 4: restart on the same chain.
    proc = _spawn_ck_server(port, ckdir)
    try:
        _wait_ck_health(proc, port)
        assert _metric(
            port, "throttlecrab_tpu_checkpoint_recoveries_total"
        ) == 1

        def allows_until_denied(key):
            n = 0
            while n <= BURST and _http_throttle(port, key)["allowed"]:
                n += 1
            return n

        # Tracked keys: 3 spends were durably checkpointed pre-kill.
        # Oracle remaining = BURST - 3 = 2.  Over-allow-only means the
        # server grants AT LEAST the oracle's remaining (never a wrong
        # deny) and at most a fresh bucket (worst-case staleness); the
        # +1 tolerates sub-token GCRA leak across the test's runtime.
        for key in keys:
            n = allows_until_denied(key)
            assert 2 <= n <= 3, (key, n)
        # Mid-load keys: durability at the kill instant is unknowable,
        # but the differential bound still holds — forgetting acked
        # spends only ever ALLOWS more.
        for key, spent in acked.items():
            n = allows_until_denied(key)
            assert n >= max(0, BURST - spent), (key, spent, n)
            assert n <= BURST, (key, spent, n)
        # Warm start, not cold: the tracked keys above already proved
        # restored state gated decisions (n < BURST with zero denials
        # of oracle-allowed requests).
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except Exception:
            proc.kill()
