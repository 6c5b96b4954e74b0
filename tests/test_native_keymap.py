"""C++ keymap vs the Python keymap: identical resolution + segment info."""

import numpy as np
import pytest

from throttlecrab_tpu.native import (
    NativeKeyMap,
    keymap_build_error,
    native_available,
    toolchain_available,
)
from throttlecrab_tpu.tpu.limiter import segment_info

if not native_available() and toolchain_available():
    pytest.fail(
        "C++ keymap failed to build with g++ present:\n"
        f"{keymap_build_error()}",
        pytrace=False,
    )
pytestmark = pytest.mark.skipif(
    not native_available(),
    reason=f"native keymap toolchain unavailable: {keymap_build_error()}",
)


def test_basic_resolution():
    km = NativeKeyMap(64)
    keys = [b"alpha", b"beta", b"alpha", b"gamma", b"beta", b"alpha"]
    valid = np.ones(len(keys), bool)
    slots, rank, is_last, n_full = km.resolve(keys, valid)
    assert n_full == 0
    assert len(km) == 3
    # Same key → same slot; different keys → different slots.
    assert slots[0] == slots[2] == slots[5]
    assert slots[1] == slots[4]
    assert len({slots[0], slots[1], slots[3]}) == 3
    # Segment info: ranks count occurrences, is_last marks finals.
    assert rank.tolist() == [0, 0, 1, 0, 1, 2]
    assert is_last.tolist() == [False, False, False, True, True, True]


def test_matches_python_segment_info():
    rng = np.random.RandomState(3)
    km = NativeKeyMap(128)
    for trial in range(5):
        n = int(rng.randint(1, 40))
        keys = [f"k{rng.randint(10)}".encode() for _ in range(n)]
        valid = rng.rand(n) > 0.2
        slots, rank, is_last, _ = km.resolve(keys, valid)
        rank2, is_last2 = segment_info(slots, valid)
        np.testing.assert_array_equal(rank, rank2, err_msg=f"trial {trial}")
        np.testing.assert_array_equal(
            is_last, is_last2, err_msg=f"trial {trial}"
        )
        assert (slots[~valid] == -1).all()


def test_full_then_grow():
    km = NativeKeyMap(4)
    keys = [f"x{i}".encode() for i in range(8)]
    valid = np.ones(8, bool)
    slots, _, _, n_full = km.resolve(keys, valid)
    assert n_full == 4
    assert (slots >= 0).sum() == 4
    km.grow(16)
    missing = slots == -1
    slots2, _, _, n_full2 = km.resolve(keys, missing)
    assert n_full2 == 0
    merged = np.where(missing, slots2, slots)
    assert (merged >= 0).all()
    assert len(set(merged.tolist())) == 8
    assert len(km) == 8


def test_free_and_recycle():
    km = NativeKeyMap(16)
    keys = [f"k{i}".encode() for i in range(10)]
    valid = np.ones(10, bool)
    slots, _, _, _ = km.resolve(keys, valid)
    freed = km.free_slots(slots[:5])
    assert freed == 5
    assert len(km) == 5
    # Freed keys are re-insertable; surviving keys keep their slots.
    slots2, _, _, _ = km.resolve(keys, valid)
    assert (slots2[5:] == slots[5:]).all()
    assert len(km) == 10
    # Double free is a no-op.
    assert km.free_slots(slots[:5]) in range(0, 6)


def test_unicode_and_long_keys():
    km = NativeKeyMap(16)
    keys = ["пользователь:🔑".encode(), b"x" * 1000, b""]
    valid = np.ones(3, bool)
    slots, rank, is_last, n_full = km.resolve(keys, valid)
    assert n_full == 0
    assert len(set(slots.tolist())) == 3
    slots2, _, _, _ = km.resolve(keys, valid)
    assert (slots == slots2).all()


def test_churn_against_python_reference():
    rng = np.random.RandomState(11)
    km = NativeKeyMap(32)
    pydict: dict = {}
    for step in range(30):
        n = int(rng.randint(1, 20))
        keys = [f"c{rng.randint(30)}".encode() for _ in range(n)]
        valid = np.ones(n, bool)
        slots, _, _, n_full = km.resolve(keys, valid)
        assert n_full == 0
        for k, s in zip(keys, slots):
            if k in pydict:
                assert pydict[k] == s, f"slot moved for {k!r} at step {step}"
            else:
                pydict[k] = s
        if step % 7 == 6:
            drop = [k for i, k in enumerate(pydict) if i % 3 == 0]
            km.free_slots(np.array([pydict[k] for k in drop], np.int32))
            for k in drop:
                del pydict[k]
        assert len(km) == len(pydict)


def test_grow_keeps_probe_invariant():
    """Regression: grow_slots must keep nbuckets >= 2x capacity.  The old
    rehash sizing left nbuckets == capacity after a grow, so a full table
    spun forever on the next miss probe instead of reporting full."""
    from throttlecrab_tpu.native import NativeKeyMap

    km = NativeKeyMap(64)
    km.grow(128)
    keys = [b"g:%d" % i for i in range(129)]
    valid = np.ones(len(keys), bool)
    slots, _, _, n_full = km.resolve(keys, valid)
    assert n_full == 1  # 129 keys into 128 slots: one reported full
    assert (slots >= 0).sum() == 128


def test_arena_follows_live_keys_under_churn():
    """Insert-then-free over a bounded universe: the freed keys' bytes
    are reclaimed, so the arena stays within twice the live keys' bytes
    plus what it reserved at creation, and every key that survives a
    round still resolves to the slot it had."""
    rng = np.random.RandomState(26)
    km = NativeKeyMap(4096)
    reserve = km.arena_stats()["allocated"]
    universe = [b"key:%08d" % i for i in range(2000)]
    held: dict = {}
    reclaimed = False
    for rnd in range(50):
        keys = [universe[i] for i in rng.choice(2000, 1500)]
        slots, _, _, n_full = km.resolve(keys, np.ones(len(keys), bool))
        assert n_full == 0
        for k, s in zip(keys, slots.tolist()):
            assert held.setdefault(k, s) == s, f"{k!r} moved in round {rnd}"
        drop = [k for k in held if rng.rand() < 0.5]
        assert km.free_slots(
            np.array([held.pop(k) for k in drop], np.int32)
        ) == len(drop)
        stats = km.arena_stats()
        live = sum(len(k) for k in held)
        assert stats["used"] - stats["dead"] == live
        assert stats["used"] <= 2 * live
        assert stats["allocated"] <= 2 * live + reserve, (rnd, stats)
        reclaimed |= stats["dead"] == 0 and stats["used"] == live
    assert reclaimed
    survivors = list(held)
    slots, _, _, _ = km.resolve(survivors, np.ones(len(survivors), bool))
    assert slots.tolist() == [held[k] for k in survivors]
    assert len(km) == len(held)


def _reverse(km):
    return {slot: key for key, slot in km.items()}


def test_keys_for_slots_reads_the_slot_column():
    """keys_for_slots answers, slot for slot, what items() pairs with
    each live slot, and None for a free or out-of-range one: on a few
    thousand keys, after frees and a re-insert that recycles a slot,
    and after the arena compaction those frees trigger."""
    cap = 8192
    km = NativeKeyMap(cap)
    keys = [b"user:%06d" % i for i in range(3000)]
    keys += [b"", "пользователь:🔑".encode(), b"x" * 1000]
    slots, _, _, n_full = km.resolve(keys, np.ones(len(keys), bool))
    assert n_full == 0
    probe = list(range(cap)) + [-1, cap, 2**31 - 1]
    rev = _reverse(km)
    assert km.keys_for_slots(probe) == [rev.get(s) for s in probe]
    assert km.keys_for_slots(slots.tolist()) == keys
    assert km.keys_for_slots([]) == []
    free_slot = next(s for s in range(cap) if s not in rev)
    assert km.keys_for_slots([free_slot, -1, cap]) == [None] * 3

    # Free two thirds of the keys: their bytes outweigh the live ones',
    # so free_slots compacts the arena.
    gone = slots[: 2000]
    assert km.free_slots(gone) == 2000
    stats = km.arena_stats()
    assert stats["dead"] == 0
    assert stats["used"] == sum(len(k) for k in keys[2000:])
    assert km.keys_for_slots(gone.tolist()) == [None] * 2000
    rev = _reverse(km)
    assert km.keys_for_slots(probe) == [rev.get(s) for s in probe]
    assert km.keys_for_slots(slots[2000:].tolist()) == keys[2000:]

    # A new key takes a freed slot: the lookup names it at once.
    fresh = [b"fresh:%d" % i for i in range(50)]
    got, _, _, _ = km.resolve(fresh, np.ones(len(fresh), bool))
    assert set(got.tolist()) <= set(gone.tolist())
    assert km.keys_for_slots(got.tolist()) == fresh
    rev = _reverse(km)
    assert km.keys_for_slots(probe) == [rev.get(s) for s in probe]
