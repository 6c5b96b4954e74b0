"""Always-on slice of the round-5 tier-ladder fuzz campaign.

scripts/fuzz_wire_tiers.py is the full campaign (hundreds of seeds);
this keeps a few seeds — one per traffic profile — running in the
regular suite so the differential class (w32/cur/4-plane tier
selection, hwm crossings, poison keys, degenerate probes, clock
regressions, sweeps, snapshot round trips vs the scalar oracle) can
never silently rot.
"""

import importlib.util
import pathlib

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "fuzz_wire_tiers",
    pathlib.Path(__file__).resolve().parent.parent
    / "scripts"
    / "fuzz_wire_tiers.py",
)
fuzz = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(fuzz)


@pytest.mark.parametrize("seed", [3000, 3001, 3002])  # benign/edges/hostile
def test_tier_ladder_fuzz_slice(seed):
    from conftest import require_devices

    try:
        require_devices(2)
        from throttlecrab_tpu.parallel.sharded import make_mesh

        mesh = make_mesh(2)
    except Exception:
        mesh = None
    before = fuzz.TOTAL["requests"]
    fuzz.run_seed(seed, steps=8, sharded_mesh=mesh)
    assert fuzz.TOTAL["requests"] > before


@pytest.mark.parametrize("seed", [3100, 3101])  # edges/hostile profiles
def test_tier_ladder_fuzz_insight_single(seed):
    """The edges and hostile profiles through the composed-XLA tier
    ladder, pinned to the scalar oracle request-by-request.  Odd seeds
    arm the insight tier on the single-device limiter too (the
    INS_WIDTH rows and the gcra_*_ins launches), which the plain slice
    never arms; the hostile profile (3101) drives the degenerate
    three-view orbit and the ladder's mid-stream downgrades there."""
    from conftest import require_devices

    try:
        require_devices(2)
        from throttlecrab_tpu.parallel.sharded import make_mesh

        mesh = make_mesh(2)
    except Exception:
        mesh = None
    before = fuzz.TOTAL["requests"]
    fuzz.run_seed(
        seed, steps=6, sharded_mesh=mesh, insight_single=bool(seed % 2)
    )
    assert fuzz.TOTAL["requests"] > before


def test_hotkey_abuse_deny_cache_slice():
    """One seed of the hot-key abuse profile (harness `hotkey-abuse`
    pattern) through the front tier's deny cache: cache-on and cache-off
    decisions pinned equal request-by-request, and the cache must have
    actually served (hits > 0 — equality alone would be vacuous)."""
    before = fuzz.TOTAL["requests"]
    hits = fuzz.run_hotkey_deny_seed(4000, steps=24)
    assert fuzz.TOTAL["requests"] > before
    assert hits > 0


@pytest.mark.parametrize("seed", [6000, 6001])
def test_trace_codec_fuzz_slice(seed):
    """Always-on slice of the record/replay trace-codec mutation fuzz
    (truncation, corruption, count-vs-size lies): every rejection must
    be the typed TraceError — the full campaign lives in
    scripts/fuzz_wire_tiers.py alongside the cluster-codec fuzzer."""
    n = fuzz.run_trace_frame_fuzz(seed, iters=250)
    assert n == 250
