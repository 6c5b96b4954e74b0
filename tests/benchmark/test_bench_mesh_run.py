"""The four-chip tenant mesh cell end to end at a tiny size, on four
virtual CPU devices: sound, it is correct, its per-tenant counters
agree with the reference, and every launch it served had been compiled
in set-up; with the control in the program's place it is not correct."""

import json

import pytest

from bench_rehearse import rehearse

CELL = "tenants-mesh4.zipf-sat"


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """A sound run, with the launch-shape probe (which breaks nothing)
    recording every sharded launch."""
    shapes = tmp_path_factory.mktemp("mesh") / "shapes.json"
    out = rehearse(CELL, 2**31 + 21, fault="launch_shapes",
                   env={"LAUNCH_SHAPES_OUT": str(shapes)})
    return out, json.loads(shapes.read_text())


def test_sound_mesh_run_is_correct(sound):
    out, _ = sound
    assert out["rehearsal_correct"] is True
    assert out["device"]["count"] >= 4
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {
        "wrong_replies", "unmatched_decisions", "failed_requests",
        "clock_outside", "tenant_counts"}
    assert all(v["value"] == 0 for v in out["checks"].values())


def test_every_served_launch_was_prewarmed(sound):
    out, shapes = sound
    served = {tuple(s) for s in shapes["served"]}
    prewarmed = {tuple(s) for s in shapes["prewarm"]}
    assert len(prewarmed) == out["run"]["prewarmed_launches"]
    # The rehearsal's backlog makes single batches and scans both.
    assert {s[0] for s in served} == {"check_batch", "check_many"}
    assert served <= prewarmed


def test_the_control_in_the_mesh_programs_place_is_not_correct():
    out = rehearse(CELL, 2**31 + 22, "--control")
    assert out["rehearsal_correct"] is False
    # The control's coarser clock moves its remaining and reset fields
    # on every run; its allowed counts per tenant only sometimes.
    wrong = out["checks"]["wrong_replies"]
    assert wrong["value"] > wrong["limit"]
