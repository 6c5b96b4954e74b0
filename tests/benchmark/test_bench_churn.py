"""The sweeping cell end to end at a tiny size: sound, it is correct
while its sweeps free slots and hand them to other keys; with a sweep
that frees the keymap's entries of live rows and leaves the rows, the
same checks refuse it."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CELL = "churn-1m.uniform-sat"


def rehearse(plant: str, seed: int, env: dict = None) -> dict:
    """The result line of one rehearsal through churn_run.py."""
    cmd = [sys.executable, str(HERE / "churn_run.py"), plant,
           "--workload", CELL, "--seed", str(seed), "--seconds", "2",
           "--trace", "0", "--rehearse"]
    proc = subprocess.run(cmd, cwd=HERE.parents[1], capture_output=True,
                          timeout=900, env={**os.environ, **(env or {})})
    assert proc.returncode == 0, proc.stderr.decode()[-3000:]
    return json.loads(proc.stdout.decode().splitlines()[-1])


def test_sweeps_free_and_recycle_slots_and_stay_correct(tmp_path):
    out_path = tmp_path / "sweeps.json"
    out = rehearse("sweeps_watched", 2**31 + 26,
                   {"SWEEPS_OUT": str(out_path)})
    assert out["rehearsal_correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(v["value"] == 0 for v in out["checks"].values())
    sweeps = json.loads(out_path.read_text())
    assert sum(f > 0 for f in sweeps["freed"]) >= 3
    assert all(f <= o for f, o in zip(sweeps["freed"], sweeps["occupied"]))
    assert sweeps["recycled"] >= 1


@pytest.mark.parametrize("plant, correct", [
    ("sweep_early_rows_left", False),
    ("sweep_rows_left", True),
])
def test_a_sweep_that_leaves_rows(plant, correct):
    """Only a live row left behind changes an answer: the check refuses
    it by its wrong replies, and passes the expired rows left behind."""
    out = rehearse(plant, 2**31 + 27)
    assert out["rehearsal_correct"] is correct
    wrong = out["checks"]["wrong_replies"]
    assert (wrong["value"] > wrong["limit"]) is not correct
