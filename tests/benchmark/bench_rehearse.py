"""Run one rehearsal of a cell (a tiny size on the CPU)."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def rehearse(cell: str, seed: int, *flags: str, fault: str = None,
             env: dict = None) -> dict:
    """The result line, with the earlier `run:` line under "run"; with
    `fault`, through faulty_run.py with that fault planted; `env` adds
    to the run's environment."""
    entry = (["benchmark/run.py"] if fault is None else
             [str(Path(__file__).with_name("faulty_run.py")), fault])
    cmd = [sys.executable, *entry, "--workload", cell,
           "--seed", str(seed), "--seconds", "2", "--trace", "0",
           "--rehearse", *flags]
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, timeout=900,
                          env={**os.environ, **(env or {})})
    assert proc.returncode == 0, proc.stderr.decode()[-3000:]
    lines = proc.stdout.decode().splitlines()
    out = json.loads(lines[-1])
    out["run"] = json.loads(
        next(x for x in lines if x.startswith("run: "))[5:]
    )
    return out
