"""A benchmark run of a sweeping cell with its sweeps watched or broken:
`python tests/benchmark/churn_run.py <plant> <run.py arguments>`.  The
plants join faulty_run.py's and are planted the same way, in the test's
own process, after the harness's set-up and before the server starts."""

import json
import os
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import faulty_run  # noqa: E402

AHEAD_NS = 100_000_000


def sweeps_watched():
    """Breaks nothing: writes to the JSON file $SWEEPS_OUT, after every
    sweep, the slots each sweep freed as the server counted them
    (`freed`, `occupied`), and how many slots were freed from two
    different keys in the run (`recycled`)."""
    from throttlecrab_tpu.native import NativeKeyMap
    from throttlecrab_tpu.server.metrics import Metrics

    counted = []
    held = defaultdict(set)
    free_slots = NativeKeyMap.free_slots
    record_sweep = Metrics.record_sweep

    def watched_free(self, slots):
        owner = {s: k for k, s in self.items()}
        for s in slots.tolist():
            if s in owner:
                held[s].add(owner[s])
        return free_slots(self, slots)

    def watched_record(self, freed, occupied):
        record_sweep(self, freed, occupied)
        counted.append((freed, occupied))
        with open(os.environ["SWEEPS_OUT"], "w") as f:
            json.dump({
                "freed": [c[0] for c in counted],
                "occupied": [c[1] for c in counted],
                "recycled": sum(len(k) > 1 for k in held.values()),
            }, f)

    NativeKeyMap.free_slots = watched_free
    Metrics.record_sweep = watched_record


def _keymap_only_sweep(ahead_ns):
    """A sweep that frees the keymap's entries of every row expired
    `ahead_ns` past the sweep's clock and vacates no row."""
    import numpy as np

    from throttlecrab_tpu.tpu import limiter

    def sweep(self, now_ns):
        expiry = np.asarray(self.table.expiry)
        return self.keymap.free_slots(
            np.flatnonzero(expiry <= now_ns + ahead_ns))

    limiter.TpuRateLimiter.sweep = sweep


def sweep_rows_left():
    """Not a fault: the keymap frees the expired keys' entries and their
    rows stay as they were.  A row left behind has expired, and an
    expired row decides as an empty one, so no answer changes."""
    _keymap_only_sweep(0)


def sweep_early_rows_left():
    """The keymap frees the entries of rows still live for another
    AHEAD_NS, and their rows stay: a key that takes a freed slot
    inherits another key's live row, and a freed key starts afresh."""
    _keymap_only_sweep(AHEAD_NS)


faulty_run.FAULTS.update({f.__name__: f for f in (
    sweeps_watched, sweep_rows_left, sweep_early_rows_left)})

if __name__ == "__main__":
    sys.exit(faulty_run.main(sys.argv[1:]))
