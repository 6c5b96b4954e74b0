"""A benchmark run with the timed path broken underneath:
`python tests/benchmark/faulty_run.py <fault> <run.py arguments>`.
The faults are planted here, in the test's own process, after the
harness's set-up has imported the program and before the server starts,
so that the harness carries no path of its own for them."""

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness  # noqa: E402


def answer_altered():
    """One answer altered where the device's results are fetched: one
    row of each launch, on one chip; one row of each batch, on a mesh."""
    from throttlecrab_tpu.parallel import sharded
    from throttlecrab_tpu.tpu import limiter

    fetch = limiter._PendingWireLaunch.fetch

    def altered(self):
        res = fetch(self)
        for r in res:
            if len(r.remaining):
                r.remaining[0] += 1
                break
        return res

    limiter._PendingWireLaunch.fetch = altered
    result = sharded.ShardedTpuRateLimiter._make_result

    def altered_result(valid, max_burst, status, allowed, remaining,
                       *rest):
        if len(remaining):
            remaining[0] += 1
        return result(valid, max_burst, status, allowed, remaining, *rest)

    sharded.ShardedTpuRateLimiter._make_result = staticmethod(altered_result)


def state_unchanged():
    """A launch that decides but leaves the table's state as it was."""
    from throttlecrab_tpu.tpu import table

    launch = table.BucketTable.check_many_packed

    def unchanged(self, *a, **kw):
        saved = {
            k: getattr(self, k).copy()
            for k in ("state", "exp_acc", "ins_counts")
            if getattr(self, k, None) is not None
        }
        out = launch(self, *a, **kw)
        for k, v in saved.items():
            setattr(self, k, v)
        return out

    table.BucketTable.check_many_packed = unchanged

    from throttlecrab_tpu.parallel import sharded

    for name in ("check_batch", "check_many"):
        mesh_launch = getattr(sharded.ShardedBucketTable, name)

        def mesh_unchanged(self, *a, _launch=mesh_launch, **kw):
            saved = self.state.copy()  # the launch donates its state
            out = _launch(self, *a, **kw)
            self.state = saved
            return out

        setattr(sharded.ShardedBucketTable, name, mesh_unchanged)


def coarse_clock():
    """The driver reads its clock in whole milliseconds and records what
    it read, so the replies agree with the recorded clock."""
    from throttlecrab_tpu.server import native_redis

    init = native_redis.NativeRedisTransport.__init__

    def coarse(self, *a, **kw):
        init(self, *a, **kw)
        self.now_fn = lambda: time.time_ns() // 1_000_000 * 1_000_000

    native_redis.NativeRedisTransport.__init__ = coarse


def tenant_count_shifted():
    """One tenant's allowed counter off by one, where a launch's
    per-tenant counts are folded into the totals."""
    from throttlecrab_tpu.parallel import sharded

    bump = sharded.ShardedTpuRateLimiter._bump_counters
    done = []

    def shifted(self, allowed, denied, expired=0, tcounts=None):
        if tcounts is not None and not done and tcounts.any():
            tcounts = tcounts.copy()
            tcounts[int(tcounts.sum(axis=1).argmax()), 0] += 1
            done.append(True)
        return bump(self, allowed, denied, expired, tcounts=tcounts)

    sharded.ShardedTpuRateLimiter._bump_counters = shifted


def exchange_left_out():
    """The mesh's cross-chip sum left out: only the first chip's
    counters enter the psum, so every chip takes them as the total; the
    replies stand and the counts lose the other chips' decisions."""
    from jax import lax

    from throttlecrab_tpu.parallel import sharded

    class NoExchange:
        def __getattr__(self, name):
            return getattr(lax, name)

        @staticmethod
        def psum(x, axis):
            first = lax.axis_index(axis) == 0
            return lax.psum(x * first.astype(x.dtype), axis)

    sharded.lax = NoExchange()


def launch_shapes():
    """Breaks nothing: records the (kind, K, B, tier) of every sharded
    launch in the JSON file $LAUNCH_SHAPES_OUT -- the prewarm's idle
    launches under "prewarm", the served ones under "served"."""
    from throttlecrab_tpu.parallel import sharded

    seen = {"prewarm": set(), "served": set()}

    def note(kind, valid, compact):
        shape = valid.shape
        k, b = (1, shape[1]) if len(shape) == 2 else (shape[1], shape[2])
        group = "served" if valid.any() else "prewarm"
        seen[group].add((kind, k, b, str(compact)))
        with open(os.environ["LAUNCH_SHAPES_OUT"], "w") as f:
            json.dump({g: sorted(v) for g, v in seen.items()}, f)

    for name in ("check_batch", "check_many"):
        launch = getattr(sharded.ShardedBucketTable, name)

        def noted(self, *a, _launch=launch, _name=name, **kw):
            note(_name, a[6], kw.get("compact"))
            return _launch(self, *a, **kw)

        setattr(sharded.ShardedBucketTable, name, noted)


FAULTS = {f.__name__: f for f in (answer_altered, state_unchanged,
                                  coarse_clock, tenant_count_shifted,
                                  exchange_left_out, launch_shapes)}


def main(argv):
    plant = FAULTS[argv[0]]
    prepare = harness.Run.prepare

    def prepare_then_break(self):
        prepare(self)
        plant()

    harness.Run.prepare = prepare_then_break
    return harness.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
