"""Mesh (parallel/sharded.py): the share of the chips' busy time spent in
cross-chip collectives -- the device time of the all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute operations (the psum
of the launch's global and per-tenant counters) over the union of all
operations, each averaged over the chips (device trace).  None on one
chip, or where no collective ran."""

from benchmark import trace_reduce

COLLECTIVES = (r"all-reduce", r"all-gather", r"reduce-scatter",
               r"all-to-all", r"collective-permute")


def read(ctx):
    trace = ctx.trace
    if trace is None or not trace["busy_s"] or trace["devices"] < 2:
        return None
    seconds = trace_reduce.op_seconds(trace, COLLECTIVES)
    if seconds is None:
        return None
    return 100.0 * seconds / trace["busy_s"]
