"""Kernels (tpu/kernel.py): the expiry sweep's share of its roofline in
the traced window.  Needed bytes over the device time of the programs
whose jit names match PROGRAMS, over the chip's HBM peak.

The needed bytes are fixed by what a sweep must do, not by how it does
it: read the expiry (i64) of every slot that holds a key, and for every
slot it frees write the empty row (TAT and expiry, i64 each) and return
the slot's 4 B id.  The program counters give them summed over the
window's sweeps (delta throttlecrab_tpu_sweep_occupied_total and
throttlecrab_tpu_slots_freed, over delta throttlecrab_tpu_sweeps); they
are taken per sweep and counted for the sweeps in the trace, since the
counters and the trace open and close apart.  A sweep that skips the
empty slots still reads every occupied one, so this never passes 100%.
None where the program has no such counter or no sweep is in the
trace."""

from benchmark import trace_reduce

PROGRAMS = (r"sweep_expired",)
EXPIRY_READ = 8
EMPTY_ROW_WRITE = 2 * 8  # TAT, expiry
SLOT_ID_OUT = 4
OCCUPIED = "throttlecrab_tpu_sweep_occupied_total"
FREED = "throttlecrab_tpu_slots_freed"
SWEEPS = "throttlecrab_tpu_sweeps"


def needed_bytes(occupied: int, freed: int) -> int:
    return (EXPIRY_READ * int(occupied)
            + (EMPTY_ROW_WRITE + SLOT_ID_OUT) * int(freed))


def read(ctx):
    if ctx.trace is None or OCCUPIED not in ctx.counters_after:
        return None
    found = trace_reduce.module_seconds(ctx.trace, PROGRAMS)
    sweeps = ctx.delta(SWEEPS)
    if found is None or found[0] <= 0 or sweeps <= 0:
        return None
    seconds, traced = found
    per_sweep = needed_bytes(ctx.delta(OCCUPIED), ctx.delta(FREED)) / sweeps
    peak = ctx.peaks()["hbm_bytes_per_s"]
    return 100.0 * per_sweep * traced / seconds / peak
