"""Server process: the share of the window spent in garbage-collector
passes, in any thread of the process that serves -- delta
throttlecrab_process_gc_seconds_total (span process.gc) over the
window's seconds (program counter).  `.sat` and `.paced` read it alike.
None where the program has no such counter."""

GC = "throttlecrab_process_gc_seconds_total"


def read(ctx):
    if GC not in ctx.counters_after:
        return None
    return 100.0 * ctx.delta(GC) / ctx.window_s
