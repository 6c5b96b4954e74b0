"""Cleanup (tpu/cleanup.py, the native driver's sweep hook): the share of
the window the native driver spends in its cleanup hook -- the policy's
bookkeeping each window, and every sweep with its expired-hit drain,
device sweep, keymap frees and deny-cache drop (span cleanup.sweep) --
delta throttlecrab_tpu_cleanup_seconds_total over the window's seconds
(program counter).  `.sat` reads it.  None where the program has no such
counter."""

CLEANUP = "throttlecrab_tpu_cleanup_seconds_total"


def read(ctx):
    if CLEANUP not in ctx.counters_after:
        return None
    return 100.0 * ctx.delta(CLEANUP) / ctx.window_s
