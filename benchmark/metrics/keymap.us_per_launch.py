"""Keymap (native/keymap.cpp): microseconds per device launch of the
wire window's key resolve and row packing and of the result finish --
delta throttlecrab_tpu_keymap_seconds_total (spans keymap.prepare and
keymap.finish) over delta throttlecrab_tpu_device_launches (program
counters).  `.sat` and `.paced` read it alike.  None where the program
has no such counter."""

KEYMAP = "throttlecrab_tpu_keymap_seconds_total"
LAUNCHES = "throttlecrab_tpu_device_launches"


def read(ctx):
    if KEYMAP not in ctx.counters_after:
        return None
    launches = ctx.delta(LAUNCHES)
    if launches <= 0:
        return None
    return 1e6 * ctx.delta(KEYMAP) / launches
