"""Native driver (server/native_redis.py): microseconds per device
launch of the driver thread's own work -- its time outside the wire
wait less the front tier, keymap and fetch stages: delta
throttlecrab_tpu_driver_busy_seconds_total minus the deltas of
throttlecrab_tpu_front_seconds_total, throttlecrab_tpu_keymap_seconds_total
and throttlecrab_tpu_fetch_seconds_total, over delta
throttlecrab_tpu_device_launches (program counters).  What remains is
capture, launch assembly, merge, the recorder's capture, replies,
metrics and the periodic ticks.  `.sat` and `.paced` read it alike.
None where the program has no such counter."""

BUSY = "throttlecrab_tpu_driver_busy_seconds_total"
STAGES = (
    "throttlecrab_tpu_front_seconds_total",
    "throttlecrab_tpu_keymap_seconds_total",
    "throttlecrab_tpu_fetch_seconds_total",
)
LAUNCHES = "throttlecrab_tpu_device_launches"


def read(ctx):
    if BUSY not in ctx.counters_after:
        return None
    launches = ctx.delta(LAUNCHES)
    if launches <= 0:
        return None
    own = ctx.delta(BUSY) - sum(ctx.delta(s) for s in STAGES)
    return 1e6 * own / launches
