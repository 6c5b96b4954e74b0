"""Front tier (front/): microseconds of the native driver's deny-cache
and admission work per device launch -- delta
throttlecrab_tpu_front_seconds_total (spans front.filter and
front.observe) over delta throttlecrab_tpu_device_launches (program
counters).  `.sat` and `.paced` read it alike.  None where the program
has no such counter."""

FRONT = "throttlecrab_tpu_front_seconds_total"
LAUNCHES = "throttlecrab_tpu_device_launches"


def read(ctx):
    if FRONT not in ctx.counters_after:
        return None
    launches = ctx.delta(LAUNCHES)
    if launches <= 0:
        return None
    return 1e6 * ctx.delta(FRONT) / launches
