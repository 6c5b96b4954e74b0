"""Native driver (server/native_redis.py): microseconds per device
launch that the driver waits in the fetch of the launch's result (the
`np.asarray` of the device output, which also waits for the launch to
finish) -- delta throttlecrab_tpu_fetch_seconds_total (span
driver.fetch) over delta throttlecrab_tpu_device_launches (program
counters).  `.sat` and `.paced` read it alike.  None where the program
has no such counter."""

FETCH = "throttlecrab_tpu_fetch_seconds_total"
LAUNCHES = "throttlecrab_tpu_device_launches"


def read(ctx):
    if FETCH not in ctx.counters_after:
        return None
    launches = ctx.delta(LAUNCHES)
    if launches <= 0:
        return None
    return 1e6 * ctx.delta(FETCH) / launches
