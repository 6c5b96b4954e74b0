"""Front tier (front/): frame rows whose deny-cache handle no longer
named their key when observed, so the cache looked the key up again --
delta throttlecrab_tpu_front_handle_fallbacks over the window (program
counter, GET /metrics).  0 while filter_frame's handles carry every miss
row to observe_frame; None where the program has no such counter."""

FALLBACKS = "throttlecrab_tpu_front_handle_fallbacks"


def read(ctx):
    if FALLBACKS not in ctx.counters_after:
        return None
    return ctx.delta(FALLBACKS)
