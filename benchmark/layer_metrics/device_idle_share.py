"""`device_idle_share.*`: 1 - (union of the intervals in which an
operation ran on the device) / (traced window), in %, from the profiler
trace."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
