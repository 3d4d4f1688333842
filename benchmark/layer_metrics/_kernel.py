"""The verify program's device seconds in the traced window."""

# the jitted RLC program as the profiler names it (`jit_<function>`); the
# per-lane attribution program is `verify_kernel` and runs in no window
PROGRAM = r"verify_rlc_core_pallas"


def seconds(ctx):
    if ctx.trace is None:
        return None
    hit = ctx.trace.program_seconds(PROGRAM)
    if hit is None or hit[0] <= 0:
        return None
    return hit[0]       # canary runs included; the [trace] line has the count
