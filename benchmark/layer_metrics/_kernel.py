"""The verify program's device seconds in the traced window."""

# the jitted RLC program as the profiler names it (`jit_<function>`); the
# per-lane attribution program is `jit_verify_core`, which this does not
# match: `_attribution.py` reads it, in the cell where it runs
PROGRAM = r"verify_rlc_core_pallas"


def seconds(ctx):
    if ctx.trace is None:
        return None
    hit = ctx.trace.program_seconds(PROGRAM)
    if hit is None or hit[0] <= 0:
        return None
    return hit[0]       # canary runs included; the [trace] line has the count
