"""`rlc_kernel_us_per_sig.*`: summed device durations of the RLC verify
program's executions in the traced window, over the real (unpadded)
signatures of the window. Padding lanes and the canary's executions are
time the program spends to serve these signatures, so they count."""

from benchmark.layer_metrics import _kernel


def read(ctx):
    s = _kernel.seconds(ctx)
    lanes = ctx.result["facts"]["lanes"]
    if s is None or not lanes:
        return None
    return s * 1e6 / lanes
