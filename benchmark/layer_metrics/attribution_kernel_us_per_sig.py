"""`attribution_kernel_us_per_sig.*`: summed device durations of the
per-lane attribution program's executions in the traced window
(`verify_kernel`, run for a chunk whose RLC equation failed), over the
real lanes of those chunks (`ops.ed25519.batch_stats()`). The chunk is
padded to the bucket; padding is time spent to blame these lanes, so it
counts."""

from benchmark.layer_metrics import _attribution


def read(ctx):
    got = _attribution.seconds_and_lanes(ctx)
    if got is None:
        return None
    return got[0] * 1e6 / got[1]
