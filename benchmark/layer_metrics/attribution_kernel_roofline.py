"""`attribution_kernel_roofline.*`: the least time this device could take
to verify the attributed chunks' real lanes (rooflines/ed25519_verify.py
over peaks.json: the same reference work whatever kernel does it) over
the per-lane attribution program's device time, in %."""

from benchmark.layer_metrics import _attribution


def read(ctx):
    got = _attribution.seconds_and_lanes(ctx)
    if got is None:
        return None
    seconds, lanes, hash_blocks = got
    least, bound = ctx.roofline("ed25519_verify").least_seconds(
        lanes, hash_blocks, ctx.peaks())
    print(f"[layer] roofline of {lanes} attributed lanes: {least:.9f}s, "
          f"bounded by {bound}", flush=True)
    return 100.0 * least / seconds
