"""`rlc_kernel_roofline.*`: the least time this device could take for the
window's real signatures (rooflines/ed25519_verify.py over peaks.json;
the integer ceiling is the int8 peak, see peaks.json) over the RLC verify
program's device time, in %. Says which of operations and bytes bounds it
on an earlier line."""

from benchmark.layer_metrics import _kernel


def read(ctx):
    s = _kernel.seconds(ctx)
    facts = ctx.result["facts"]
    if s is None or not facts["lanes"]:
        return None
    least, bound = ctx.roofline("ed25519_verify").least_seconds(
        facts["lanes"], facts["hash_blocks"], ctx.peaks())
    print(f"[layer] roofline of {facts['lanes']} lanes: {least:.6f}s, "
          f"bounded by {bound}", flush=True)
    return 100.0 * least / s
