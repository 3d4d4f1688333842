"""The per-lane attribution program's device seconds in the traced
window, and the real lanes it was run for."""

# the jitted per-lane Straus kernel (`ops.ed25519.verify_kernel` =
# `jax.jit(verify_core)`) as the profiler names it; the RLC program is
# `jit_verify_rlc_core_pallas`, which this does not match
PROGRAM = r"^jit_verify_core$"


def seconds_and_lanes(ctx):
    """(seconds, lanes, hash blocks), or None where nothing ran, the
    run has no device trace or the driver counts no attributed lanes."""
    facts = ctx.result["facts"]
    lanes = facts.get("attributed_lanes")
    if ctx.trace is None or not lanes:
        return None
    hit = ctx.trace.program_seconds(PROGRAM)
    if hit is None or hit[0] <= 0:
        return None
    print(f"[layer] attribution: {hit[1]} executions of the per-lane "
          f"program, {hit[0]:.6f}s, for {lanes} real lanes", flush=True)
    return hit[0], lanes, facts["attributed_hash_blocks"]
