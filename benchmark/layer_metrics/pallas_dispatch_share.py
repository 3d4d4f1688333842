"""`pallas_dispatch_share.*`: Pallas dispatches counted by
`ops.ed25519._dispatches` over the window, as a share of the bucket-wide
chunks the traffic implies. 100 means every chunk went to the Pallas
kernel; nothing to read where the traffic implies none (a CPU run)."""


def read(ctx):
    c = ctx.result["counters"]
    if not c.get("implied_chunks"):
        return None
    return 100.0 * c["dispatches"] / c["implied_chunks"]
