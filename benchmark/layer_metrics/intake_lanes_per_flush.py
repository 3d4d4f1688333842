"""`intake_lanes_per_flush.*`: lanes the vote intake flushed through the
crypto.batch seam over the window, over its flushes
(`consensus.state.intake_stats()`): how full the 512-lane bucket rides.
Nothing to read where there was no flush."""


def read(ctx):
    c = ctx.result["counters"]
    if not c.get("intake_flushes"):
        return None
    return c["intake_device_lanes"] / c["intake_flushes"]
