"""`lanes_per_flush.*`: lanes the sequential light client flushed through
the crypto.batch seam over the window, over its flushes
(`light.client.tile_stats()`): how many headers' lanes ride one flush of
512-lane chunks. Nothing to read where the driver reports no such
counters (before PR 36), or there was no flush (a CPU run)."""


def read(ctx):
    c = ctx.result["counters"]
    if not c.get("light_flushes"):
        return None
    return c["light_device_lanes"] / c["light_flushes"]
