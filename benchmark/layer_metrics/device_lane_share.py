"""`device_lane_share.*`: of the lanes the sequential light client had to
verify over the window (`light.client.tile_stats()`: those the +2/3 rule
took and the cache did not answer), the share that went through a flush
of the crypto.batch seam, the device on a TPU, in %; the rest was
verified natively, lane by lane. 0 means the device never saw a header.
Nothing to read where the driver reports no such counters (before
PR 36), or no lane was verified."""


def read(ctx):
    c = ctx.result["counters"]
    device, native = (c.get("light_device_lanes"),
                      c.get("light_native_lanes"))
    if device is None or native is None or not device + native:
        return None
    return 100.0 * device / (device + native)
