"""`bisect_device_lane_share.*`: of the distinct lanes the skipping light
client verified over the window (`light.client.bisect_stats()`: those
its checks took and the cache did not answer, a signature two checks of
a step take once), the share that went through a flush of the
crypto.batch seam, the device on a TPU, in %; the rest was verified
natively, lane by lane. Nothing to read where the driver reports no
such counters (before the planned skipping walk), or no lane was
verified."""


def read(ctx):
    c = ctx.result["counters"]
    device, native = (c.get("bisect_device_lanes"),
                      c.get("bisect_native_lanes"))
    if device is None or native is None or not device + native:
        return None
    return 100.0 * device / (device + native)
