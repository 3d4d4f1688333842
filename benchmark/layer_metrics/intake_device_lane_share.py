"""`intake_device_lane_share.*`: of the lanes the vote intake had to
verify over the window (`consensus.state.intake_stats()`: those the cache
did not answer), the share that went through a flush of the crypto.batch
seam, the device on a TPU, in %; the rest was left to the native
per-vote check. 0 means the device never saw a vote. Nothing to read
where the driver reports no such counters, or no lane missed."""


def read(ctx):
    c = ctx.result["counters"]
    device, native = (c.get("intake_device_lanes"),
                      c.get("intake_native_lanes"))
    if device is None or native is None or not device + native:
        return None
    return 100.0 * device / (device + native)
