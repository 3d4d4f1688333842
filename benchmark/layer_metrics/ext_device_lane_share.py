"""`ext_device_lane_share.*`: of the vote-extension signatures the vote
intake had to verify over the window (`consensus.state.intake_stats()`
`ext_device_lanes` and `ext_native_lanes`: a non-nil precommit's second
lane, where the cache did not answer it), the share that went through a
flush of the crypto.batch seam, the device on a TPU, in %; the rest was
left to the native check. Nothing to read where the program does not
count extension lanes, or none missed."""


def read(ctx):
    c = ctx.result["counters"]
    device, native = (c.get("intake_ext_device_lanes"),
                      c.get("intake_ext_native_lanes"))
    if device is None or native is None or not device + native:
        return None
    return 100.0 * device / (device + native)
