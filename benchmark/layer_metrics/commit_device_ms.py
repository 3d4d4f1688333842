"""`commit_device_ms.*`: device busy time per `verify_commit` call of the
traced window, in ms; the call's median latency minus this is the host's
share (sign-bytes, marshalling, dispatch, read-back)."""


def read(ctx):
    t, calls = ctx.trace, ctx.result["facts"].get("calls")
    if t is None or not calls or t.busy_s <= 0:
        return None
    return t.busy_s * 1e3 / calls
