"""`respeculated_share.*`: of the signatures of the commits the window
applied, the share that took the synchronous route
(`SyncStats.respeculated_sigs`, engine/blocksync.py), in %. Lower is
better: those commits are verified one dispatch each with nothing
overlapped. Nothing to read where the driver reports no such counter."""


def read(ctx):
    sigs = ctx.result["counters"].get("respeculated_sigs")
    lanes = ctx.result["facts"].get("lanes")
    if sigs is None or not lanes:
        return None
    return 100.0 * sigs / lanes
