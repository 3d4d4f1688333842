"""`ban_refetch_ms.*`: the program's `pipeline.ban` spans
(pipeline/scheduler.py: from the ban of the peer that served a bad block,
through the cancelling of the tiles in flight and the refetch in a fresh
pass, to the first block applied afterwards), host clock, median (the
traffic that is there bans once). A ban whose refetch never applied a
block (the sync gave up) is not a refetch and is left out. Nothing to
read where the program opens no such span."""

from benchmark.layer_metrics import _spans


def read(ctx):
    done = [s for s in ctx.spans
            if s.get("attrs", {}).get("outcome") != "gave-up"]
    return _spans.median_ms(done, "pipeline.ban")
