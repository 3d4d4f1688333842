"""`barrier_ms_per_change.*`: median of the program's `pipeline.barrier`
spans (pipeline/scheduler.py: from the tile that sees a header with
another `validators_hash`, through the drain of the tiles in flight and
the synchronous rest of that tile, to the resumption of speculation from
the new set), host clock. A barrier that a ban cut short (the tiles in
flight were cancelled; the next pass meets the same change again) is
left out. Nothing to read where the program opens no such span (before
PR 29, the synchronous loop of a CPU run, or a chain whose set never
changes)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    whole = [s for s in ctx.spans
             if s.get("attrs", {}).get("outcome") != "cut-short"]
    return _spans.median_ms(whole, "pipeline.barrier")
