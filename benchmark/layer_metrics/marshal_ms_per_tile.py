"""`marshal_ms_per_tile.*`: median of the program's `pipeline.marshal`
spans (pipeline/scheduler.py: sign-bytes, sigcache lookups and lane lists
of one tile), host clock, count printed on an earlier line."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "pipeline.marshal")
