"""`apply_ms_per_tile.*`: median of the program's `pipeline.apply` spans
(pipeline/scheduler.py: one tile's blocks through `_apply_one` —
validate, block store, application, state), host clock. Nothing to read
where the program opens no such span (before PR 27, or the synchronous
loop of a CPU run)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "pipeline.apply")
