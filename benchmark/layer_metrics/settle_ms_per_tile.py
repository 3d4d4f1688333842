"""`settle_ms_per_tile.*`: median of the program's `pipeline.settle`
spans (the wait for the tile's verdicts under the watchdog, then
`settle_tile`: verdicts to commits, sigcache inserts), host clock."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "pipeline.settle")
