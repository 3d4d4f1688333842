"""`settle_wait_ms_per_tile.*`: median of the program's
`pipeline.settle.wait` spans (pipeline/scheduler.py `_settle`: the main
thread asleep in `watchdog.result(...)` / `future.result()` until the
dispatch thread has set the tile's verdicts; a tile whose verdicts were
final when it was built opens none), host clock. The tile was handed to
the dispatch thread `pipeline_depth - 1` tiles earlier, so this should
read 0; `settle_ms_per_tile.*` minus it is `settle_tile`'s host work.
Nothing to read where the program opens no such span (before PR 35)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "pipeline.settle.wait")
