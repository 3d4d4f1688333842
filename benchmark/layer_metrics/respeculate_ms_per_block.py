"""`respeculate_ms_per_block.*`: median of the program's
`pipeline.respeculate` spans (engine/blocksync.py `_apply_one`: one
commit of a tile broken by a validator-set change, verified synchronously
by `types.validation.verify_commit` against the true set, a dispatch of
its own and nothing overlapped), host clock. Nothing to read where the
program opens no such span (before PR 29, or a chain whose set never
changes)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "pipeline.respeculate")
