"""`finalize_ms_per_height.*`: median of the program's
`consensus.finalize` spans (consensus/state.py, around
`_finalize_commit`: the seen commit, `save_block`, the end-of-height WAL
record with its fsync, `apply_block`, `on_commit`, the next height's
round state), host clock, in ms. Nothing to read where the program opens
no such span (before PR 34)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "consensus.finalize")
