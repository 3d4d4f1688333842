"""`validate_commit_ms.*`: median of the program's `commit.verify` spans
(types/validation.py, around the body of `verify_commit`: sign-bytes,
cache lookups, the flush or the native checks of what missed), host
clock, in ms. Nothing to read where the program opens no such span
(before PR 34)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "commit.verify")
