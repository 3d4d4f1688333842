"""`prepare_ms_per_chunk.*`: median of the program's `ed25519.prepare`
spans (ops/ed25519.py `_verify_batch_loop`: one bucket-wide chunk's lanes
turned into the five device arrays by `prepare_batch`, padding lanes
included), host clock, count printed on an earlier line. On the dispatch
thread for a catch-up's tiles, where it shares the interpreter with the
main thread's stages, and on the caller's for a single commit. Nothing to
read where the program opens no such span (before PR 30, or a run whose
lanes all take the native route)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "ed25519.prepare")
