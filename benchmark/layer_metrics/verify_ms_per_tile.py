"""`verify_ms_per_tile.*`: median of the program's `light.verify` spans
(light/client.py `_verify_sequential`), one a tile of the sequential
light client's walk, host clock, in ms; count printed on an earlier
line. The flush: from the first lane handed to the verifier to the last
verdict read (`ed25519.prepare` and `ed25519.readback` fall inside it).
Nothing to read where the program opens no such span (before PR 36)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "light.verify")
