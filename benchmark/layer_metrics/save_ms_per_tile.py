"""`save_ms_per_tile.*`: median of the program's `light.save` spans
(light/client.py `_verify_sequential`), one a tile of the sequential
light client's walk, host clock, in ms; count printed on an earlier
line. The verdicts read lane by lane, each verified-true lane added to the
sigcache, and `LightStore.save_light_block` header by header, in order.
Nothing to read where the program opens no such span (before PR 36)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "light.save")
