"""`plan_ms_per_tile.*`: median of the program's `light.plan` spans
(light/client.py `_verify_sequential`), one a tile of the sequential
light client's walk, host clock, in ms; count printed on an earlier
line. From the first fetch from the provider to the last header planned:
`validate_basic`, the checks that need no signature, the commit's lanes
with their sign-bytes and cache lookups.
Nothing to read where the program opens no such span (before PR 36)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "light.plan")
