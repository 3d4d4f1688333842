"""`bisect_save_ms_per_update.*`: median of the program's
`light.bisect.save` spans, one an update of the skipping light client,
host clock, in ms: the verdicts read step by step, `save_light_block`
a step, the true lanes into the sigcache in one keyed insert. Nothing to
read where the program opens no such span (before the planned skipping
walk)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "light.bisect.save")
