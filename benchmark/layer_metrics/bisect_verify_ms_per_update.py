"""`bisect_verify_ms_per_update.*`: median of the program's
`light.bisect.verify` spans, one an update of the skipping light client,
host clock, in ms: the one flush of the update's distinct lanes through
the crypto.batch seam (the device on a TPU), from the first lane handed
over to the last verdict read. Nothing to read where the program opens
no such span (before the planned skipping walk)."""

from benchmark.layer_metrics import _spans


def read(ctx):
    return _spans.median_ms(ctx.spans, "light.bisect.verify")
