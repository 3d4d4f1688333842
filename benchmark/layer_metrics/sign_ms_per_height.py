"""`sign_ms_per_height.*`: the program's `privval.sign` spans
(consensus/state.py `_sign_add_vote`: the node's own vote signed by its
`FilePV`, the signer's state fsynced) inside the height's
`consensus.intake` runs, summed by the run's `height`, median over the
heights, in ms, host clock (`_intake_split.py`); the prevote that the
proposal triggers is signed outside them, and the `[layer]` line says
how long. Nothing to read where the program opens no such span."""

from benchmark.layer_metrics import _intake_split


def read(ctx):
    return _intake_split.median_part(ctx.spans, "privval.sign")
