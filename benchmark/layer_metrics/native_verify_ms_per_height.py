"""`native_verify_ms_per_height.*`: the program's `vote.verify` spans
(types/vote_set.py `VoteSet._check_signature`: one native ed25519 check
of a vote whose signature the cache did not hold; none on a hit) inside
the height's `consensus.intake` runs, summed by the run's `height`,
median over the heights, in ms, host clock (`_intake_split.py`). Nothing
to read where the program opens no such span."""

from benchmark.layer_metrics import _intake_split


def read(ctx):
    return _intake_split.median_part(ctx.spans, "vote.verify")
