"""The plain reference of the light client's commit rule: which lanes of
a commit `VerifyCommitLight` takes, and the bytes each of them signed
(whether a lane verifies is `ed25519_ref`'s to say). Written
from the published rule (reference types/validation.go:61-116
`VerifyCommitLight`, light/verifier.go:91-143 `VerifyAdjacent`) and from
nothing of the program.

A set is `members`: [(public key, voting power)] in the set's order
(power descending, then address ascending: `valset_replay.ordered`). A
commit is `lanes`: one entry a member, in that order, None where the
validator's signature is absent, else (seconds, nanos, signature) of its
precommit for the block.

The rule: walk the lanes in the set's order; a lane that is absent adds
nothing; every other lane is TAKEN (its signature has to verify) and its
member's power is added to the tally; the walk stops as soon as the
tally passes 2/3 of the set's total power (floor(total * 2 / 3), to be
passed strictly). A commit whose lanes end before that is refused for
want of power, whatever its signatures. Lanes behind the stop are never
looked at: which lanes are checked depends on the distribution of the
power, not on the count. The header before binds the set: its
`next_validators_hash` is the Merkle root of these members
(`valset_replay.validators_hash`)."""

from __future__ import annotations

from benchmark.reference import canonical_vote, valset_replay


def taken(members: list, lanes: list):
    """Indices of the lanes the rule takes, in order, or None where the
    commit is refused for want of power."""
    needed = sum(power for _pub, power in members) * 2 // 3
    tally, out = 0, []
    for i, ((_pub, power), lane) in enumerate(zip(members, lanes)):
        if lane is None:
            continue
        out.append(i)
        tally += power
        if tally > needed:
            return out
    return None


def sign_bytes(chain_id: str, height: int, block: tuple, lane: tuple
               ) -> bytes:
    """The bytes one lane signed: `block` = (hash, parts total, parts
    hash), `lane` = (seconds, nanos, signature)."""
    block_hash, parts_total, parts_hash = block
    return canonical_vote.precommit_sign_bytes(
        chain_id, height, 0, block_hash, parts_total, parts_hash, lane[0],
        lane[1])


validators_hash = valset_replay.validators_hash
