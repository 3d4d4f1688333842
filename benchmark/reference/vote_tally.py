"""The plain reference of one step's vote tally: which deliveries are
votes, which are exact duplicates, which conflict, at which delivery more
than 2/3 of the voting power stands behind one block, and which
precommits a commit made at that delivery holds. Written from the
published rules (reference types/vote_set.go `AddVote` /
`addVerifiedVote`, types/vote.go `VoteSignBytes`,
proto/tendermint/types/canonical.proto) and from nothing of the program.

A delivery is a dict: `index` (the validator's place in the set),
`block` (hash, parts total, parts hash; None for nil), `seconds`,
`nanos` (the vote's timestamp), `signature`, and optionally `own`: the
node's own vote, signed at run time, which the tally takes as handled
and whose signature the caller checks where it finds it.

Rules: a vote counts only if its index names a validator and its
signature verifies against that validator's key over the CanonicalVote
bytes; the first vote of a validator stands; the same vote again (same
block, same signature) is an exact duplicate and is ignored; the same
block under another signature is refused; another block from the same
validator is a conflict, kept as evidence with both votes and not
counted (no peer has claimed a majority for it). The step's block is the
first to gather more than 2/3 of the total power."""

from __future__ import annotations

from benchmark.reference import canonical_vote, ed25519_ref

PREVOTE, PRECOMMIT = 1, 2
VALID, DUPLICATE, CONFLICT, REFUSED = "valid", "duplicate", "conflict", \
    "refused"


def vote_sign_bytes(chain_id: str, type_: int, height: int, round_: int,
                    block, seconds: int, nanos: int) -> bytes:
    """CanonicalVote, varint-length-delimited, for either vote type:
    field 1 is the type, the rest is laid out as a precommit's (see
    canonical_vote.py for the layout); a nil vote leaves field 4 out."""
    c = canonical_vote
    block_id = b""
    if block is not None:
        block_hash, parts_total, parts_hash = block
        psh = c._varint_field(1, parts_total) + c._bytes_field(2, parts_hash)
        block_id = c._message_field(4, c._bytes_field(1, block_hash)
                                    + c._message_field(2, psh))
    ts = c._varint_field(1, seconds) + c._varint_field(2, nanos)
    body = (c._varint_field(1, type_) + c._sfixed64_field(2, height)
            + c._sfixed64_field(3, round_) + block_id
            + c._message_field(5, ts)
            + c._bytes_field(6, chain_id.encode("utf-8")))
    return c._uvarint(len(body)) + body


def tally(chain_id: str, type_: int, height: int, round_: int, pubs: list,
          powers: list, deliveries: list) -> dict:
    """The step's outcome: `verdicts` (one per delivery), `standing`
    (index -> the delivery that stands for that validator), `conflicts`
    ((first delivery, conflicting delivery) pairs), `crossing` (position
    of the delivery at which a block first has more than 2/3, or None),
    `block` (that block) and `holders` (index -> delivery: the votes for
    that block handled up to and including the crossing)."""
    quorum = sum(powers) * 2 // 3 + 1
    verdicts, standing, conflicts = [], {}, []
    behind: dict = {}
    crossing = block = None
    holders: dict = {}
    for pos, d in enumerate(deliveries):
        i = d["index"]
        if not 0 <= i < len(pubs):
            verdicts.append(REFUSED)
            continue
        first = standing.get(i)
        if first is not None and first["block"] == d["block"]:
            verdicts.append(DUPLICATE if first["signature"]
                            == d["signature"] else REFUSED)
            continue
        if not d.get("own") and not ed25519_ref.verify(
                pubs[i], vote_sign_bytes(chain_id, type_, height, round_,
                                         d["block"], d["seconds"],
                                         d["nanos"]), d["signature"]):
            verdicts.append(REFUSED)
            continue
        if first is not None:
            verdicts.append(CONFLICT)
            conflicts.append((first, d))
            continue
        verdicts.append(VALID)
        standing[i] = d
        behind[d["block"]] = behind.get(d["block"], 0) + powers[i]
        if crossing is None and behind[d["block"]] >= quorum:
            crossing, block = pos, d["block"]
            holders = {j: s for j, s in standing.items()
                       if s["block"] == block}
    return {"verdicts": verdicts, "standing": standing,
            "conflicts": conflicts, "crossing": crossing, "block": block,
            "holders": holders}
