"""A block header's hash: CometBFT's `Header.Hash` (reference
types/block.go:440-475), the simple merkle root over the encodings of
its fourteen fields, each scalar wrapped in gogotypes' value message
(`cdcEncode`), written from the wire format and from nothing of the
program.

    Consensus        1 block (varint)      2 app (varint)
    StringValue, Int64Value, BytesValue    1 value
    Timestamp        1 seconds (varint)    2 nanos (varint)
    BlockID          1 hash (bytes)        2 part_set_header (message)
    PartSetHeader    1 total (varint)      2 hash (bytes)

proto3: a scalar that is zero or empty is left out; the part set header
is always written (gogoproto nullable=false)."""

from __future__ import annotations

from benchmark.reference.canonical_vote import (_bytes_field,
                                                _message_field,
                                                _varint_field)
from benchmark.reference.valset_replay import merkle_root


def header_hash(chain_id: str, height: int, time: tuple,
                last_block_id: tuple, hashes: dict,
                proposer_address: bytes, version_block: int = 11,
                version_app: int = 0) -> bytes:
    """`time` = (seconds, nanos); `last_block_id` = (hash, parts total,
    parts hash); `hashes` the header's seven hashes by field name."""
    block_hash, parts_total, parts_hash = last_block_id
    fields = [
        _varint_field(1, version_block) + _varint_field(2, version_app),
        _bytes_field(1, chain_id.encode("utf-8")),
        _varint_field(1, height),
        _varint_field(1, time[0]) + _varint_field(2, time[1]),
        _bytes_field(1, block_hash) + _message_field(
            2, _varint_field(1, parts_total) + _bytes_field(2, parts_hash)),
    ] + [_bytes_field(1, hashes[name]) for name in (
        "last_commit_hash", "data_hash", "validators_hash",
        "next_validators_hash", "consensus_hash", "app_hash",
        "last_results_hash", "evidence_hash")] + [
        _bytes_field(1, proposer_address)]
    return merkle_root(fields)
