"""The bytes a validator signs for a precommit: CometBFT's
`CanonicalVote`, varint-length-delimited (reference types/vote.go
`VoteSignBytes`, proto/tendermint/types/canonical.proto), written from
the wire format and from nothing of the program.

    CanonicalVote        1 type (varint)        2 height (sfixed64)
                         3 round (sfixed64)     4 block_id (message, opt)
                         5 timestamp (message)  6 chain_id (string)
    CanonicalBlockID     1 hash (bytes)         2 part_set_header (message)
    CanonicalPartSetHeader 1 total (varint)     2 hash (bytes)
    Timestamp            1 seconds (varint)     2 nanos (varint)

proto3: a scalar that is zero is left out; the timestamp and the part set
header are always written (gogoproto nullable=false)."""

from __future__ import annotations

PRECOMMIT = 2


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(field: int, wire: int) -> bytes:
    return _uvarint((field << 3) | wire)


def _varint_field(field: int, n: int) -> bytes:
    return b"" if n == 0 else _tag(field, 0) + _uvarint(
        n & 0xFFFFFFFFFFFFFFFF)


def _sfixed64_field(field: int, n: int) -> bytes:
    return b"" if n == 0 else _tag(field, 1) + (
        n & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")


def _bytes_field(field: int, b: bytes) -> bytes:
    return b"" if not b else _tag(field, 2) + _uvarint(len(b)) + b


def _message_field(field: int, payload: bytes) -> bytes:
    return _tag(field, 2) + _uvarint(len(payload)) + payload


def precommit_sign_bytes(chain_id: str, height: int, round_: int,
                         block_hash: bytes, parts_total: int,
                         parts_hash: bytes, ts_seconds: int,
                         ts_nanos: int) -> bytes:
    psh = _varint_field(1, parts_total) + _bytes_field(2, parts_hash)
    block_id = _bytes_field(1, block_hash) + _message_field(2, psh)
    ts = _varint_field(1, ts_seconds) + _varint_field(2, ts_nanos)
    body = (_varint_field(1, PRECOMMIT) + _sfixed64_field(2, height)
            + _sfixed64_field(3, round_) + _message_field(4, block_id)
            + _message_field(5, ts)
            + _bytes_field(6, chain_id.encode("utf-8")))
    return _uvarint(len(body)) + body
