"""The per-instance wire memo of the frozen `CommitSig`
(types/block.py): same bytes as before, computed once an instance, never
seeded from foreign bytes, never carried through pickle or copy, and
invisible to `==`, `hash`, `repr` and `dataclasses.replace`. CPU only,
no kernel."""

import copy
import dataclasses
import pickle

import pytest

from cometbft_tpu.crypto import merkle
from cometbft_tpu.types import block as block_mod
from cometbft_tpu.types import proto
from cometbft_tpu.types.block import (BLOCK_ID_FLAG_ABSENT,
                                      BLOCK_ID_FLAG_COMMIT,
                                      BLOCK_ID_FLAG_NIL, Block, BlockID,
                                      Commit, CommitSig, Header,
                                      PartSetHeader)
from cometbft_tpu.types.proto import Timestamp

MEMO = "_wire_memo"
BID = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))


def _sig(flag: int, i: int = 0) -> CommitSig:
    if flag == BLOCK_ID_FLAG_ABSENT:
        return CommitSig.absent()
    return CommitSig(flag, bytes([(i + 1) % 256]) * 20,
                     Timestamp(1_700_000_000 + i, 7 * i),
                     bytes([(0xA0 + i) % 256]) * 64)


FLAGS = pytest.mark.parametrize(
    "flag", [BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL],
    ids=["absent", "commit", "nil"])


def _encode_by_hand(cs: CommitSig) -> bytes:
    """The formula `CommitSig.encode` had before the memo."""
    return (proto.f_varint(1, cs.block_id_flag)
            + proto.f_bytes(2, cs.validator_address)
            + proto.f_embed(3, cs.timestamp.encode())
            + proto.f_bytes(4, cs.signature))


def _commit(n: int = 5) -> Commit:
    flags = [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_NIL]
    return Commit(height=9, round=1, block_id=BID,
                  signatures=[_sig(flags[i % 3], i) for i in range(n)])


@FLAGS
def test_memoised_encoding_equals_a_fresh_instances(flag):
    cs = _sig(flag)
    first = cs.encode()
    assert first == _encode_by_hand(cs) == _sig(flag).encode()
    assert cs.encode() is first
    assert cs.__dict__[MEMO] is first


@FLAGS
def test_round_trip_through_the_wire_is_unchanged(flag):
    cs = _sig(flag)
    assert CommitSig.decode(cs.encode()) == cs
    assert CommitSig.decode(cs.encode()).encode() == cs.encode()


@FLAGS
def test_the_memo_is_no_field(flag):
    plain, memoised = _sig(flag), _sig(flag)
    memoised.encode()
    assert plain == memoised and hash(plain) == hash(memoised)
    assert repr(plain) == repr(memoised)
    assert [f.name for f in dataclasses.fields(memoised)] == [
        "block_id_flag", "validator_address", "timestamp", "signature"]
    assert dataclasses.asdict(plain) == dataclasses.asdict(memoised)
    with pytest.raises(dataclasses.FrozenInstanceError):
        memoised.signature = b""


@FLAGS
@pytest.mark.parametrize("carry", [
    lambda cs: pickle.loads(pickle.dumps(cs)),
    lambda cs: pickle.loads(pickle.dumps(cs, pickle.HIGHEST_PROTOCOL)),
    copy.copy, copy.deepcopy], ids=["pickle", "pickle-highest", "copy",
                                    "deepcopy"])
def test_the_memo_does_not_travel(flag, carry):
    cs = _sig(flag)
    wire = cs.encode()
    moved = carry(cs)
    assert moved == cs and type(moved) is CommitSig
    assert MEMO not in moved.__dict__
    assert moved.encode() == wire


def test_a_pickled_block_arrives_without_any_memo():
    commit = _commit()
    block = Block(header=Header(chain_id="memo", height=10,
                                validators_hash=b"\x33" * 32,
                                proposer_address=b"\x44" * 20,
                                last_commit_hash=commit.hash()),
                  last_commit=commit)
    wire = block.encode()
    assert all(MEMO in cs.__dict__ for cs in commit.signatures)
    moved = pickle.loads(pickle.dumps(
        {"blocks": [block]}, pickle.HIGHEST_PROTOCOL))["blocks"][0]
    assert not any(MEMO in cs.__dict__ for cs in moved.last_commit.signatures)
    computed = block_mod.SIG_ENCODINGS[0]
    assert moved.encode() == wire
    assert block_mod.SIG_ENCODINGS[0] - computed == commit.size()


@FLAGS
def test_replace_encodes_its_own_fields(flag):
    cs = _sig(flag)
    cs.encode()
    other = dataclasses.replace(cs, signature=b"\x5a" * 64)
    assert MEMO not in other.__dict__
    assert other.encode() == _encode_by_hand(other) != cs.encode()


def _reordered(cs: CommitSig) -> bytes:
    return (proto.f_bytes(4, cs.signature)
            + proto.f_varint(1, cs.block_id_flag)
            + proto.f_bytes(2, cs.validator_address)
            + proto.f_embed(3, cs.timestamp.encode()))


def _non_minimal_flag(cs: CommitSig) -> bytes:
    wire = _encode_by_hand(cs)      # tag 0x08, then the flag in one byte
    return bytes([wire[0], wire[1] | 0x80, 0x00]) + wire[2:]


@pytest.mark.parametrize("foreign_form", [
    # an unknown field 15 (varint 1) after the message
    lambda cs: _encode_by_hand(cs) + bytes([15 << 3, 1]),
    _non_minimal_flag,
    _reordered,
], ids=["unknown-field", "non-minimal-varint", "reordered"])
def test_decode_does_not_keep_foreign_bytes(foreign_form):
    cs = _sig(BLOCK_ID_FLAG_COMMIT, 3)
    canonical = _encode_by_hand(cs)
    foreign = foreign_form(cs)
    assert foreign != canonical
    decoded = CommitSig.decode(foreign)
    assert decoded == cs and MEMO not in decoded.__dict__
    assert decoded.encode() == canonical


@pytest.mark.parametrize("n", [0, 1, 5, 200])
def test_commit_encode_and_hash_by_the_old_formulas(n):
    commit = _commit(n)
    want = (proto.f_varint(1, commit.height)
            + proto.f_varint(2, commit.round)
            + proto.f_embed(3, commit.block_id.encode()))
    for cs in commit.signatures:
        want += proto.f_embed(4, _encode_by_hand(cs))
    want_hash = merkle.hash_from_byte_slices(
        [_encode_by_hand(cs) for cs in commit.signatures])
    for _ in range(2):      # the first pass computes, the second reuses
        assert commit.encode() == want
        assert commit.hash() == want_hash
    assert Commit.decode(want) == commit


def test_commit_itself_keeps_no_memo():
    commit = _commit()
    before, before_hash = commit.encode(), commit.hash()
    assert not [k for k in commit.__dict__ if k not in (
        "height", "round", "block_id", "signatures")]
    commit.signatures = list(reversed(commit.signatures))
    assert commit.encode() != before and commit.hash() != before_hash
    commit.signatures[0] = _sig(BLOCK_ID_FLAG_COMMIT, 9)
    assert commit.encode() == Commit.decode(commit.encode()).encode()
    commit.height += 1
    assert Commit.decode(commit.encode()).height == commit.height


def test_aggregated_commit_builds_on_the_same_encodings():
    from cometbft_tpu.types.agg_commit import AggregatedCommit
    plain = _commit()
    agg = AggregatedCommit(height=plain.height, round=plain.round,
                           block_id=plain.block_id,
                           signatures=list(plain.signatures),
                           bitmap=b"\x15", agg_sig=b"\x07" * 96)
    want = (plain.encode() + proto.f_bytes(5, agg.bitmap)
            + proto.f_bytes(6, agg.agg_sig))
    assert agg.encode() == want
    decoded = Commit.decode(want)
    assert isinstance(decoded, AggregatedCommit) and decoded.encode() == want
    assert agg.hash() != plain.hash()


def test_the_counters_tell_computed_from_reused():
    commit = _commit(6)
    computed, reused = block_mod.SIG_ENCODINGS
    commit.encode()
    assert block_mod.SIG_ENCODINGS == [computed + 6, reused]
    commit.hash()
    commit.encode()
    assert block_mod.SIG_ENCODINGS == [computed + 6, reused + 12]
