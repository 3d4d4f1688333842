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


def _commit_by_hand(commit: Commit) -> tuple:
    """(wire, hash) by the formulas `Commit` had before the one pass."""
    want = (proto.f_varint(1, commit.height)
            + proto.f_varint(2, commit.round)
            + proto.f_embed(3, commit.block_id.encode()))
    for cs in commit.signatures:
        want += proto.f_embed(4, _encode_by_hand(cs))
    return want, merkle.hash_from_byte_slices(
        [_encode_by_hand(cs) for cs in commit.signatures])


@pytest.mark.parametrize("n", [0, 1, 5, 200])
def test_commit_encode_and_hash_by_the_old_formulas(n):
    commit = _commit(n)
    want, want_hash = _commit_by_hand(commit)
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


# --- the one encoding rule, in a commit's one pass and alone ---------------

GO_ZERO = Timestamp()
TIMESTAMPS = {
    # negative seconds: a ten-byte varint
    "go-zero": GO_ZERO,
    "seconds-0": Timestamp(0, 0),
    "seconds-0-nanos-1": Timestamp(0, 1),
    **{f"seconds-2^{k}{edge}": Timestamp(2 ** k + d, 5)
       for k in (7, 14, 21, 28, 35) for edge, d in (("-1", -1), ("", 0))},
    "nanos-0": Timestamp(1_700_000_000, 0),
    "nanos-1": Timestamp(1_700_000_000, 1),
    "nanos-max": Timestamp(1_700_000_000, 999_999_999),
}


def _fresh(sigs) -> list:
    """The same CommitSigs as new instances: no memo travels."""
    return [copy.copy(cs) for cs in sigs]


def _assert_encodes_as_by_hand(sigs) -> None:
    """Alone, and in a commit's one pass (encode and hash): the bytes of
    `_encode_by_hand` and `_commit_by_hand`, and the decoded round trip."""
    for cs in _fresh(sigs):
        assert cs.encode() == _encode_by_hand(cs)
        assert CommitSig.decode(cs.encode()) == cs
    for first in ("encode", "hash"):
        commit = Commit(height=9, round=1, block_id=BID,
                        signatures=_fresh(sigs))
        want, want_hash = _commit_by_hand(commit)
        if first == "hash":
            assert commit.hash() == want_hash
        assert commit.encode() == want and commit.hash() == want_hash
        assert [cs.__dict__[MEMO] for cs in commit.signatures] == [
            _encode_by_hand(cs) for cs in commit.signatures]
        decoded = Commit.decode(want)
        assert decoded == commit and decoded.encode() == want


@pytest.mark.parametrize("sig_len", [0, 64, 96])
@pytest.mark.parametrize("addr_len", [0, 20])
@FLAGS
def test_every_frame_encodes_as_by_hand(flag, addr_len, sig_len):
    """Each (flag, address length) head and signature head, under every
    timestamp of `TIMESTAMPS`, one lane each."""
    _assert_encodes_as_by_hand([
        CommitSig(flag, bytes([0x30 + i]) * addr_len, ts,
                  bytes([0x60 + i]) * sig_len)
        for i, ts in enumerate(TIMESTAMPS.values())])


@pytest.mark.parametrize("ts", TIMESTAMPS.values(), ids=TIMESTAMPS.keys())
def test_every_timestamp_encodes_as_by_hand(ts):
    """A whole commit of lanes under one timestamp: the seconds field of
    the first lane serves all the others."""
    _assert_encodes_as_by_hand([
        CommitSig(BLOCK_ID_FLAG_COMMIT, bytes([i + 1]) * 20, ts,
                  bytes([0xA0 + i]) * 64) for i in range(4)])


def _mixed() -> list:
    """Every flag, address and signature length and timestamp of the
    cases above in one commit, seconds repeating and alternating."""
    stamps = list(TIMESTAMPS.values())
    sizes = [(a, s) for a in (0, 20) for s in (0, 64, 96)]
    flags = [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_NIL, 0]
    sigs = []
    for i in range(3 * len(stamps)):
        # the timestamps in order, then alternating pairs, then repeats
        ts = stamps[i if i < len(stamps) else
                    (i % 2 if i < 2 * len(stamps) else i % 3)]
        addr_len, sig_len = sizes[i % len(sizes)]
        sigs.append(CommitSig(flags[i % len(flags)],
                              bytes([i % 256]) * addr_len, ts,
                              bytes([(7 * i) % 256]) * sig_len))
    return sigs


def test_a_commit_mixing_every_case():
    _assert_encodes_as_by_hand(_mixed())


@pytest.mark.parametrize("memoised", [(), (0, 3, 4), "all"],
                         ids=["none-memoised", "some-memoised",
                              "all-memoised"])
def test_ts_prefix_counts_the_lanes_whose_seconds_repeat(memoised):
    """Of the lanes a pass builds, those whose seconds an earlier lane of
    the same pass built; a memoised lane is served, not built, and
    SIG_ENCODINGS counts a signature as it always has."""
    sigs = _mixed()
    memoised = range(len(sigs)) if memoised == "all" else memoised
    for i in memoised:
        sigs[i].encode()
    built = [cs for i, cs in enumerate(sigs) if i not in memoised]
    distinct = len({cs.timestamp.seconds for cs in built})
    enc, prefix = list(block_mod.SIG_ENCODINGS), list(block_mod.SIG_TS_PREFIX)
    commit = Commit(height=9, round=1, block_id=BID, signatures=sigs)
    commit.encode()
    assert block_mod.SIG_TS_PREFIX == [prefix[0] + len(built),
                                       prefix[1] + len(built) - distinct]
    assert block_mod.SIG_ENCODINGS == [enc[0] + len(built),
                                       enc[1] + len(sigs) - len(built)]
    commit.hash()       # every lane from its memo now: nothing built
    assert block_mod.SIG_TS_PREFIX == [prefix[0] + len(built),
                                       prefix[1] + len(built) - distinct]
    assert block_mod.SIG_ENCODINGS[1] == enc[1] + 2 * len(sigs) - len(built)


def test_foreign_flags_and_lengths_leave_the_frame_tables_as_they_are():
    """A peer's commit whose lanes carry a thousand flags, address and
    signature lengths that `validate_basic` refuses encodes by the
    formulas, and no frame of them is kept."""
    tables = (block_mod._SIG_HEADS, block_mod._TS_HEADS,
              block_mod._FIELD4_HEADS)
    before = [dict(table) for table in tables]
    _assert_encodes_as_by_hand([
        CommitSig(4 + i, bytes([i % 256]) * (21 + i), Timestamp(i, i),
                  bytes([(3 * i) % 256]) * (97 + i)) for i in range(1000)])
    assert [dict(table) for table in tables] == before


def test_one_rule_alone_and_in_the_pass(monkeypatch):
    """`CommitSig.encode` and the commit's pass build through the same
    rule, once a lane built."""
    calls = []
    rule = block_mod._commit_sig_wire
    monkeypatch.setattr(block_mod, "_commit_sig_wire",
                        lambda cs, ts: calls.append(cs) or rule(cs, ts))
    sigs = _mixed()
    sigs[0].encode()
    assert calls == [sigs[0]]
    Commit(height=9, round=1, block_id=BID, signatures=sigs).encode()
    assert calls == sigs
