"""The sign-bytes template of a `Commit` (types/block.py, PR 30): the
lanes of one commit share a CanonicalVote's head and tail, built once,
and `vote_sign_bytes` still returns what the encoder returns the long
way and what the signed `Vote` signs, for the commit as it is at the
call. The template is no field: it does not travel through pickle or
copy and `==`, `repr` and `dataclasses.replace` do not see it. CPU only,
no kernel."""

import copy
import dataclasses
import pickle

import pytest

from cometbft_tpu.types import block as block_mod
from cometbft_tpu.types import proto
from cometbft_tpu.types.agg_commit import AggregatedCommit
from cometbft_tpu.types.block import (BLOCK_ID_FLAG_ABSENT,
                                      BLOCK_ID_FLAG_COMMIT,
                                      BLOCK_ID_FLAG_NIL, BlockID, Commit,
                                      CommitSig, PartSetHeader)
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.vote import PRECOMMIT_TYPE, Vote

MEMO = "_sign_bytes_template"
BID = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
OTHER_BID = BlockID(b"\x33" * 32, PartSetHeader(3, b"\x44" * 32))

# the timestamp's own length moves the outer length: both fields, one,
# none (the Unix epoch encodes as an empty message), a negative second
# (ten bytes of varint), Go's zero time
TIMESTAMPS = [Timestamp(1_700_000_000, 123_456_789), Timestamp(1_700_000_000, 0),
              Timestamp(0, 5), Timestamp(0, 0), Timestamp(-1, 999_999_999),
              Timestamp(), Timestamp(1, 1), Timestamp(1 << 40, 127),
              Timestamp(1 << 40, 128)]
FLAGS = [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_ABSENT]


def _sigs():
    """Every flag under every timestamp; an absent entry is empty."""
    out = []
    for i, ts in enumerate(TIMESTAMPS):
        for flag in FLAGS:
            if flag == BLOCK_ID_FLAG_ABSENT:
                out.append(CommitSig.absent())
            else:
                out.append(CommitSig(flag, bytes([i + 1]) * 20, ts,
                                     bytes([0xA0 + i]) * 64))
    return out


def _long_way(commit, chain_id, idx):
    """`vote_sign_bytes` as it was before the template."""
    cs = commit.signatures[idx]
    return proto.marshal_delimited(proto.canonical_vote(
        PRECOMMIT_TYPE, commit.height, commit.round,
        cs.block_id(commit.block_id).canonical(), cs.timestamp, chain_id))


def _signed_vote(commit, idx):
    cs = commit.signatures[idx]
    return Vote(type_=PRECOMMIT_TYPE, height=commit.height,
                round=commit.round, block_id=cs.block_id(commit.block_id),
                timestamp=cs.timestamp,
                validator_address=cs.validator_address,
                validator_index=idx)


def _aggregated(height, round_):
    return AggregatedCommit(height=height, round=round_, block_id=BID,
                            signatures=_sigs(), bitmap=b"\x01",
                            agg_sig=b"\x05" * 96)


COMMITS = {
    "round-0": lambda: Commit(7, 0, BID, _sigs()),
    "round-3": lambda: Commit(1 << 33, 3, BID, _sigs()),
    "nil-block-id": lambda: Commit(7, 1, BlockID(), _sigs()),
    "aggregated": lambda: _aggregated(9, 2),
}


@pytest.mark.parametrize("chain_id", ["", "c1", "x" * 50, "x" * 200],
                         ids=["empty", "short", "50", "two-byte-length"])
@pytest.mark.parametrize("kind", sorted(COMMITS))
def test_bytes_equal_the_long_way_and_the_signed_votes(kind, chain_id):
    commit = COMMITS[kind]()
    for idx in range(commit.size()):
        got = commit.vote_sign_bytes(chain_id, idx)
        assert got == _long_way(commit, chain_id, idx)
        assert got == _signed_vote(commit, idx).sign_bytes(chain_id)
        assert type(got) is bytes
    # the lanes did differ in length, or the case proves nothing
    assert len({len(commit.vote_sign_bytes(chain_id, i))
                for i in range(commit.size())}) >= 5


CHANGES = {
    "height": lambda c: setattr(c, "height", c.height + 1),
    "round": lambda c: setattr(c, "round", c.round + 1),
    "block_id": lambda c: setattr(c, "block_id", OTHER_BID),
    "block_id-to-nil": lambda c: setattr(c, "block_id", BlockID()),
}


@pytest.mark.parametrize("field", sorted(CHANGES))
def test_a_changed_field_gives_the_new_bytes(field):
    commit = COMMITS["round-0"]()
    before = [commit.vote_sign_bytes("c1", i) for i in range(commit.size())]
    CHANGES[field](commit)
    after = [commit.vote_sign_bytes("c1", i) for i in range(commit.size())]
    assert after == [_long_way(commit, "c1", i)
                     for i in range(commit.size())]
    assert after[0] != before[0]


def test_another_chain_id_gives_the_new_bytes_and_back():
    commit = COMMITS["round-0"]()
    for chain_id in ("c1", "c2", "c1", "", "c1"):
        assert commit.vote_sign_bytes(chain_id, 0) == _long_way(
            commit, chain_id, 0)


def test_a_replaced_signature_is_read_at_the_call():
    commit = COMMITS["round-0"]()
    commit.vote_sign_bytes("c1", 0)
    commit.signatures[0] = CommitSig(BLOCK_ID_FLAG_NIL, b"\x09" * 20,
                                     Timestamp(5, 6), b"\x0A" * 64)
    assert commit.vote_sign_bytes("c1", 0) == _long_way(commit, "c1", 0)


def test_an_unknown_flag_raises_what_it_raised():
    commit = Commit(7, 0, BID, [
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x01" * 20, Timestamp(1, 1),
                  b"\x02" * 64),
        CommitSig(9, b"\x01" * 20, Timestamp(1, 1), b"\x02" * 64)])
    for _ in range(2):  # with no template yet, and with one
        with pytest.raises(ValueError, match="unknown BlockIDFlag 9"):
            commit.vote_sign_bytes("c1", 1)
        with pytest.raises(ValueError, match="unknown BlockIDFlag 9"):
            _long_way(commit, "c1", 1)
        commit.vote_sign_bytes("c1", 0)
    with pytest.raises(IndexError):
        commit.vote_sign_bytes("c1", 2)


@pytest.mark.parametrize("kind", sorted(COMMITS))
@pytest.mark.parametrize("carry", [
    lambda c: pickle.loads(pickle.dumps(c)), copy.deepcopy, copy.copy,
    lambda c: dataclasses.replace(c, round=c.round)],
    ids=["pickle", "deepcopy", "copy", "replace"])
def test_no_template_travels(kind, carry):
    commit = COMMITS[kind]()
    commit.vote_sign_bytes("c1", 0)
    assert MEMO in commit.__dict__
    other = carry(commit)
    assert type(other) is type(commit) and other == commit
    assert MEMO not in other.__dict__
    assert MEMO in commit.__dict__  # and the original keeps its own
    built = block_mod.SIGN_BYTES_TEMPLATES[0]
    assert other.vote_sign_bytes("c1", 0) == commit.vote_sign_bytes("c1", 0)
    assert block_mod.SIGN_BYTES_TEMPLATES[0] == built + 1


def test_the_template_is_invisible():
    commit, fresh = COMMITS["round-0"](), COMMITS["round-0"]()
    commit.vote_sign_bytes("c1", 0)
    assert commit == fresh and repr(commit) == repr(fresh)
    assert dataclasses.asdict(commit) == dataclasses.asdict(fresh)
    assert commit.encode() == fresh.encode()
    assert commit.hash() == fresh.hash()
    assert Commit.decode(commit.encode()) == fresh


def test_one_template_a_commit_and_one_lane_a_call():
    commits = [COMMITS["round-0"](), COMMITS["aggregated"]()]
    built, served = block_mod.SIGN_BYTES_TEMPLATES
    for commit in commits:
        for idx in range(commit.size()):
            commit.vote_sign_bytes("c1", idx)
    lanes = sum(c.size() for c in commits)
    assert block_mod.SIGN_BYTES_TEMPLATES == [built + 2,
                                              served + lanes - 2]
    # a template that no longer fits is built again, and counted
    commits[0].height += 1
    commits[0].vote_sign_bytes("c1", 0)
    commits[0].vote_sign_bytes("c2", 0)
    commits[0].vote_sign_bytes("c2", 1)
    assert block_mod.SIGN_BYTES_TEMPLATES == [built + 4,
                                              served + lanes - 1]


def test_frame_and_embed_header_are_the_encoders_own_parts():
    ts = Timestamp(12, 34)
    for bid in (None, BID.canonical()):
        head, tail = proto.canonical_vote_frame(2, 7, 1, bid, "c1")
        assert proto.canonical_vote(2, 7, 1, bid, ts, "c1") == (
            head + proto.f_embed(5, ts.encode()) + tail)
    assert proto.f_embed(5, b"abc") == proto.embed_header(5, 3) + b"abc"
    assert [proto.uvarint(n) for n in (0, 1, 127, 128, 300)] == [
        b"\x00", b"\x01", b"\x7f", b"\x80\x01", b"\xac\x02"]
