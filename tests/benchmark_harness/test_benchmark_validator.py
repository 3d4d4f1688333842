"""The validator cell's own pieces (ISSUE 34): the plain reference of
the vote tally against the program's `VoteSet` over seeded steps and
against a small `ConsensusState` run; its sign-bytes against the
program's; the driver's faults (`correct` false under both plants and
under a node that skips a vote); the six readers on hand-built spans and
counters."""

import os
import random
import time
import types

import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest
from benchmark.reference import ed25519_ref, vote_tally

CELL = "hub-validator-150.vote-intake"
CHAIN = "tally-chain"
BLOCK = (b"\x11" * 32, 1, b"\x22" * 32)
OTHER = (b"\x33" * 32, 2, b"\x44" * 32)


def run(root, seed, trace=False, plant=""):
    return runner.run_cell(root, CELL, seed, 2.0, trace, time.perf_counter(),
                           look_for_chip=False, in_process_traffic=True,
                           plant=plant)


def over(out):
    return {n for n, row in out["checks"].items()
            if row["value"] > row["limit"]}


# --- the reference ----------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference",
                           "vote_tally.py")) as f:
        text = f.read()
    assert "cometbft_tpu" not in text
    assert [line for line in text.splitlines()
            if line.startswith(("import ", "from "))] == [
        "from __future__ import annotations",
        "from benchmark.reference import canonical_vote, ed25519_ref"]


@pytest.mark.parametrize("type_", [vote_tally.PREVOTE, vote_tally.PRECOMMIT])
@pytest.mark.parametrize("block", [BLOCK, OTHER, None])
def test_reference_sign_bytes_equal_the_programs(type_, block):
    from cometbft_tpu.types.block import BlockID, PartSetHeader
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.vote import Vote
    bid = BlockID() if block is None else BlockID(
        block[0], PartSetHeader(block[1], block[2]))
    for height, round_, nanos in ((1, 0, 0), (300, 2, 199), (2**40, 0, 7)):
        vote = Vote(type_=type_, height=height, round=round_, block_id=bid,
                    timestamp=Timestamp(1_700_000_000, nanos),
                    validator_address=b"\x33" * 20, validator_index=0)
        assert vote.sign_bytes(CHAIN) == vote_tally.vote_sign_bytes(
            CHAIN, type_, height, round_, block, 1_700_000_000, nanos)


def _step(rng, n):
    """A seeded step: every validator's vote for BLOCK in a drawn order,
    with exact duplicates, conflicting votes, altered signatures, votes
    under another signature, an index outside the set and a vote signed
    by a stranger mixed in."""
    signers = [ed25519_ref.Signer(bytes([i + 1]) * 32) for i in range(n)]
    stranger = ed25519_ref.Signer(b"\xee" * 32)

    def vote(i, block=BLOCK, signer=None, nanos=None, index=None):
        nanos = i if nanos is None else nanos
        sig = (signer or signers[i]).sign(vote_tally.vote_sign_bytes(
            CHAIN, vote_tally.PRECOMMIT, 5, 0, block, 1_700_000_005, nanos))
        return {"index": i if index is None else index, "block": block,
                "seconds": 1_700_000_005, "nanos": nanos, "signature": sig}
    order = list(range(n))
    rng.shuffle(order)
    deliveries = [vote(i) for i in order]
    for kind in (0, 1, 2, 3, 4, 5, rng.randrange(6), rng.randrange(6)):
        i, at = rng.randrange(n), rng.randrange(len(deliveries) + 1)
        extra = [vote(i), vote(i, block=OTHER), vote(i, nanos=900 + i),
                 dict(vote(i), signature=ed25519_ref.tamper(
                     vote(i)["signature"])),
                 vote(i, index=n + 3), vote(i, signer=stranger)][kind]
        deliveries.insert(at, extra)
    return [s.pub for s in signers], deliveries


@pytest.mark.parametrize("seed", range(6))
def test_tally_agrees_with_the_programs_vote_set(seed, fresh_sigcache):
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.types.block import BlockID, PartSetHeader
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.types.vote_set import (ErrVoteConflictingVotes,
                                             VoteError, VoteSet)
    rng = random.Random(seed)
    n = 9
    pubs, deliveries = _step(rng, n)
    # equal powers keep the set in the order of its addresses; hand the
    # reference the same order
    vals = ValidatorSet([Validator(Ed25519PubKey(p), 10) for p in pubs])
    place = {v.pub_key.bytes_(): k for k, v in enumerate(vals.validators)}
    ordered = [v.pub_key.bytes_() for v in vals.validators]
    moved = [dict(d, index=place[pubs[d["index"]]]
                  if d["index"] < n else d["index"]) for d in deliveries]
    told = vote_tally.tally(CHAIN, vote_tally.PRECOMMIT, 5, 0, ordered,
                            [10] * n, moved)
    vs = VoteSet(CHAIN, 5, 0, vote_tally.PRECOMMIT, vals)
    crossing, verdicts = None, []
    for pos, d in enumerate(moved):
        i = d["index"]
        vote = Vote(
            type_=vote_tally.PRECOMMIT, height=5, round=0,
            block_id=BlockID(d["block"][0], PartSetHeader(*d["block"][1:])),
            timestamp=Timestamp(d["seconds"], d["nanos"]),
            validator_address=vals.validators[i].address if i < n
            else b"\x01" * 20, validator_index=i, signature=d["signature"])
        try:
            verdicts.append(vote_tally.VALID if vs.add_vote(vote)
                            else vote_tally.DUPLICATE)
        except ErrVoteConflictingVotes:
            verdicts.append(vote_tally.CONFLICT)
        except VoteError:
            verdicts.append(vote_tally.REFUSED)
        if crossing is None and vs.has_two_thirds_majority():
            crossing = pos
            held = {k: v.signature for k, v in enumerate(vs.votes)
                    if v is not None and v.block_id == vs.maj23}
    assert told["verdicts"] == verdicts
    assert set(verdicts) == {"valid", "duplicate", "conflict", "refused"}
    assert told["crossing"] == crossing is not None
    assert told["block"] == BLOCK and vs.maj23.hash == BLOCK[0]
    assert {k: d["signature"] for k, d in told["holders"].items()} == held
    assert len(told["conflicts"]) == verdicts.count("conflict")


# --- the driver at its tiny sizes -----------------------------------------------------

def test_the_stored_commit_is_the_tallys_and_the_check_bites(
        tiny_root, fresh_sigcache, monkeypatch):
    """Every height of a small `ConsensusState` run stores exactly the
    precommits the reference says were handled up to +2/3; told of a
    validator's deliveries that were never made, the comparison says
    so."""
    manifest = Manifest(tiny_root)
    driver = manifest.load_module("drivers", "consensus_vote_intake")
    seen = []
    real = driver._stored_commit_diff

    def kept(session, row, deliveries):
        seen.append((session, row, deliveries))
        # asked while the node and its store are still there
        gone = next(d["index"] for d in deliveries if not d.get("own"))
        seen[-1] += (real(session, row, [d for d in deliveries
                                         if d["index"] != gone]),)
        return real(session, row, deliveries)
    monkeypatch.setattr(driver, "_stored_commit_diff", kept)
    out = run(tiny_root, 2**31 + 3401)
    assert out["correct"] and out["checks"]["stored_commit_diff"] == {
        "value": 0, "limit": 0}
    assert len(seen) == 16 + 1          # 16 window heights and the probe
    assert all(without_one > 0 for *_rest, without_one in seen)
    assert out["attempted"] == 16 and out["failed"] == 0


def _plant(name):
    return lambda monkeypatch: name


def _skip_a_vote(monkeypatch):
    """A node that leaves one vote of every height out: a delivery the
    reference counts is in no vote set."""
    from cometbft_tpu.consensus.state import ConsensusState
    real = ConsensusState._try_add_vote

    def skipping(self, vote, peer_id):
        if peer_id and vote.validator_index == 2 and vote.type_ == 2:
            return
        real(self, vote, peer_id)
    monkeypatch.setattr(ConsensusState, "_try_add_vote", skipping)
    return ""


@pytest.mark.parametrize("fault, must_fail", [
    (_plant("accept_all"), "probe_altered_admitted"),
    (_plant("half_lanes"), "probe_altered_admitted"),
    (_skip_a_vote, "stored_commit_diff"),
])
def test_vote_intake_with_a_fault_is_not_correct(tiny_root, fresh_sigcache,
                                                 monkeypatch, fault,
                                                 must_fail):
    # eight validators: the probe's burst is four precommits, which the
    # batched intake flushes once the threshold is theirs
    from cometbft_tpu.types import validation
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 4)
    monkeypatch.setattr(
        Manifest(tiny_root).load_module("drivers", "consensus_vote_intake"),
        "STUCK_S", 3.0)
    out = run(tiny_root, 2**31 + 3402, plant=fault(monkeypatch))
    assert not out["correct"] and must_fail in over(out)


def test_the_flush_is_in_the_tiny_run_once_the_threshold_allows(
        tiny_root, fresh_sigcache, monkeypatch, capfd):
    from cometbft_tpu.types import validation
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 4)
    out = run(tiny_root, 2**31 + 3403, trace=True)
    assert out["correct"] and over(out) == set()
    m = out["metrics"]
    assert 0 < m["intake_device_lane_share.validator"]["value"] <= 100
    assert 4 <= m["intake_lanes_per_flush.validator"]["value"] <= 7
    assert m["commit_cache_hit_share.validator"]["value"] == 100.0
    for name in ("intake_ms_per_height.validator",
                 "validate_commit_ms.validator",
                 "finalize_ms_per_height.validator"):
        assert m[name]["value"] > 0
    assert "stream_exhausted" in capfd.readouterr().out


# --- the readers --------------------------------------------------------------------

def _ctx(spans=(), counters=None):
    return types.SimpleNamespace(spans=list(spans),
                                 result={"counters": counters or {},
                                         "facts": {}})


def _span(name, ms, t0=0, **attrs):
    return {"name": name, "t0": t0, "t1": t0 + int(ms * 1e6), "attrs": attrs}


def _reader(name):
    return Manifest(REPO).layer_reader(name).read


def test_span_readers_on_hand_built_spans():
    spans = [_span("consensus.intake", 2.0, height=7, votes=3),
             _span("consensus.intake", 3.0, height=7, votes=90),
             _span("consensus.intake", 1.0, height=8, votes=1),
             _span("consensus.intake", 10.0, height=9, votes=128),
             _span("commit.verify", 0.5, lanes=149),
             _span("commit.verify", 0.7, lanes=149),
             _span("commit.verify", 4.0, lanes=149),
             _span("consensus.finalize", 3.0, height=7),
             _span("consensus.finalize", 5.0, height=8),
             _span("pipeline.apply", 99.0)]
    ctx = _ctx(spans)
    # heights 7, 8, 9 sum to 5, 1 and 10 ms
    assert _reader("intake_ms_per_height.validator")(ctx) == 5.0
    assert _reader("validate_commit_ms.validator")(ctx) == 0.7
    assert _reader("finalize_ms_per_height.validator")(ctx) == 3.0
    for name in ("intake_ms_per_height.validator",
                 "validate_commit_ms.validator",
                 "finalize_ms_per_height.validator"):
        assert _reader(name)(_ctx([_span("pipeline.apply", 1.0)])) is None


def test_counter_readers_on_hand_built_counters():
    c = {"intake_device_lanes": 300, "intake_native_lanes": 100,
         "intake_flushes": 4, "intake_cache_hits": 9,
         "sigcache_hits_commit": 298, "sigcache_misses_commit": 2}
    ctx = _ctx(counters=c)
    assert _reader("intake_device_lane_share.validator")(ctx) == 75.0
    assert _reader("intake_lanes_per_flush.validator")(ctx) == 75.0
    assert _reader("commit_cache_hit_share.validator")(ctx) == \
        pytest.approx(100 * 298 / 300)
    # the device never saw a vote: 0, which is a reading; no flush: none
    cpu = _ctx(counters=dict(c, intake_device_lanes=0, intake_flushes=0))
    assert _reader("intake_device_lane_share.validator")(cpu) == 0.0
    assert _reader("intake_lanes_per_flush.validator")(cpu) is None
    # a program or a driver without the counters
    for name in ("intake_device_lane_share.validator",
                 "intake_lanes_per_flush.validator",
                 "commit_cache_hit_share.validator"):
        assert _reader(name)(_ctx()) is None
    assert _reader("commit_cache_hit_share.validator")(_ctx(counters={
        "sigcache_hits_commit": 0, "sigcache_misses_commit": 0})) is None


def test_the_new_entries_name_their_layer_and_the_cell(doc):
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name, layer, source in (
            ("intake_ms_per_height.validator", "consensus intake",
             "program_span"),
            ("intake_device_lane_share.validator", "consensus intake",
             "program_counter"),
            ("intake_lanes_per_flush.validator", "consensus intake",
             "program_counter"),
            ("commit_cache_hit_share.validator", "crypto seam",
             "program_counter"),
            ("validate_commit_ms.validator", "crypto seam", "program_span"),
            ("finalize_ms_per_height.validator", "engine", "program_span")):
        m = by_name[name]
        assert (m["layer"], m["source"], m["moves"]) == (
            layer, source, "commit_verify_p50_ms")
        assert CELL in m["workloads"]
    for name in ("prewarm_s", "pallas_dispatch_share.commit",
                 "rlc_kernel_us_per_sig.commit",
                 "rlc_kernel_roofline.commit", "device_idle_share.commit",
                 "commit_device_ms.commit", "prepare_ms_per_chunk.commit"):
        assert CELL in by_name[name]["workloads"]
    for m in doc["end_to_end"]:
        if m["name"].startswith("commit_verify_"):
            assert CELL in m["workloads"]
