"""The batched vote intake of ISSUE 34 (consensus/state.py `_intake`,
types/vote_set.py `preverify_lanes`) and the rule that the batch
threshold counts the lanes that MISS the verified-signature cache
(types/validation.py `_verify_commit_lanes`).

Two `ConsensusState`s take the same seeded message sequence: one through
`receive_routine` with the inbox filled beforehand, so that it drains
runs of votes and flushes them through a stubbed `crypto.batch` verifier
(as tests/test_churn_counters.py stubs kernels), one vote by vote through
`handle_msg`. WAL records in order, the round state after every message,
the seen commit and the evidence must be equal."""

import itertools

import pytest

from cluster import make_genesis
from cometbft_tpu import trace as program_trace
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.consensus import state as cs_mod
from cometbft_tpu.consensus.state import (
    STEP_NEW_HEIGHT, BlockPartMessage, ConsensusConfig, ConsensusState,
    ProposalMessage, VoteMessage, intake_stats)
from cometbft_tpu.consensus.ticker import ManualTicker, TimeoutInfo
from cometbft_tpu.consensus.wal import encode_message
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto.keys import Ed25519PubKey
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.libs import timesource
from cometbft_tpu.pipeline.cache import reset_shared_cache, shared_cache
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.state import State, StateStore
from cometbft_tpu.store.blockstore import BlockStore
from cometbft_tpu.types import validation
from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, BlockID, Commit,
                                      CommitSig, PartSetHeader)
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import (PRECOMMIT_TYPE, PREVOTE_TYPE, Proposal,
                                     Vote)
from cometbft_tpu.types.vote_set import ErrVoteInvalidSignature, VoteSet

N = 12              # quorum 81 of 120: nine votes
THRESHOLD = 4       # what the tests set BATCH_VERIFY_THRESHOLD to
CHAIN = "intake-chain"
OTHER = BlockID(b"\x99" * 32, PartSetHeader(1, b"\x9a" * 32))


class RecordingWAL:
    def __init__(self):
        self.records = []

    def write(self, msg):
        self.records.append(("write", encode_message(msg)))

    def write_sync(self, msg):
        self.records.append(("sync", encode_message(msg)))

    def replay_messages(self, after_height):
        return []

    def close(self):
        pass


class StubVerifier:
    """A `crypto.batch` verifier that answers natively and keeps the
    lane count of every flush; `answer` alters the verdict list."""
    flushes: list = []
    answer = staticmethod(lambda oks: oks)

    def __init__(self):
        self.lanes = []

    def __len__(self):
        return len(self.lanes)

    def add(self, pk, msg, sig):
        self.lanes.append((pk, msg, sig))

    def verify(self):
        oks = [pk.verify_signature(m, s) for pk, m, s in self.lanes]
        StubVerifier.flushes.append(len(oks))
        oks = StubVerifier.answer(oks)
        return all(oks), oks


@pytest.fixture
def stub(monkeypatch):
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", THRESHOLD)
    monkeypatch.setattr(crypto_batch, "create_batch_verifier",
                        lambda pk: (StubVerifier(), True))
    monkeypatch.setattr(StubVerifier, "flushes", [])
    monkeypatch.setattr(StubVerifier, "answer", staticmethod(lambda o: o))
    reset_shared_cache()
    yield StubVerifier
    reset_shared_cache()


@pytest.fixture(scope="module")
def chain():
    """Keys, genesis, the node under test (never the proposer of height
    1 or 2, round 0) and the two blocks."""
    pvs, gen = make_genesis(N, chain_id=CHAIN, seed=34)
    state = State.from_genesis(gen)
    app = KVStoreApplication()
    app.init_chain(CHAIN, 1, gen.validators, b"")
    executor = BlockExecutor(app)
    blocks = []
    last_commit = Commit()
    proposers = []
    for h in (1, 2):
        prop = state.validators.get_proposer()
        proposers.append(prop.address)
        block = state.make_block(h, [b"k%d=v%d" % (h, h)], last_commit,
                                 prop.address,
                                 timestamp=Timestamp(1_700_000_000 + h, 0))
        parts = block.make_part_set()
        bid = BlockID(block.hash(), parts.header)
        blocks.append((block, parts, bid))
        sigs = []
        for i, val in enumerate(state.validators.validators):
            v = Vote(type_=PRECOMMIT_TYPE, height=h, round=0, block_id=bid,
                     timestamp=Timestamp(1_700_000_000 + h, i),
                     validator_address=val.address, validator_index=i)
            v.signature = pvs[i].priv_key.sign(v.sign_bytes(CHAIN))
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address,
                                  v.timestamp, v.signature))
        last_commit = Commit(height=h, round=0, block_id=bid,
                             signatures=sigs)
        state, _ = executor.apply_block(state, bid, block, verified=True)
    me = next(i for i, v in enumerate(gen.validators)
              if v.address not in proposers)
    return {"pvs": pvs, "gen": gen, "blocks": blocks, "me": me,
            "proposers": proposers}


def _vote(chain, i, type_, height, bid, round_=0, good=True):
    val = chain["gen"].validators[i]
    v = Vote(type_=type_, height=height, round=round_, block_id=bid,
             timestamp=Timestamp(1_700_000_000 + height, i),
             validator_address=val.address, validator_index=i)
    v.signature = chain["pvs"][i].priv_key.sign(v.sign_bytes(CHAIN))
    if not good:
        v.signature = v.signature[:40] + bytes([v.signature[40] ^ 1]) \
            + v.signature[41:]
    return v


def _sequence(chain):
    """The inbox's entries, in order. Runs of peer votes are broken by a
    stale timeout where the test wants two runs."""
    me = chain["me"]
    peers = [i for i in range(N) if i != me]
    (block, parts, bid) = chain["blocks"][0]
    prop_idx = next(i for i, v in enumerate(chain["gen"].validators)
                    if v.address == chain["proposers"][0])
    proposal = Proposal(height=1, round=0, pol_round=-1, block_id=bid,
                        timestamp=block.header.time)
    proposal.signature = chain["pvs"][prop_idx].priv_key.sign(
        proposal.sign_bytes(CHAIN))
    stale = TimeoutInfo(0, 1, 0, STEP_NEW_HEIGHT)    # stale once in round 0
    pid = (f"peer{k % 3}" for k in itertools.count())

    def votes(*vs):
        return [(VoteMessage(v), next(pid)) for v in vs]

    outsider = _vote(chain, peers[0], PREVOTE_TYPE, 1, bid)
    outsider.validator_index = N + 5
    wrong_key = _vote(chain, peers[1], PREVOTE_TYPE, 1, bid)
    wrong_key.signature = chain["pvs"][peers[2]].priv_key.sign(
        wrong_key.sign_bytes(CHAIN))
    seq = [TimeoutInfo(0, 1, 0, STEP_NEW_HEIGHT),
           (ProposalMessage(proposal), "peer0")]
    seq += [(BlockPartMessage(1, 0, p), "peer1") for p in parts.parts]
    # run 1, prevotes: a bad signature, an exact duplicate, a conflicting
    # vote (evidence), a vote outside the set, one signed by another
    # key, votes for other heights, the +2/3 crossing in its middle
    seq += votes(
        _vote(chain, peers[0], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[1], PREVOTE_TYPE, 1, bid, good=False),
        _vote(chain, peers[2], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[2], PREVOTE_TYPE, 1, bid),          # duplicate
        _vote(chain, peers[3], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[3], PREVOTE_TYPE, 1, OTHER),        # conflict
        outsider, wrong_key,
        _vote(chain, peers[4], PREVOTE_TYPE, 7, bid),          # parked
        _vote(chain, peers[4], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[5], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[6], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[7], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[8], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[1], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[9], PREVOTE_TYPE, 1, bid),
        _vote(chain, peers[10], PREVOTE_TYPE, 1, bid))
    seq.append(stale)
    # run 2, too short to flush, with a bad signature
    seq += votes(_vote(chain, peers[0], PRECOMMIT_TYPE, 1, bid),
                 _vote(chain, peers[1], PRECOMMIT_TYPE, 1, bid, good=False))
    seq.append(stale)
    # run 3, precommits: +2/3 in mid-run (height 1 commits there), the
    # rest of the run lands in `last_commit`; a catch-up round's vote
    # with a bad signature, which the intake never looks at
    seq += votes(
        _vote(chain, peers[1], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[2], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[3], PRECOMMIT_TYPE, 1, bid, round_=5,
              good=False),
        _vote(chain, peers[3], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[4], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[5], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[6], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[7], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[8], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[9], PRECOMMIT_TYPE, 1, bid, good=False))
    seq.append(stale)
    # run 4, late precommits into `last_commit` during STEP_NEW_HEIGHT,
    # the last of which starts round 0 of height 2 (skip_timeout_commit)
    seq += votes(
        _vote(chain, peers[8], PRECOMMIT_TYPE, 1, bid),        # duplicate
        _vote(chain, peers[9], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[10], PRECOMMIT_TYPE, 1, bid),
        _vote(chain, peers[0], PRECOMMIT_TYPE, 2, chain["blocks"][1][2]))
    return seq


def _node(chain):
    gen = chain["gen"]
    app = KVStoreApplication()
    app.init_chain(CHAIN, 1, gen.validators, b"")
    store = BlockStore(MemDB())
    state_store = StateStore(MemDB())
    state = State.from_genesis(gen)
    state_store.save(state)
    executor = BlockExecutor(app, state_store=state_store,
                             block_store=store)
    pv = type(chain["pvs"][0])(chain["pvs"][chain["me"]].priv_key, None)
    wal = RecordingWAL()
    cs = ConsensusState(ConsensusConfig(), state, executor, store,
                        priv_validator=pv, wal=wal, ticker_cls=ManualTicker)
    steps = []
    real = cs._handle_one

    def handle_one(msg, peer_id=""):
        real(msg, peer_id)
        steps.append((cs.rs.height, cs.rs.round, cs.rs.step))
    cs._handle_one = handle_one
    return cs, wal, store, steps


def _run(chain, batched):
    """One node over the sequence, on a clock that counts calls, so that
    both nodes stamp their own votes alike."""
    reset_shared_cache()
    ticks = itertools.count(1_800_000_000_000_000_000, 1_000_000)
    timesource.install(lambda: next(ticks))
    try:
        cs, wal, store, steps = _node(chain)
        seq = _sequence(chain)
        if batched:
            for entry in seq:
                cs.inbox.put(entry)
            cs.inbox.put(None)
            cs.receive_routine()
        else:
            for entry in seq:
                cs._handle_guarded(entry)
    finally:
        timesource.reset()
    seen = store.load_seen_commit(1)
    evidence = [(e.vote_a.encode(), e.vote_b.encode())
                for e in cs.conflicting_votes]
    return {"wal": wal.records, "steps": steps,
            "seen": seen.encode() if seen is not None else None,
            "evidence": evidence, "height": cs.rs.height,
            "step": cs.rs.step, "parked": len(cs._pending),
            "last_commit": [v.encode() if v else None
                            for v in cs.rs.last_commit.votes]}


def test_batched_intake_equals_vote_by_vote(chain, stub):
    before = intake_stats()
    batched = _run(chain, batched=True)
    after = intake_stats()
    flushes = list(stub.flushes)
    single = _run(chain, batched=False)
    assert stub.flushes == flushes           # vote by vote flushed nothing
    assert intake_stats() == after           # and touched no counter
    for key in single:
        assert batched[key] == single[key], key
    # it is the scenario the sequence describes
    assert batched["height"] == 2 and batched["seen"] is not None
    assert len(batched["evidence"]) == 1 and batched["parked"] == 1
    assert any(kind == "sync" for kind, _ in batched["wal"])
    # runs 1 and 3 flushed; run 2 (two lanes) and run 4 (three) did not
    assert len(flushes) == 2 and all(n >= THRESHOLD for n in flushes)
    delta = {k: after[k] - before[k] for k in after}
    n_votes = sum(1 for e in _sequence(chain) if cs_mod._is_peer_vote(e))
    assert delta["votes_handled"] == n_votes
    assert delta["runs"] == 4 and delta["flushes"] == 2
    assert delta["device_lanes"] == sum(flushes)
    # run 2's two lanes and run 4's three (its duplicate has none; its
    # precommit for height 2 is of the height the node is at by then)
    assert delta["native_lanes"] == 2 + 3 and delta["cache_hits"] == 0


@pytest.mark.parametrize("answer, label", [
    (lambda oks: oks[:len(oks) // 2], "short verdict list"),
    (lambda oks: [], "no verdicts"),
    (lambda oks: [True] * len(oks), "all true"),
])
def test_fail_closed(chain, stub, answer, label):
    """Too few verdicts admit nothing beyond them: the lanes left out
    are verified natively and the outcome is the vote-by-vote one. An
    all-true verifier admits what it was handed, and only that: the bad
    signatures of run 2 (too short to flush) and of the catch-up round
    (never looked at) are refused as ever."""
    single = _run(chain, batched=False)
    stub.answer = staticmethod(answer)
    batched = _run(chain, batched=True)
    assert stub.flushes
    if label != "all true":
        for key in single:
            assert batched[key] == single[key], (label, key)
        return
    assert batched["height"] == 2
    me = chain["me"]
    peers = [i for i in range(N) if i != me]
    seen = Commit.decode(batched["seen"])
    # peers[1]'s bad precommit of run 2 is not the one in the commit
    assert seen.signatures[peers[1]].signature == \
        _vote(chain, peers[1], PRECOMMIT_TYPE, 1,
              chain["blocks"][0][2]).signature
    bad_round5 = _vote(chain, peers[3], PRECOMMIT_TYPE, 1,
                       chain["blocks"][0][2], round_=5, good=False)
    assert not shared_cache().seen(
        chain["gen"].validators[peers[3]].pub_key.bytes_(),
        bad_round5.sign_bytes(CHAIN), bad_round5.signature)


def test_tracing_off_opens_no_span_and_on_names_the_runs(chain, stub):
    program_trace.disable()
    _run(chain, batched=True)
    assert program_trace.shared_recorder().snapshot() == []
    program_trace.enable(seed=0, ring=1 << 12)
    try:
        _run(chain, batched=True)
        spans = program_trace.shared_recorder().snapshot()
    finally:
        program_trace.disable()
    runs = [s["attrs"] for s in spans if s["name"] == "consensus.intake"]
    assert [r["votes"] for r in runs] == [17, 2, 10, 4]
    assert [r["flushed"] for r in runs] == [1, 0, 1, 0]
    # the height is the run's first vote's: run 4 opens with a late
    # precommit of height 1 though the node stands at height 2
    assert [r["height"] for r in runs] == [1, 1, 1, 1]
    for r in runs:
        assert set(r) == {"height", "votes", "cache_hits", "device_lanes",
                          "native_lanes", "flushed", "cpu_ns"}
    (fin,) = [s for s in spans if s["name"] == "consensus.finalize"]
    assert fin["attrs"] == {"height": 1}
    # the node validated no block with a last commit: height 2's
    # proposal never came
    assert [s for s in spans if s["name"] == "commit.verify"] == []


# --- the threshold counts the lanes that miss ----------------------------------

@pytest.fixture(scope="module")
def hub():
    """150 validators and one commit all of them signed."""
    pvs, gen = make_genesis(150, chain_id=CHAIN, seed=150)
    vals = ValidatorSet([Validator(v.pub_key, v.voting_power)
                         for v in gen.validators])
    bid = BlockID(b"\x77" * 32, PartSetHeader(1, b"\x88" * 32))
    votes = []
    for i, val in enumerate(vals.validators):
        pv = next(p for p in pvs if p.get_pub_key().address()
                  == val.address)
        v = Vote(type_=PRECOMMIT_TYPE, height=9, round=0, block_id=bid,
                 timestamp=Timestamp(1_700_000_009, i),
                 validator_address=val.address, validator_index=i)
        v.signature = pv.priv_key.sign(v.sign_bytes(CHAIN))
        votes.append(v)
    return vals, bid, votes


@pytest.fixture
def routes(monkeypatch):
    """The real threshold; flushes and native checks counted."""
    native = []
    real = Ed25519PubKey.verify_signature

    def counted(self, msg, sig):
        native.append(1)
        return real(self, msg, sig)
    monkeypatch.setattr(crypto_batch, "create_batch_verifier",
                        lambda pk: (StubVerifier(), True))
    monkeypatch.setattr(StubVerifier, "flushes", [])
    monkeypatch.setattr(StubVerifier, "answer", staticmethod(lambda o: o))
    reset_shared_cache()
    yield StubVerifier, native, lambda: monkeypatch.setattr(
        Ed25519PubKey, "verify_signature", counted)
    reset_shared_cache()


def _tamper(sig):
    return sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]


def _miss(votes, vals, missing):
    """All but the LAST `missing` lanes put into the cache."""
    cache = shared_cache()
    for v in votes[:len(votes) - missing]:
        cache.add(vals.validators[v.validator_index].pub_key.bytes_(),
                  v.sign_bytes(CHAIN), v.signature)


@pytest.mark.parametrize("missing", [0, 3, 63, 64, 150])
def test_verify_commit_routes_on_missing_lanes(hub, routes, missing):
    assert validation.BATCH_VERIFY_THRESHOLD == 64
    vals, bid, votes = hub
    stub, native, count_native = routes

    def commit(bad=None):
        return Commit(height=9, round=0, block_id=bid, signatures=[
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.validator_address, v.timestamp,
                      _tamper(v.signature) if i == bad else v.signature)
            for i, v in enumerate(votes)])
    _miss(votes, vals, missing)
    count_native()
    validation.verify_commit(CHAIN, vals, bid, 9, commit())
    # (the stub itself verifies natively, so either route counts there)
    assert stub.flushes == ([missing] if missing >= 64 else [])
    assert len(native) == missing
    # now every lane is cached: a second pass verifies nothing
    del native[:], stub.flushes[:]
    validation.verify_commit(CHAIN, vals, bid, 9, commit())
    assert native == [] and stub.flushes == []
    # an altered signature among the missing lanes: the same verdict and
    # the same blamed index on either route
    if missing:
        reset_shared_cache()
        _miss(votes, vals, missing)
        bad = 150 - missing + missing // 2
        with pytest.raises(validation.ErrWrongSignature) as err:
            validation.verify_commit(CHAIN, vals, bid, 9, commit(bad))
        assert err.value.idx == bad
        assert len(stub.flushes) == (1 if missing >= 64 else 0)


@pytest.mark.parametrize("missing", [0, 3, 63, 64, 150])
def test_add_votes_routes_on_missing_lanes(hub, routes, missing):
    vals, bid, votes = hub
    stub, native, count_native = routes
    _miss(votes, vals, missing)
    bad = 150 - missing + missing // 2 if missing else None
    sent = [v if i != bad else Vote(**dict(v.__dict__,
                                           signature=_tamper(v.signature)))
            for i, v in enumerate(votes)]
    count_native()
    vs = VoteSet(CHAIN, 9, 0, PRECOMMIT_TYPE, vals)
    out = vs.add_votes(sent)
    single = VoteSet(CHAIN, 9, 0, PRECOMMIT_TYPE, vals)
    for i, v in enumerate(sent):
        if i == bad:
            assert isinstance(out[i], ErrVoteInvalidSignature)
            with pytest.raises(ErrVoteInvalidSignature):
                single.add_vote(v)
        else:
            assert out[i] is True and single.add_vote(v) is True
    assert [v.encode() if v else None for v in vs.votes] == \
        [v.encode() if v else None for v in single.votes]
    assert vs.maj23 == single.maj23
    assert stub.flushes == ([missing] if missing >= 64 else [])


def test_a_vote_set_with_extensions_batches_nothing(hub, routes):
    vals, bid, votes = hub
    stub, _native, _count = routes
    vs = VoteSet(CHAIN, 9, 0, PRECOMMIT_TYPE, vals, extensions_enabled=True)
    assert all(vs.lane_validator(v) is None for v in votes)
    out = vs.add_votes(votes[:70])
    assert stub.flushes == []
    # no extension signature: every one is refused, as add_vote refuses
    assert all(isinstance(o, ErrVoteInvalidSignature) for o in out)
