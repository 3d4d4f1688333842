"""The batched vote intake on a chain with vote extensions on: an
extended precommit is two lanes of the run's flush (its vote's
sign-bytes and its extension's), both looked up in the verified-signature
cache by `VoteSet._check_signature` and by `ConsensusState._add_vote`'s
extension check, none verified natively twice; the flush cuts its lanes
in order into chunks, each at the SHA-512 axis of its longest message
(`ops/ed25519.py` `_plan_chunks`), and sends a lane whose shape is not
warm to the native check (`verify_batch_warm`); the extending kvstore
app; the node's warm of the extension shapes.

Verdicts are compared with the native per-vote path (the same sequence
handled message by message) and with the plain reference
(`benchmark/reference/canonical_vote_extension.py` over `ed25519_ref`,
the `cryptography` wheel), on seeded keys at a tiny size."""

import itertools

import numpy as np
import pytest

from benchmark.reference import canonical_vote_extension as cve
from benchmark.reference import ed25519_ref, vote_tally
from cluster import Cluster, make_genesis
from cometbft_tpu import trace as program_trace
from cometbft_tpu.abci.kvstore import (ExtendingKVStoreApplication,
                                       vote_extension_bytes)
from cometbft_tpu.consensus.state import (
    STEP_NEW_HEIGHT, BlockPartMessage, ConsensusConfig, ConsensusState,
    ProposalMessage, VoteMessage, intake_stats)
from cometbft_tpu.consensus.ticker import ManualTicker, TimeoutInfo
from cometbft_tpu.consensus.wal import encode_message
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import keys as crypto_keys
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.libs import timesource
from cometbft_tpu.node.node import Node
from cometbft_tpu.ops import ed25519 as e5
from cometbft_tpu.pipeline.cache import reset_shared_cache, shared_cache
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.state import State, StateStore
from cometbft_tpu.store.blockstore import BlockStore
from cometbft_tpu.types import validation
from cometbft_tpu.types.block import BlockID, Commit, PartSetHeader
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE, Proposal, Vote
from cometbft_tpu.types.vote_set import (ErrVoteInvalidSignature, VoteError,
                                         VoteSet, preverify_lanes)

N = 12              # quorum 81 of 120: nine votes
THRESHOLD = 4
CHAIN = "ext-intake-chain"
SIZE = 2048         # the cell's extension: ~2,090 sign-bytes, 17 blocks
T0 = 1_700_000_000


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


class RefVerifier:
    """A `crypto.batch` verifier that answers by the plain reference and
    keeps the lanes of every flush."""
    flushes: list = []

    def __init__(self):
        self.lanes = []

    def __len__(self):
        return len(self.lanes)

    def add(self, pk, msg, sig):
        self.lanes.append((pk.bytes_(), msg, sig))

    def verify(self):
        oks = [ed25519_ref.verify(p, m, s) for p, m, s in self.lanes]
        RefVerifier.flushes.append(list(self.lanes))
        return all(oks), oks


@pytest.fixture
def flush(monkeypatch):
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", THRESHOLD)
    monkeypatch.setattr(crypto_batch, "create_batch_verifier",
                        lambda pk: (RefVerifier(), True))
    monkeypatch.setattr(RefVerifier, "flushes", [])
    reset_shared_cache()
    yield RefVerifier
    reset_shared_cache()


@pytest.fixture
def native_checks(monkeypatch):
    """Every native ed25519 check of the program, as (key, message)."""
    seen = []
    real = crypto_keys.Ed25519PubKey.verify_signature

    def counting(self, msg, sig):
        seen.append((self.bytes_(), msg, sig))
        return real(self, msg, sig)
    monkeypatch.setattr(crypto_keys.Ed25519PubKey, "verify_signature",
                        counting)
    return seen


@pytest.fixture(scope="module")
def chain():
    """Keys, a genesis with vote extensions on from height 1, the node
    under test (not the proposer of height 1) and height 1's block."""
    pvs, gen = make_genesis(N, chain_id=CHAIN, seed=40)
    gen.consensus_params.vote_extensions_enable_height = 1
    gen.genesis_time = Timestamp(T0, 0)
    state = State.from_genesis(gen)
    prop = state.validators.get_proposer()
    block = state.make_block(1, [b"k1=v1"], Commit(), prop.address,
                             timestamp=Timestamp(T0, 0))
    parts = block.make_part_set()
    bid = BlockID(block.hash(), parts.header)
    me = next(i for i, v in enumerate(gen.validators)
              if v.address != prop.address)
    prop_idx = next(i for i, v in enumerate(gen.validators)
                    if v.address == prop.address)
    return {"pvs": pvs, "gen": gen, "block": block, "parts": parts,
            "bid": bid, "me": me, "prop": prop_idx}


def _ext(chain, i, height=1):
    return vote_extension_bytes(height, chain["gen"].validators[i].address,
                                SIZE)


def _vote(chain, i, type_=PRECOMMIT_TYPE, height=1, nil=False,
          forge_vote=False, forge_ext=False, ext=None, sign_ext=True,
          ext_on_nil=False):
    val = chain["gen"].validators[i]
    key = chain["pvs"][i].priv_key
    v = Vote(type_=type_, height=height, round=0,
             block_id=BlockID() if nil else chain["bid"],
             timestamp=Timestamp(T0 + height, i),
             validator_address=val.address, validator_index=i)
    v.signature = key.sign(v.sign_bytes(CHAIN))
    if type_ == PRECOMMIT_TYPE and (not nil or ext_on_nil):
        v.extension = _ext(chain, i, height) if ext is None else ext
        if sign_ext:
            v.extension_signature = key.sign(v.extension_sign_bytes(CHAIN))
    if forge_vote:
        v.signature = ed25519_ref.tamper(v.signature)
    if forge_ext:
        v.extension_signature = ed25519_ref.tamper(v.extension_signature)
    return v


def _cases(chain):
    """The precommits of the tests, by name: one valid, and one of each
    refusal, each from its own validator."""
    me = chain["me"]
    peers = [i for i in range(N) if i != me]
    wrong = bytes(x ^ 0x5a for x in _ext(chain, peers[4]))
    return peers, {
        "forged_vote": _vote(chain, peers[0], forge_vote=True),
        "forged_ext": _vote(chain, peers[1], forge_ext=True),
        "altered_bytes": _vote(chain, peers[2],
                               ext=bytes([_ext(chain, peers[2])[0] ^ 1])
                               + _ext(chain, peers[2])[1:],
                               sign_ext=False),
        "nil_with_ext": _vote(chain, peers[3], nil=True, ext_on_nil=True),
        "app_rejects": _vote(chain, peers[4], ext=wrong),
        "missing_ext_sig": _vote(chain, peers[5], sign_ext=False),
    }


def _signed_over_original(chain, case, vote):
    """`altered_bytes` carries the signature of the bytes it replaced."""
    if case == "altered_bytes":
        i = vote.validator_index
        orig = Vote(**dict(vote.__dict__, extension=_ext(chain, i)))
        vote.extension_signature = chain["pvs"][i].priv_key.sign(
            orig.extension_sign_bytes(CHAIN))
    return vote


def _head(chain):
    """The proposal and parts, then every peer's prevote in one run."""
    peers = [i for i in range(N) if i != chain["me"]]
    bid = chain["bid"]
    proposal = Proposal(height=1, round=0, pol_round=-1, block_id=bid,
                        timestamp=chain["block"].header.time)
    proposal.signature = chain["pvs"][chain["prop"]].priv_key.sign(
        proposal.sign_bytes(CHAIN))
    stale = TimeoutInfo(0, 1, 0, STEP_NEW_HEIGHT)
    pid = (f"peer{k % 3}" for k in itertools.count())

    def votes(*vs):
        return [(VoteMessage(v), next(pid)) for v in vs]
    seq = [TimeoutInfo(0, 1, 0, STEP_NEW_HEIGHT),
           (ProposalMessage(proposal), "peer0")]
    seq += [(BlockPartMessage(1, 0, p), "peer1") for p in chain["parts"].parts]
    seq += votes(*[_vote(chain, i, PREVOTE_TYPE) for i in peers])
    seq.append(stale)
    return seq, votes, stale


def _sequence(chain):
    """The inbox's entries: `_head`, then one run of precommits holding
    every refusal, valid precommits of four refused validators and the
    +2/3 crossing (the rest of the run lands in `last_commit`), then
    late precommits into `last_commit` at STEP_NEW_HEIGHT, one with a
    forged extension signature."""
    peers, cases = _cases(chain)
    cases = {k: _signed_over_original(chain, k, v) for k, v in cases.items()}
    seq, votes, stale = _head(chain)
    seq += votes(*cases.values(),
                 *[_vote(chain, i) for i in peers[6:]],
                 _vote(chain, peers[6]),                  # a duplicate
                 _vote(chain, peers[0]), _vote(chain, peers[1]),
                 _vote(chain, peers[2]),                  # +2/3 here
                 _vote(chain, peers[5]))
    seq.append(stale)
    seq += votes(_vote(chain, peers[3], forge_ext=True),
                 _vote(chain, peers[3]), _vote(chain, peers[4]))
    return seq, cases


def _refusals_only(chain):
    """`_head`, then the refusals whose extension or its signature is at
    fault, in one run: the app must hear of the last alone."""
    _peers, cases = _cases(chain)
    seq, votes, _stale = _head(chain)
    names = ("forged_ext", "altered_bytes", "missing_ext_sig", "app_rejects")
    picked = {k: _signed_over_original(chain, k, cases[k]) for k in names}
    return seq + votes(*picked.values()), picked


class AskedApp(ExtendingKVStoreApplication):
    def __init__(self, *a):
        super().__init__(*a)
        self.asked = []

    def verify_vote_extension(self, height, addr, ext):
        ok = super().verify_vote_extension(height, addr, ext)
        self.asked.append((addr, ext, ok))
        return ok


def _node(chain):
    gen = chain["gen"]
    me = chain["pvs"][chain["me"]]
    app = AskedApp(SIZE, gen.validators[chain["me"]].address)
    app.init_chain(CHAIN, 1, gen.validators, b"")
    store = BlockStore(MemDB())
    state_store = StateStore(MemDB())
    state = State.from_genesis(gen)
    state_store.save(state)
    executor = BlockExecutor(app, state_store=state_store, block_store=store)
    pv = type(me)(me.priv_key, None)
    wal = RecordingWAL()
    cs = ConsensusState(ConsensusConfig(), state, executor, store,
                        priv_validator=pv, wal=wal, ticker_cls=ManualTicker)
    return cs, wal, store, app


def _run(chain, batched, sequence=_sequence):
    reset_shared_cache()
    ticks = itertools.count(1_800_000_000_000_000_000, 1_000_000)
    timesource.install(lambda: next(ticks))
    try:
        cs, wal, store, app = _node(chain)
        seq, cases = sequence(chain)
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
    if store.load_seen_commit(1) is None:
        return {"asked": app.asked, "cases": cases}
    return {"wal": wal.records, "height": cs.rs.height,
            "seen": store.load_seen_commit(1).encode(),
            "extended": store.load_extended_commit(1).encode(),
            "last_commit": [v.encode() if v else None
                            for v in cs.rs.last_commit.votes],
            "asked": app.asked, "cs": cs, "store": store, "cases": cases}


def _ref_accepts(chain, vote) -> bool:
    nil = vote.block_id.is_nil()
    block = None if nil else (chain["bid"].hash, chain["bid"].parts.total,
                              chain["bid"].parts.hash)
    sb = vote_tally.vote_sign_bytes(CHAIN, vote.type_, vote.height, 0, block,
                                    vote.timestamp.seconds,
                                    vote.timestamp.nanos)
    pub = chain["gen"].validators[vote.validator_index].pub_key.bytes_()
    return cve.accepts(CHAIN, pub, sb, vote.signature, vote.height, 0,
                       not nil, vote.extension, vote.extension_signature,
                       SIZE)


def test_extended_intake_equals_vote_by_vote_and_the_reference(
        chain, flush, native_checks):
    batched = _run(chain, batched=True)
    flushes = len(flush.flushes)
    single = _run(chain, batched=False)
    for key in ("wal", "height", "seen", "extended", "last_commit",
                "asked"):
        assert batched[key] == single[key], key
    assert batched["height"] == 2 and flushes >= 2
    # the flushes held both lanes of the extended precommits: sign-bytes
    # of both lengths in one flush
    lengths = {len(m) for lanes in flush.flushes for _p, m, _s in lanes}
    assert min(lengths) < 176 and max(lengths) > 2000
    # each case refused, as the reference refuses it: the seen commit,
    # made at the crossing, holds none of their validators; three of them
    # are in it by a valid precommit later in the run
    seen = batched["store"].load_seen_commit(1)
    for name, vote in batched["cases"].items():
        assert not _ref_accepts(chain, vote), name
        held = seen.signatures[vote.validator_index].for_block()
        assert held == (name in ("forged_vote", "forged_ext",
                                 "altered_bytes")), name
    last = batched["cs"].rs.last_commit
    assert all(last.get_by_index(v.validator_index) is not None
               for v in batched["cases"].values())
    # the stored extended commit: the reference's extension and an
    # accepted signature wherever the seen commit holds a precommit
    ext = batched["store"].load_extended_commit(1)
    for i, (cs_, es) in enumerate(zip(seen.signatures, ext.signatures)):
        if not cs_.for_block():
            assert not es.extension and not es.extension_signature
            continue
        pub = chain["gen"].validators[i].pub_key.bytes_()
        assert es.extension == cve.extension(1, cve.address(pub), SIZE)
        assert ed25519_ref.verify(pub, cve.extension_sign_bytes(
            CHAIN, 1, 0, es.extension), es.extension_signature)


@pytest.mark.parametrize("case", ["valid", "forged_vote", "forged_ext",
                                  "altered_bytes", "nil_with_ext",
                                  "missing_ext_sig"])
def test_the_vote_set_verdict_is_the_reference_and_the_native_paths(
        chain, flush, case):
    """One precommit added to an extended set three ways: natively
    (nothing cached), after a flush of its lanes, and as the reference
    judges it: the same verdict, the same error type as before the
    intake batched extensions."""
    peers, cases = _cases(chain)
    vote = _vote(chain, peers[7]) if case == "valid" else \
        _signed_over_original(chain, case, cases[case])

    vals = State.from_genesis(chain["gen"]).validators

    def add(preflush):
        reset_shared_cache()
        vs = VoteSet(CHAIN, 1, 0, PRECOMMIT_TYPE, vals,
                     extensions_enabled=True)
        if preflush:
            fillers = [_vote(chain, i) for i in peers[8:]]
            preverify_lanes([lane for v in fillers + [vote]
                             if (val := vs.lane_validator(v)) is not None
                             for lane in vs.lanes(v, val)])
        try:
            return vs.add_vote(vote)
        except VoteError as exc:
            return type(exc)
    native, batched = add(False), add(True)
    assert native == batched
    if case == "valid":
        assert native is True and _ref_accepts(chain, vote)
    else:
        assert not _ref_accepts(chain, vote)
        assert native is (VoteError if case == "nil_with_ext"
                          else ErrVoteInvalidSignature)


def test_both_signatures_cached_after_a_flush_and_never_checked_twice(
        chain, flush, native_checks):
    batched = _run(chain, batched=True)
    cache = shared_cache()
    # no lane a flush verified true was checked natively afterwards
    good = {(p, m, s) for lanes in flush.flushes for p, m, s in lanes
            if ed25519_ref.verify(p, m, s)}
    assert good and not good & set(native_checks)
    # every signature the program checked natively, it checked once
    counts = {}
    for lane in native_checks:
        counts[lane] = counts.get(lane, 0) + 1
    assert max(counts.values()) == 1
    # a valid precommit's two lanes are both in the cache
    seen = batched["store"].load_seen_commit(1)
    for i, cs_ in enumerate(seen.signatures):
        if not cs_.for_block() or i == chain["me"]:
            continue
        v = _vote(chain, i)
        pkb = chain["gen"].validators[i].pub_key.bytes_()
        assert cache.seen(pkb, v.sign_bytes(CHAIN), cs_.signature, "vote")
        assert cache.seen(pkb, v.extension_sign_bytes(CHAIN),
                          v.extension_signature, "ext")


def test_the_app_never_sees_an_extension_whose_signature_failed(
        chain, flush):
    for batched in (True, False):
        out = _run(chain, batched, _refusals_only)
        v = out["cases"]["app_rejects"]
        assert out["asked"] == [(v.validator_address, v.extension, False)]


def test_the_counters_split_the_extension_lanes(chain, flush):
    before = intake_stats()
    _run(chain, batched=True)
    after = intake_stats()
    d = {k: after[k] - before[k] for k in after}
    ext = d["ext_cache_hits"] + d["ext_device_lanes"] + d["ext_native_lanes"]
    assert d["ext_device_lanes"] > 0 and 0 < ext
    assert d["device_lanes"] >= d["ext_device_lanes"]
    flushed = sum(len(lanes) for lanes in flush.flushes)
    assert d["device_lanes"] == flushed
    assert d["ext_device_lanes"] == sum(
        len(m) > 2000 for lanes in flush.flushes for _p, m, _s in lanes)


def test_the_extension_check_is_a_span_with_its_cache_hit(chain, flush):
    program_trace.reset_shared()
    program_trace.enable(seed=0, ring=1 << 14)
    try:
        _run(chain, batched=True)
        spans = program_trace.shared_recorder().snapshot()
    finally:
        program_trace.disable()
        program_trace.reset_shared()
    checks = [s for s in spans if s["name"] == "consensus.ext_check"]
    assert checks and all(s["attrs"]["height"] == 1 for s in checks)
    assert any(s["attrs"].get("cache_hit") == 1 for s in checks)
    assert {s["attrs"].get("app_ok") for s in checks} >= {0, 1}
    assert any(s["name"] == "vote.verify" and s["attrs"].get("path") == "ext"
               for s in spans)


# --- the dispatch: lanes of two lengths in one call -----------------------------

def _lanes_from_arrays(pub_a, sig_a, hb, hn):
    """Each lane's (key, message, signature) read back from the arrays a
    chunk was dispatched with: the message from its SHA-512 blocks,
    whose last 16 bytes of the last live block hold its bit length."""
    out = []
    for i in range(pub_a.shape[0]):
        flat = hb[i].reshape(-1)
        last = hb[i, hn[i] - 1]
        total = int.from_bytes(bytes(last[-16:]), "big") // 8
        out.append((bytes(pub_a[i]), bytes(flat[64:total]), bytes(sig_a[i])))
    return out


def _mixed_lanes(votes_alone=0):
    """A flush's lanes: the vote lanes of the first `votes_alone`
    validators, then the vote and extension lanes of all N."""
    pvs, gen = make_genesis(N, chain_id=CHAIN, seed=41)
    chain = {"gen": gen, "pvs": pvs,
             "bid": BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))}
    votes = [_vote(chain, i) for i in range(N)]
    pubs, msgs, sigs = [], [], []
    for i in range(votes_alone):
        pubs.append(gen.validators[i].pub_key)
        msgs.append(votes[i].sign_bytes(CHAIN))
        sigs.append(votes[i].signature)
    for i, v in enumerate(votes):
        pk = gen.validators[i].pub_key
        pubs += [pk, pk]
        msgs += [v.sign_bytes(CHAIN), v.extension_sign_bytes(CHAIN)]
        sigs += [v.signature, v.extension_signature]
    return pubs, msgs, sigs


def test_a_mixed_flush_equals_the_native_loop_chunk_by_chunk():
    pubs, msgs, sigs = _mixed_lanes(votes_alone=8)
    sigs[2] = ed25519_ref.tamper(sigs[2])       # a chunk of votes alone
    sigs[8 + 3] = ed25519_ref.tamper(sigs[8 + 3])   # an extension lane
    sigs[8 + 8] = ed25519_ref.tamper(sigs[8 + 8])   # a vote lane beside one
    shapes = []

    def dispatch(pub_a, sig_a, hb, hn, z):
        lanes = _lanes_from_arrays(pub_a, sig_a, hb, hn)
        pad = e5._dummy()[2]
        shapes.append((hb.shape[1], {len(m) for _p, m, _s in lanes
                                     if m != pad}))
        oks = np.array([ed25519_ref.verify(*lane) for lane in lanes])
        return bool(oks.all()), np.ones_like(oks)

    def fallback(pub_a, sig_a, hb, hn):
        return np.array([ed25519_ref.verify(*lane) for lane in
                         _lanes_from_arrays(pub_a, sig_a, hb, hn)])

    before = e5.batch_stats()
    got = e5._verify_batch_loop([p.bytes_() for p in pubs], msgs, sigs, 8,
                                dispatch, fallback)
    after = e5.batch_stats()
    want = crypto_keys.verify_native([p.bytes_() for p in pubs], msgs, sigs)
    assert list(got) == list(want)
    assert not got[2] and not got[8 + 3] and not got[8 + 8]
    # in order, 8 lanes a chunk: the votes alone at their own 2 blocks,
    # every chunk after them at the 18 of its longest, an extension
    chunks = [msgs[lo:lo + 8] for lo in range(0, len(msgs), 8)]
    assert [blocks for blocks, _lens in shapes] == [2] + [18] * (
        len(chunks) - 1)
    assert [lens for _blocks, lens in shapes] == [
        {len(m) for m in chunk} for chunk in chunks]
    need = sum(e5.hash_blocks_needed(len(m)) for m in msgs)
    assert after["hash_blocks_real"] - before["hash_blocks_real"] == need
    assert after["hash_blocks_dispatched"] - \
        before["hash_blocks_dispatched"] == 8 * 2 + 8 * 18 * (len(chunks) - 1)


def test_a_lane_of_a_cold_shape_is_verified_natively(monkeypatch):
    """An extension whose SHA-512 shape this process never warmed goes
    to the native check, and no kernel is asked for its shape; once the
    shape is warm (`prewarm_verify_kernels`, its kernels stubbed), the
    same lanes go to the kernel."""
    from cometbft_tpu.libs.jax_cache import CompileLedger, ledger
    monkeypatch.setattr(ledger(), "_proc_warm", set())
    monkeypatch.setattr(CompileLedger, "record", lambda *a: None)
    monkeypatch.setattr(crypto_keys, "kernel_width", lambda: 8)
    asked = []

    def kernel(pubs, msgs, sigs, batch_size):
        asked.append({e5.hash_block_bucket(len(m)) for m in msgs})
        return crypto_keys.verify_native(pubs, msgs, sigs)
    monkeypatch.setattr(e5, "verify_batch", kernel)
    pubs, msgs, sigs = _mixed_lanes()
    sigs[3] = ed25519_ref.tamper(sigs[3])
    sigs[4] = ed25519_ref.tamper(sigs[4])
    want = [ed25519_ref.verify(p.bytes_(), m, s)
            for p, m, s in zip(pubs, msgs, sigs)]

    def flush():
        bv = crypto_keys.Ed25519BatchVerifier()
        for lane in zip(pubs, msgs, sigs):
            bv.add(*lane)
        before = e5.batch_stats()["cold_shape_lanes"]
        _all_ok, oks = bv.verify()
        assert oks == want
        return e5.batch_stats()["cold_shape_lanes"] - before

    assert not e5.shape_warm(8, 18)
    assert flush() == N and asked == [{2}]
    monkeypatch.setattr(e5, "_rlc_dispatch", lambda *a: None)
    monkeypatch.setattr(e5, "verify_kernel", lambda *a, **k: None)
    e5.prewarm_verify_kernels(batch_size=8, msg_cap=len(msgs[1]))
    assert e5.shape_warm(8, 18) and not e5.shape_warm(16, 18)
    assert not e5.shape_warm(8, 3)
    asked.clear()
    assert flush() == 0 and asked == [{2, 18}]


@pytest.mark.parametrize("n, blocks", [
    (0, 2), (107, 2), (128, 2), (175, 2), (176, 3), (303, 3), (304, 4),
    (1967, 16), (2048, 18), (2090, 18), (2123, 18), (4096, 36)])
def test_the_hash_block_bucket(n, blocks):
    assert e5.hash_block_bucket(n) == blocks
    assert e5.hash_blocks_needed(n) <= blocks
    assert e5.hash_blocks_needed(e5.msg_cap_of(blocks)) == blocks


def test_no_lane_computes_more_than_an_eighth_over_its_need():
    for n in range(176, 1 << 16, 7):
        need = e5.hash_blocks_needed(n)
        assert need <= e5.hash_block_bucket(n) <= need * 9 / 8 + 1e-9


def test_every_shape_has_its_canary(monkeypatch):
    from cometbft_tpu.ops import pallas_verify as pv
    monkeypatch.setattr(e5, "use_pallas_rlc", lambda: True)
    monkeypatch.setattr(pv, "TILE", 8)
    monkeypatch.setattr(e5, "_pallas_broken", False)
    monkeypatch.setattr(e5, "_shape_dispatches", {})
    canaries = []
    monkeypatch.setattr(e5, "_run_canary",
                        lambda batch, blocks: canaries.append(
                            (batch, blocks, len(canaries))))
    monkeypatch.setattr(e5, "verify_rlc_kernel_pallas",
                        lambda *a: (True, None))
    order = []
    for k in range(2 * e5._CANARY_INTERVAL + 2):
        blocks = 2 if k % 2 else 18
        order.append(blocks)
        e5._rlc_dispatch(np.zeros((8, 32)), None,
                         np.zeros((8, blocks, 128)), None, None)
    # each shape's 1st and 17th dispatch, whatever the interleaving
    assert sorted((b, s) for b, s, _k in canaries) == [
        (8, 2), (8, 2), (8, 18), (8, 18)]


# --- the app, the node's warm ---------------------------------------------------

def test_the_extending_app():
    addr = b"\x07" * 20
    app = ExtendingKVStoreApplication(64, addr)
    ext = app.extend_vote(5, 0)
    assert len(ext) == 64 and ext == cve.extension(5, addr, 64)
    assert app.verify_vote_extension(5, addr, ext)
    assert not app.verify_vote_extension(6, addr, ext)
    assert not app.verify_vote_extension(5, b"\x08" * 20, ext)
    assert app.extension_checks == {"accepted": 1, "refused": 2}
    app.last_height = 5
    assert app.prepare_proposal([b"a=b"], 100,
                                [(0, addr, ext)]) == [b"a=b"]
    assert app.extensions_prepared == 1
    with pytest.raises(ValueError):
        app.prepare_proposal([], 100, [(0, addr, bytes(64))])


def test_the_node_builds_the_app_and_warms_the_extension_shapes(monkeypatch):
    from cometbft_tpu.config import Config
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    cfg = Config()
    assert type(Node.builtin_app(cfg)) is KVStoreApplication
    cfg.base.vote_extension_size = SIZE
    pv = make_genesis(1, seed=3)[0][0]
    app = Node.builtin_app(cfg, pv)
    assert isinstance(app, ExtendingKVStoreApplication)
    assert app.validator_address == pv.get_pub_key().address()
    assert "vote_extension_size = 2048" in cfg.to_toml()
    warmed = []
    monkeypatch.setattr(e5, "prewarm_verify_kernels",
                        lambda batch_size, msg_cap: warmed.append(
                            (batch_size, e5.hash_block_bucket(msg_cap))))
    Node._warm_shapes(8, 0)
    Node._warm_shapes(8, SIZE)
    assert warmed == [(8, 2), (8, 2), (8, 18)]


def test_a_cluster_with_extensions_feeds_them_to_the_proposer():
    c = Cluster(4, params={"vote_extensions_enable_height": 1})
    for node, pv in zip(c.nodes, c.pvs):
        node.app.__class__ = ExtendingKVStoreApplication
        node.app.vote_extension_size = 96
        node.app.validator_address = pv.get_pub_key().address()
        node.app.extensions_prepared = 0
        node.app.extension_checks = {"accepted": 0, "refused": 0}
    try:
        c.start()
        c.wait_for_height(4, timeout=90)
    finally:
        c.stop()
    ec = c.nodes[0].block_store.load_extended_commit(2)
    assert ec is not None and ec.extensions()
    for _i, addr, ext in ec.extensions():
        assert ext == cve.extension(2, addr, 96)
    assert sum(n.app.extensions_prepared for n in c.nodes) > 0
    assert all(n.app.extension_checks["refused"] == 0 for n in c.nodes)
