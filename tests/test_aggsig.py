"""aggsig/ — the BLS12-381 aggregate-commit fast path.

Pins, roughly bottom-up: the signer-bitmap codec, aggregate ==
sum-of-signatures, proof-of-possession admission (including the
textbook rogue-key attack, which must verify MATHEMATICALLY and be
stopped exactly by the PoP gate), the AggregatedCommit wire form and
its structure validation, the assembly gate (uniformly-BLS valset +
registered PoPs and nothing else), sync-vs-aggregate verdict
equivalence through the public verify_commit forms, the batch
verifier's attribution (solo and inside MixedBatchVerifier), the
whole-aggregate SigCache keying, blocksync catch-up over aggregated
seals, the FinalExpChecker canary/quarantine discipline, and the
compile ledger. The JAX kernel itself is oracle-pinned under the slow
marker (its scan compile is the multi-minute XLA:CPU hazard).

Pure-python pairings cost ~0.3-1s each, so expensive artifacts are
module-scoped.
"""

import dataclasses
import json
import os

import pytest

from cometbft_tpu.aggsig import aggregate as agg
from cometbft_tpu.aggsig import verify as aggv
from cometbft_tpu.crypto import bls12381 as bls
from cometbft_tpu.engine.chain_gen import LocalChainSource, generate_chain
from cometbft_tpu.pipeline.cache import reset_shared_cache, shared_cache
from cometbft_tpu.types import validation
from cometbft_tpu.types.agg_commit import (AggregatedCommit, from_commit,
                                           maybe_aggregate)
from cometbft_tpu.types.block import Commit, CommitSig


@pytest.fixture(scope="module")
def agg_chain():
    """2-block, 4-validator uniformly-BLS chain with aggregated seals
    (genesis PoPs registered as a side effect of generation)."""
    return generate_chain(n_blocks=2, n_validators=4, txs_per_block=1,
                          chain_id="aggsig-test", seed=7,
                          key_type="bls12_381", aggregate=True)


@pytest.fixture(scope="module")
def plain_chain():
    """1-block BLS chain with PLAIN per-lane commits (distinct
    per-validator timestamps) — the per-signature reference side."""
    return generate_chain(n_blocks=1, n_validators=4, txs_per_block=1,
                          chain_id="aggsig-plain", seed=8,
                          key_type="bls12_381", aggregate=False)


# --- bitmap + aggregation primitives -----------------------------------------

def test_bitmap_codec():
    bits = [True, False, False, True, True, False, False, False, True]
    bm = agg.bitmap_encode(bits)
    assert len(bm) == 2
    assert agg.bitmap_decode(bm, 9) == bits
    with pytest.raises(ValueError):
        agg.bitmap_decode(bm, 8)                     # wrong length
    with pytest.raises(ValueError):
        agg.bitmap_decode(b"\xff\x01", 7)            # stray high bit
    assert agg.bitmap_decode(b"", 0) == []


def test_aggregate_is_sum_of_signatures():
    """aggregate(s1..sk) decompresses to the G2 sum, and aggregate
    verification equals the product of the individual pairings (same
    message -> one pairing group)."""
    msg = b"one shared canonical message, longer than thirty-two bytes"
    keys = [bls.Bls12381PrivKey.generate(seed=bytes([i]) * 4)
            for i in range(3)]
    sigs = [k.sign(msg) for k in keys]
    s_agg = agg.aggregate_signatures(sigs)
    acc = None
    for s in sigs:
        pt = bls.g2_decompress(s)
        acc = pt if acc is None else bls._fq2.pt_add(acc, pt)
    assert bls.g2_decompress(s_agg) == acc
    pk_sum = agg.aggregate_pubkey_points(
        [k.pub_key().point for k in keys])
    h = bls.hash_to_g2_cached(bls._fixed_msg(msg))
    assert bls.multi_pairing_is_one(
        [(bls.G1_NEG, bls.g2_decompress(s_agg)), (pk_sum, h)])
    with pytest.raises(ValueError):
        agg.aggregate_signatures([])


# --- proof of possession ------------------------------------------------------

def test_pop_roundtrip_and_forgery():
    sk = bls.Bls12381PrivKey.generate(seed=b"pop-key")
    pub = sk.pub_key().bytes_()
    pop = agg.pop_prove(sk)
    assert agg.pop_verify(pub, pop)
    other = bls.Bls12381PrivKey.generate(seed=b"other-key")
    # a PoP binds the pubkey bytes: replaying it for another key fails
    assert not agg.pop_verify(other.pub_key().bytes_(), pop)
    assert not agg.pop_verify(pub, agg.pop_prove(other))
    assert not agg.pop_verify(b"\x00" * 48, pop)


def test_rogue_key_attack_rejected_by_pop(agg_chain, monkeypatch):
    """The textbook rogue-key attack: pk_rogue = pk_atk - pk_victim
    makes the two-signer aggregate verify with the attacker's lone
    signature. The pairing math MUST check out (else this test pins
    nothing) and the PoP admission gate must be what rejects it."""
    from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, BlockID,
                                          PartSetHeader)
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    atk = bls.Bls12381PrivKey.generate(seed=b"attacker")
    victim = bls.Bls12381PrivKey.generate(seed=b"victim")
    v_pub = victim.pub_key()
    agg.register_pop(v_pub.bytes_(), agg.pop_prove(victim))
    rogue_pt = bls._fq.pt_add(atk.pub_key().point,
                              bls._fq.pt_neg(v_pub.point))
    rogue_pub = bls.Bls12381PubKey(bls.g1_compress(rogue_pt))
    vals = ValidatorSet([Validator(rogue_pub, 10),
                         Validator(v_pub, 10)])

    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    ts = Timestamp(1_700_000_123, 0)
    order = [v.pub_key for v in vals.validators]
    sigs = [CommitSig(BLOCK_ID_FLAG_COMMIT, pk.address(), ts, b"")
            for pk in order]
    commit = AggregatedCommit(
        height=1, round=0, block_id=bid, signatures=sigs,
        bitmap=agg.bitmap_encode([True, True]), agg_sig=b"\x00" * 96)
    # the attacker signs the canonical message ALONE; the aggregate of
    # (rogue + victim) pubkeys collapses to the attacker's key
    msg = commit.vote_sign_bytes("rogue-chain", 0)
    h = bls.hash_to_g2_cached(bls._fixed_msg(msg))
    forged = bls.g2_compress(bls._fq2.pt_mul(atk._sk, h))
    commit.agg_sig = forged

    def run():
        validation.verify_commit("rogue-chain", vals, bid, 1, commit)

    # the PoP gate rejects: the rogue key cannot produce a PoP
    with pytest.raises(aggv.AggregateVerificationError,
                       match="proof of possession"):
        run()
    # ...and it is exactly the gate doing the work: with PoP checking
    # disabled the forged aggregate's pairing equation HOLDS
    monkeypatch.setattr(aggv, "has_pop", lambda _pub: True)
    run()  # must NOT raise — the attack is mathematically sound


def test_register_pops_batch_attribution():
    a = bls.Bls12381PrivKey.generate(seed=b"batch-a")
    c = bls.Bls12381PrivKey.generate(seed=b"batch-c")
    good_a = agg.pop_prove(a)
    ok = agg.register_pops_batch({
        a.pub_key().bytes_(): good_a,
        c.pub_key().bytes_(): good_a,   # wrong key's PoP -> reject
    })
    assert not ok
    assert agg.has_pop(a.pub_key().bytes_())
    assert not agg.has_pop(c.pub_key().bytes_())


# --- the AggregatedCommit seal ------------------------------------------------

def test_wire_roundtrip_and_hash_domain(agg_chain):
    c = agg_chain.seen_commits[0]
    assert isinstance(c, AggregatedCommit)
    dec = Commit.decode(c.encode())
    assert isinstance(dec, AggregatedCommit)
    assert dec.encode() == c.encode()
    assert dec.hash() == c.hash()
    # the seal is hash-bound: same lanes without the seal hash differ
    plain_twin = Commit(height=c.height, round=c.round,
                        block_id=c.block_id, signatures=c.signatures)
    assert plain_twin.hash() != c.hash()
    # and a plain commit still decodes as a plain commit
    assert type(Commit.decode(plain_twin.encode())) is Commit


def test_validate_basic_rejections(agg_chain):
    c = agg_chain.seen_commits[0]
    c.validate_basic()
    bad = dataclasses.replace(
        c, bitmap=agg.bitmap_encode([True, True, True, False]))
    with pytest.raises(ValueError, match="missing from bitmap"):
        bad.validate_basic()
    with pytest.raises(ValueError, match="length"):
        dataclasses.replace(c, agg_sig=b"\x01" * 64).validate_basic()
    with pytest.raises(ValueError):
        dataclasses.replace(c, bitmap=c.bitmap + b"\x00").validate_basic()
    sigs = list(c.signatures)
    sigs[0] = dataclasses.replace(sigs[0], signature=b"\x01" * 96)
    with pytest.raises(ValueError, match="per-lane signature"):
        dataclasses.replace(c, signatures=sigs).validate_basic()


def test_assembly_gate(plain_chain):
    plain = plain_chain.seen_commits[0]
    vals = plain_chain.valsets[0]
    got = maybe_aggregate(plain, vals)
    assert isinstance(got, AggregatedCommit)
    assert got.covered_indices() == [0, 1, 2, 3]
    # without registered PoPs the gate stays closed
    saved = dict(agg._POP_OK)
    try:
        agg.reset_pop_registry()
        assert maybe_aggregate(plain, vals) is plain
    finally:
        with agg._POP_LOCK:
            agg._POP_OK.update(saved)
    # ed25519 valsets are untouched
    ed = generate_chain(n_blocks=1, n_validators=2, txs_per_block=1,
                        chain_id="ed-gate", seed=3)
    assert maybe_aggregate(ed.seen_commits[0], ed.valsets[0]) \
        is ed.seen_commits[0]


# --- verification equivalence + cache ----------------------------------------

def test_verdict_equivalence_clean_and_tampered(plain_chain):
    """The per-signature reference and the aggregate path agree; the
    full tamper matrix (forged bitmap, undercount) runs in the
    bls-valset scenario (simnet/bls_valset.py)."""
    plain = plain_chain.seen_commits[0]
    vals = plain_chain.valsets[0]
    bid = plain_chain.block_ids[0]
    cid = plain_chain.chain_id

    def verdict(c):
        try:
            validation.verify_commit(cid, vals, bid, 1, c)
            return True
        except validation.CommitVerificationError:
            return False

    assert verdict(plain) and verdict(from_commit(plain))
    val0 = vals.validators[0]
    wrong = plain_chain.keys[val0.address].sign(b"some other message!!")
    tampered = dataclasses.replace(plain, signatures=[
        dataclasses.replace(cs, signature=wrong) if i == 0 else cs
        for i, cs in enumerate(plain.signatures)])
    assert not verdict(tampered)
    assert not verdict(from_commit(tampered))


def test_aggregate_verdict_cached(agg_chain):
    reset_shared_cache()
    c = agg_chain.seen_commits[1]
    vals = agg_chain.valsets[1]
    bid = agg_chain.block_ids[1]
    c0 = dict(bls.OP_COUNTERS)
    validation.verify_commit(agg_chain.chain_id, vals, bid, 2, c)
    cold = bls.OP_COUNTERS["final_exps"] - c0["final_exps"]
    assert cold >= 1
    c1 = dict(bls.OP_COUNTERS)
    validation.verify_commit(agg_chain.chain_id, vals, bid, 2,
                             Commit.decode(c.encode()))
    assert bls.OP_COUNTERS["final_exps"] == c1["final_exps"]  # cache hit
    assert shared_cache().hits.get("aggsig", 0) >= 1


def test_blocksync_catchup_over_aggregated_chain(agg_chain):
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore
    reset_shared_cache()
    app = KVStoreApplication()
    app.init_chain(agg_chain.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    ex = BlockExecutor(app, state_store=StateStore(db), block_store=store)
    st = State.from_genesis(agg_chain.genesis)
    r = BlocksyncReactor(ex, store, LocalChainSource(agg_chain),
                         agg_chain.chain_id, tile_size=4, batch_size=0,
                         cache=shared_cache())
    st = r.sync(st)
    assert st.last_block_height == agg_chain.max_height()
    assert r.stats.blocks_applied == agg_chain.max_height()
    # a corrupt aggregate from a peer is banned, then sync completes
    reset_shared_cache()
    app2 = KVStoreApplication()
    app2.init_chain(agg_chain.chain_id, 1, [], b"")
    db2 = MemDB()
    store2 = BlockStore(db2)
    ex2 = BlockExecutor(app2, state_store=StateStore(db2),
                        block_store=store2)
    st2 = State.from_genesis(agg_chain.genesis)
    # corrupt height 2: its last_commit is the AGGREGATED seal of
    # height 1 (height 1's own last_commit is the empty genesis one)
    src = LocalChainSource(agg_chain, corrupt_heights={2: "sig"})
    r2 = BlocksyncReactor(ex2, store2, src, agg_chain.chain_id,
                          tile_size=4, batch_size=0)
    st2 = r2.sync(st2)
    assert st2.last_block_height == agg_chain.max_height()
    assert src.banned


# --- batch verifier -----------------------------------------------------------

def test_bls_batch_verifier_attribution():
    msgs = [b"batch message %d, padded well past thirty-two bytes" % i
            for i in range(3)]
    keys = [bls.Bls12381PrivKey.generate(seed=b"bv%d" % i)
            for i in range(3)]
    bv = agg.BlsBatchVerifier()
    for k, m in zip(keys, msgs):
        bv.add(k.pub_key(), m, k.sign(m))
    ok, oks = bv.verify()
    assert ok and oks == [True, True, True]
    bad = agg.BlsBatchVerifier()
    for i, (k, m) in enumerate(zip(keys, msgs)):
        sig = k.sign(msgs[1]) if i == 2 else k.sign(m)  # lane 2 wrong msg
        bad.add(k.pub_key(), m, sig)
    ok, oks = bad.verify()
    assert not ok and oks == [True, True, False]
    assert agg.BlsBatchVerifier().verify() == (False, [])


def test_mixed_batch_routes_bls():
    """Satellite: crypto/batch now hands BLS keys a real batch
    verifier, so MixedBatchVerifier keeps exact per-lane attribution
    on mixed-curve vote sets instead of silently going per-sig.
    (sr25519 + secp lanes ride along for the bucket/single routing;
    ed25519 is deliberately absent — its batch verifier would compile
    the XLA:CPU RLC kernel, minutes of cost this unit test doesn't
    need, and its routing is already pinned by test_curves.)"""
    import random
    from cometbft_tpu.crypto.batch import (MixedBatchVerifier,
                                           create_batch_verifier,
                                           supports_batch_verifier)
    from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
    from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
    rng = random.Random(11)
    bkey = bls.Bls12381PrivKey.generate(seed=b"mixed-b")
    assert supports_batch_verifier(bkey.pub_key())
    bv, ok = create_batch_verifier(bkey.pub_key())
    assert ok and isinstance(bv, agg.BlsBatchVerifier)
    skey = Sr25519PrivKey.generate(rng)
    ckey = Secp256k1PrivKey.generate(rng)
    msg = b"mixed-batch message padded well past thirty-two bytes!!"
    mixed = MixedBatchVerifier()
    mixed.add(skey.pub_key(), msg, skey.sign(msg))
    mixed.add(bkey.pub_key(), msg, bkey.sign(msg))
    mixed.add(ckey.pub_key(), msg, ckey.sign(msg))    # single lane
    mixed.add(bkey.pub_key(), msg, b"\x00" * 96)      # bad bls lane
    ok, oks = mixed.verify()
    assert not ok and oks == [True, True, True, False]


# --- FinalExpChecker canary / quarantine discipline ---------------------------

class _Corrupt:
    """Stands in for ops.bls12: answers every lane True (including the
    known-bad canary)."""

    @staticmethod
    def final_exp_is_one_batch(batch):
        return [True] * len(batch)


class _Sup:
    def __init__(self):
        self.trips = []
        self.corruptions = []

    def report_trip(self, exc):
        self.trips.append(exc)

    def report_corruption(self, detail=""):
        self.corruptions.append(detail)


def test_finalexp_checker_canary_quarantine(monkeypatch):
    import cometbft_tpu.ops as ops_pkg
    sup = _Sup()
    chk = aggv.FinalExpChecker("kernel", supervisor=sup)
    monkeypatch.setattr(ops_pkg, "bls12", _Corrupt(), raising=False)
    msg = bls._fixed_msg(b"canary message longer than thirty-two bytes")
    h = bls.hash_to_g2_cached(msg)
    good = bls.miller_product([(bls.G1_NEG, h), (bls.G1_GEN, h)])
    bad = bls.miller_loop(bls.G1_GEN, h)
    out = chk.check([bad, good])
    # the corrupt kernel said all-true; the known-bad canary exposes
    # it, the batch re-verifies on CPU, and the kernel is quarantined
    assert out == [False, True]
    assert chk.quarantined and chk.canary_failures == 1
    assert sup.corruptions
    out2 = chk.check([bad])
    assert out2 == [False]          # stays on the CPU oracle
    assert chk.canary_failures == 1


def test_finalexp_checker_kernel_error_degrades(monkeypatch):
    import cometbft_tpu.ops as ops_pkg

    class _Boom:
        @staticmethod
        def final_exp_is_one_batch(batch):
            raise RuntimeError("compile exploded")

    sup = _Sup()
    chk = aggv.FinalExpChecker("kernel", supervisor=sup)
    monkeypatch.setattr(ops_pkg, "bls12", _Boom(), raising=False)
    msg = bls._fixed_msg(b"degrade message longer than thirty-two byt")
    h = bls.hash_to_g2_cached(msg)
    good = bls.miller_product([(bls.G1_NEG, h), (bls.G1_GEN, h)])
    assert chk.check([good]) == [True]
    assert chk.quarantined and sup.trips


# --- compile ledger -----------------------------------------------------------

def test_compile_ledger(tmp_path):
    from cometbft_tpu.libs.jax_cache import CompileLedger
    path = os.path.join(tmp_path, "ledger.json")
    led = CompileLedger(path)
    assert not led.seen("k", 64)
    with led.compile_guard("k", 64):
        pass
    assert led.seen("k", 64)
    assert led.attribution()["misses"] == 1
    with led.compile_guard("k", 64):
        pass
    assert led.attribution()["hits"] == 1
    # a RAISING guard records nothing: a transient failure is not a
    # compile
    with pytest.raises(RuntimeError):
        with led.compile_guard("k", 256):
            raise RuntimeError("transient stand-in")
    assert not led.seen("k", 256)
    assert not led.warm_in_process("k", 256)
    led.record("k", 256, 1.0)
    assert led.seen("k", 256) and led.warm_in_process("k", 256)
    # persisted: a fresh instance reads the same entries (warmth is
    # the process's own and is not), and saves MERGE over foreign
    # writers' entries instead of erasing them
    led2 = CompileLedger(path)
    assert led2.seen("k", 64) and led2.seen("k", 256)
    assert not led2.warm_in_process("k", 256)
    led3 = CompileLedger(path)
    led2.record("other-kernel", 4, 2.0)     # concurrent writer A
    led3.record("third-kernel", 8, 3.0)     # concurrent writer B
    led4 = CompileLedger(path)
    assert led4.seen("other-kernel", 4) and led4.seen("third-kernel", 8)
    assert json.load(open(path))


# --- durable-state round-trips ------------------------------------------------

def test_bls_state_and_privval_roundtrip(plain_chain, tmp_path):
    from cometbft_tpu.privval.file import FilePV
    from cometbft_tpu.state.state import (StateStore, State,
                                          _valset_from_json,
                                          _valset_to_json)
    vals = plain_chain.valsets[0]
    back = _valset_from_json(_valset_to_json(vals))
    assert back.hash() == vals.hash()
    assert back.validators[0].pub_key.type_() == "bls12_381"

    from cometbft_tpu.db.kv import MemDB
    store = StateStore(MemDB())
    st = State.from_genesis(plain_chain.genesis)
    store.save(st)
    loaded = store.load()
    assert loaded.validators.hash() == st.validators.hash()
    assert loaded.validators.validators[0].pub_key.type_() == "bls12_381"

    key = plain_chain.keys[vals.validators[0].address]
    pv_path = os.path.join(tmp_path, "pv.json")
    pv = FilePV(key, pv_path)
    pv._save()
    pv2 = FilePV.load(pv_path)
    assert pv2.priv_key.type_() == "bls12_381"
    assert pv2.priv_key.bytes_() == key.bytes_()


def test_genesis_file_roundtrip_with_pops(plain_chain, tmp_path):
    from cometbft_tpu.node.node import load_genesis, save_genesis
    path = os.path.join(tmp_path, "genesis.json")
    save_genesis(plain_chain.genesis, path)
    gen = load_genesis(path)
    assert gen.bls_pops == plain_chain.genesis.bls_pops
    assert gen.validators[0].pub_key.type_() == "bls12_381"
    assert [v.address for v in gen.validators] == \
        [v.address for v in plain_chain.genesis.validators]


# --- the JAX kernel, oracle-pinned (slow: scan compiles) ----------------------

@pytest.mark.slow
def test_kernel_mont_mul_oracle():
    import random

    import jax.numpy as jnp
    import numpy as np

    from cometbft_tpu.ops import bls12 as K
    rng = random.Random(5)
    for _ in range(4):
        a = rng.randrange(bls.P)
        b = rng.randrange(bls.P)
        am = jnp.asarray(K.limbs_from_int(a * K.R_INT % bls.P)[:, None])
        bm = jnp.asarray(K.limbs_from_int(b * K.R_INT % bls.P)[:, None])
        got = K.int_from_limbs(np.asarray(K.mont_mul(am, bm))[:, 0])
        assert got == a * b * K.R_INT % bls.P


@pytest.mark.slow
def test_kernel_pow_small_exponent_oracle():
    from cometbft_tpu.ops import bls12 as K
    m = bls.miller_loop(bls.G1_GEN, bls.hash_to_g2(b"\x07" * 32))
    e = 0b1100101
    bits = tuple(int(c) for c in bin(e)[2:])
    got = K.pow_is_one_batch([m, bls.F12_ONE], bits, 4)
    assert got == [bls.f12_pow(m, e) == bls.F12_ONE, True]


@pytest.mark.slow
def test_kernel_final_exp_matches_cpu(tmp_path):
    from cometbft_tpu.libs.jax_cache import ledger, reset_ledger
    from cometbft_tpu.ops import bls12 as K
    reset_ledger(os.path.join(tmp_path, "ledger.json"))
    try:
        h = bls.hash_to_g2(b"\x09" * 32)
        good = bls.miller_product([(bls.G1_NEG, h), (bls.G1_GEN, h)])
        bad = bls.miller_loop(bls.G1_GEN, h)
        assert K.final_exp_is_one_batch([good, bad, good]) == \
            [True, False, True]
        att = ledger().attribution()
        assert att["misses"] >= 1    # the compile was attributed
    finally:
        reset_ledger()


# --- review-hardening regressions --------------------------------------------

def test_node_restart_readmits_genesis_pops(tmp_path):
    """A RESTARTED node loads state from the store and skips
    State.from_genesis — the sole original PoP-registration site — so
    Node boot must re-admit the genesis PoPs or every valid aggregated
    commit would be rejected in the new process (registry is
    process-local; a real restart starts empty)."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import Config
    from cometbft_tpu.node.node import Node, save_genesis
    from cometbft_tpu.privval.file import FilePV
    from cometbft_tpu.state.state import GenesisDoc
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator

    key = bls.Bls12381PrivKey.generate(seed=b"restart-pop")
    pub = key.pub_key().bytes_()
    gen = GenesisDoc(chain_id="restart-pop",
                     genesis_time=Timestamp(1_700_000_000, 0),
                     validators=[Validator(key.pub_key(), 10)],
                     bls_pops={pub: agg.pop_prove(key)})
    root = tmp_path / "node"
    os.makedirs(root / "config", exist_ok=True)
    os.makedirs(root / "data", exist_ok=True)

    def make_node():
        cfg = Config(root_dir=str(root))
        cfg.base.db_backend = "filedb"  # persists across "processes"
        save_genesis(gen, str(root / "config/genesis.json"))
        pv = FilePV(key, str(root / "pv.json"))
        return Node(cfg, KVStoreApplication(), genesis=gen,
                    priv_validator=pv)

    saved = dict(agg._POP_OK)
    try:
        agg.reset_pop_registry()
        make_node()                       # fresh boot: from_genesis
        assert agg.has_pop(pub)
        agg.reset_pop_registry()          # "new process"
        n2 = make_node()                  # state now loads from store
        assert n2.consensus.state.last_block_height == 0
        assert agg.has_pop(pub), \
            "restart path failed to re-admit genesis PoPs"
    finally:
        with agg._POP_LOCK:
            agg._POP_OK.clear()
            agg._POP_OK.update(saved)


def test_mixed_valset_commit_verifies():
    """A heterogeneous valset (sr25519 + BLS) must batch through
    MixedBatchVerifier — the proposer-keyed single-curve verifier
    would TypeError on the first foreign lane."""
    import random
    from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
    from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, BlockID,
                                          PartSetHeader)
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import Vote, PRECOMMIT_TYPE

    rng = random.Random(21)
    keys = [Sr25519PrivKey.generate(rng),
            bls.Bls12381PrivKey.generate(seed=b"mixed-commit")]
    vals = ValidatorSet([Validator(k.pub_key(), 10) for k in keys])
    by_addr = {k.pub_key().address(): k for k in keys}
    bid = BlockID(b"\x31" * 32, PartSetHeader(1, b"\x32" * 32))
    sigs = []
    for i, v in enumerate(vals.validators):
        ts = Timestamp(1_700_000_777, i)
        vote = Vote(type_=PRECOMMIT_TYPE, height=1, round=0,
                    block_id=bid, timestamp=ts,
                    validator_address=v.address, validator_index=i)
        sigs.append(CommitSig(
            BLOCK_ID_FLAG_COMMIT, v.address, ts,
            by_addr[v.address].sign(vote.sign_bytes("mixed-chain"))))
    commit = Commit(height=1, round=0, block_id=bid, signatures=sigs)
    reset_shared_cache()
    validation.verify_commit("mixed-chain", vals, bid, 1, commit)
    bad = dataclasses.replace(commit, signatures=[
        dataclasses.replace(sigs[0],
                            signature=sigs[0].signature[:-1] + b"\x00"),
        sigs[1]])
    reset_shared_cache()
    with pytest.raises(validation.CommitVerificationError):
        validation.verify_commit("mixed-chain", vals, bid, 1, bad)


def test_blocksync_plain_bls_commits(plain_chain):
    """Blocksync must accept PLAIN per-lane commits on a BLS valset
    (either commit form is valid for BLS valsets): the marshal stage
    routes them through the generic host-side verify instead of the
    ed25519 lane kernel, which would reject every 48-byte pubkey."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore
    assert type(plain_chain.seen_commits[0]) is Commit
    reset_shared_cache()
    app = KVStoreApplication()
    app.init_chain(plain_chain.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    ex = BlockExecutor(app, state_store=StateStore(db), block_store=store)
    st = State.from_genesis(plain_chain.genesis)
    r = BlocksyncReactor(ex, store, LocalChainSource(plain_chain),
                         plain_chain.chain_id, tile_size=4, batch_size=0)
    st = r.sync(st)
    assert st.last_block_height == plain_chain.max_height()


def test_ledger_platform_override_keys(tmp_path):
    """Ledger entries are keyed by platform: an entry recorded under
    'cpu' is visible via platform='cpu' whatever this process's own
    backend is, and a device entry never satisfies a cpu-keyed
    lookup."""
    import os as _os
    from cometbft_tpu.libs.jax_cache import CompileLedger

    path = _os.path.join(str(tmp_path), "ledger.json")
    led = CompileLedger(path)
    cpu_key = led.key("rlc-xla", 256, platform="cpu")
    dev_key = led.key("rlc-xla", 256, platform="tpu")
    assert cpu_key != dev_key and "|cpu|" in cpu_key

    # write a cpu-keyed entry
    led._entries[cpu_key] = {"kernel": "rlc-xla", "bucket": 256,
                             "compile_s": 1.0}
    assert led.seen("rlc-xla", 256, platform="cpu")
    assert not led.seen("rlc-xla", 256, platform="tpu")


# --- PairingChecker: fused Miller + final-exp verdicts ------------------------

def _pairing_fixtures():
    """(good, bad, good3) pair-list items: good is the 2-pair commit
    equation shape, good3 a 3-pair multi-group shape (oversize for the
    kernel's fixed MILLER_PAIRS)."""
    h = bls.hash_to_g2(b"\x0b" * 32)
    s1, s2 = 7, 11
    good = [(bls.G1_NEG, bls._fq2.pt_mul(s1, h)),
            (bls._fq.pt_mul(s1, bls.G1_GEN), h)]
    bad = [(bls.G1_NEG, bls._fq2.pt_mul(s1, h)), (bls.G1_GEN, h)]
    good3 = [(bls.G1_NEG, bls._fq2.pt_mul(s1 + s2, h)),
             (bls._fq.pt_mul(s1, bls.G1_GEN), h),
             (bls._fq.pt_mul(s2, bls.G1_GEN), h)]
    return good, bad, good3


class _HonestMiller:
    """Stands in for ops.bls12 with host-math verdicts — exercises the
    PairingChecker/register_pops_batch kernel ARC without paying the
    real scan compile (the slow test below pins the real kernel)."""

    MILLER_PAIRS = 2

    def __init__(self):
        self.batches = []

    def miller_finalexp_is_one_batch(self, items):
        self.batches.append(len(items))
        return [bls.final_exponentiation(bls.miller_product(p))
                == bls.F12_ONE for p in items]


def test_pairing_checker_cpu_oracle():
    pc = aggv.PairingChecker("cpu")
    good, bad, good3 = _pairing_fixtures()
    assert pc.check([]) == []
    assert pc.check([good, bad, good3, [(None, None)]]) == \
        [True, False, True, True]
    # the shared instance is a singleton riding the shared finalexp
    assert aggv.shared_pairing() is aggv.shared_pairing()
    assert aggv.shared_pairing().finalexp is aggv.shared_finalexp()


def test_pairing_checker_canary_quarantine(monkeypatch):
    import cometbft_tpu.ops as ops_pkg

    class _CorruptMiller:
        MILLER_PAIRS = 2

        @staticmethod
        def miller_finalexp_is_one_batch(items):
            return [True] * len(items)

    sup = _Sup()
    pc = aggv.PairingChecker("kernel", supervisor=sup,
                             finalexp=aggv.FinalExpChecker("cpu"))
    monkeypatch.setattr(ops_pkg, "bls12", _CorruptMiller(), raising=False)
    good, bad, _ = _pairing_fixtures()
    cpu_before = aggv.AGG_COUNTERS["aggregates_cpu"]
    out = pc.check([bad, good])
    # the corrupt kernel answered the known-not-one canary True: the
    # whole batch re-verifies on the pure-CPU oracle (NOT through the
    # possibly-corrupt final-exp kernel) and the checker quarantines
    assert out == [False, True]
    assert pc.quarantined and pc.canary_failures == 1
    assert sup.corruptions
    assert aggv.AGG_COUNTERS["aggregates_cpu"] == cpu_before + 2
    assert pc.check([bad]) == [False]       # stays on the CPU oracle
    assert pc.canary_failures == 1


def test_pairing_checker_kernel_error_degrades(monkeypatch):
    import cometbft_tpu.ops as ops_pkg

    class _BoomMiller:
        MILLER_PAIRS = 2

        @staticmethod
        def miller_finalexp_is_one_batch(items):
            raise RuntimeError("miller compile exploded")

    sup = _Sup()
    pc = aggv.PairingChecker("kernel", supervisor=sup,
                             finalexp=aggv.FinalExpChecker("cpu"))
    monkeypatch.setattr(ops_pkg, "bls12", _BoomMiller(), raising=False)
    good, bad, _ = _pairing_fixtures()
    assert pc.check([good, bad]) == [True, False]
    assert pc.quarantined and sup.trips


def test_pairing_checker_oversize_item_rides_cpu_miller(monkeypatch):
    """An item with more live pairs than the kernel's fixed shape
    (multi-group commit) takes the host Miller product; the 2-pair
    items still fuse — and the fused batch carries exactly the two
    canary lanes on top."""
    import cometbft_tpu.ops as ops_pkg
    stub = _HonestMiller()
    pc = aggv.PairingChecker("kernel", supervisor=_Sup(),
                             finalexp=aggv.FinalExpChecker("cpu"))
    monkeypatch.setattr(ops_pkg, "bls12", stub, raising=False)
    good, bad, good3 = _pairing_fixtures()
    kern_before = aggv.AGG_COUNTERS["aggregates_kernel"]
    assert pc.check([good, good3, bad]) == [True, True, False]
    assert stub.batches == [4]              # good + bad + 2 canaries
    assert not pc.quarantined and pc.canary_failures == 0
    assert aggv.AGG_COUNTERS["aggregates_kernel"] == kern_before + 2


def test_register_pops_batch_kernel_route(tmp_path, monkeypatch):
    """Ledger-warm kernel backend admits PoPs as exact per-key 2-pair
    lanes; cold ledger (every genesis/state-reload boot) declines to
    the RLC host path — the PR-7 re-admission arc keeps working."""
    import cometbft_tpu.ops as ops_pkg
    from cometbft_tpu.libs.jax_cache import ledger, reset_ledger
    from cometbft_tpu.ops import bls12 as real_bls12  # pin sys.modules

    keys = [bls.Bls12381PrivKey.generate(seed=b"pop-kernel-%d" % i)
            for i in range(3)]
    pubs = [k.pub_key().bytes_() for k in keys]
    pops = {pubs[0]: agg.pop_prove(keys[0]),
            pubs[1]: agg.pop_prove(keys[1]),
            pubs[2]: agg.pop_prove(keys[0]),   # wrong signer: invalid
            b"\x05" * 48: b"\x00" * 5}         # malformed pop lane
    stub = _HonestMiller()
    monkeypatch.setenv(aggv.ENV_KERNEL, "1")
    monkeypatch.setattr(ops_pkg, "bls12", stub, raising=False)
    saved = dict(agg._POP_OK)
    reset_ledger(os.path.join(tmp_path, "ledger.json"))
    aggv.reset_shared_finalexp()
    try:
        agg.reset_pop_registry()
        # cold ledger: kernel route declines, RLC path still admits
        assert agg.register_pops_batch(dict(pops)) is False
        assert stub.batches == []
        assert agg.has_pop(pubs[0]) and agg.has_pop(pubs[1])
        assert not agg.has_pop(pubs[2])
        agg.reset_pop_registry()
        bucket = real_bls12.bucket_for(len(pops) + 2)
        with ledger().compile_guard("bls-miller", bucket):
            pass                               # mark process-warm
        kern_before = aggv.AGG_COUNTERS["aggregates_kernel"]
        assert agg.register_pops_batch(dict(pops)) is False
        # 3 decompressible lanes + 2 canaries (malformed pop rejected
        # before the device sees it)
        assert stub.batches == [5]
        assert agg.has_pop(pubs[0]) and agg.has_pop(pubs[1])
        assert not agg.has_pop(pubs[2])
        assert aggv.AGG_COUNTERS["aggregates_kernel"] == kern_before + 3
        # idempotent: everything pending already registered or invalid
        assert agg.register_pops_batch({pubs[0]: pops[pubs[0]]}) is True
        assert stub.batches == [5]             # nothing re-verified
    finally:
        aggv.reset_shared_finalexp()
        reset_ledger()
        with agg._POP_LOCK:
            agg._POP_OK.clear()
            agg._POP_OK.update(saved)


@pytest.mark.slow
def test_kernel_miller_finalexp_matches_cpu(tmp_path):
    """The REAL fused kernel (batched Miller scan + in-kernel final
    exp) against host math, sharing one bucket-4 compile between the
    raw batch call and a canary-gated PairingChecker."""
    from cometbft_tpu.libs.jax_cache import ledger, reset_ledger
    from cometbft_tpu.ops import bls12 as K
    reset_ledger(os.path.join(tmp_path, "ledger.json"))
    try:
        good, bad, _ = _pairing_fixtures()
        h = bls.hash_to_g2(b"\x0b" * 32)
        single = [(bls.G1_GEN, h)]             # e(g1, h) != 1
        empty = [(None, h)]                    # no live pairs -> 1
        assert K.miller_finalexp_is_one_batch(
            [good, bad, single, empty]) == [True, False, False, True]
        sup = _Sup()
        pc = aggv.PairingChecker("kernel", supervisor=sup,
                                 finalexp=aggv.FinalExpChecker("cpu"))
        loops_before = bls.OP_COUNTERS["miller_loops"]
        assert pc.check([good, bad]) == [True, False]  # 2 + 2 canaries
        assert not pc.quarantined and pc.canary_failures == 0
        assert not sup.trips and not sup.corruptions
        assert bls.OP_COUNTERS["miller_loops"] == loops_before + 4
        att = ledger().attribution()
        assert att["misses"] >= 1 and att["hits"] >= 1
    finally:
        reset_ledger()
