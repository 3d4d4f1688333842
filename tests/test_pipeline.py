"""pipeline/ — asynchronous multi-tile verification pipeline
(cometbft_tpu/pipeline: scheduler, watchdog, cache; docs/PIPELINE.md).

Pins the properties the subsystem exists for:
- verdict equivalence: the pipelined path accepts/rejects exactly what
  the synchronous tile loop does, on clean, tampered, and
  valset-change chains, at every depth (depth=1 IS the synchronous
  degenerate case);
- wedge liveness: a device that never answers completes the sync
  through the watchdog's sticky CPU fallback instead of stalling;
- cache correctness: only verified-TRUE signatures are stored, LRU
  eviction is bounded, hits are attributed per intake path, and cached
  lanes produce the same verdicts while skipping device work.

The slow-marked depth sweep (run_suite.sh) soaks K in {1,2,4,8}.
"""

import numpy as np
import pytest
from _kernel_shape import LOCAL_LANES

from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.engine.blocksync import (BlocksyncReactor, SyncStalled,
                                           TiledCommitVerifier,
                                           marshal_commit)
from cometbft_tpu.engine.chain_gen import (LocalChainSource,
                                           generate_chain)
from cometbft_tpu.libs.metrics import Registry
from cometbft_tpu.libs.metrics_gen import PipelineMetrics
from cometbft_tpu.pipeline.cache import SigCache
from cometbft_tpu.pipeline.scheduler import (FixedLatencyBackend,
                                             HangingBackend,
                                             LocalAsyncBackend,
                                             PipelinedBlocksync)
from cometbft_tpu.pipeline.watchdog import DeviceWatchdog
from cometbft_tpu.state.execution import BlockExecutor, BlockValidationError
from cometbft_tpu.state.state import State, StateStore
from cometbft_tpu.store.blockstore import BlockStore

pytestmark = pytest.mark.pipeline

CHAIN = generate_chain(n_blocks=12, n_validators=4, txs_per_block=2)


def _fresh_node(chain):
    app = KVStoreApplication()
    app.init_chain(chain.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    sstore = StateStore(db)
    executor = BlockExecutor(app, state_store=sstore, block_store=store)
    state = State.from_genesis(chain.genesis)
    return app, store, sstore, executor, state


def _sync(chain, depth, src=None, tile=4, backend=None, watchdog=None,
          cache=None, metrics=None, max_retries=3):
    app, store, _ss, executor, state = _fresh_node(chain)
    src = src or LocalChainSource(chain)
    reactor = BlocksyncReactor(
        executor, store, src, chain.chain_id, tile_size=tile,
        batch_size=64, max_retries=max_retries, pipeline_depth=depth,
        backend=backend, watchdog=watchdog, cache=cache, metrics=metrics)
    state = reactor.sync(state)
    return state, reactor, src, app


def _valset_change_chain():
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    new_key = Ed25519PrivKey(b"\x99" * 32)
    val_tx = b"val:" + new_key.pub_key().bytes_().hex().encode() + b"!15"
    chain = generate_chain(n_blocks=10, n_validators=4, seed=3,
                           val_tx_heights={4: val_tx},
                           extra_keys=[new_key])
    return chain, new_key


# --- scheduler: catch-up + equivalence ---------------------------------------

def test_pipeline_catches_up_depth4():
    state, reactor, _src, app = _sync(CHAIN, depth=4)
    assert state.last_block_height == 12
    assert reactor.stats.blocks_applied == 12
    assert reactor.stats.tiles_flushed >= 2
    assert app.state["k12-0"] == "v12-0"
    assert app.state["k1-1"] == "v1-1"


def test_pipeline_matches_sync_on_clean_chain():
    s1, r1, _, a1 = _sync(CHAIN, depth=1)
    s4, r4, _, a4 = _sync(CHAIN, depth=4)
    assert s1.last_block_height == s4.last_block_height == 12
    assert s1.app_hash == s4.app_hash
    assert a1.state == a4.state
    assert r1.stats.blocks_applied == r4.stats.blocks_applied
    assert r1.stats.sigs_verified == r4.stats.sigs_verified


def test_pipeline_matches_sync_on_corrupt_sig():
    outs = {}
    for depth in (1, 4):
        src = LocalChainSource(CHAIN, corrupt_heights={7: "sig"})
        state, _r, src, _a = _sync(CHAIN, depth=depth, src=src)
        outs[depth] = (state.last_block_height, bool(src.banned))
    assert outs[1] == outs[4] == (12, True)


def test_pipeline_matches_sync_on_tampered_data():
    outs = {}
    for depth in (1, 4):
        src = LocalChainSource(CHAIN, corrupt_heights={5: "data"})
        state, _r, src, _a = _sync(CHAIN, depth=depth, src=src)
        outs[depth] = (state.last_block_height, 5 in src.banned)
    assert outs[1] == outs[4] == (12, True)


def test_pipeline_exhausts_retries_like_sync():
    class StubbornSource(LocalChainSource):
        def ban(self, height):
            self.banned.append(height)  # keeps serving corrupt data

    for depth in (1, 4):
        src = StubbornSource(CHAIN, corrupt_heights={3: "sig"})
        with pytest.raises(BlockValidationError):
            _sync(CHAIN, depth=depth, src=src, max_retries=2)


def test_pipeline_matches_sync_on_valset_change():
    chain, new_key = _valset_change_chain()
    s1, r1, _, _ = _sync(chain, depth=1, tile=8)
    s4, r4, _, _ = _sync(chain, depth=4, tile=8)
    assert s1.last_block_height == s4.last_block_height == 10
    assert s1.app_hash == s4.app_hash
    addr = new_key.pub_key().address()
    assert s1.validators.has_address(addr)
    assert s4.validators.has_address(addr)
    assert r4.stats.respeculations >= 1


def test_depth1_is_synchronous_degenerate_case():
    """PipelinedBlocksync at depth=1 produces the _sync_tile results."""
    app, store, _ss, executor, state = _fresh_node(CHAIN)
    reactor = BlocksyncReactor(executor, store, LocalChainSource(CHAIN),
                               CHAIN.chain_id, tile_size=5, batch_size=64)
    pipe = PipelinedBlocksync(reactor, depth=1)
    try:
        while state.last_block_height < 12:
            state = pipe.run(state, 12)
    finally:
        pipe.close()
    assert state.last_block_height == 12
    assert reactor.stats.blocks_applied == 12
    assert store.height() == 12


def test_pipeline_stall_propagates():
    class EmptySource:
        def max_height(self):
            return 9

        def fetch(self, height):
            return None

        def ban(self, height):
            pass

        def pending_fetches(self):
            return 7

    app, store, _ss, executor, state = _fresh_node(CHAIN)
    reactor = BlocksyncReactor(executor, store, EmptySource(),
                               CHAIN.chain_id, tile_size=4, batch_size=0,
                               pipeline_depth=2, max_retries=1)
    with pytest.raises(SyncStalled) as ei:
        reactor.sync(state)
    # satellite: the stalled height and the pending fetch count are in
    # the message
    assert "height 1" in str(ei.value)
    assert "7 fetches pending" in str(ei.value)


# --- watchdog ----------------------------------------------------------------

def test_wedged_device_completes_via_cpu_fallback():
    reg = Registry()
    metrics = PipelineMetrics(reg)
    wd = DeviceWatchdog(base_deadline_s=0.05, per_sig_s=0.0,
                        metrics=metrics)
    state, reactor, _src, app = _sync(
        CHAIN, depth=2, backend=HangingBackend(), watchdog=wd)
    assert state.last_block_height == 12
    assert app.state["k12-0"] == "v12-0"
    assert wd.wedged and wd.trips == 1
    assert wd.fallbacks >= 1
    assert metrics.wedge_fallbacks.value() == wd.fallbacks
    assert "pipeline_wedge_fallbacks" in reg.expose()


def test_wedge_verdicts_match_sync_on_corrupt_chain():
    """CPU fallback must keep FULL verify semantics: a tampered sig is
    still rejected while the device hangs."""
    src = LocalChainSource(CHAIN, corrupt_heights={7: "sig"})
    wd = DeviceWatchdog(base_deadline_s=0.05, per_sig_s=0.0)
    state, _r, src, _a = _sync(CHAIN, depth=3, src=src,
                               backend=HangingBackend(), watchdog=wd)
    assert state.last_block_height == 12
    assert src.banned


def test_watchdog_sticky_and_scaled_deadline():
    wd = DeviceWatchdog(base_deadline_s=2.0, per_sig_s=0.01)
    assert wd.deadline_for(0) == pytest.approx(2.0)
    assert wd.deadline_for(4096) == pytest.approx(2.0 + 40.96)
    # backend exception trips the wedge exactly like a timeout
    fut = LocalAsyncBackend(lambda p, m, s: 1 / 0).submit([b"x"], [b"y"],
                                                          [b"z"])
    assert wd.result(fut, 1) is None
    assert wd.wedged
    # sticky: a healthy future is not even consulted afterwards
    done = FixedLatencyBackend(0.0).submit([b"x"], [b"y"], [b"z"])
    assert wd.result(done, 1) is None
    assert wd.fallbacks == 2


def test_remote_batch_verifier_retries_once_then_local():
    from cometbft_tpu.crypto import ref_ed25519 as ref
    from cometbft_tpu.device.client import (DeviceUnprocessable,
                                            RemoteBatchVerifier)
    from cometbft_tpu.device.health import (DeviceSupervisor, HEALTHY,
                                            SUSPECT)
    from cometbft_tpu.crypto.keys import Ed25519PubKey

    class FlakyClient:
        def __init__(self, exc):
            self.exc = exc
            self.calls = 0

        def verify(self, p, m, s):
            self.calls += 1
            raise self.exc

    def sup():
        # explicit per-case supervisor: never leak SUSPECT into the
        # process-wide shared instance from a test fixture client
        return DeviceSupervisor(backoff_base_s=0.01, backoff_cap_s=0.1)

    # LOCAL_LANES signatures a flush: going local is the point, not the
    # kernel: a CPU backend verifies natively at any width
    # (_kernel_shape.py)
    seed = b"\x05" * 32
    pk = Ed25519PubKey(ref.pubkey_from_seed(seed))
    signed = [(m, ref.sign(seed, m)) for m in
              (b"hello %d" % i for i in range(LOCAL_LANES))]

    def fill(rbv):
        for msg, sig in signed:
            rbv.add(pk, msg, sig)
        return rbv

    # dead link: exactly one retry (shared_client may reconnect), then
    # local; the transport failures report to the supervisor
    flaky = FlakyClient(ConnectionError("link down"))
    s1 = sup()
    ok, oks = fill(RemoteBatchVerifier(flaky, supervisor=s1)).verify()
    assert ok and oks == [True] * LOCAL_LANES
    assert flaky.calls == 2
    assert s1.state == SUSPECT and s1.trips == 2

    # a deadline miss means the server is wedged: retrying would double
    # the consensus-path stall — go local immediately
    wedged = FlakyClient(TimeoutError("wedged"))
    s2 = sup()
    ok, oks = fill(RemoteBatchVerifier(wedged, supervisor=s2)).verify()
    assert ok and oks == [True] * LOCAL_LANES
    assert wedged.calls == 1
    assert s2.state == SUSPECT

    # unprocessable batches go straight local (a retry can't shrink) —
    # and are NOT a health signal: the device answered coherently
    unproc = FlakyClient(DeviceUnprocessable("too big"))
    s3 = sup()
    ok, oks = fill(RemoteBatchVerifier(unproc, supervisor=s3)).verify()
    assert ok and oks == [True] * LOCAL_LANES
    assert unproc.calls == 1
    assert s3.state == HEALTHY


def test_device_deadline_env_override(monkeypatch):
    from cometbft_tpu.device import client as dc
    assert dc.deadline_for(4096) == pytest.approx(
        dc.DEFAULT_DEADLINE_BASE_S
        + dc.DEFAULT_DEADLINE_PER_SIG_S * 4096)
    monkeypatch.setenv(dc.ENV_DEADLINE_BASE, "3")
    monkeypatch.setenv(dc.ENV_DEADLINE_PER_SIG, "0.5")
    assert dc.deadline_for(10) == pytest.approx(8.0)


# --- verified-signature cache ------------------------------------------------

def test_cache_lru_eviction():
    c = SigCache(capacity=4)
    for i in range(6):
        c.add(b"pk%d" % i, b"msg", b"sig")
    assert len(c) == 4
    assert c.evictions == 2
    # the two oldest fell out; the newest four are present
    assert not c.seen(b"pk0", b"msg", b"sig")
    assert not c.seen(b"pk1", b"msg", b"sig")
    assert c.seen(b"pk5", b"msg", b"sig")


def test_cache_lru_touch_on_hit():
    c = SigCache(capacity=2)
    c.add(b"a", b"m", b"s")
    c.add(b"b", b"m", b"s")
    assert c.seen(b"a", b"m", b"s")  # refresh a
    c.add(b"c", b"m", b"s")          # evicts b, not a
    assert c.seen(b"a", b"m", b"s")
    assert not c.seen(b"b", b"m", b"s")


def test_cache_attribution_and_hit_rate():
    c = SigCache(capacity=16)
    c.add(b"p", b"m", b"s")
    assert c.seen(b"p", b"m", b"s", path="vote")
    assert not c.seen(b"q", b"m", b"s", path="vote")
    assert c.seen(b"p", b"m", b"s", path="blocksync")
    assert c.hits == {"vote": 1, "blocksync": 1}
    assert c.misses == {"vote": 1}
    assert c.hit_rate("vote") == pytest.approx(0.5)
    assert c.hit_rate() == pytest.approx(2 / 3)


def test_cache_metrics_wiring():
    reg = Registry()
    m = PipelineMetrics(reg)
    c = SigCache(capacity=1, metrics=m)
    c.add(b"p", b"m", b"s")
    c.seen(b"p", b"m", b"s", path="commit")
    c.seen(b"x", b"m", b"s", path="commit")
    c.add(b"x", b"m", b"s")  # evicts p
    assert m.cache_hits.value(path="commit") == 1
    assert m.cache_misses.value(path="commit") == 1
    assert m.cache_evictions.value() == 1


def test_cache_disabled_capacity_zero():
    c = SigCache(capacity=0)
    c.add(b"p", b"m", b"s")
    assert not c.seen(b"p", b"m", b"s")
    assert len(c) == 0


def _lane(i: int):
    return (b"pk%d" % i, b"sign-bytes %d" % (i % 3), b"sig%d" % i)


# scripted sequences: ("lookup", lane numbers, path or a path a lane) and
# ("insert", lane numbers); an insert hands over the keys its lanes were
# looked up with, as every keyed call site does
_CACHE_SCRIPTS = {
    "one-path-under-capacity": (64, [
        ("lookup", [0, 1, 2, 3], "blocksync"),
        ("insert", [0, 1, 3]),
        ("lookup", [0, 1, 2, 3, 4], "blocksync"),
        ("insert", [2, 4]),
        ("lookup", [4, 3, 2, 1, 0], "commit")]),
    "at-and-over-capacity": (4, [
        ("lookup", [0, 1, 2, 3], "light"),
        ("insert", [0, 1, 2, 3]),          # exactly at capacity
        ("lookup", [1, 5, 6], "light"),    # touches 1: 0 is now oldest
        ("insert", [5, 6, 1]),             # evicts 0 and 2; 1 re-inserted
        ("lookup", [0, 1, 2, 3, 5, 6, 7, 8, 9, 10, 11], "light"),
        ("insert", [7, 8, 9, 10, 11]),     # a batch larger than capacity
        ("lookup", [6, 7, 8, 9, 10, 11], "commit")]),
    "a-path-a-lane": (3, [
        ("lookup", [0, 1, 2], ["vote", "ext", "vote"]),
        ("insert", [0, 2]),
        ("lookup", [0, 1, 2, 3], ["ext", "ext", "vote", "vote"]),
        ("insert", [1, 3]),
        ("lookup", [2, 3, 1, 0], ["vote", "ext", "vote", "ext"])]),
    "duplicates-in-a-batch": (8, [
        ("lookup", [0, 0, 1], "blocksync"),
        ("insert", [0, 0, 1]),
        ("lookup", [1, 1, 0], "blocksync")]),
    "capacity-zero": (0, [
        ("lookup", [0, 1], "vote"),
        ("insert", [0, 1]),
        ("lookup", [0, 1], ["vote", "commit"])]),
}


def _play(script, capacity, batched):
    """Runs a script through `lookup`/`insert` or lane by lane through
    `seen`/`add`; returns what each lookup answered, the cache and its
    metrics."""
    metrics = PipelineMetrics(Registry())
    c = SigCache(capacity=capacity, metrics=metrics)
    keys, answers = {}, []
    for op, lanes, *path in script:
        if op == "lookup":
            paths = path[0] if isinstance(path[0], list) \
                else [path[0]] * len(lanes)
            if batched:
                got, hits = c.lookup([_lane(i) for i in lanes], path[0])
                keys.update(zip(lanes, got))
            else:
                hits = [c.seen(*_lane(i), path=p)
                        for i, p in zip(lanes, paths)]
            answers.append(hits)
        elif batched:
            c.insert([keys[i] for i in lanes])
        else:
            for i in lanes:
                c.add(*_lane(i))
    return answers, keys, c, metrics


@pytest.mark.parametrize("name", sorted(_CACHE_SCRIPTS))
def test_cache_batch_ops_equal_lane_by_lane(name):
    """`lookup` and `insert` leave what the same lanes through `seen`
    and `add` one at a time leave: answers, per-path hits and misses,
    LRU order, evictions and metric increments; and the keys are
    `SigCache.key`'s."""
    capacity, script = _CACHE_SCRIPTS[name]
    answers, keys, c, m = _play(script, capacity, batched=True)
    want_answers, _none, ref, ref_m = _play(script, capacity, batched=False)
    assert keys and all(k == SigCache.key(*_lane(i))
                        for i, k in keys.items())
    assert answers == want_answers
    with c._lock, ref._lock:
        assert list(c._entries) == list(ref._entries)
        assert (c.hits, c.misses, c.evictions) == \
            (ref.hits, ref.misses, ref.evictions)
        assert len(c._entries) <= max(capacity, 0)
    assert m.cache_evictions.value() == ref_m.cache_evictions.value()
    paths = {p for _op, _l, *path in script for p in
             (path[0] if path and isinstance(path[0], list) else path)}
    for p in paths:
        assert m.cache_hits.value(path=p) == ref_m.cache_hits.value(path=p)
        assert m.cache_misses.value(path=p) == \
            ref_m.cache_misses.value(path=p)
    inserted = sum(len(lanes) for op, lanes, *_p in script
                   if op == "insert") if capacity > 0 else 0
    assert c.insert_counts() == (inserted, inserted)
    assert ref.insert_counts() == (inserted, 0)
    if capacity == 0:   # always miss, never store, count nothing
        assert not any(h for a in answers for h in a)
        assert len(c) == 0 and c.hits == c.misses == {}


def _only_true_lanes_cached(cache, good, bad):
    """Every true lane findable under the key its lookup computes, the
    false one not, nothing else stored, and every insert keyed."""
    assert len(cache) == len(good)
    assert all(cache.seen(*t) for t in good)
    assert not cache.seen(*bad)
    assert cache.insert_counts() == (len(good), len(good))


def _tampered(sig: bytes) -> bytes:
    return bytes([sig[0] ^ 1]) + sig[1:]


def test_settle_inserts_true_lanes_never_the_false_one():
    """A tile with one false lane among true ones: settle inserts the
    true lanes, the false lane's commit's too, each on its own verdict
    and with the key marshal looked it up with."""
    from cometbft_tpu.engine.blocksync import TileEntry
    from cometbft_tpu.types.block import Commit, CommitSig
    cache = SigCache(capacity=1024)
    v = TiledCommitVerifier(CHAIN.chain_id, batch_size=0, cache=cache)
    entries, good, bad = [], [], None
    for h in (1, 2, 3):
        commit, vals = CHAIN.seen_commits[h - 1], CHAIN.valsets[h - 1]
        if h == 2:
            sigs = [CommitSig(cs.block_id_flag, cs.validator_address,
                              cs.timestamp, _tampered(cs.signature))
                    if i == 1 else cs
                    for i, cs in enumerate(commit.signatures)]
            commit = Commit(commit.height, commit.round, commit.block_id,
                            sigs)
        for i, cs in enumerate(commit.signatures):
            t = (vals.get_by_index(i).pub_key.bytes_(),
                 commit.vote_sign_bytes(CHAIN.chain_id, i), cs.signature)
            if (h, i) == (2, 1):
                bad = t
            else:
                good.append(t)
        entries.append(TileEntry(
            height=h, block=CHAIN.blocks[h - 1],
            block_id=CHAIN.block_ids[h - 1], valset=vals, commit=commit))
    v.verify_tile(entries)
    assert [e.commit_ok for e in entries] == [True, False, True]
    _only_true_lanes_cached(cache, good, bad)


def test_a_pipelined_catch_up_inserts_every_lane_with_its_key():
    cache = SigCache(capacity=1024)
    state, _r, _s, _a = _sync(CHAIN, depth=4, cache=cache)
    assert state.last_block_height == 12
    inserted, keyed = cache.insert_counts()
    assert inserted == keyed == len(cache) > 0


class _NativeBatch:
    """A `crypto.batch` verifier that answers natively."""

    def __init__(self):
        self.lanes = []

    def add(self, pk, msg, sig):
        self.lanes.append((pk, msg, sig))

    def verify(self):
        oks = [pk.verify_signature(m, s) for pk, m, s in self.lanes]
        return all(oks), oks


@pytest.mark.parametrize("route", ["native", "batch"])
def test_commit_verify_inserts_true_lanes_never_the_false_one(monkeypatch,
                                                              route):
    """Both routes of commit verification with one false lane among
    true ones: the native route inserts what verified before it and
    names it, the batch route every true lane; never the false one."""
    import cometbft_tpu.pipeline.cache as pc
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.types import validation
    from cometbft_tpu.types.block import Commit, CommitSig
    fresh = SigCache(capacity=256)
    monkeypatch.setattr(pc, "_shared", fresh)
    if route == "batch":
        monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 2)
        monkeypatch.setattr(crypto_batch, "create_batch_verifier",
                            lambda pk: (_NativeBatch(), True))
    commit, vals = CHAIN.seen_commits[2], CHAIN.valsets[2]
    sigs = [CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp,
                      _tampered(cs.signature)) if i == 1 else cs
            for i, cs in enumerate(commit.signatures)]
    commit = Commit(commit.height, commit.round, commit.block_id, sigs)
    lanes = [(vals.get_by_index(i).pub_key.bytes_(),
              commit.vote_sign_bytes(CHAIN.chain_id, i), cs.signature)
             for i, cs in enumerate(commit.signatures)]
    with pytest.raises(validation.ErrWrongSignature, match=r"\(#1\)"):
        validation.verify_commit(CHAIN.chain_id, vals, commit.block_id, 3,
                                 commit)
    # the native route stops at the false lane, the batch route does not
    good = lanes[:1] if route == "native" else lanes[:1] + lanes[2:]
    _only_true_lanes_cached(fresh, good, lanes[1])


def test_tile_cache_skips_device_lanes_same_verdicts():
    """A warm cache marshals ZERO device lanes and still reproduces the
    exact per-commit verdicts (including structural/negative ones)."""
    from cometbft_tpu.engine.blocksync import TileEntry
    cache = SigCache(capacity=1024)
    v = TiledCommitVerifier(CHAIN.chain_id, batch_size=0, cache=cache)

    def entries():
        out = []
        for h in (1, 2, 3):
            blk = CHAIN.blocks[h - 1]
            out.append(TileEntry(
                height=h, block=blk, block_id=CHAIN.block_ids[h - 1],
                valset=CHAIN.valsets[h - 1],
                commit=CHAIN.seen_commits[h - 1]))
        return out

    first = entries()
    v.verify_tile(first)
    assert all(e.commit_ok for e in first)
    n_sigs = sum(len(c.signatures) for c in CHAIN.seen_commits[:3])
    assert cache.misses.get("blocksync") == n_sigs

    second = entries()
    pubs, msgs, sigs = [], [], []
    metas = [marshal_commit(v.chain_id, e, pubs, msgs, sigs, v.cache)
             for e in second]
    assert pubs == [] and all(rows for _e, rows, _n in metas)
    v.verify_tile(entries())  # end-to-end warm pass
    assert cache.hits.get("blocksync") >= 2 * n_sigs


def test_cache_never_stores_failed_signatures():
    cache = SigCache(capacity=1024)
    src = LocalChainSource(CHAIN, corrupt_heights={7: "sig"})
    state, _r, _s, _a = _sync(CHAIN, depth=4, src=src, cache=cache)
    assert state.last_block_height == 12
    # the corrupted sig bytes must not be cached: re-presenting them
    # must miss
    bad = src.chain.seen_commits[5]
    # (the corruption flips a bit of sig[0] of commit sealing height 6)
    sig = bytes([bad.signatures[0].signature[0] ^ 1]) \
        + bad.signatures[0].signature[1:]
    vals = CHAIN.valsets[5]
    pk = vals.get_by_index(0).pub_key.bytes_()
    msg = bad.vote_sign_bytes(CHAIN.chain_id, 0)
    assert not cache.seen(pk, msg, sig)


def test_vote_intake_uses_shared_cache(monkeypatch):
    import cometbft_tpu.pipeline.cache as pc
    from cometbft_tpu.types.vote_set import VoteSet
    from cometbft_tpu.types.vote import PRECOMMIT_TYPE
    fresh = SigCache(capacity=256)
    monkeypatch.setattr(pc, "_shared", fresh)

    chain = CHAIN
    commit = chain.seen_commits[0]
    vals = chain.valsets[0]

    def votes():
        from cometbft_tpu.types.vote import Vote
        out = []
        for i, cs in enumerate(commit.signatures):
            v = Vote(type_=PRECOMMIT_TYPE, height=1, round=0,
                     block_id=commit.block_id, timestamp=cs.timestamp,
                     validator_address=cs.validator_address,
                     validator_index=i)
            v.signature = cs.signature
            out.append(v)
        return out

    vs = VoteSet(chain.chain_id, 1, 0, PRECOMMIT_TYPE, vals)
    for v in votes():
        assert vs.add_vote(v)
    assert fresh.misses.get("vote") == 4
    # a re-gossiped burst into a FRESH VoteSet hits the cache
    vs2 = VoteSet(chain.chain_id, 1, 0, PRECOMMIT_TYPE, vals)
    for v in votes():
        assert vs2.add_vote(v)
    assert fresh.hits.get("vote") == 4
    assert vs2.two_thirds_majority() == commit.block_id


def test_light_commit_verify_uses_shared_cache(monkeypatch):
    import cometbft_tpu.pipeline.cache as pc
    from cometbft_tpu.types import validation
    fresh = SigCache(capacity=256)
    monkeypatch.setattr(pc, "_shared", fresh)

    commit = CHAIN.seen_commits[2]
    vals = CHAIN.valsets[2]
    validation.verify_commit_light(CHAIN.chain_id, vals, commit.block_id,
                                   3, commit, count_all=True)
    assert fresh.misses.get("commit") == 4 and not fresh.hits
    # the light client re-verifying the same commit is all hits
    validation.verify_commit(CHAIN.chain_id, vals, commit.block_id, 3,
                             commit)
    assert fresh.hits.get("commit") == 4


# --- metrics + occupancy -----------------------------------------------------

def test_pipeline_metrics_populated_during_sync():
    reg = Registry()
    metrics = PipelineMetrics(reg)
    state, _r, _s, _a = _sync(CHAIN, depth=3, metrics=metrics,
                              backend=FixedLatencyBackend(0.001))
    assert state.last_block_height == 12
    assert metrics.tiles_dispatched.value() >= 3
    assert metrics.tiles_in_flight.value() == 0  # drained at exit
    text = reg.expose()
    assert "pipeline_tiles_dispatched" in text
    assert 'pipeline_stage_occupancy{stage="dispatch"}' in text


# --- engine/pool satellites --------------------------------------------------

def test_blockpool_pop_timeout_is_constructor_param():
    import time
    from cometbft_tpu.engine.pool import BlockPool
    pool = BlockPool(lambda h: None, lambda: 0, start_height=1,
                     pop_timeout=0.05, n_workers=1)
    t0 = time.monotonic()
    assert pool.pop(99) is None
    assert time.monotonic() - t0 < 2.0
    pool.stop()


def test_pooled_source_reports_pending_fetches():
    import threading
    from cometbft_tpu.engine.pool import PooledSource
    gate = threading.Event()

    class SlowInner:
        def max_height(self):
            return 4

        def fetch(self, height):
            gate.wait(2.0)
            return None

        def ban(self, height):
            pass

    ps = PooledSource(SlowInner(), start_height=1, lookahead=4,
                      n_workers=1, pop_timeout=0.05)
    assert ps.fetch(1) is None  # times out fast (constructor param)
    assert ps.pending_fetches() >= 1
    gate.set()
    ps.stop()


# --- slow depth-sweep soak (run_suite.sh) ------------------------------------

@pytest.mark.slow
def test_depth_sweep_soak():
    """K in {1,2,4,8} over clean, tampered, and valset-change chains
    against a realistic (verdict-computing) fixed-latency stub device:
    every depth produces the synchronous verdicts and final state."""
    from cometbft_tpu.engine.blocksync import verify_lanes
    chain_v, _ = _valset_change_chain()
    cases = [
        ("clean", CHAIN, None),
        ("sig", CHAIN, {7: "sig"}),
        ("data", CHAIN, {5: "data"}),
        ("valset", chain_v, None),
    ]
    for name, chain, corrupt in cases:
        ref = None
        for depth in (1, 2, 4, 8):
            src = LocalChainSource(
                chain, corrupt_heights=dict(corrupt) if corrupt else None)
            backend = FixedLatencyBackend(
                0.005, verify_fn=lambda p, m, s: verify_lanes(p, m, s, 0))
            state, _r, src, app = _sync(chain, depth=depth, src=src,
                                        backend=backend)
            got = (state.last_block_height, state.app_hash,
                   sorted(set(src.banned)) != [] if corrupt else False,
                   app.state)
            if ref is None:
                ref = got
            assert got == ref, (name, depth)


@pytest.mark.slow
def test_pipeline_overlaps_device_latency():
    """With device latency ~ tile host time, depth 4 must be well
    faster than depth 1 (the whole point of the subsystem). Generous
    margins: stub latency dominates host work on this chain size."""
    import time
    chain = generate_chain(n_blocks=24, n_validators=4, txs_per_block=1)

    def run(depth):
        t0 = time.perf_counter()
        state, _r, _s, _a = _sync(chain, depth=depth, tile=4,
                                  backend=FixedLatencyBackend(0.12))
        assert state.last_block_height == 24
        return time.perf_counter() - t0

    t_sync = run(1)
    t_pipe = run(4)
    assert t_pipe < t_sync / 1.5, (t_sync, t_pipe)
