"""The counters and spans that PR 29 put on the paths a validator-set
change and a lying peer take (engine/blocksync.py `SyncStats`,
pipeline/scheduler.py `pipeline.barrier` / `pipeline.ban`,
ops/ed25519.py `batch_stats`): what each counts, on both tile loops,
and that a sync with tracing off opens nothing."""

import numpy as np
import pytest

from cometbft_tpu import trace as program_trace
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.engine.blocksync import BlocksyncReactor
from cometbft_tpu.engine.chain_gen import LocalChainSource, generate_chain
from cometbft_tpu.ops import ed25519 as e5
from cometbft_tpu.state.execution import BlockExecutor, BlockValidationError
from cometbft_tpu.state.state import State, StateStore
from cometbft_tpu.store.blockstore import BlockStore

pytestmark = pytest.mark.pipeline

NEW_KEY = Ed25519PrivKey(b"\x29" * 32)
# the set changes at height 6 (a fifth validator joins): tiles of 4 break
# in their second height
CHAIN = generate_chain(
    n_blocks=12, n_validators=4, seed=29, extra_keys=[NEW_KEY],
    val_tx_heights={4: b"val:" + NEW_KEY.pub_key().bytes_().hex().encode()
                    + b"!10"})


def _sync(depth, src=None, max_retries=3):
    app = KVStoreApplication()
    app.init_chain(CHAIN.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    executor = BlockExecutor(app, state_store=StateStore(db),
                             block_store=store)
    reactor = BlocksyncReactor(
        executor, store, src or LocalChainSource(CHAIN), CHAIN.chain_id,
        tile_size=4, batch_size=64, max_retries=max_retries,
        pipeline_depth=depth)
    return reactor, reactor.sync(State.from_genesis(CHAIN.genesis))


def _traced(fn):
    program_trace.enable(seed=0, ring=1 << 12)
    try:
        out = fn()
        return out, program_trace.shared_recorder().snapshot()
    finally:
        program_trace.disable()


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.mark.parametrize("depth", [1, 4])
def test_respeculated_sigs_are_the_lanes_of_the_synchronous_route(depth):
    (reactor, state), spans = _traced(lambda: _sync(depth))
    st = reactor.stats
    assert state.last_block_height == 12 and st.bans == 0
    # heights 6, 7, 8: the rest of the tile 5-8 that the change broke,
    # five lanes each
    respec = _named(spans, "pipeline.respeculate")
    assert [s["attrs"]["height"] for s in respec] == [6, 7, 8]
    assert st.respeculations == 3
    assert st.respeculated_sigs == sum(s["attrs"]["lanes"]
                                       for s in respec) == 15
    # every commit was given a verdict once, by a tile or by that route
    assert st.sigs_verified + st.respeculated_sigs == 5 * 4 + 7 * 5
    barriers = _named(spans, "pipeline.barrier")
    if depth == 1:
        assert barriers == []       # the synchronous loop has no pipeline
    else:
        (b,) = barriers
        assert b["attrs"] == {"change_height": 6, "tiles_drained": 2}
        # it covers the synchronous route of that change
        assert b["t0"] <= respec[0]["t0"] and respec[-1]["t1"] <= b["t1"]
    assert _named(spans, "pipeline.ban") == []


@pytest.mark.parametrize("depth", [1, 4])
def test_a_ban_is_counted_and_its_span_ends_at_the_refetch(depth):
    src = LocalChainSource(CHAIN, corrupt_heights={11: "sig"})
    (reactor, state), spans = _traced(lambda: _sync(depth, src))
    assert state.last_block_height == 12
    assert reactor.stats.bans == len(src.banned) == 1
    bans = _named(spans, "pipeline.ban")
    if depth == 1:
        assert bans == []
    else:
        (b,) = bans
        assert b["attrs"]["height"] == b["attrs"]["refetched"] == 10
        assert b["attrs"]["tiles_cancelled"] == 0       # 9-12 is the last
        assert "outcome" not in b["attrs"]


def test_a_sync_that_gives_up_closes_its_ban_span():
    class Stubborn(LocalChainSource):
        def ban(self, height):
            self.banned.append(height)      # and goes on lying

    src = Stubborn(CHAIN, corrupt_heights={3: "sig"})

    def refused():
        with pytest.raises(BlockValidationError):
            _sync(4, src, max_retries=2)
    _none, spans = _traced(refused)
    bans = _named(spans, "pipeline.ban")
    assert [b["attrs"]["height"] for b in bans] == [2]
    assert bans[0]["attrs"]["outcome"] == "gave-up"
    # in flight behind the bad tile: 5-8, which speculated only height 5
    # (the change at 6 broke it and stopped the filling)
    assert bans[0]["attrs"]["tiles_cancelled"] == 1
    assert bans[0]["attrs"]["lanes_abandoned"] == 4
    (cut,) = [b for b in _named(spans, "pipeline.barrier")
              if b["attrs"].get("outcome") == "cut-short"][:1]
    assert cut["attrs"]["change_height"] == 6
    # the pass that applied height 1, then one refused pass a retry
    assert src.banned == [2] * 4


def test_a_ban_ends_the_spans_of_the_tiles_it_abandons():
    """The bad tile 1-4 is applied up to its bad height while 5-8 is in
    flight behind it: the ban throws 5-8 away, and its `pipeline.tile`
    span ends with `outcome` = abandoned; every tile span built ends."""
    src = LocalChainSource(CHAIN, corrupt_heights={3: "sig"})
    (reactor, state), spans = _traced(lambda: _sync(4, src))
    assert state.last_block_height == 12 and reactor.stats.bans == 1
    (ban,) = _named(spans, "pipeline.ban")
    tiles = _named(spans, "pipeline.tile")
    thrown = [t for t in tiles if t["attrs"].get("outcome") == "abandoned"]
    assert [t["attrs"]["start"] for t in thrown] == [5]
    assert len(thrown) == ban["attrs"]["tiles_cancelled"] == 1
    assert len({t["sid"] for t in tiles}) == len(tiles)
    # the tiles built (every one ends, settled or thrown away) are the
    # tile spans, which allocate their ids in that order
    starts = [t["attrs"]["start"] for t in sorted(tiles,
                                                  key=lambda t: t["sid"])]
    assert starts[:2] == [1, 5] and starts[-1] <= 12


def test_tracing_off_opens_nothing():
    program_trace.disable()
    reactor, state = _sync(4)
    assert state.last_block_height == 12
    assert reactor.stats.respeculated_sigs == 15
    assert program_trace.shared_recorder().snapshot() == []


def test_batch_stats_count_chunks_lanes_and_attribution():
    """`_verify_batch_loop` with the kernels stubbed: 3 lanes a bucket,
    8 signatures, the second chunk's RLC equation fails."""
    pub, sig, msg = e5._dummy()
    calls = []

    def dispatch(pub_a, sig_a, hb, hn, z):
        calls.append("rlc")
        return len(calls) != 2, np.ones(3, dtype=bool)

    def fallback(pub_a, sig_a, hb, hn):
        calls.append("per-lane")
        return np.array([True, False, True])

    before = e5.batch_stats()
    out = e5._verify_batch_loop([pub] * 8, [msg] * 8, [sig] * 8, 3,
                                dispatch, fallback)
    after = e5.batch_stats()
    # dispatch all, then read back: attribution comes after the last
    assert calls == ["rlc", "rlc", "rlc", "per-lane"]
    assert list(out) == [True] * 4 + [False] + [True] * 3
    # the dummy's 21-byte message needs one SHA-512 block; its chunks'
    # block axis is the 2 of every vote-sized message, for each of a
    # chunk's 3 lanes, padding in
    assert {k: after[k] - before[k] for k in after} == {
        "chunks": 3, "lanes": 8, "attributed_chunks": 1,
        "attributed_lanes": 3, "hash_blocks_real": 8,
        "hash_blocks_dispatched": 18, "cold_shape_lanes": 0}
    # strict mode has no RLC pass: its chunks are no attribution
    e5._verify_batch_loop([pub] * 2, [msg] * 2, [sig] * 2, 3, None,
                          lambda *a: np.ones(3, dtype=bool))
    strict = e5.batch_stats()
    assert strict["chunks"] - after["chunks"] == 1
    assert strict["attributed_chunks"] == after["attributed_chunks"]
