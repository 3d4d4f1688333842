"""The pipelined catch-up's `pipeline.apply` span and the CommitSig and
validator-set encodings its `pipeline.fetch` and `pipeline.apply` spans
carry (pipeline/scheduler.py `_host_stage_span`): one apply span a
tile, parented on the tile's span, over a whole sync every signature
encoded once and met three times more, and of a block's four
validator-set encodings one computed. 8 validators in 4-block tiles on
the in-process backend: 32 lanes a tile take the native route on a CPU,
so nothing is jitted."""

import pickle

import pytest

from cometbft_tpu import trace
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.engine.blocksync import BlocksyncReactor
from cometbft_tpu.engine.chain_gen import LocalChainSource, generate_chain
from cometbft_tpu.state.execution import BlockExecutor, BlockValidationError
from cometbft_tpu.state.state import State, StateStore
from cometbft_tpu.store.blockstore import BlockStore

pytestmark = pytest.mark.pipeline

BLOCKS, VALIDATORS, TILE = 12, 8, 4
STAGES = ("pipeline.fetch", "pipeline.apply")


@pytest.fixture(scope="module")
def pickled_chain():
    return pickle.dumps(generate_chain(
        n_blocks=BLOCKS, n_validators=VALIDATORS, txs_per_block=2, seed=27))


@pytest.fixture
def chain(pickled_chain):
    # through a pickle, as the benchmark's chain arrives: every test
    # meets commits that nobody in this process has encoded, the
    # generator's memos having stayed behind
    return pickle.loads(pickled_chain)


def _sync(chain, depth, src=None, traced=True):
    """(state or None if the sync was refused, reactor, recorded spans)"""
    app = KVStoreApplication()
    app.init_chain(chain.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    executor = BlockExecutor(app, state_store=StateStore(db),
                             block_store=store)
    reactor = BlocksyncReactor(
        executor, store, src or LocalChainSource(chain), chain.chain_id,
        tile_size=TILE, batch_size=64, pipeline_depth=depth)
    if traced:
        trace.enable(seed=0)
    try:
        try:
            state = reactor.sync(State.from_genesis(chain.genesis))
        except BlockValidationError:
            state = None
        return state, reactor, trace.shared_recorder().snapshot()
    finally:
        trace.disable()


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _encodings(spans, key):
    return sum(s["attrs"][key] for s in spans if s["name"] in STAGES)


def test_one_apply_span_a_tile_under_the_tiles_span(chain):
    state, reactor, spans = _sync(chain, depth=4)
    assert state.last_block_height == BLOCKS
    tiles = {s["sid"]: s for s in _named(spans, "pipeline.tile")}
    applies = _named(spans, "pipeline.apply")
    assert len(applies) == len(tiles) == BLOCKS // TILE
    assert sorted(a["pid"] for a in applies) == sorted(tiles)
    for a in applies:
        tile = tiles[a["pid"]]
        assert a["tid"] == tile["tid"]
        assert a["t0"] >= tile["t1"] and a["t1"] >= a["t0"]
    # every stage of a tile is still there, once
    for name in ("pipeline.fetch", "pipeline.marshal", "pipeline.settle"):
        assert sorted(s["pid"] for s in _named(spans, name)) == sorted(tiles)


def test_every_signature_is_encoded_once_and_met_three_times_more(chain):
    _state, reactor, spans = _sync(chain, depth=4)
    served = BLOCKS * VALIDATORS
    assert reactor.stats.sigs_verified == served
    for s in spans:
        if s["name"] in STAGES:
            assert set(s["attrs"]) >= {"sig_enc_computed", "sig_enc_reused"}
    # the node paid every first encoding itself ...
    assert _encodings(spans, "sig_enc_computed") == served
    # ... and the commit that seals the tip is only stored (`SC:`), never
    # a block's last_commit: three reuses for every other one
    assert _encodings(spans, "sig_enc_reused") == 3 * (served - VALIDATORS)
    # with the next tiles fetched ahead, fetch pays every first encoding
    # but the tip's seal's, which no fetched block carries as its parts
    assert sum(s["attrs"]["sig_enc_computed"]
               for s in _named(spans, "pipeline.apply")) == VALIDATORS


def test_apply_asks_four_valset_encodings_a_block_and_computes_one(chain):
    _state, reactor, spans = _sync(chain, depth=4)
    for s in spans:
        if s["name"] in STAGES:
            assert set(s["attrs"]) >= {"valset_enc_computed",
                                       "valset_enc_reused"}
    # fetch saves no state
    assert all(s["attrs"]["valset_enc_computed"]
               == s["attrs"]["valset_enc_reused"] == 0
               for s in _named(spans, "pipeline.fetch"))
    applies = [s["attrs"] for s in _named(spans, "pipeline.apply")]
    # StateStore.save: the State's three sets and the `vals:` index
    assert [a["valset_enc_computed"] + a["valset_enc_reused"]
            for a in applies] == [4 * TILE] * (BLOCKS // TILE)
    # only next_validators, whose priorities rotated, is new; the first
    # save of a sync has nothing to reuse but `validators` itself
    assert [a["valset_enc_computed"] for a in applies] == \
        [TILE + 2] + [TILE] * (BLOCKS // TILE - 1)
    assert reactor.stats.blocks_applied == BLOCKS


def test_an_apply_that_fails_still_ends_its_span(chain):
    class Stubborn(LocalChainSource):
        def ban(self, height):
            self.banned.append(height)      # goes on serving the fault

    src = Stubborn(chain, corrupt_heights={6: "data"})
    state, reactor, spans = _sync(chain, depth=4, src=src)
    assert state is None and src.banned
    applies = _named(spans, "pipeline.apply")
    assert applies and all(a["t1"] >= a["t0"] > 0 for a in applies)
    assert all("sig_enc_reused" in a["attrs"]
               and "valset_enc_reused" in a["attrs"] for a in applies)


def test_the_synchronous_loop_keeps_its_own_spans(chain):
    state, _reactor, spans = _sync(chain, depth=1)
    assert state.last_block_height == BLOCKS
    assert len(_named(spans, "blocksync.apply")) == BLOCKS // TILE
    assert not _named(spans, "pipeline.apply")


def test_with_tracing_off_no_span_is_recorded(chain):
    state, _reactor, spans = _sync(chain, depth=4, traced=False)
    assert state.last_block_height == BLOCKS and spans == []


def test_marshal_builds_one_sign_bytes_template_a_commit(chain):
    _state, reactor, spans = _sync(chain, depth=4)
    marshals = _named(spans, "pipeline.marshal")
    assert len(marshals) == BLOCKS // TILE
    for s in marshals:
        # a tile's commits, each one's first lane building its template
        assert s["attrs"]["sign_bytes_templates"] == TILE
        assert s["attrs"]["sign_bytes_templated"] == TILE * (VALIDATORS - 1)
    assert reactor.stats.sigs_verified == sum(
        s["attrs"]["sign_bytes_templates"] + s["attrs"]["sign_bytes_templated"]
        for s in marshals)
