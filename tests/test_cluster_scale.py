"""Scale tests (VERDICT r4 item 6): a >=20-validator in-process net
committing blocks, and a 175-validator valset (the QA-testnet
configuration, docs/references/qa/CometBFT-QA-v1.md) through the
chain-gen + tiled blocksync pipeline."""

import pytest

from cluster import Cluster


@pytest.mark.slow
def test_twenty_validator_net_commits():
    """20 live consensus state machines over the in-process fabric
    (reference common_test's nets cap at 4; the QA story needs
    an order more — every vote set here tallies 20 signatures)."""
    c = Cluster(20)
    try:
        c.start()
        c.wait_for_height(3, timeout=300)
        for h in range(1, 4):
            hashes = {n.block_store.load_block(h).hash() for n in c.nodes}
            assert len(hashes) == 1, f"fork at height {h}"
    finally:
        c.stop()


@pytest.mark.slow
def test_blocksync_at_qa_valset_scale():
    """Blocksync over a 175-validator chain (the QA baseline valset:
    175 validators per net, CometBFT-QA-v1.md) — the tile carries
    175 sigs/commit through the tiled verifier's marshalling path.
    Runs the native verify path (CPU platform; the device path is the
    benchmark's job on the chip — the catchup-200 cells)."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.engine.chain_gen import (
        LocalChainSource, generate_chain)
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore

    from cometbft_tpu.store.blockstore import BlockStore

    chain = generate_chain(n_blocks=4, n_validators=175)
    app = KVStoreApplication()
    app.init_chain(chain.chain_id, 1, [], b"")
    db = MemDB()
    executor = BlockExecutor(app, state_store=StateStore(db),
                             block_store=BlockStore(db))
    state = State.from_genesis(chain.genesis)
    reactor = BlocksyncReactor(
        executor, BlockStore(db), LocalChainSource(chain),
        chain.chain_id, tile_size=4, batch_size=0)  # 0 = native verify
    state = reactor.sync(state)
    assert state.last_block_height == 4
    assert reactor.stats.sigs_verified == 4 * 175
