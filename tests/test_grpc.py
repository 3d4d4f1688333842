"""gRPC surfaces: ABCI gRPC server/client (reference
abci/server/grpc_server.go, abci/client/grpc_client.go), a node running
against an external gRPC app, and the companion services —
VersionService, BlockService (incl. the GetLatestHeight stream),
BlockResultsService, and the privileged PruningService (reference
rpc/grpc/server, rpc/grpc/server/privileged,
proto/cometbft/services/*/v1)."""

import os
import threading
import time

import pytest

from cometbft_tpu.abci.application import RequestFinalizeBlock
from cometbft_tpu.abci.grpc import GRPCClient, GRPCServer
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.config import Config, ConsensusTimeoutsConfig
from cometbft_tpu.node.node import Node, save_genesis
from cometbft_tpu.privval.file import FilePV
from cometbft_tpu.state.state import GenesisDoc
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.validator import Validator


# --- ABCI over gRPC ---------------------------------------------------------


def test_abci_grpc_roundtrip_all_methods():
    """Every ABCIService method crosses the wire and returns the same
    shapes the in-process app produces (reference
    abci/client/grpc_client_test.go)."""
    app = KVStoreApplication()
    srv = GRPCServer(app)
    srv.start()
    c = GRPCClient(*srv.addr)
    try:
        assert c.echo("ping") == "ping"
        info = c.info()
        assert info.last_block_height == 0
        _updates, app_hash = c.init_chain("grpc-chain", 1, [], b"")
        assert isinstance(app_hash, bytes)
        r = c.check_tx(b"a=1")
        assert r.code == 0
        txs = c.prepare_proposal([b"a=1", b"b=2"], 1 << 20)
        assert txs == [b"a=1", b"b=2"]
        assert c.process_proposal(txs, 1)
        fr = c.finalize_block(RequestFinalizeBlock(
            txs=[b"a=1"], height=1, time=Timestamp(1, 0),
            proposer_address=b"\0" * 20, hash=b"\1" * 32,
            next_validators_hash=b"\2" * 32))
        assert fr.tx_results[0].code == 0
        c.commit()
        code, val = c.query("/store", b"a")
        assert (code, val) == (0, b"1")
        # query_prove answers from the PREVIOUS committed snapshot
        # (absence provable there) — only the wire shape matters here
        code, _val, _height, _proof = c.query_prove("/store", b"a")
        assert code == 0
        ext = c.extend_vote(1, 0)
        assert c.verify_vote_extension(1, b"\0" * 20, ext)
        assert c.list_snapshots() == []
    finally:
        c.close()
        srv.stop()


def test_abci_grpc_app_error_is_grpc_status():
    """An app exception surfaces as a ConnectionError (INTERNAL status),
    not a hung or silently-dropped call."""
    class Boom(KVStoreApplication):
        def query(self, path, data):
            raise RuntimeError("boom")

    srv = GRPCServer(Boom())
    srv.start()
    c = GRPCClient(*srv.addr)
    try:
        with pytest.raises(ConnectionError, match="boom"):
            c.query("/store", b"x")
        # the channel survives the error
        assert c.echo("still-up") == "still-up"
    finally:
        c.close()
        srv.stop()


def test_grpc_client_connect_timeout():
    with pytest.raises(ConnectionError):
        GRPCClient("127.0.0.1", 1, connect_retry_s=0.5)


@pytest.mark.slow
def test_node_with_remote_grpc_app(tmp_path):
    """[base] proxy_app = grpc://host:port runs the node against an
    external ABCI app over gRPC (reference commands/run_node.go
    --abci grpc): consensus, queries, and snapshots all ride the
    channel."""
    app = KVStoreApplication()
    srv = GRPCServer(app)
    srv.start()
    node = None
    try:
        pv = FilePV.generate(None)
        gen = GenesisDoc(chain_id="grpc-app",
                         genesis_time=Timestamp.now(),
                         validators=[Validator(pv.get_pub_key(), 10)])
        root = tmp_path / "grpcnode"
        os.makedirs(root / "config", exist_ok=True)
        cfg = Config(root_dir=str(root))
        cfg.base.db_backend = "memdb"
        cfg.base.proxy_app = f"grpc://127.0.0.1:{srv.addr[1]}"
        cfg.consensus = ConsensusTimeoutsConfig(
            timeout_propose=500, timeout_propose_delta=250,
            timeout_prevote=250, timeout_prevote_delta=150,
            timeout_precommit=250, timeout_precommit_delta=150,
            timeout_commit=50, wal_file="data/cs.wal")
        save_genesis(gen, str(root / "config/genesis.json"))
        node = Node(cfg, priv_validator=pv, genesis=gen)
        node.mempool.check_tx(b"grpc=app")
        node.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if node.consensus.state.last_block_height >= 3 and \
                    app.query("/store", b"grpc")[1] == b"app":
                break
            time.sleep(0.05)
        else:
            raise TimeoutError(
                f"stuck at {node.consensus.state.last_block_height}")
        code, val = node.app_conns.query.query("/store", b"grpc")
        assert val == b"app"
    finally:
        if node is not None:
            node.stop()
        srv.stop()


# --- companion services -----------------------------------------------------


def _make_node(tmp_path, name, grpc=True, privileged=True):
    pv = FilePV.generate(None)
    gen = GenesisDoc(chain_id=f"{name}-chain",
                     genesis_time=Timestamp.now(),
                     validators=[Validator(pv.get_pub_key(), 10)])
    root = tmp_path / name
    os.makedirs(root / "config", exist_ok=True)
    cfg = Config(root_dir=str(root))
    cfg.base.db_backend = "memdb"
    cfg.consensus = ConsensusTimeoutsConfig(
        timeout_propose=500, timeout_propose_delta=250,
        timeout_prevote=250, timeout_prevote_delta=150,
        timeout_precommit=250, timeout_precommit_delta=150,
        timeout_commit=50, wal_file="data/cs.wal")
    if grpc:
        cfg.grpc.laddr = "127.0.0.1:0"
    if privileged:
        cfg.grpc.privileged_laddr = "127.0.0.1:0"
        cfg.grpc.pruning_service = True
    cfg.storage.pruning_interval_ms = 100
    save_genesis(gen, str(root / "config/genesis.json"))
    return Node(cfg, priv_validator=pv, genesis=gen)


def _wait_height(node, h, timeout=120):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if node.consensus.state.last_block_height >= h:
            return
        time.sleep(0.05)
    raise TimeoutError(
        f"stuck at {node.consensus.state.last_block_height} < {h}")


@pytest.mark.slow
def test_grpc_services_and_pruning(tmp_path):
    """One live node exercises the whole companion surface: GetVersion,
    GetByHeight, the GetLatestHeight stream, GetBlockResults, and the
    privileged pruning APIs actually pruning the stores."""
    from cometbft_tpu import __version__
    from cometbft_tpu.rpc.grpc import GRPCServiceClient

    node = _make_node(tmp_path, "svc")
    try:
        node.mempool.check_tx(b"svc=1")
        node.start()
        _wait_height(node, 4)
        client = GRPCServiceClient(*node.grpc_addr)
        priv = GRPCServiceClient(*node.grpc_priv_addr)
        try:
            # VersionService
            v = client.get_version()
            assert v["node"] == __version__
            assert v["abci"] and v["p2p"] and v["block"]

            # BlockService.GetByHeight (+ latest default)
            b2 = client.get_block_by_height(2)
            assert b2["block"]["header"]["height"] == 2
            latest = client.get_block_by_height()
            assert latest["block"]["header"]["height"] >= 2

            # BlockService.GetLatestHeight stream: collect two commits
            got = []
            stream = client.get_latest_height_stream()

            def drain():
                for msg in stream:
                    got.append(msg["height"])
                    if len(got) >= 2:
                        return
            t = threading.Thread(target=drain, daemon=True)
            t.start()
            t.join(timeout=60)
            stream.cancel()
            assert len(got) >= 2 and got[1] > got[0]

            # BlockResultsService
            r = client.get_block_results(2)
            assert r["height"] == 2
            # an out-of-range height is INVALID_ARGUMENT, not a hang
            import grpc as grpc_mod
            try:
                client.get_block_results(10_000)
                raise AssertionError("expected INVALID_ARGUMENT")
            except grpc_mod.RpcError as e:
                assert e.code() == \
                    grpc_mod.StatusCode.INVALID_ARGUMENT

            # privileged PruningService: retain heights round-trip and
            # the pruner applies them
            _wait_height(node, 5)
            priv.pruning("SetBlockRetainHeight", height=3)
            rh = priv.pruning("GetBlockRetainHeight")
            assert rh["pruning_service_retain_height"] == 3
            priv.pruning("SetBlockResultsRetainHeight", height=3)
            assert priv.pruning("GetBlockResultsRetainHeight")[
                "pruning_service_retain_height"] == 3
            priv.pruning("SetTxIndexerRetainHeight", height=3)
            assert priv.pruning("GetTxIndexerRetainHeight")[
                "height"] == 3
            priv.pruning("SetBlockIndexerRetainHeight", height=3)
            assert priv.pruning("GetBlockIndexerRetainHeight")[
                "height"] == 3

            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and (
                    node.block_store.base() < 3
                    or node.state_store.load_finalize_block_response(1)
                    is not None):
                time.sleep(0.1)
            assert node.block_store.base() == 3
            assert node.state_store.load_finalize_block_response(1) \
                is None

            # setting a retain height beyond the tip is rejected
            try:
                priv.pruning("SetBlockRetainHeight", height=10_000)
                raise AssertionError("expected INVALID_ARGUMENT")
            except grpc_mod.RpcError as e:
                assert e.code() == \
                    grpc_mod.StatusCode.INVALID_ARGUMENT
        finally:
            client.close()
            priv.close()
    finally:
        node.stop()


def test_indexer_prune_unit():
    """TxIndexer/BlockIndexer.prune delete records+postings strictly
    below the retain height and keep the rest searchable."""
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.indexer.kv import BlockIndexer, TxIndexer
    from cometbft_tpu.pubsub.query import Query
    from cometbft_tpu.types.block import tx_hash

    class _Res:
        code = 0

    txi = TxIndexer(MemDB())
    for h in (1, 2, 3):
        txi.index(h, 0, b"tx%d" % h, _Res(),
                  {"tx.height": [str(h)], "app.key": ["k"]})
    assert txi.prune(3) > 0
    assert txi.get(tx_hash(b"tx1")) is None
    assert txi.get(tx_hash(b"tx2")) is None
    assert txi.get(tx_hash(b"tx3")) is not None
    assert txi.search(Query("app.key = 'k'")) == [tx_hash(b"tx3")]

    bi = BlockIndexer(MemDB())
    for h in (1, 2, 3):
        bi.index(h, {"block.height": [str(h)]})
    assert bi.prune(3) == 2
    assert bi.search(Query("block.height >= 1")) == [3]


def test_grpc_config_validation_and_roundtrip():
    cfg = Config()
    cfg.grpc.laddr = "127.0.0.1:26670"
    cfg.grpc.privileged_laddr = "127.0.0.1:26671"
    cfg.grpc.pruning_service = True
    text = cfg.to_toml()
    assert "[grpc]" in text
    back = Config.from_toml(text)
    assert back.grpc.laddr == "127.0.0.1:26670"
    assert back.grpc.pruning_service is True

    bad = Config()
    bad.grpc.pruning_service = True     # no privileged_laddr
    with pytest.raises(ValueError):
        bad.validate_basic()
