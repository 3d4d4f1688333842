"""Full node assembly: a 4-validator network over real TCP (consensus
gossip through the Switch, encrypted links), txs in via JSON-RPC, state
out via abci_query — the e2e shape of test/e2e's ci testnet compressed
in-process (reference node/node_test.go, test/e2e)."""

import os
import time

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.config import (Config, ConsensusTimeoutsConfig)
from cometbft_tpu.node.node import Node, load_genesis, save_genesis
from cometbft_tpu.privval.file import FilePV
from cometbft_tpu.rpc.client import RPCClient
from cometbft_tpu.state.state import GenesisDoc
from cometbft_tpu.types.validator import Validator


def _make_net(tmp_path, n=4, timeout_commit=50, skip_timeout_commit=True):
    import random
    rng = random.Random(17)
    pvs = [FilePV.generate(str(tmp_path / f"pv{i}.json"), rng)
           for i in range(n)]
    for pv in pvs:
        pv._save()
    vals = [Validator(pv.get_pub_key(), 10) for pv in pvs]
    order = sorted(range(n), key=lambda i: vals[i].address)
    from cometbft_tpu.types.proto import Timestamp
    gen = GenesisDoc(chain_id="node-net",
                     genesis_time=Timestamp.now(),
                     validators=[vals[i] for i in order])
    nodes = []
    for rank, i in enumerate(order):
        root = tmp_path / f"node{rank}"
        os.makedirs(root / "config", exist_ok=True)
        cfg = Config(root_dir=str(root))
        cfg.base.moniker = f"n{rank}"
        cfg.base.db_backend = "memdb"
        cfg.rpc.unsafe = True  # route tests drive dial_*/unsafe_flush
        cfg.consensus = ConsensusTimeoutsConfig(
            timeout_propose=500, timeout_propose_delta=250,
            timeout_prevote=250, timeout_prevote_delta=150,
            timeout_precommit=250, timeout_precommit_delta=150,
            timeout_commit=timeout_commit,
            skip_timeout_commit=skip_timeout_commit,
            wal_file="data/cs.wal")
        save_genesis(gen, str(root / "config/genesis.json"))
        nodes.append(Node(cfg, KVStoreApplication(), genesis=gen,
                          priv_validator=pvs[i]))
    return nodes


def test_config_toml_roundtrip(tmp_path):
    cfg = Config(root_dir=str(tmp_path))
    cfg.base.chain_id = "toml-chain"
    cfg.consensus.timeout_propose = 1234
    cfg.mempool.size = 99
    cfg.statesync.enable = True
    cfg.statesync.rpc_servers = "127.0.0.1:1,127.0.0.1:2"
    cfg.statesync.trust_height = 7
    cfg.statesync.trust_hash = "ab" * 32
    cfg.storage.discard_abci_responses = True
    cfg.tx_index.indexer = "null"
    path = cfg.write()
    loaded = Config.load(str(tmp_path))
    assert loaded.base.chain_id == "toml-chain"
    assert loaded.consensus.timeout_propose == 1234
    assert loaded.mempool.size == 99
    assert loaded.statesync.enable and loaded.statesync.trust_height == 7
    assert loaded.statesync.trust_hash == "ab" * 32
    assert loaded.statesync.rpc_servers.count(",") == 1
    assert loaded.storage.discard_abci_responses is True
    assert loaded.tx_index.indexer == "null"
    assert loaded.blocksync.version == "v0"


def test_config_validation_rejects_bad_sections(tmp_path):
    import pytest as _pytest
    cfg = Config(root_dir=str(tmp_path))
    cfg.statesync.enable = True  # no rpc_servers / trust anchor
    with _pytest.raises(ValueError):
        cfg.validate_basic()
    cfg = Config(root_dir=str(tmp_path))
    cfg.tx_index.indexer = "elastic"
    with _pytest.raises(ValueError):
        cfg.validate_basic()
    cfg = Config(root_dir=str(tmp_path))
    cfg.blocksync.version = "v9"
    with _pytest.raises(ValueError):
        cfg.validate_basic()


def test_unsafe_routes_gated_by_config():
    """dial_seeds/dial_peers/unsafe_flush_mempool exist only with
    rpc.unsafe=true (reference routes.go:56-62): statesync makes
    operators expose RPC publicly, and these routes flush mempools and
    steer peering for any caller."""
    from cometbft_tpu.rpc.client import RPCClient, RPCClientError
    from cometbft_tpu.rpc.server import RPCEnvironment, RPCServer
    srv = RPCServer(RPCEnvironment(chain_id="gate-test"))
    srv.start()
    try:
        c = RPCClient(*srv.addr)
        for method in ("unsafe_flush_mempool", "dial_seeds",
                       "dial_peers"):
            with pytest.raises(RPCClientError):
                c.call(method)
        c.call("health")  # safe routes unaffected
    finally:
        srv.stop()


def test_genesis_file_roundtrip(tmp_path):
    pv = FilePV.generate(None)
    gen = GenesisDoc(chain_id="g", validators=[
        Validator(pv.get_pub_key(), 7)])
    p = str(tmp_path / "gen.json")
    save_genesis(gen, p)
    back = load_genesis(p)
    assert back.chain_id == "g"
    assert back.validators[0].pub_key.bytes_() == \
        pv.get_pub_key().bytes_()
    assert back.validators[0].voting_power == 7


def test_four_node_network_commits_and_serves_rpc(tmp_path):
    # the p2p mesh rides SecretConnection; simnet covers the multi-node
    # protocol logic in containers without the cryptography wheel
    pytest.importorskip("cryptography")
    nodes = _make_net(tmp_path)
    try:
        # start all; wire the mesh by dialing node 0
        nodes[0].start()
        h0, p0 = nodes[0].p2p_addr
        for nd in nodes[1:]:
            nd.config.p2p.persistent_peers = f"{h0}:{p0}"
            nd.start()
        # full mesh via node0 relay is not automatic; dial pairwise
        addrs = [nd.p2p_addr for nd in nodes]
        for i, nd in enumerate(nodes):
            for j, (h, p) in enumerate(addrs):
                if j > i:
                    try:
                        nd.switch.dial(h, p)
                    except OSError:
                        pass

        # generous: the CI box has one core and sibling suites may be
        # compiling kernels concurrently
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if all(nd.consensus.state.last_block_height >= 2
                   for nd in nodes):
                break
            time.sleep(0.05)
        else:
            raise TimeoutError(
                f"heights: "
                f"{[nd.consensus.state.last_block_height for nd in nodes]}")

        # tx in via RPC on node 2, visible via abci_query on node 1
        rpc2 = RPCClient(*nodes[2].rpc_server.addr)
        r = rpc2.broadcast_tx_sync(b"net=works")
        assert r["code"] == 0
        deadline = time.monotonic() + 90
        rpc1 = RPCClient(*nodes[1].rpc_server.addr)
        while time.monotonic() < deadline:
            q = rpc1.abci_query("/store", b"net")
            if bytes.fromhex(q["value"]) == b"works":
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("tx never reached node 1's app")

        # status + block + validators routes
        st = rpc1.status()
        assert st["sync_info"]["latest_block_height"] >= 2
        blk = rpc1.block(1)
        assert blk["block"]["header"]["height"] == 1
        vals = rpc1.validators(1)
        assert len(vals["validators"]) == 4
        # tx_search finds the committed tx
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            found = rpc1.call("tx_search", query="tx.height > 0")
            if found["total_count"] >= 1:
                break
            time.sleep(0.1)
        assert found["total_count"] >= 1

        # breadth routes (reference rpc/core/routes.go surface)
        cs = rpc1.call("consensus_state")
        assert cs["round_state"]["height"] >= 2
        dump = rpc1.call("dump_consensus_state")
        assert "height_vote_set" in dump["round_state"]
        cp = rpc1.call("consensus_params")
        assert cp["consensus_params"]["block"]["max_bytes"] > 0
        bh = blk["block_id"]["hash"]
        byh = rpc1.call("block_by_hash", hash=bh)
        assert byh["block"]["header"]["height"] == 1
        assert rpc1.call("header_by_hash", hash=bh)[
            "header"]["height"] == 1
        assert rpc1.call("header", height=1)["header"]["height"] == 1
        assert "n_txs" in rpc1.call("num_unconfirmed_txs")
        assert rpc1.call("check_tx", tx=b"fmt".hex())["code"] != 0
        g = rpc1.call("genesis_chunked")
        assert g["total"] >= 1 and g["data"]
        commit = rpc1.call("commit", height=1)
        assert commit["signed_header"]["commit"]["signatures"]
        done = rpc1.call("broadcast_tx_commit",
                         tx=b"committed=yes".hex())
        assert done["tx_result"]["code"] == 0 and done["height"] > 0

        # round-4 tail routes (reference rpc/core/routes.go parity)
        br = rpc1.call("block_results", height=done["height"])
        assert br["height"] == done["height"]
        assert any(t["code"] == 0 for t in br["txs_results"])
        assert br["app_hash"]
        assert rpc1.call("unsafe_flush_mempool") == {}
        assert "dialed" in rpc1.call(
            "dial_peers",
            peers=f"{addrs[3][0]}:{addrs[3][1]}")["log"]
        assert "dialed" in rpc1.call(
            "dial_seeds",
            seeds=f"{addrs[3][0]}:{addrs[3][1]}")["log"]
        # tx inclusion proof verifies against the header's data_hash
        from cometbft_tpu.rpc.codec import proof_from_json
        from cometbft_tpu.types.block import tx_hash as _txh
        found = rpc1.call("tx_search", query="tx.height > 0")
        hsh = found["txs"][0]["hash"]
        t = rpc1.call("tx", hash=hsh, prove=True)
        pf = proof_from_json(t["proof"]["proof"])
        raw_tx = bytes.fromhex(t["tx"])
        root = bytes.fromhex(t["proof"]["root_hash"])
        assert pf.verify(root, _txh(raw_tx))
        hdr = rpc1.call("header", height=t["height"])["header"]
        assert hdr["data_hash"] == t["proof"]["root_hash"]
        # validators pagination: page windows tile the full set
        v1 = rpc1.call("validators", height=1, page=1, per_page=3)
        v2 = rpc1.call("validators", height=1, page=2, per_page=3)
        assert v1["total"] == 4 and v1["count"] == 3 and v2["count"] == 1
        assert len({v["address"] for v in
                    v1["validators"] + v2["validators"]}) == 4

        from test_evidence_gossip import _craft_double_sign
        ev = _craft_double_sign(nodes)
        r = rpc1.call("broadcast_evidence",
                      evidence=ev.encode().hex())
        assert r["hash"] == ev.hash().hex().upper()
        # rejected garbage gets a clean error, not a crash
        from cometbft_tpu.rpc.client import RPCClientError
        with pytest.raises(RPCClientError):
            rpc1.call("broadcast_evidence", evidence="deadbeef")
    finally:
        for nd in nodes:
            nd.stop()


def test_node_with_remote_socket_app(tmp_path):
    """[base] proxy_app = tcp://host:port runs the node against an
    EXTERNAL ABCI app over the socket protocol (reference
    commands/run_node.go --proxy_app + abci/client/socket_client.go):
    consensus, queries, and the snapshot connection all ride the wire."""
    import os

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.abci.socket import ABCIServer
    from cometbft_tpu.config import Config, ConsensusTimeoutsConfig
    from cometbft_tpu.node.node import Node, save_genesis
    from cometbft_tpu.privval.file import FilePV
    from cometbft_tpu.state.state import GenesisDoc
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator

    app = KVStoreApplication()
    srv = ABCIServer(app)
    srv.start()
    node = None
    try:
        pv = FilePV.generate(None)
        gen = GenesisDoc(chain_id="remote-app",
                         genesis_time=Timestamp.now(),
                         validators=[Validator(pv.get_pub_key(), 10)])
        root = tmp_path / "remotenode"
        os.makedirs(root / "config", exist_ok=True)
        cfg = Config(root_dir=str(root))
        cfg.base.db_backend = "memdb"
        cfg.base.proxy_app = f"tcp://127.0.0.1:{srv.addr[1]}"
        cfg.consensus = ConsensusTimeoutsConfig(
            timeout_propose=500, timeout_propose_delta=250,
            timeout_prevote=250, timeout_prevote_delta=150,
            timeout_precommit=250, timeout_precommit_delta=150,
            timeout_commit=50, wal_file="data/cs.wal")
        save_genesis(gen, str(root / "config/genesis.json"))
        node = Node(cfg, priv_validator=pv, genesis=gen)
        node.mempool.check_tx(b"remote=app")
        node.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if node.consensus.state.last_block_height >= 3 and \
                    app.query("/store", b"remote")[1] == b"app":
                break
            time.sleep(0.05)
        else:
            raise TimeoutError(
                f"stuck at {node.consensus.state.last_block_height}")
        # the query connection rides the wire too
        code, val = node.app_conns.query.query("/store", b"remote")
        assert val == b"app"
        # the snapshot connection's methods ride the wire (interval
        # snapshots appear at height 5). Listing and loading are polled
        # together under the same deadline: the node keeps committing,
        # the app retains two snapshots, and a listed one can be gone
        # when a starved test thread comes to load it
        snaps, chunk = [], b""
        while not chunk and time.monotonic() < deadline:
            time.sleep(0.05)
            if node.consensus.state.last_block_height >= 6:
                snaps = node.app_conns.snapshot.list_snapshots()
                if snaps:
                    chunk = node.app_conns.snapshot.load_snapshot_chunk(
                        snaps[0].height, snaps[0].format, 0)
        assert snaps and snaps[0].height % 5 == 0
        assert chunk and b"remote" in chunk
    finally:
        if node is not None:
            node.stop()
        srv.stop()


def test_prometheus_metrics_endpoint(tmp_path):
    """[instrumentation] prometheus=true serves live consensus metrics
    over HTTP in the Prometheus text format (reference node.go metrics
    server + internal/consensus/metrics.go): height/rounds/validators
    move with the chain."""
    import urllib.request

    from cometbft_tpu.types.proto import Timestamp

    pv = FilePV.generate(None)
    gen = GenesisDoc(chain_id="metrics-net",
                     genesis_time=Timestamp.now(),
                     validators=[Validator(pv.get_pub_key(), 10)])
    root = tmp_path / "metricsnode"
    os.makedirs(root / "config", exist_ok=True)
    cfg = Config(root_dir=str(root))
    cfg.base.db_backend = "memdb"
    cfg.instrumentation.prometheus = True
    cfg.consensus = ConsensusTimeoutsConfig(
        timeout_propose=500, timeout_propose_delta=250,
        timeout_prevote=250, timeout_prevote_delta=150,
        timeout_precommit=250, timeout_precommit_delta=150,
        timeout_commit=50, wal_file="data/cs.wal")
    save_genesis(gen, str(root / "config/genesis.json"))
    node = Node(cfg, KVStoreApplication(), genesis=gen,
                priv_validator=pv)
    try:
        node.start()
        deadline = time.monotonic() + 60
        while node.consensus.state.last_block_height < 3:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        host, port = node.metrics_addr
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10
        ).read().decode()
        assert "# TYPE cometbft_tpu_consensus_height gauge" in body
        h = [ln for ln in body.splitlines()
             if ln.startswith("cometbft_tpu_consensus_height ")][0]
        assert float(h.split()[-1]) >= 3
        assert "cometbft_tpu_consensus_validators 1" in body
        assert 'cometbft_tpu_consensus_rounds{reason="new_height"}' \
            in body
        assert "consensus_block_processing_seconds_count" in body
    finally:
        node.stop()
