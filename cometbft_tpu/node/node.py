"""Node assembly: the dependency-injection graph wiring every subsystem
(reference node/node.go:273-536 NewNode, :539-609 OnStart).

Boot order follows the reference: DBs → state (store or genesis) →
proxy app conns → ABCI handshake/replay → event bus + indexers →
mempool/evidence → consensus (+WAL) → reactors → switch → RPC.
"""

from __future__ import annotations

import json
import os
import threading
from typing import List, Optional

from ..abci.application import Application, RequestFinalizeBlock
from ..config import Config
from ..consensus.reactor import ConsensusReactor
from ..consensus.state import ConsensusConfig, ConsensusState
from ..consensus.wal import WAL
from ..crypto.keys import (Ed25519PrivKey, Ed25519PubKey,
                           pubkey_from_type_bytes)
from ..db.kv import open_db
from ..engine.reactor import BlocksyncNetReactor, NetSource
from ..evidence.pool import EvidencePool
from ..indexer.kv import BlockIndexer, IndexerService, TxIndexer
from ..mempool.mempool import CListMempool
from ..p2p.switch import Switch
from ..privval.file import FilePV
from ..proxy.multi_app_conn import AppConns, local_client_creator
from ..pubsub.events import EventBus
from ..rpc.server import RPCEnvironment, RPCServer
from ..state.execution import BlockExecutor
from ..state.state import GenesisDoc, State, StateStore
from ..state.state import ConsensusParams
from ..store.blockstore import BlockStore
from ..types.block import BlockID
from ..types.proto import Timestamp
from ..types.validator import Validator


def load_or_generate_node_key(path: str) -> Ed25519PrivKey:
    """Persistent p2p identity key (reference p2p/node_key.go) — the
    node id must survive restarts or peer allow/ban lists break."""
    if os.path.exists(path):
        with open(path) as f:
            return Ed25519PrivKey(bytes.fromhex(json.load(f)["priv_key"]))
    key = Ed25519PrivKey.generate()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"priv_key": key.seed.hex(),
                   "node_id": key.pub_key().address().hex()}, f)
    return key


def save_genesis(gen: GenesisDoc, path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({
            "chain_id": gen.chain_id,
            "initial_height": gen.initial_height,
            "genesis_time": [gen.genesis_time.seconds,
                             gen.genesis_time.nanos],
            "validators": [{"pub_key": v.pub_key.bytes_().hex(),
                            "type": v.pub_key.type_(),
                            "power": v.voting_power}
                           for v in gen.validators],
            "app_state": gen.app_state.hex(),
            "app_hash": gen.app_hash.hex(),
            "bls_pops": {pub.hex(): pop.hex()
                         for pub, pop in gen.bls_pops.items()},
        }, f, indent=1)


def load_genesis(path: str) -> GenesisDoc:
    with open(path) as f:
        d = json.load(f)
    return GenesisDoc(
        chain_id=d["chain_id"],
        initial_height=d.get("initial_height", 1),
        genesis_time=Timestamp(*d.get("genesis_time", [0, 0])),
        validators=[Validator(
            pubkey_from_type_bytes(v.get("type", "ed25519"),
                                   bytes.fromhex(v["pub_key"])),
            v["power"]) for v in d["validators"]],
        app_state=bytes.fromhex(d.get("app_state", "")),
        app_hash=bytes.fromhex(d.get("app_hash", "")),
        bls_pops={bytes.fromhex(pub): bytes.fromhex(pop)
                  for pub, pop in d.get("bls_pops", {}).items()})


class Node:
    """reference node/node.go Node."""

    def __init__(self, config: Config, app: Optional[Application] = None,
                 genesis: Optional[GenesisDoc] = None,
                 priv_validator: Optional[FilePV] = None,
                 node_key: Optional[Ed25519PrivKey] = None,
                 client_creator=None):
        config.validate_basic()
        self.config = config
        self.genesis = genesis or load_genesis(
            config.path(config.base.genesis_file))

        # --- DBs (node.go:284 initDBs) ---------------------------------------
        be, ddir = config.base.db_backend, config.path(config.base.db_dir)
        self.block_store = BlockStore(open_db(be, "blockstore", ddir))
        self.state_store = StateStore(
            open_db(be, "state", ddir),
            retain_abci_responses=not config.storage.discard_abci_responses)
        self._indexer_db = open_db(be, "indexer", ddir)

        # --- boot-time recovery doctor (store/recovery.py) -------------------
        # Runs BEFORE the handshake and reactors: cross-checks WAL
        # ENDHEIGHT vs state vs blockstore, repairs crash litter, and
        # refuses to boot (RecoveryError) on anything unrepairable.
        # The metrics registry is created here (not with the consensus
        # metrics below) so doctor repairs — including the ones FileDB
        # already performed while opening above — are attributed in
        # StorageMetrics.
        from ..libs.metrics import Registry
        self.metrics_registry = Registry()
        from ..libs.metrics_gen import StorageMetrics
        from ..store import recovery as _recovery
        self.storage_metrics = StorageMetrics(self.metrics_registry)
        if _recovery._metrics is None:  # first node wins, like SigCache
            _recovery.set_metrics(self.storage_metrics)
        _wal_doctor = WAL(
            config.path(config.consensus.wal_file),
            head_size_limit=config.consensus.wal_head_size_limit,
            total_size_limit=config.consensus.wal_total_size_limit)
        try:
            import sys as _sys
            self.recovery_report = _recovery.run_doctor(
                block_store=self.block_store,
                state_store=self.state_store,
                wal=_wal_doctor, db_dir=ddir,
                pv_state_path=config.path(
                    config.base.priv_validator_file),
                log=lambda s: print(f"[{config.base.moniker}] {s}",
                                    file=_sys.stderr))
        finally:
            _wal_doctor.close()

        # --- state: stored or genesis (node.go:289) --------------------------
        state = self.state_store.load()
        if state is None:
            state = State.from_genesis(self.genesis)
            # bootstrap-save so the genesis validator set is indexed at
            # the initial height (reference state/store.go Bootstrap)
            self.state_store.save(state)
        elif self.genesis.bls_pops:
            # the PoP registry is process-local: a RESTARTED node loads
            # state from the store and skips from_genesis, so the
            # genesis proofs of possession must be re-admitted here or
            # every valid aggregated commit would be rejected for
            # missing PoPs (docs/AGGSIG.md "PoP policy")
            from ..aggsig.aggregate import register_pops_batch
            register_pops_batch(self.genesis.bls_pops)

        # --- privval (node.go:343; loaded before the app, whose vote
        # extensions the validator's address keys) -------------------------
        if priv_validator is None:
            pv_path = config.path(config.base.priv_validator_file)
            priv_validator = FilePV.load_or_generate(pv_path)
        self.priv_validator = priv_validator

        # --- proxy app (node.go:319): in-process app, explicit client
        # creator, or [base] proxy_app = tcp://host:port (the socket
        # flavor — reference proxy.DefaultClientCreator) ----------------------
        if client_creator is None:
            if app is not None:
                client_creator = local_client_creator(app)
            else:
                target = config.base.proxy_app
                if target == "kvstore":
                    client_creator = local_client_creator(
                        self.builtin_app(config, priv_validator))
                elif target.startswith("grpc://"):
                    from ..proxy.multi_app_conn import (
                        remote_grpc_client_creator)
                    host, port = self._split_addr(
                        target.removeprefix("grpc://"))
                    client_creator = remote_grpc_client_creator(host,
                                                                port)
                else:
                    from ..proxy.multi_app_conn import (
                        remote_client_creator)
                    host, port = self._split_addr(
                        target.removeprefix("tcp://"))
                    client_creator = remote_client_creator(host, port)
        self.app_conns = AppConns(client_creator)
        self._handshake(state)

        # --- event bus + indexers (node.go:328-334) --------------------------
        self.event_bus = EventBus()
        if config.tx_index.indexer == "sqlite":
            # relational sink (reference psql sink's role,
            # state/indexer/sink/psql): same interface, sqlite file
            from ..indexer.sqlite import open_sqlite_indexers
            self.tx_indexer, self.block_indexer = open_sqlite_indexers(
                config.path(config.base.db_dir))
        else:
            self.tx_indexer = TxIndexer(self._indexer_db)
            self.block_indexer = BlockIndexer(self._indexer_db)
        self.indexer_service = IndexerService(
            self.tx_indexer, self.block_indexer, self.event_bus)

        # --- mempool + evidence (node.go:385-409) ----------------------------
        mc = config.mempool
        self.mempool = CListMempool(
            lambda tx: (self.app_conns.mempool.check_tx(tx).code, 0),
            max_tx_bytes=mc.max_tx_bytes, max_txs_bytes=mc.max_txs_bytes,
            size=mc.size, cache_size=mc.cache_size, recheck=mc.recheck)
        self.evidence_pool = EvidencePool(
            state_store=self.state_store, block_store=self.block_store)

        # --- executor + consensus (node.go:413-448) --------------------------
        self.executor = BlockExecutor(
            self.app_conns.consensus, state_store=self.state_store,
            block_store=self.block_store, mempool=self.mempool,
            evidence_pool=self.evidence_pool, event_bus=self.event_bus)
        from ..state.pruner import Pruner
        self.pruner = Pruner(
            self.block_store, self.state_store,
            interval_s=config.storage.pruning_interval_ms / 1000.0,
            tx_indexer=self.tx_indexer,
            block_indexer=self.block_indexer)
        self.executor.pruner = self.pruner
        from ..libs.metrics import ConsensusMetrics
        # (metrics_registry was created up in the doctor section so
        # storage repairs during DB open are attributed)
        # mosaic-miscompile canary counters (ops/ed25519._run_canary):
        # trips > 0 means a pallas kernel claimed batch_ok on a batch
        # with a known-invalid lane and was permanently disabled
        from ..ops.ed25519 import canary_stats, pallas_degraded
        self.metrics_registry.callback_gauge(
            "crypto_pallas_canary_runs",
            "Tampered-lane canary executions against the pallas kernel",
            fn=lambda: canary_stats()["runs"])
        self.metrics_registry.callback_gauge(
            "crypto_pallas_canary_trips",
            "Silent-accept miscompiles caught (pallas then disabled)",
            fn=lambda: canary_stats()["trips"])
        self.metrics_registry.callback_gauge(
            "crypto_pallas_degraded",
            "1 when aligned batches are served by the XLA kernel "
            "instead of pallas (sticky after a canary trip)",
            fn=lambda: int(pallas_degraded()))
        # generated metrics structs (tools/metricsgen.py from
        # libs/metrics_defs.py — the reference's scripts/metricsgen
        # role): mempool occupancy now, p2p wiring after the switch
        # exists below
        from ..libs.metrics_gen import (AggsigMetrics, DeviceMetrics,
                                        MempoolMetrics, P2PMetrics,
                                        PipelineMetrics)
        self._p2p_metrics_cls = P2PMetrics
        self.mempool.metrics = MempoolMetrics(self.metrics_registry)
        self.pipeline_metrics = PipelineMetrics(self.metrics_registry)
        self.device_metrics = DeviceMetrics(self.metrics_registry)
        # aggregate-commit verification counters (aggsig/verify.py) —
        # module-shared like the SigCache: several in-process nodes
        # verify through one aggsig path, first node wins
        from ..aggsig import verify as _aggsig_verify
        self.aggsig_metrics = AggsigMetrics(self.metrics_registry)
        if _aggsig_verify._metrics is None:
            _aggsig_verify.set_metrics(self.aggsig_metrics)
        # the per-process device health supervisor (device/health.py):
        # wedge recovery probing, canary-verified batches, reconnect
        # backoff. Knobs from [device]; first node wins for metrics and
        # configuration (several in-process nodes share one device),
        # matching the shared-cache posture below.
        from ..device.health import shared_supervisor
        shared_supervisor().configure(config.device,
                                      metrics=self.device_metrics)
        # multi-chip mesh serving ([device] mesh — docs/MESH.md): latch
        # the config so mesh.shared_executor() can build the process
        # topology lazily (first node wins, same posture as the device
        # supervisor); MeshMetrics rides the same registry
        from .. import mesh as _mesh
        from ..libs.metrics_gen import MeshMetrics
        self.mesh_metrics = MeshMetrics(self.metrics_registry)
        _mesh.configure(config.device)
        # flight-recorder tracing ([instrumentation] trace —
        # docs/TRACE.md): same first-node-wins latch as the device
        # supervisor; COMETBFT_TPU_TRACE* env knobs override
        from .. import trace as _trace
        from ..libs.metrics_gen import TraceMetrics
        self.trace_metrics = TraceMetrics(self.metrics_registry)
        _trace.configure(config.instrumentation,
                         metrics=self.trace_metrics)
        # the process-wide verified-signature cache (vote intake, light
        # client, blocksync) reports hit/miss/eviction through the same
        # struct. First node wins: with several nodes in one process
        # (in-process tests) re-pointing the singleton would misfile
        # every earlier node's counts under the newest registry.
        from ..pipeline.cache import shared_cache
        if shared_cache().metrics is None:
            shared_cache().metrics = self.pipeline_metrics
        # batched CheckTx admission ([mempool] ingest_batch —
        # docs/INGEST.md): broadcast_tx_* and p2p-relayed txs coalesce
        # into shared signature batches over the same SigCache +
        # DeviceClient seam as vote intake and blocksync, with
        # explicit backpressure
        self.ingest = None
        if mc.ingest_batch:
            from ..ingest import IngestPipeline
            from ..libs.metrics_gen import IngestMetrics
            self.ingest = IngestPipeline(
                self.mempool, cache=shared_cache(),
                metrics=IngestMetrics(self.metrics_registry))
        cc = config.consensus
        self.consensus = ConsensusState(
            ConsensusConfig(
                timeout_propose=cc.timeout_propose,
                timeout_propose_delta=cc.timeout_propose_delta,
                timeout_prevote=cc.timeout_prevote,
                timeout_prevote_delta=cc.timeout_prevote_delta,
                timeout_precommit=cc.timeout_precommit,
                timeout_precommit_delta=cc.timeout_precommit_delta,
                timeout_commit=cc.timeout_commit,
                create_empty_blocks=cc.create_empty_blocks,
                skip_timeout_commit=cc.skip_timeout_commit),
            state, self.executor, self.block_store,
            priv_validator=self.priv_validator,
            wal=WAL(config.path(cc.wal_file),
                    head_size_limit=cc.wal_head_size_limit,
                    total_size_limit=cc.wal_total_size_limit),
            name=config.base.moniker,
            metrics=ConsensusMetrics(self.metrics_registry))
        self.consensus.evidence_pool = self.evidence_pool

        # --- reactors + switch (node.go:456-494) -----------------------------
        self.node_key = node_key or load_or_generate_node_key(
            config.path(config.base.node_key_file))
        self.switch = Switch(self.node_key, self.genesis.chain_id,
                             config.base.moniker,
                             send_rate=config.p2p.send_rate,
                             recv_rate=config.p2p.recv_rate)
        self.switch.metrics = self._p2p_metrics_cls(
            self.metrics_registry)
        self.consensus_reactor = ConsensusReactor(self.consensus)
        self.consensus_reactor.attach(self.switch)
        # every node SERVES seals (the provider reads straight out of
        # the stores, zero cost when nobody asks); CONSUMING them at
        # boot is gated by [blocksync] seal_sync below
        from ..libs.metrics_gen import SealsyncMetrics
        from ..sealsync import SealProvider
        self.sealsync_metrics = SealsyncMetrics(self.metrics_registry)
        self.seal_provider = SealProvider(
            self.block_store, state_store=self.state_store,
            metrics=self.sealsync_metrics)
        self.blocksync_reactor = BlocksyncNetReactor(
            self.block_store, seal_provider=self.seal_provider)
        from ..mempool.reactor import MempoolReactor
        self.mempool_reactor = MempoolReactor(self.mempool,
                                              ingest=self.ingest)
        self.mempool_reactor.attach(self.switch)
        from ..evidence.reactor import EvidenceReactor
        self.evidence_reactor = EvidenceReactor(
            self.evidence_pool, lambda: self.consensus.state)
        self.evidence_reactor.attach(self.switch)
        from ..statesync.reactor import StatesyncNetReactor
        # every node SERVES snapshots (reference node.go always mounts
        # the statesync reactor); consuming them at boot is gated by
        # [statesync] enable
        self.statesync_reactor = StatesyncNetReactor(
            self.app_conns.snapshot)
        self.switch.add_reactor(self.consensus_reactor)
        self.switch.add_reactor(self.blocksync_reactor)
        self.switch.add_reactor(self.mempool_reactor)
        self.switch.add_reactor(self.evidence_reactor)
        self.switch.add_reactor(self.statesync_reactor)

        # --- RPC (node.go:559 — started first on OnStart) --------------------
        # light-client verification farm ([rpc] light_farm): serves
        # many clients' skipping checks from this node's own stores,
        # coalesced into shared device batches (docs/FARM.md)
        self.farm = None
        if config.rpc.light_farm:
            from ..farm import VerificationFarm
            from ..libs.metrics_gen import FarmMetrics
            from ..light.provider import BlockStoreProvider
            self.farm = VerificationFarm(
                self.genesis.chain_id,
                BlockStoreProvider(self.genesis.chain_id,
                                   self.block_store, self.state_store),
                metrics=FarmMetrics(self.metrics_registry))
        self.rpc_env = RPCEnvironment(
            chain_id=self.genesis.chain_id,
            block_store=self.block_store,
            state_store=self.state_store, mempool=self.mempool,
            consensus=self.consensus, event_bus=self.event_bus,
            tx_indexer=self.tx_indexer,
            block_indexer=self.block_indexer,
            app_query=self.app_conns.query, genesis=self.genesis,
            switch=self.switch,
            evidence_pool=self.evidence_pool,
            unsafe=config.rpc.unsafe, farm=self.farm,
            ingest=self.ingest, sealsync=self.seal_provider)
        self.rpc_server: Optional[RPCServer] = None
        if config.rpc.enable:
            host, port = self._split_addr(config.rpc.laddr)
            rc = config.rpc
            self.rpc_server = RPCServer(
                self.rpc_env, host, port,
                max_body_bytes=rc.max_body_bytes,
                timeout_s=rc.timeout_ms / 1000.0,
                cors_origins=rc.cors_allowed_origins,
                cors_methods=rc.cors_allowed_methods,
                cors_headers=rc.cors_allowed_headers,
                tls_cert_file=config.path(rc.tls_cert_file)
                if rc.tls_cert_file else "",
                tls_key_file=config.path(rc.tls_key_file)
                if rc.tls_key_file else "")

        # --- companion gRPC services (node.go:805-845) -----------------------
        self.grpc_services = None
        self.grpc_privileged = None
        gc = config.grpc
        if gc.laddr:
            from ..rpc.grpc import GRPCServices
            host, port = self._split_addr(gc.laddr)
            self.grpc_services = GRPCServices(
                self.rpc_env, host, port,
                version_service=gc.version_service,
                block_service=gc.block_service,
                block_results_service=gc.block_results_service)
        if gc.privileged_laddr and gc.pruning_service:
            from ..rpc.grpc import PrivilegedGRPCServices
            host, port = self._split_addr(gc.privileged_laddr)
            self.grpc_privileged = PrivilegedGRPCServices(
                self.pruner, self.block_store, host, port)

    @staticmethod
    def _split_addr(addr: str):
        host, _, port = addr.rpartition(":")
        return host or "127.0.0.1", int(port)

    def _handshake(self, state: State) -> None:
        """ABCI handshake: sync the app to the stored state by replaying
        blocks it hasn't seen (reference node/node.go:365 doHandshake →
        internal/consensus/replay.go:242-284)."""
        info = self.app_conns.consensus.info()
        app_height = info.last_block_height
        if app_height == 0:
            # fresh app: InitChain even when the store is ahead — the
            # replay below brings it to the stored height
            self.app_conns.consensus.init_chain(
                self.genesis.chain_id, self.genesis.initial_height,
                self.genesis.validators, self.genesis.app_state)
        # replay stored blocks the app is missing (crash between
        # SaveBlock and app commit, or a fresh app behind an old store)
        h = app_height + 1
        while h <= state.last_block_height:
            blk = self.block_store.load_block(h)
            if blk is None:
                break
            self.app_conns.consensus.finalize_block(RequestFinalizeBlock(
                txs=blk.data.txs, height=h, time=blk.header.time,
                proposer_address=blk.header.proposer_address,
                hash=blk.hash(),
                next_validators_hash=blk.header.next_validators_hash))
            self.app_conns.consensus.commit()
            h += 1

    # --- lifecycle (node.go:539-609) -----------------------------------------

    def start(self) -> None:
        if self.ingest is not None:
            # flusher first: relayed/async txs must settle even before
            # any RPC waiter performs a cooperative flush
            self.ingest.start()
        from .. import mesh as _mesh
        if _mesh.mesh_enabled():
            # warm the shared mesh executor off the boot path: the
            # first build compiles the bucket ladder (minutes on real
            # hardware) and the farm/ingest batchers route through the
            # mesh whenever no device server is configured — a cold
            # build inside a live flush would stall every submitter
            threading.Thread(
                target=lambda: _mesh.shared_executor(
                    metrics=self.mesh_metrics),
                name="mesh-warm", daemon=True).start()
        if self.rpc_server is not None:
            self.rpc_server.start()          # RPC first (node.go:559)
        if self.grpc_services is not None:
            self.grpc_services.start()
            self.grpc_addr = self.grpc_services.addr
        if self.grpc_privileged is not None:
            self.grpc_privileged.start()
            self.grpc_priv_addr = self.grpc_privileged.addr
        if self.config.tx_index.indexer != "null":
            # "null" = no indexing (reference state/txindex null sink):
            # the service never subscribes, searches return empty
            self.indexer_service.start()
        self.pruner.start()
        self.consensus_reactor.start_reconciler()
        if self.config.instrumentation.prometheus:
            self._start_metrics_server()
        host, port = self._split_addr(self.config.p2p.laddr)
        self.p2p_addr = self.switch.listen(host, port)
        for peer in filter(None, self.config.p2p.persistent_peers.split(",")):
            ph, _, pp = peer.strip().rpartition(":")
            # registered (not one-shot dialed): the switch's
            # ensure-peers routine dials now and re-dials on any drop —
            # a node that loses all links otherwise stays isolated
            # forever and stalls consensus
            self.switch.add_persistent_peer(ph, int(pp))
        if self.config.base.block_sync:
            # blocksync to the peer tip BEFORE consensus (the reference's
            # blocksync mode → switchToConsensus,
            # internal/blocksync/reactor.go:388); consensus messages
            # arriving meanwhile queue in the inbox and replay on start
            threading.Thread(target=self._sync_then_consensus,
                             name="blocksync-boot", daemon=True).start()
        else:
            self.consensus.start()

    @staticmethod
    def _device_batch_size() -> int:
        """Device tile size for blocksync verification, or 0 = native
        single-sig path: `crypto/keys.kernel_width()`, the one rule
        (the kernel bucket on a TPU backend, native on cpu). A client
        of the host's device server ships its tiles there while the
        server is reachable and asks no backend of its own."""
        from ..crypto.keys import kernel_bucket, kernel_width
        from ..libs.jax_cache import DEVICE_SERVER_ENV
        if os.environ.get(DEVICE_SERVER_ENV):
            from ..device.client import shared_client
            return kernel_bucket() if shared_client() is not None else 0
        return kernel_width()

    @staticmethod
    def builtin_app(config: Config, priv_validator=None) -> Application:
        """`[base] proxy_app = "kvstore"`: the kvstore, or with `[base]
        vote_extension_size` above 0 the kvstore that extends its
        validator's precommits by that many bytes and verifies its
        peers' (`ExtendingKVStoreApplication`, the reference e2e app's
        `vote_extension_size`)."""
        from ..abci.kvstore import (ExtendingKVStoreApplication,
                                    KVStoreApplication)
        size = config.base.vote_extension_size
        if size <= 0:
            return KVStoreApplication()
        address = (priv_validator.get_pub_key().address()
                   if priv_validator is not None else b"")
        return ExtendingKVStoreApplication(size, address)

    @classmethod
    def boot_kernels(cls, vote_extension_size: int = 0) -> dict:
        """What a node does at boot before its first verification: the
        compile cache on, and the kernels of its bucket warm for every
        shape `_warm_shapes` names. Returns the bucket (0 = the native
        path, nothing warmed) and the seconds the warm took."""
        import time
        from ..libs.jax_cache import enable_compile_cache
        enable_compile_cache()
        batch = cls._device_batch_size()
        prewarm_s = 0.0
        if batch > 0:
            # the compile's real seconds, whatever clock a simulation
            # installs
            t0 = time.perf_counter()  # staticcheck: allow(wallclock)
            cls._warm_shapes(batch, vote_extension_size)
            t1 = time.perf_counter()  # staticcheck: allow(wallclock)
            prewarm_s = t1 - t0
        return {"batch": batch, "prewarm_s": prewarm_s}

    @staticmethod
    def _warm_shapes(batch: int, vote_extension_size: int) -> None:
        """Warm the kernels of every SHA-512 bucket
        (`ops.ed25519.hash_block_bucket`) the node's flushes can dispatch:
        a vote's or a commit's sign-bytes, and on a chain with vote
        extensions of `vote_extension_size` bytes the sign-bytes of such
        an extension at the shortest and the longest chain id and round
        (`types.vote.extension_sign_bytes_span`)."""
        from ..ops.ed25519 import (VOTE_MSG_CAP, hash_block_bucket,
                                   prewarm_verify_kernels)
        from ..types.vote import extension_sign_bytes_span
        caps = [VOTE_MSG_CAP]
        if vote_extension_size > 0:
            caps += extension_sign_bytes_span(vote_extension_size)
        warmed = set()
        for cap in caps:
            if hash_block_bucket(cap) not in warmed:
                warmed.add(hash_block_bucket(cap))
                prewarm_verify_kernels(batch_size=batch, msg_cap=cap)

    def _prewarm_kernels(self) -> None:
        """Compile the node bucket's kernels (and run the miscompile
        canary) BEFORE the first tile is dispatched: the pipeline
        watchdog arms a deadline per dispatch, and a first dispatch
        that is still compiling would trip it — sticky — and drain a
        healthy chip's every later tile to native CPU verify. A chain
        with vote extensions warms their shapes too (`_warm_shapes`).
        The one warm of the program (`boot_kernels`). A failure here is
        a broken device path and propagates."""
        params = self.consensus.state.consensus_params
        self.boot_kernels(self.config.base.vote_extension_size
                          if params.vote_extensions_enable_height > 0 else 0)

    def _run_statesync(self):
        """Snapshot-sync a fresh node (reference node.go:591-601
        startStateSync): discover snapshots on the p2p channel, restore
        the app from chunks, anchor against the light client built from
        [statesync] rpc_servers, persist the bootstrapped state + seen
        commit, and return the State for blocksync to continue from.
        Returns None when nothing usable was found (boot falls back to
        blocksync-from-genesis)."""
        from ..libs import timesource
        from ..statesync.stateprovider import light_provider_from_config
        from ..statesync.syncer import Syncer, StateSyncError
        from ..statesync.reactor import net_snapshot_sources

        ss = self.config.statesync
        provider = light_provider_from_config(ss, self.genesis)

        # discovery waits read the timesource seam: wall clocks on a
        # live node, and under a simnet virtual source the deadline
        # math follows the simulated clock (timesource.sleep degrades
        # to a real yield so the sim thread that advances time runs)
        deadline = timesource.monotonic() + ss.discovery_time_ms / 1000.0
        state = None
        while timesource.monotonic() < deadline:
            sources = net_snapshot_sources(self.statesync_reactor)
            if sources:
                try:
                    state = Syncer(self.app_conns.snapshot, provider,
                                   sources).sync()
                    break
                except StateSyncError:
                    # snapshots may be too close to the tip for the
                    # height+2 anchor; the chain advances — retry
                    pass
            timesource.sleep(0.5)
        if state is None:
            return None
        # persist the bootstrap (reference node.go:152 BootstrapState)
        self.state_store.save(state)
        self.block_store.bootstrap_seen_commit(
            state.last_block_height,
            provider.commit(state.last_block_height))
        return state

    def _sync_then_consensus(self) -> None:
        from ..engine.blocksync import (BlocksyncReactor, SyncStalled)
        from ..engine.pool import PooledSource
        from ..pipeline.cache import shared_cache
        from ..state.execution import BlockValidationError
        src = NetSource(self.blocksync_reactor, self.switch)
        state = self.consensus.state
        if self.config.statesync.enable and state.last_block_height == 0:
            try:
                synced = self._run_statesync()
            except Exception:  # noqa: BLE001 — statesync is best-effort;
                # blocksync-from-genesis remains the safe fallback
                import traceback
                traceback.print_exc()
                synced = None
            if synced is not None:
                state = synced
        if self.config.blocksync.seal_sync:
            # sealsync (docs/SEALSYNC.md): adopt decided heights from
            # aggregate seals FIRST — O(pivots) pairings for the whole
            # gap instead of one per height — then let the blocksync
            # loop below backfill bodies (every adopted commit is a
            # SigCache hit, so backfill re-verifies nothing)
            from ..sealsync import AdoptionError, SealAdopter
            from ..engine.reactor import NetSealSource
            bs = self.config.blocksync
            try:
                SealAdopter(
                    self.genesis.chain_id, self.block_store,
                    NetSealSource(self.blocksync_reactor, self.switch),
                    tile_size=bs.seal_tile, max_skip=bs.seal_max_skip,
                    cache=shared_cache(),
                    metrics=self.sealsync_metrics).adopt(state)
            except AdoptionError:
                # adoption is an accelerator, never a gate: a corrupt
                # or seal-less peer set just means plain blocksync
                import traceback
                traceback.print_exc()
        # catch up until no peer is ahead (each pass re-queries peer
        # status; a fresh net reports height 0 and falls through fast)
        warmed = False  # in-process kernels compiled before a deadline arms
        for _round in range(100):
            target = src.max_height()
            if target <= state.last_block_height:
                break
            pooled = PooledSource(src, state.last_block_height + 1,
                                  lookahead=32, n_workers=4)
            # device-backed nodes run the asynchronous verification
            # pipeline (device verify of tile N overlaps fetch/marshal/
            # apply of neighbors) under the wedge watchdog; CPU nodes
            # keep the synchronous loop — native verify has no device
            # latency to hide and threads would only add overhead
            batch = self._device_batch_size()
            depth = (self.config.blocksync.pipeline_depth
                     if batch > 0 else 1)
            watchdog = backend = supervisor = None
            if depth > 1:
                from ..pipeline.watchdog import DeviceWatchdog
                # with the host's TPU-owner server configured, dispatch
                # through the non-blocking DeviceClient.submit() seam;
                # otherwise the scheduler's in-process dispatch thread
                # drives the local JAX kernels. The health supervisor
                # (and its canary lanes) only applies to the remote
                # link — in-process dispatch has no transport to
                # supervise, so it keeps the standalone sticky watchdog
                from ..device.client import shared_client
                client = shared_client()
                if client is not None:
                    from ..device.health import shared_supervisor
                    from ..pipeline.scheduler import DeviceClientBackend
                    supervisor = shared_supervisor()
                    backend = DeviceClientBackend(client)
                else:
                    # no TPU-owner server: with [device] mesh on, this
                    # process owns the local devices directly as one
                    # sharded mesh (mesh/executor). The scheduler then
                    # sizes its queue from the shard count (K tiles in
                    # flight PER shard). No node-level supervisor:
                    # verdict gating is the executor's own per-shard
                    # canaries (a lying shard masks + re-factors, and
                    # its batch re-verifies on CPU internally).
                    from .. import mesh as _mesh
                    backend = _mesh.shared_executor(
                        metrics=self.mesh_metrics)
                    if not warmed:
                        self._prewarm_kernels()
                        warmed = True
                watchdog = DeviceWatchdog(
                    metrics=self.pipeline_metrics,
                    supervisor=supervisor)
            engine = BlocksyncReactor(
                self.executor, self.block_store, pooled,
                self.genesis.chain_id, tile_size=16,
                batch_size=batch, pipeline_depth=depth,
                backend=backend, watchdog=watchdog,
                cache=shared_cache(), metrics=self.pipeline_metrics,
                supervisor=supervisor)
            try:
                state = engine.sync(state, target)
            except (BlockValidationError, SyncStalled):
                # peers can't serve clean blocks right now; consensus
                # gossip takes over from wherever sync actually got to
                state = self._recover_sync_state(state)
                break
            except Exception:  # noqa: BLE001 — never boot-loop silently
                import traceback
                traceback.print_exc()
                state = self._recover_sync_state(state)
                break
            finally:
                pooled.stop()
        if state is not self.consensus.state:
            self.consensus.state = state
            self.consensus._update_to_state(state)
        self.consensus.start()

    def _recover_sync_state(self, fallback):
        """Blocksync applies tile-by-tile through the executor (which
        persists after each block), so on failure the authoritative
        partially-advanced state lives in the state store — reusing the
        pre-sync snapshot would re-execute blocks the app already saw."""
        stored = self.state_store.load()
        if stored is not None and \
                stored.last_block_height > fallback.last_block_height:
            return stored
        return fallback

    def _start_metrics_server(self) -> None:
        """Serve Registry.expose() at [instrumentation] prometheus_laddr
        (reference node.go Prometheus metrics server)."""
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        registry = self.metrics_registry

        class Handler(BaseHTTPRequestHandler):
            timeout = 10  # a stalled scraper must not wedge shutdown

            def log_message(self, *a):
                pass

            def do_GET(self):
                body = registry.expose().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        host, port = self._split_addr(
            self.config.instrumentation.prometheus_laddr or
            "127.0.0.1:0")
        self._metrics_server = ThreadingHTTPServer((host, port), Handler)
        self._metrics_server.daemon_threads = True
        self.metrics_addr = self._metrics_server.server_address
        threading.Thread(target=self._metrics_server.serve_forever,
                         name="metrics", daemon=True).start()

    def stop(self) -> None:
        self.consensus.stop()
        self.consensus_reactor.stop()
        if self.ingest is not None:
            self.ingest.stop()
        if getattr(self, "_metrics_server", None) is not None:
            self._metrics_server.shutdown()
            self._metrics_server.server_close()  # free the listen FD
        self.switch.stop()
        self.pruner.stop()
        self.indexer_service.stop()
        if self.rpc_server is not None:
            self.rpc_server.stop()
        if self.grpc_services is not None:
            self.grpc_services.stop()
        if self.grpc_privileged is not None:
            self.grpc_privileged.stop()
        self.app_conns.stop()
