"""Driver `blocksync_sync`: the window is ONE continuous
`BlocksyncReactor.sync` of a fresh node to the tip of a chain it has
never seen, served by a local peer.

The reactor is built as `Node._sync_then_consensus` builds it
(node/node.py): tile size from the configuration's `tile_size`
(the literal 16 there), lane bucket `Node._device_batch_size()`,
`BlockSyncConfig().pipeline_depth`, a `DeviceWatchdog`, the in-process
backend and `cache=shared_cache()`."""

from __future__ import annotations

import random
import time

from jax.profiler import TraceAnnotation

from benchmark.drivers import node_boot
from benchmark.harness import stats
from benchmark.reference import canonical_vote, ed25519_ref, kv_replay

SIGCACHE_PATH = "blocksync"
warm = node_boot.boot


class Peer:
    """A peer at height N+1: serves blocks 1..N+1, and says its tip is N
    (block N+1 is only the carrier of the commit that seals N)."""

    def __init__(self, chain: dict):
        self.chain = chain
        self.banned: list = []

    def max_height(self) -> int:
        return self.chain["n_blocks"]

    def fetch(self, height: int):
        with TraceAnnotation("bench.fetch"):
            if not 1 <= height <= self.chain["n_blocks"] + 1:
                return None
            return (self.chain["blocks"][height - 1],
                    self.chain["block_ids"][height - 1])

    def ban(self, height: int) -> None:
        self.banned.append(height)


class TamperingPeer(Peer):
    """Serves the commit that seals `bad_height` with one signature's s
    altered (structurally valid: only the equation fails, so the lane
    has to be attributed), and goes on doing so after a ban."""

    def __init__(self, chain: dict, bad_height: int, bad_index: int):
        super().__init__(chain)
        self.bad_height, self.bad_index = bad_height, bad_index
        self.altered = None     # (pub index, message, altered signature)

    def fetch(self, height: int):
        got = super().fetch(height)
        if got is None or height != self.bad_height + 1:
            return got
        from cometbft_tpu.types.block import Block, Commit, CommitSig
        block, block_id = got
        lc = block.last_commit
        sigs = list(lc.signatures)
        cs = sigs[self.bad_index]
        bad = ed25519_ref.tamper(cs.signature)
        sigs[self.bad_index] = CommitSig(
            cs.block_id_flag, cs.validator_address, cs.timestamp, bad)
        self.altered = (lc, self.bad_index, bad)
        return Block(header=block.header, data=block.data,
                     last_commit=Commit(lc.height, lc.round, lc.block_id,
                                        sigs)), block_id


class Session:
    def __init__(self, config: dict, payload: dict, batch: int, seed: int):
        self.config, self.payload = config, payload
        self.batch, self.seed = batch, seed
        self.main = self.node(payload["main"], Peer(payload["main"]))

    def node(self, chain: dict, peer) -> dict:
        from cometbft_tpu.abci.kvstore import KVStoreApplication
        from cometbft_tpu.config import BlockSyncConfig
        from cometbft_tpu.db.kv import MemDB
        from cometbft_tpu.engine.blocksync import BlocksyncReactor
        from cometbft_tpu.pipeline.cache import shared_cache
        from cometbft_tpu.pipeline.watchdog import DeviceWatchdog
        from cometbft_tpu.state.execution import BlockExecutor
        from cometbft_tpu.state.state import State, StateStore
        from cometbft_tpu.store.blockstore import BlockStore
        app = KVStoreApplication()
        app.init_chain(chain["chain_id"], 1, [], b"")
        db = MemDB()
        store = BlockStore(db)
        executor = BlockExecutor(app, state_store=StateStore(db),
                                 block_store=store)
        depth = BlockSyncConfig().pipeline_depth if self.batch > 0 else 1
        watchdog = DeviceWatchdog() if depth > 1 else None
        reactor = BlocksyncReactor(
            executor, store, peer, chain["chain_id"],
            tile_size=self.config["tile_size"], batch_size=self.batch,
            pipeline_depth=depth, watchdog=watchdog, cache=shared_cache())
        return {"reactor": reactor, "store": store, "app": app,
                "watchdog": watchdog, "peer": peer, "chain": chain,
                "state": State.from_genesis(chain["genesis"])}


def build(config: dict, traffic: dict, payload: dict, boot: dict,
          seed: int) -> Session:
    """Build the window's reactor, and take a throwaway chain through
    the same entry once, so that threads, lazy imports and the device
    path's first transfer are paid in set-up."""
    session = Session(config, payload, boot["batch"], seed)
    warm_chain = payload["warmup"]
    node = session.node(warm_chain, Peer(warm_chain))
    state = node["reactor"].sync(node["state"])
    if state.last_block_height != warm_chain["n_blocks"]:
        raise RuntimeError("the warm-up sync fell short")
    return session


def _tile_lanes(chain: dict, tile: int):
    lanes = []
    n = chain["n_blocks"]
    for lo in range(1, n + 1, tile):
        lanes.append(sum(
            1 for h in range(lo, min(lo + tile, n + 1))
            for cs in chain["blocks"][h].last_commit.signatures
            if not cs.absent_()))
    return lanes


def window(session: Session, seconds: float) -> dict:
    node = session.main
    chain = node["chain"]
    tile_lanes = _tile_lanes(chain, session.config["tile_size"])
    n_sigs = sum(tile_lanes)
    before = node_boot.device_counters()
    t0 = time.perf_counter()
    from cometbft_tpu.engine.blocksync import SyncStalled
    from cometbft_tpu.state.execution import BlockValidationError
    with TraceAnnotation("bench.sync"):
        try:
            node["state"] = node["reactor"].sync(node["state"])
        except (BlockValidationError, SyncStalled) as exc:
            # a sync that gives up is judged by how far it got
            print(f"[window] sync gave up: {exc!r}", flush=True)
            stored = node["reactor"].executor.state_store.load()
            if stored is not None:
                node["state"] = stored
    elapsed = time.perf_counter() - t0
    counters = node_boot.delta(before, node_boot.device_counters(),
                               SIGCACHE_PATH)
    wd = node["watchdog"]
    counters["watchdog_trips"] = wd.trips if wd else 0
    counters["cpu_drains"] = wd.fallbacks if wd else 0
    counters["implied_chunks"] = node_boot.implied_chunks(
        tile_lanes, session.batch)
    msg_len = len(chain["blocks"][1].last_commit.vote_sign_bytes(
        chain["chain_id"], 0))
    return {
        "end_to_end": {"catchup_sigs_per_s": stats.rate(n_sigs, elapsed)},
        "attempted": chain["n_blocks"],
        "failed": chain["n_blocks"] - node["state"].last_block_height,
        "counters": counters,
        "facts": {"window_s": elapsed, "lanes": n_sigs,
                  "hash_blocks": n_sigs * node_boot.hash_blocks(msg_len),
                  "tiles": len(tile_lanes), "blocks": chain["n_blocks"],
                  "calls": 1},
    }


def _reference_lane(chain: dict, commit, idx: int, sig: bytes):
    """(pub, message, signature) of one lane, the message from the
    benchmark's own encoder."""
    cs = commit.signatures[idx]
    pub = next(v.pub_key.bytes_() for v in chain["genesis"].validators
               if v.address == cs.validator_address)
    bid = commit.block_id
    msg = canonical_vote.precommit_sign_bytes(
        chain["chain_id"], commit.height, commit.round, bid.hash,
        bid.parts.total, bid.parts.hash, cs.timestamp.seconds,
        cs.timestamp.nanos)
    return pub, msg, sig


def judge(session: Session, result: dict, compiles: int) -> list:
    """Every number compared, as (name, value, limit): all are exact
    comparisons, so every limit is 0."""
    node, chain = session.main, session.main["chain"]
    n = chain["n_blocks"]
    state, store, rng = node["state"], node["store"], random.Random(
        session.seed)
    c = result["counters"]
    want = kv_replay.replay(chain["tx_lists"])
    have = node["app"].state
    heights = sorted(set(rng.sample(range(1, n + 1), min(32, n)) + [n]))
    lanes = [(rng.randrange(1, n + 1),
              rng.randrange(chain["n_validators"])) for _ in range(512)]
    ref_rejects = signbytes_diff = 0
    for h, idx in lanes:
        commit = chain["blocks"][h].last_commit      # seals height h
        pub, msg, sig = _reference_lane(chain, commit, idx,
                                        commit.signatures[idx].signature)
        ref_rejects += not ed25519_ref.verify(pub, msg, sig)
        signbytes_diff += msg != commit.vote_sign_bytes(chain["chain_id"],
                                                        idx)
    checks = [
        ("height_short", n - state.last_block_height, 0),
        ("store_short", n - store.height(), 0),
        ("sigs_unverified",
         result["facts"]["lanes"] - node["reactor"].stats.sigs_verified, 0),
        ("respeculations", node["reactor"].stats.respeculations, 0),
        ("app_state_diff", len(set(want.items()) ^ set(have.items())), 0),
        ("app_hash_diff", int(state.app_hash != chain["app_hash"]), 0),
        ("block_hash_diff", sum(
            1 for h in heights
            if (store.load_block(h) is None or store.load_block(h).hash()
                != chain["block_ids"][h - 1].hash)), 0),
        ("ref_rejects", ref_rejects, 0),
        ("signbytes_diff", signbytes_diff, 0),
        ("window_compiles", compiles, 0),
        ("sigcache_hits", c["sigcache_hits"], 0),
        ("watchdog_trips", c["watchdog_trips"], 0),
        ("cpu_drains", c["cpu_drains"], 0),
        ("pallas_degraded", c["pallas_degraded"], 0),
        ("canary_trips", c["canary_trips"], 0),
        ("dispatch_gap", abs(c["dispatches"] - c["implied_chunks"]), 0),
    ]
    return checks + _tamper_probe(session)


def _tamper_probe(session: Session) -> list:
    """Verify-before-apply through the same entry, after the window: a
    peer that serves one altered signature mid-tile must be banned and
    the block it seals never applied."""
    from cometbft_tpu.engine.blocksync import SyncStalled
    from cometbft_tpu.state.execution import BlockValidationError
    chain = session.payload["probe"]
    bad = session.payload["probe_bad_height"]
    peer = TamperingPeer(chain, bad, session.payload["probe_bad_index"])
    node = session.node(chain, peer)
    refused = 0
    try:
        node["reactor"].sync(node["state"])
    except (BlockValidationError, SyncStalled):
        refused = 1
    ref_accepts = 1
    if peer.altered is not None:
        commit, idx, sig = peer.altered
        ref_accepts = int(ed25519_ref.verify(
            *_reference_lane(chain, commit, idx, sig)))
    wd = node["watchdog"]
    return [
        ("tamper_applied", max(0, node["store"].height() - (bad - 1)), 0),
        ("tamper_good_prefix_short",
         max(0, (bad - 1) - node["store"].height()), 0),
        ("tamper_not_refused", 1 - refused, 0),
        ("tamper_not_banned", int(not peer.banned), 0),
        ("tamper_ref_accepts", ref_accepts, 0),
        ("tamper_watchdog_trips", wd.trips if wd else 0, 0),
    ]


# --- faults for the control runs (tools/control_runs.py) -------------------------

def _plant_verify_lanes(make):
    """Replace the lane verifier under both the pipelined backend
    (pipeline/scheduler looks `verify_lanes` up at call time) and the
    synchronous tile verifier; returns the undo."""
    from cometbft_tpu.engine import blocksync
    from cometbft_tpu.pipeline import scheduler
    real = blocksync.verify_lanes
    fake = make(real)
    blocksync.verify_lanes = scheduler.verify_lanes = fake

    def undo():
        blocksync.verify_lanes = scheduler.verify_lanes = real
    return undo


def _accept_all(real):
    import numpy as np
    return lambda pubs, msgs, sigs, batch: np.ones(len(pubs), dtype=bool)


def _half_lanes(real):
    import numpy as np

    def half(pubs, msgs, sigs, batch):
        k = len(pubs) // 2
        return np.concatenate([real(pubs[:k], msgs[:k], sigs[:k], batch),
                               np.ones(len(pubs) - k, dtype=bool)])
    return half


PLANTS = {
    # the control: verify-before-apply broken, every lane taken for good
    "accept_all": lambda: _plant_verify_lanes(_accept_all),
    # half of every tile's lanes left out of the verification
    "half_lanes": lambda: _plant_verify_lanes(_half_lanes),
}
