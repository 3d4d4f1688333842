"""Driver `blocksync_churn`: the window is ONE continuous
`BlocksyncReactor.sync` of a fresh node to the tip of a chain it has
never seen, whose validator set changes on the way, served by a local
peer that lies once.

The reactor is `blocksync_sync.Session.node`'s, unchanged: built as
`Node._sync_then_consensus` builds it (tile size from the configuration,
lane bucket `Node._device_batch_size()`, `BlockSyncConfig().pipeline_depth`,
a `DeviceWatchdog`, the in-process backend, `cache=shared_cache()`), the
kernels `prewarm_verify_kernels` warms and no others. What differs from
`blocksync_sync` is the traffic and therefore the judge, which is why
the configuration names a driver of its own (benchmark/README.md,
"Adding things"):

- the chain (generator `churn_chain`) carries `val:` transactions, so the
  set in force changes every `update_period` heights. The program then
  stops speculating at the change, drains its pipeline, verifies the rest
  of the broken tile commit by commit through `verify_commit`
  (`SyncStats.respeculations` > 0, spans `pipeline.barrier` and
  `pipeline.respeculate`), and resumes from the new set;
- the peer alters `s` of signature `bad_index` in the commit sealing
  `bad_height` until it has been banned once and serves the honest block
  afterwards. The chunk that holds the lane fails its RLC equation and
  goes through per-lane attribution (`ops.ed25519.batch_stats()`), the
  block is refused, the peer banned, the tiles in flight cancelled
  (`pipeline.ban`), and the re-served tile meets the verified-signature
  cache: hits on path `blocksync` are EXPECTED here, where
  `blocksync_sync` takes one hit for a fault.

What `judge` holds the run to, every limit 0, on what the timed sync
itself produced:

- tip and store reached; application state, the final validator set and
  every height's `validators_hash` equal `reference/valset_replay.py`'s
  replay of the chain's transactions; app hash and 33 seed-drawn stored
  block hashes equal the generator's;
- 512 seed-drawn lanes accepted by `reference/ed25519_ref.py` with the
  public key taken from the REPLAYED set in force at that height,
  sign-bytes equal to the benchmark's own encoder's;
- the peer banned exactly once, at `bad_height`; the commit stored for
  that height carries the honest signature, the altered one is nowhere
  in the store, the reference rejects the altered lane and accepts the
  honest one;
- every signature of every applied commit was given a verdict
  (`sigs_verified` + `respeculated_sigs` >= signatures applied); what
  was settled beyond that is the refused rest of the banned tile, and
  the cache answers on its second serving only: 0 < hits <= that excess
  less the altered lane (fewer is no fault: the commit sealing the block
  that CARRIED the lie was refused unverified the first time, its block
  id not being the one signed); on the chip the lanes the batch loop saw
  are the lanes the cache missed plus the synchronous route's;
- attribution ran for as many chunks as held an altered lane (1 where
  there is a device path); 0 compiles in the window, watchdog trips, CPU
  drains, canary trips; `pallas_degraded` false; every chunk the batch
  loop cut went to the Pallas kernel.

It does NOT pin how many commits took the synchronous route nor where
tiles start: a later PR may verify a changed set without that route and
must stay `correct`. `PLANTS` are `blocksync_sync`'s: `accept_all` stores
the altered commit and never bans; `half_lanes` sends half of the lanes
the apply path claims (and, where the altered lane is in the upper half
of its tile, never bans either). `warm` is `node_boot.boot`."""

from __future__ import annotations

import random
import time

from jax.profiler import TraceAnnotation

from benchmark.drivers import blocksync_sync, node_boot
from benchmark.harness import stats
from benchmark.reference import canonical_vote, ed25519_ref, valset_replay

# A tree from before PR 29 has none of the counters this judge compares:
# there this import fails, and with it the run, at once and before the
# minutes of kernel tracing.
from cometbft_tpu.ops.ed25519 import batch_stats

SIGCACHE_PATH = "blocksync"
PLANTS = blocksync_sync.PLANTS
# `node_boot.boot` itself, not a function of this file around it: with a
# frame of the driver on the stack under which the kernels are traced,
# `prewarm_s` read 124-129 s where the other cells read 80-84 s (my chip
# runs, PR 29, calls 1-3; `PERF.md` §6)
warm = node_boot.boot


class LyingOncePeer(blocksync_sync.TamperingPeer):
    """Serves the altered commit until it has been banned once, the
    honest block afterwards."""

    def fetch(self, height: int):
        if self.banned:
            return blocksync_sync.Peer.fetch(self, height)
        return super().fetch(height)


class Session(blocksync_sync.Session):
    def __init__(self, config: dict, payload: dict, batch: int, seed: int):
        self.config, self.payload = config, payload
        self.batch, self.seed = batch, seed
        chain = payload["main"]
        self.main = self.node(chain, LyingOncePeer(
            chain, payload["bad_height"], payload["bad_index"]))


def build(config: dict, traffic: dict, payload: dict, boot: dict,
          seed: int) -> Session:
    """The window's reactor, and a throwaway chain with one set change
    through the same entry once: threads, lazy imports, the device
    path's first transfer and the synchronous route's first call are
    paid in set-up."""
    session = Session(config, payload, boot["batch"], seed)
    warm_chain = payload["warmup"]
    node = session.node(warm_chain, blocksync_sync.Peer(warm_chain))
    state = node["reactor"].sync(node["state"])
    if state.last_block_height != warm_chain["n_blocks"]:
        raise RuntimeError("the warm-up sync fell short")
    return session


def window(session: Session, seconds: float) -> dict:
    from cometbft_tpu.engine.blocksync import SyncStalled
    from cometbft_tpu.state.execution import BlockValidationError
    node = session.main
    chain, reactor = node["chain"], node["reactor"]
    n = chain["n_blocks"]
    n_sigs = sum(1 for h in range(1, n + 1)
                 for cs in chain["blocks"][h].last_commit.signatures
                 if not cs.absent_())
    before = node_boot.device_counters()
    batch_before = batch_stats()
    t0 = time.perf_counter()
    with TraceAnnotation("bench.sync"):
        try:
            node["state"] = reactor.sync(node["state"])
        except (BlockValidationError, SyncStalled) as exc:
            # a sync that gives up is judged by how far it got
            print(f"[window] sync gave up: {exc!r}", flush=True)
            stored = reactor.executor.state_store.load()
            if stored is not None:
                node["state"] = stored
    elapsed = time.perf_counter() - t0
    after = node_boot.device_counters()
    counters = node_boot.delta(before, after, SIGCACHE_PATH)
    wd = node["watchdog"]
    counters["watchdog_trips"] = wd.trips if wd else 0
    counters["cpu_drains"] = wd.fallbacks if wd else 0
    for key, now in batch_stats().items():
        counters["batch_" + key] = now - batch_before[key]
    # the program's own count of the bucket-wide chunks its flushes were
    # cut into: what `pallas_dispatch_share` holds the dispatches against
    counters["implied_chunks"] = counters["batch_chunks"]
    # the synchronous route looks its lanes up on the cache's other path
    counters["sigcache_hits_commit"] = (
        after["sigcache_hits"].get("commit", 0)
        - before["sigcache_hits"].get("commit", 0))
    st = reactor.stats
    counters.update(
        tile_sigs=st.sigs_verified, respeculations=st.respeculations,
        respeculated_sigs=st.respeculated_sigs, bans=st.bans)
    msg_len = len(chain["blocks"][1].last_commit.vote_sign_bytes(
        chain["chain_id"], 0))
    return {
        "end_to_end": {"catchup_sigs_per_s": stats.rate(n_sigs, elapsed)},
        "attempted": n,
        "failed": n - node["state"].last_block_height,
        "counters": counters,
        "facts": {"window_s": elapsed, "lanes": n_sigs,
                  "hash_blocks": n_sigs * node_boot.hash_blocks(msg_len),
                  "blocks": n, "calls": 1,
                  "set_changes": len(chain["update_blocks"]),
                  "attributed_lanes": counters["batch_attributed_lanes"],
                  "attributed_hash_blocks":
                      counters["batch_attributed_lanes"]
                      * node_boot.hash_blocks(msg_len)},
    }


def _lane(chain: dict, replayed, commit, idx: int, sig: bytes):
    """(pub, message, signature) of one lane: the key from the replayed
    set in force at the commit's height, the message from the
    benchmark's own encoder."""
    pub, _power = replayed.members(commit.height)[idx]
    cs, bid = commit.signatures[idx], commit.block_id
    msg = canonical_vote.precommit_sign_bytes(
        chain["chain_id"], commit.height, commit.round, bid.hash,
        bid.parts.total, bid.parts.hash, cs.timestamp.seconds,
        cs.timestamp.nanos)
    return pub, msg, sig


def judge(session: Session, result: dict, compiles: int) -> list:
    """Every number compared, as (name, value, limit): all are exact
    comparisons, so every limit is 0."""
    node, chain = session.main, session.main["chain"]
    peer, store, state = node["peer"], node["store"], node["state"]
    n, c = chain["n_blocks"], result["counters"]
    bad_h, bad_i = peer.bad_height, peer.bad_index
    rng = random.Random(session.seed)
    replayed = valset_replay.replay(chain["genesis_members"],
                                    chain["tx_lists"])
    heights = sorted(set(rng.sample(range(1, n + 1), min(32, n)) + [n]))
    stored = {h: store.load_block(h) for h in heights}

    have_set = [(v.pub_key.bytes_(), v.voting_power)
                for v in state.validators.validators]
    hash_diff = sum(
        1 for h in range(1, n + 1)
        if chain["blocks"][h - 1].header.validators_hash
        != replayed.validators_hash(h))
    hash_diff += sum(
        1 for h in heights
        if stored[h] is None or stored[h].header.validators_hash
        != replayed.validators_hash(h))

    ref_rejects = signbytes_diff = 0
    for _ in range(512):
        h, idx = rng.randrange(1, n + 1), rng.randrange(
            chain["n_validators"])
        commit = chain["blocks"][h].last_commit      # seals height h
        pub, msg, sig = _lane(chain, replayed, commit, idx,
                              commit.signatures[idx].signature)
        ref_rejects += not ed25519_ref.verify(pub, msg, sig)
        signbytes_diff += msg != commit.vote_sign_bytes(chain["chain_id"],
                                                        idx)

    # the lie: served once, refused, never stored
    honest = chain["blocks"][bad_h].last_commit
    honest_sig = honest.signatures[bad_i].signature
    altered_sig = peer.altered[2] if peer.altered else None
    kept = (store.load_block_commit(bad_h), store.load_seen_commit(bad_h),
            getattr(store.load_block(bad_h + 1), "last_commit", None))
    altered_stored = sum(
        1 for commit in kept if commit is not None
        and commit.signatures[bad_i].signature != honest_sig)
    honest_missing = int(
        kept[1] is None
        or kept[1].signatures[bad_i].signature != honest_sig)
    ref_accepts_altered = int(altered_sig is None or ed25519_ref.verify(
        *_lane(chain, replayed, honest, bad_i, altered_sig)))
    ref_rejects_honest = int(not ed25519_ref.verify(
        *_lane(chain, replayed, honest, bad_i, honest_sig)))

    # every signature given a verdict; the excess is the refused rest of
    # the banned tile, and only there can the cache answer
    applied_sigs = result["facts"]["lanes"]
    respec = c["respeculated_sigs"]
    excess = c["tile_sigs"] + respec - applied_sigs
    altered_lanes = 1 if peer.altered else 0
    sent = (c["sigcache_misses"] + respec - c["sigcache_hits_commit"])
    on_device = session.batch > 0
    return [
        ("height_short", n - state.last_block_height, 0),
        ("store_short", n - store.height(), 0),
        ("app_state_diff", len(set(replayed.app_state.items())
                               ^ set(node["app"].state.items())), 0),
        ("app_hash_diff", int(state.app_hash != chain["app_hash"]), 0),
        ("block_hash_diff", sum(
            1 for h in heights
            if (stored[h] is None
                or stored[h].hash() != chain["block_ids"][h - 1].hash)), 0),
        ("final_valset_diff", int(have_set != replayed.members(n + 1)), 0),
        ("valset_hash_diff", hash_diff, 0),
        ("set_changes_off", int(
            replayed.change_heights()
            != [b + 2 for b in chain["update_blocks"]]), 0),
        ("ref_rejects", ref_rejects, 0),
        ("signbytes_diff", signbytes_diff, 0),
        ("bans_off", abs(len(peer.banned) - 1), 0),
        ("ban_height_off", sum(abs(h - bad_h) for h in peer.banned), 0),
        ("bans_uncounted", int(c["bans"] != len(peer.banned)), 0),
        ("altered_stored", altered_stored, 0),
        ("honest_missing", honest_missing, 0),
        ("ref_accepts_altered", ref_accepts_altered, 0),
        ("ref_rejects_honest", ref_rejects_honest, 0),
        ("sigs_unverified", max(0, -excess), 0),
        ("respeculated_uncounted",
         int((c["respeculations"] > 0) != (respec > 0)), 0),
        ("sigcache_hits_beyond_reserved", max(
            0, c["sigcache_hits"] - max(0, excess - altered_lanes)), 0),
        ("sigcache_no_hits", int(c["sigcache_hits"] <= 0), 0),
        ("device_lanes_off", abs(sent - c["batch_lanes"])
         if on_device else 0, 0),
        ("attribution_runs_off", abs(
            c["batch_attributed_chunks"] - (altered_lanes if on_device
                                            else 0)), 0),
        ("window_compiles", compiles, 0),
        ("watchdog_trips", c["watchdog_trips"], 0),
        ("cpu_drains", c["cpu_drains"], 0),
        ("pallas_degraded", c["pallas_degraded"], 0),
        ("canary_trips", c["canary_trips"], 0),
        ("dispatch_gap", abs(c["dispatches"] - c["implied_chunks"]), 0),
    ]
