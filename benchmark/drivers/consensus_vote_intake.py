"""Driver `consensus_vote_intake`: ONE real `ConsensusState`, a validator
of a chain whose other validators this driver plays, closed loop, one
height after the other until the window's seconds have passed (or the
stream, sized at about 1.5 times what the window holds, runs out:
`stream_exhausted`, printed).

The node has synced the chain's `history` (the generator says why there
is one) and is built as `Node` builds its consensus (node/node.py): the
node's own `ConsensusTimeoutsConfig` defaults (`timeout_commit` 1000 ms,
`skip_timeout_commit` true), a file `WAL` and a `FilePV` under the run's
output directory (`<checkout>/benchmark_out/validator/`), a
`BlockExecutor` over the kvstore with block store, state store and
evidence pool, the process-wide `shared_cache()`; started with `start()`,
so its own receive routine and ticker threads run. Messages enter at
`ConsensusState.send`, what `ConsensusReactor.receive` calls after
decoding.

Per height the driver sends, from the peer ids in turn: the proposal and
its block parts; the other validators' prevotes in the generator's
seed-drawn order, cut into its bursts; their precommits likewise. A
burst is put into the inbox message by message without waiting; the next
is sent when `consensus.state.intake_stats()["votes_handled"]` says the
last was consumed. The bursts after +2/3 of the precommits go to the
node's `last_commit` while it waits for all of them
(`skip_timeout_commit`); the last one starts the next height's round.

One operation is one HEIGHT: host clock from the `send` of the height's
proposal to the node's `on_commit` for it, i.e. the time this validator
needs to come by a commit it has verified itself (proposal check,
`validate_block` with `verify_commit` of the previous commit, its own two
votes with their fsyncs, both intakes, finalize and apply).

What `judge` holds a run to, every limit 0, on what the timed window
itself produced (the docstring of `judge` lists the checks), and then a
probe height through the same entry: a burst of precommits with one
altered signature in the upper half of its lanes, an exact duplicate, a
conflicting prevote, a vote signed by a key outside the set, and
`verify_commit` of a `last_commit` with three lanes the node never saw as
votes. It does NOT pin where the inbox cuts its runs, how many flushes a
height takes, nor how often the node validates a block: a later PR may
change each and must stay `correct`. `PLANTS` are the hub driver's:
under `accept_all` and `half_lanes` the altered precommit is admitted.
`warm` is `node_boot.boot` itself (`PERF.md` §6, PR 29: a frame of this
file on the stack under which the kernels are traced costs 40 s)."""

from __future__ import annotations

import os
import random
import shutil
import time

from jax.profiler import TraceAnnotation

from benchmark.drivers import node_boot, verify_commit_loop
from benchmark.harness import stats
from benchmark.reference import ed25519_ref, kv_replay, vote_tally

# A tree from before PR 34 has neither this counter nor the intake it
# counts: there this import fails, and with it the run, at once and
# before the minutes of kernel tracing.
from cometbft_tpu.consensus.state import (BlockPartMessage, ProposalMessage,
                                          VoteMessage, intake_stats)
from cometbft_tpu.ops.ed25519 import batch_stats
from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, Block, BlockID,
                                      Commit, CommitSig, Part, PartSet,
                                      PartSetHeader)
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.vote import Proposal, Vote

PLANTS = verify_commit_loop.PLANTS
warm = node_boot.boot
OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark_out", "validator")
STUCK_S = 20.0          # a burst or a commit that takes longer has failed
POLL_S = 0.00005
OTHER_BLOCK = (b"\xb4" * 32, 1, b"\x4b" * 32)


def _counters() -> dict:
    """Everything the window's counters are deltas of."""
    out = node_boot.device_counters()
    out["intake"] = intake_stats()
    out["batch"] = batch_stats()
    return out


class Session:
    def __init__(self, config: dict, payload: dict, batch: int, seed: int):
        from cometbft_tpu.abci.kvstore import KVStoreApplication
        from cometbft_tpu.config import ConsensusTimeoutsConfig
        from cometbft_tpu.consensus.state import (ConsensusConfig,
                                                  ConsensusState)
        from cometbft_tpu.consensus.wal import WAL
        from cometbft_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
        from cometbft_tpu.db.kv import MemDB
        from cometbft_tpu.evidence.pool import EvidencePool
        from cometbft_tpu.privval.file import FilePV
        from cometbft_tpu.state.execution import BlockExecutor
        from cometbft_tpu.state.state import GenesisDoc, State, StateStore
        from cometbft_tpu.store.blockstore import BlockStore
        from cometbft_tpu.types.validator import Validator
        self.config, self.payload = config, payload
        self.batch, self.seed = batch, seed
        self.chain_id = payload["chain_id"]
        self.n_peers = payload["node_index"]        # the validators played
        self.peer_ids = [f"peer{k:02d}" for k in range(config["peers"])]
        self.dir = os.path.join(OUT_DIR, f"{seed}-{os.getpid()}")
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)

        genesis = GenesisDoc(
            chain_id=self.chain_id,
            validators=[Validator(Ed25519PubKey(p), power) for p, power
                        in zip(payload["pubs"], payload["powers"])],
            genesis_time=Timestamp(payload["genesis_seconds"], 0))
        state = State.from_genesis(genesis)
        self.members = state.validators.validators
        if [v.pub_key.bytes_() for v in self.members] != payload["pubs"]:
            raise RuntimeError("the validator set orders its members "
                               "otherwise than the generator did")
        self.app = KVStoreApplication()
        self.app.init_chain(self.chain_id, genesis.initial_height, [], b"")
        self.store = BlockStore(MemDB())
        state_store = StateStore(MemDB())
        state_store.save(state)
        pool = EvidencePool(state_store=state_store, block_store=self.store)
        executor = BlockExecutor(self.app, state_store=state_store,
                                 block_store=self.store, evidence_pool=pool)
        for row in payload["history"]:
            state = self._apply_synced(executor, state, row)
        cc = ConsensusTimeoutsConfig()
        self.cs = ConsensusState(
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
            state, executor, self.store,
            priv_validator=FilePV(
                Ed25519PrivKey(payload["node_seed"]),
                os.path.join(self.dir, "priv_validator_state.json")),
            wal=WAL(os.path.join(self.dir, "cs.wal"),
                    head_size_limit=cc.wal_head_size_limit,
                    total_size_limit=cc.wal_total_size_limit),
            name="bench-validator")
        self.cs.evidence_pool = pool
        self.committed: dict = {}       # height -> (clock, commit round)
        self.cs.on_commit = self._on_commit
        self.sent = 0                   # peer votes put into the inbox
        self.handled0 = intake_stats()["votes_handled"]
        self.turn = 0
        self.heights = [self._height(row) for row in payload["heights"]]
        self.next = 0                   # index of the next height to play
        self.cs.start()

    def _apply_synced(self, executor, state, row: dict):
        """One height of the chain's history, applied as a node that
        synced it applies it (`verified=True`: nothing of it reaches the
        sigcache), block and seen commit stored."""
        header = PartSetHeader(row["parts_total"], row["parts_hash"])
        parts = PartSet.new_from_header(header)
        for raw in row["parts"]:
            parts.add_part(Part.decode(raw))
        block = Block.decode(parts.reassemble())
        bid = BlockID(row["block_hash"], header)
        commit = Commit(
            height=row["height"], round=0, block_id=bid,
            signatures=[CommitSig(BLOCK_ID_FLAG_COMMIT, v.address,
                                  Timestamp(row["seconds"], i), sig)
                        for i, (v, sig) in enumerate(zip(
                            self.members, row["precommit_sigs"]))]
            + [CommitSig.absent()])
        self.store.save_block(block, parts, commit)
        new_state, _resp = executor.apply_block(state, bid, block,
                                                verified=True)
        return new_state

    def _on_commit(self, block, commit) -> None:
        self.committed[block.header.height] = (time.perf_counter(),
                                               commit.round)

    # --- the traffic, as the program's messages -----------------------------

    def vote(self, row: dict, type_: int, index: int, signature=None,
             block=None, nanos=None):
        """A validator's vote of this height as the program's message:
        the generator's signature over the height's block at the
        stream's timestamp, unless given otherwise."""
        if block is None:
            block = (row["block_hash"], row["parts_total"],
                     row["parts_hash"])
        if signature is None:
            key = "prevote_sigs" if type_ == vote_tally.PREVOTE \
                else "precommit_sigs"
            signature = row[key][index]
        return VoteMessage(Vote(
            type_=type_, height=row["height"], round=0,
            block_id=BlockID(block[0], PartSetHeader(block[1], block[2])),
            timestamp=Timestamp(row["seconds"],
                                index if nanos is None else nanos),
            validator_address=self.members[index].address,
            validator_index=index, signature=signature))

    def _height(self, row: dict) -> dict:
        """One height's inbox entries: the proposal and parts, then the
        bursts of either step, each entry a (message, peer id)."""
        h = row["height"]
        bid = BlockID(row["block_hash"], PartSetHeader(row["parts_total"],
                                                       row["parts_hash"]))
        head = [ProposalMessage(Proposal(
            height=h, round=0, pol_round=-1, block_id=bid,
            timestamp=Timestamp(row["proposal_seconds"],
                                row["proposal_nanos"]),
            signature=row["proposal_signature"]))]
        head += [BlockPartMessage(h, 0, Part.decode(p))
                 for p in row["parts"]]
        steps = []
        for type_, key in ((vote_tally.PREVOTE, "prevote_bursts"),
                           (vote_tally.PRECOMMIT, "precommit_bursts")):
            votes = [self.vote(row, type_, i) for i in range(self.n_peers)]
            steps.append([[(votes[i], self.peer_ids[peer])
                           for i, peer in burst] for burst in row[key]])
        return {"row": row, "head": head, "steps": steps}

    # --- sending --------------------------------------------------------------

    def _peer(self) -> str:
        self.turn = (self.turn + 1) % len(self.peer_ids)
        return self.peer_ids[self.turn]

    def send_burst(self, burst: list) -> bool:
        """Put a burst into the inbox without waiting, then wait until
        the node has consumed it. False where it did not."""
        send = self.cs.send
        for msg, peer in burst:
            send(msg, peer_id=peer)
        self.sent += len(burst)
        want = self.handled0 + self.sent
        deadline = time.perf_counter() + STUCK_S
        while intake_stats()["votes_handled"] < want:
            if time.perf_counter() > deadline:
                return False
            time.sleep(POLL_S)
        return True

    def wait_commit(self, height: int) -> bool:
        deadline = time.perf_counter() + STUCK_S
        while height not in self.committed:
            if time.perf_counter() > deadline:
                return False
            time.sleep(POLL_S)
        return True

    def play(self, entry: dict):
        """One height. Returns its latency in seconds, or None where the
        node did not commit it."""
        t0 = time.perf_counter()
        for msg in entry["head"]:
            self.cs.send(msg, peer_id=self._peer())
        for bursts in entry["steps"]:
            for burst in bursts:
                if not self.send_burst(burst):
                    return None
        h = entry["row"]["height"]
        if not self.wait_commit(h):
            return None
        return self.committed[h][0] - t0

    def wal_counts(self) -> dict:
        """Stops the node and counts its WAL's records by kind (every
        record is flushed as it is written)."""
        from cometbft_tpu.consensus.wal import (EndHeightMessage,
                                                WALTimeout, WALVote)
        self.cs.stop()
        counts = {"peer_votes": 0, "own_votes": 0, "timeouts": 0, "ends": 0}
        for msg in self.cs.wal.iter_messages():
            if isinstance(msg, WALVote):
                # (a WAL record does not keep the peer id)
                own = msg.vote.validator_index == self.n_peers
                counts["own_votes" if own else "peer_votes"] += 1
            elif isinstance(msg, WALTimeout):
                counts["timeouts"] += 1
            elif isinstance(msg, EndHeightMessage):
                counts["ends"] += 1
        self.cs.wal.close()
        shutil.rmtree(self.dir, ignore_errors=True)
        return counts


def build(config: dict, traffic: dict, payload: dict, boot: dict,
          seed: int) -> Session:
    """The window's node, started, and the stream's first heights
    through the same entry (not measured): threads, lazy imports, the
    device path's first transfer, the first fsyncs."""
    session = Session(config, payload, boot["batch"], seed)
    for _ in range(payload["warmup_heights"]):
        if session.play(session.heights[session.next]) is None:
            raise RuntimeError("the warm-up heights fell short")
        session.next += 1
    return session


def window(session: Session, seconds: float) -> dict:
    latencies, failed = [], 0
    stream_end = len(session.heights) - session.payload["probe_heights"]
    first = session.next
    before = _counters()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with TraceAnnotation("bench.heights"):
        while session.next < stream_end and time.perf_counter() < deadline:
            took = session.play(session.heights[session.next])
            if took is None:        # a node that is stuck stays stuck
                failed += 1
                print(f"[window] height "
                      f"{session.heights[session.next]['row']['height']} "
                      f"was not committed", flush=True)
                break
            latencies.append(took)
            session.next += 1
    elapsed = time.perf_counter() - t0
    after = _counters()
    played = len(latencies)
    counters = node_boot.delta(before, after, "vote")
    for path in ("commit",):
        counters[f"sigcache_hits_{path}"] = (
            after["sigcache_hits"].get(path, 0)
            - before["sigcache_hits"].get(path, 0))
        counters[f"sigcache_misses_{path}"] = (
            after["sigcache_misses"].get(path, 0)
            - before["sigcache_misses"].get(path, 0))
    for group in ("intake", "batch"):
        for key, now in after[group].items():
            counters[f"{group}_{key}"] = now - before[group][key]
    # the program's own count of the bucket-wide chunks its flushes were
    # cut into: the runs' boundaries are the inbox's, not the traffic's
    counters["implied_chunks"] = counters["batch_chunks"]
    counters["stream_exhausted"] = int(session.next >= stream_end)
    session.window = (first, session.next)
    row = session.payload["heights"][first]
    msg_len = len(vote_tally.vote_sign_bytes(
        session.chain_id, vote_tally.PRECOMMIT, row["height"], 0,
        (row["block_hash"], row["parts_total"], row["parts_hash"]),
        row["seconds"], 0))
    lanes = counters["intake_device_lanes"]
    ms = [s * 1e3 for s in latencies]
    return {
        "end_to_end": {"commit_verify_p50_ms": stats.percentile(ms, 50),
                       "commit_verify_p95_ms": stats.percentile(ms, 95)}
        if ms else {},
        "attempted": played + failed, "failed": failed,
        "counters": counters,
        "facts": {"window_s": elapsed, "lanes": lanes,
                  "hash_blocks": lanes * node_boot.hash_blocks(msg_len),
                  "calls": played, "heights": played,
                  "heights_per_s": played / elapsed,
                  "votes_sent": sum(len(b) for e in
                                    session.heights[first:session.next]
                                    for step in e["steps"] for b in step)},
    }


# --- the comparison that decides `correct` ----------------------------------------

def _deliveries(session: Session, row: dict, type_: int) -> list:
    """One step as the reference takes it: the node's own vote first
    (it votes as soon as the proposal is whole, or the prevotes have
    their +2/3, before the next message of its inbox), then the
    deliveries in the order sent."""
    key, sigs = (("prevote_bursts", "prevote_sigs")
                 if type_ == vote_tally.PREVOTE
                 else ("precommit_bursts", "precommit_sigs"))
    block = (row["block_hash"], row["parts_total"], row["parts_hash"])
    own = {"index": session.payload["node_index"], "block": block,
           "seconds": 0, "nanos": 0, "signature": b"", "own": True}
    return [own] + [
        {"index": i, "block": block, "seconds": row["seconds"], "nanos": i,
         "signature": row[sigs][i]} for burst in row[key] for i, _p in burst]


def _stored_commit_diff(session: Session, row: dict, deliveries: list) -> int:
    """Lanes of the stored seen commit that differ from what the
    reference's tally of the delivered precommits says it holds, plus
    the signatures of it that the reference does not accept."""
    p = session.payload
    seen = session.store.load_seen_commit(row["height"])
    if seen is None or seen.round != 0 or seen.block_id.hash != \
            row["block_hash"] or len(seen.signatures) != len(p["pubs"]):
        return len(p["pubs"])
    told = vote_tally.tally(session.chain_id, vote_tally.PRECOMMIT,
                            row["height"], 0, p["pubs"], p["powers"],
                            deliveries)
    block = (row["block_hash"], row["parts_total"], row["parts_hash"])
    diff = int(told["block"] != block)
    for i, cs in enumerate(seen.signatures):
        holds = told["holders"].get(i)
        if holds is None:
            diff += not cs.absent_()
            continue
        if holds.get("own"):
            seconds, nanos = cs.timestamp.seconds, cs.timestamp.nanos
        else:
            seconds, nanos = holds["seconds"], holds["nanos"]
            diff += cs.signature != holds["signature"]
        diff += not cs.for_block() or not ed25519_ref.verify(
            p["pubs"][i], vote_tally.vote_sign_bytes(
                session.chain_id, vote_tally.PRECOMMIT, row["height"], 0,
                block, seconds, nanos), cs.signature)
    return diff


def _probe(session: Session) -> dict:
    """The probe height, through the same entry. Returns what went
    wrong, by name."""
    from cometbft_tpu.types import validation
    p, cs = session.payload, session.cs
    entry = session.heights[session.next]
    row = entry["row"]
    h, n = row["height"], session.n_peers
    block = (row["block_hash"], row["parts_total"], row["parts_hash"])
    rng = random.Random(session.seed)
    signers = [ed25519_ref.Signer(s) for s in p["signer_seeds"]]
    out = {}

    def signed(type_, index, signer, blk=block, nanos=None):
        nn = index if nanos is None else nanos
        sig = signer.sign(vote_tally.vote_sign_bytes(
            session.chain_id, type_, h, 0, blk, row["seconds"], nn))
        return session.vote(row, type_, index, signature=sig, block=blk,
                            nanos=nanos)

    def sent(burst) -> bool:
        return session.send_burst([(m, session._peer()) for m in burst])

    for msg in entry["head"]:
        cs.send(msg, peer_id=session._peer())
    order = list(range(n))
    rng.shuffle(order)
    quorum_at = next(k for k in range(1, n + 1) if sum(
        p["powers"][i] for i in order[:k]) + p["powers"][n]
        >= sum(p["powers"]) * 2 // 3 + 1)
    j, outsider = order[0], ed25519_ref.Signer(b"\x5a" * 32)
    prevotes = [session.vote(row, vote_tally.PREVOTE, i) for i in order]
    evidence0 = len(cs.conflicting_votes)
    ok = sent([prevotes[0],
               session.vote(row, vote_tally.PREVOTE, j),      # duplicate
               signed(vote_tally.PREVOTE, j, signers[j],
                      blk=OTHER_BLOCK),                       # conflict
               signed(vote_tally.PREVOTE, order[1], outsider)])
    prevote_set = cs.rs.votes.prevotes(0) if cs.rs.height == h else None
    out["probe_duplicate_counted"] = int(
        prevote_set is None or prevote_set.votes_bit_array.num_true_bits()
        != 2)       # the node's own and validator j's, nothing else
    out["probe_outsider_admitted"] = int(
        prevote_set is None
        or prevote_set.get_by_index(order[1]) is not None)
    found = cs.conflicting_votes[evidence0:]
    out["probe_conflict_evidence_off"] = int(
        len(found) != 1 or {found[0].vote_a.block_id.hash,
                            found[0].vote_b.block_id.hash}
        != {block[0], OTHER_BLOCK[0]}
        or found[0].vote_a.validator_index != j)
    ok = ok and sent(prevotes[1:])
    # a burst of precommits, one altered in the upper half of its lanes
    width = min(96, quorum_at - 1)
    bad_at = width // 2 + rng.randrange(width - width // 2)
    bad = order[bad_at]
    burst = [session.vote(row, vote_tally.PRECOMMIT, i)
             for i in order[:width]]
    altered = ed25519_ref.tamper(row["precommit_sigs"][bad])
    burst[bad_at] = session.vote(row, vote_tally.PRECOMMIT, bad,
                                 signature=altered)
    ok = ok and sent(burst)
    precommits = cs.rs.votes.precommits(0) if cs.rs.height == h else None
    out["probe_altered_admitted"] = int(
        precommits is None or precommits.get_by_index(bad) is not None)
    out["probe_good_lanes_refused"] = 0 if precommits is None else sum(
        1 for i in order[:width]
        if i != bad and precommits.get_by_index(i) is None)
    out["probe_ref_accepts_altered"] = int(ed25519_ref.verify(
        p["pubs"][bad], vote_tally.vote_sign_bytes(
            session.chain_id, vote_tally.PRECOMMIT, h, 0, block,
            row["seconds"], bad), altered))
    ok = ok and sent([session.vote(row, vote_tally.PRECOMMIT, i)
                      for i in order[width:] + [bad]])
    out["probe_height_uncommitted"] = int(
        not ok or not session.wait_commit(h))
    deliveries = _deliveries(session, row, vote_tally.PRECOMMIT)[:1] + [
        {"index": i, "block": block, "seconds": row["seconds"], "nanos": i,
         "signature": altered if (k == bad_at and i == bad)
         else row["precommit_sigs"][i]}
        for k, i in enumerate(order[:width] + order[width:] + [bad])]
    out["probe_commit_diff"] = _stored_commit_diff(session, row, deliveries)

    # `verify_commit` as `validate_block` calls it: the last commit with
    # three lanes the node never saw as votes (other timestamps)
    fresh = sorted(rng.sample(range(n), 3))
    bid = BlockID(block[0], PartSetHeader(block[1], block[2]))

    def commit(altered_lane=None):
        sigs = []
        for i in range(n):
            nanos, sig = i, row["precommit_sigs"][i]
            if i in fresh:
                nanos = 1000 + i
                sig = signers[i].sign(vote_tally.vote_sign_bytes(
                    session.chain_id, vote_tally.PRECOMMIT, h, 0, block,
                    row["seconds"], nanos))
                if i == altered_lane:
                    sig = ed25519_ref.tamper(sig)
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT,
                                  session.members[i].address,
                                  Timestamp(row["seconds"], nanos), sig))
        return Commit(height=h, round=0, block_id=bid,
                      signatures=sigs + [CommitSig.absent()])
    vals = cs.state.last_validators
    before = _counters()
    try:
        validation.verify_commit(session.chain_id, vals, bid, h, commit())
        out["probe_unseen_lanes_refused"] = 0
    except validation.CommitVerificationError:
        out["probe_unseen_lanes_refused"] = 1
    after = _counters()
    out["probe_commit_lanes_looked_up_off"] = abs(
        (after["sigcache_misses"].get("commit", 0)
         - before["sigcache_misses"].get("commit", 0)) - 3) + abs(
        (after["sigcache_hits"].get("commit", 0)
         - before["sigcache_hits"].get("commit", 0)) - (n - 3))
    out["probe_commit_on_device"] = (after["batch"]["lanes"]
                                     - before["batch"]["lanes"])
    wrong = fresh[rng.randrange(3)]
    try:
        validation.verify_commit(session.chain_id, vals, bid, h,
                                 commit(altered_lane=wrong))
        out["probe_altered_commit_accepted"] = 1
        out["probe_altered_commit_misattributed"] = 0
    except validation.ErrWrongSignature as exc:
        out["probe_altered_commit_accepted"] = 0
        out["probe_altered_commit_misattributed"] = int(exc.idx != wrong)
    except validation.CommitVerificationError:
        out["probe_altered_commit_accepted"] = 0
        out["probe_altered_commit_misattributed"] = 1
    return out


def judge(session: Session, result: dict, compiles: int) -> list:
    """Every number compared, as (name, value, limit): all exact, so
    every limit is 0. Of the window: every height sent was committed, in
    round 0, with no timeout moving a step (the WAL holds the one that
    started the node and no other); 16 seed-drawn heights: the stored
    block hash is the generator's, the stored seen commit holds exactly
    the precommits the reference's tally says were handled up to +2/3,
    each signature accepted by the reference; application state and app
    hash equal `kv_replay`'s and the generator's; votes handled = votes
    sent = peer votes in the WAL, two own votes and one end-of-height
    record a height; every valid distinct vote was a lane of the intake
    once (cache hits + flushed + left to the native check), what the
    device verified is what the intake flushed (no lane of a
    `last_commit` there: all of them cache hits, at least one
    `verify_commit` a height); dispatches = the program's own chunk
    count; 0 traces and compiles, canary trips, `pallas_degraded`
    false. Then the probe height (`_probe`)."""
    p, c = session.payload, result["counters"]
    first, end = session.window
    played = end - first
    rows = p["heights"][first:end]
    rng = random.Random(session.seed)
    round_nonzero = sum(1 for r in rows
                        if session.committed.get(r["height"],
                                                 (0, 1))[1] != 0)
    block_hash_diff = commit_diff = 0
    for r in rng.sample(rows, min(16, len(rows))):
        stored = session.store.load_block(r["height"])
        block_hash_diff += stored is None or \
            stored.hash() != r["block_hash"]
        commit_diff += _stored_commit_diff(
            session, r, _deliveries(session, r, vote_tally.PRECOMMIT))
    upto = p["history"] + p["heights"][:end]
    app_state_diff = len(
        set(kv_replay.replay([r["txs"] for r in upto]).items())
        ^ set(session.app.state.items()))
    app_hash_diff = int(not upto or session.cs.state.app_hash
                        != upto[-1]["app_hash"])
    distinct = 2 * session.n_peers * played
    lanes = (c["intake_cache_hits"] + c["intake_device_lanes"]
             + c["intake_native_lanes"])
    on_device = session.batch > 0
    probe = _probe(session)
    wal = session.wal_counts()
    heights_all = end + 1       # warm-up, window and the probe height
    votes_all = session.sent
    return [
        ("heights_failed", result["failed"], 0),
        ("heights_short", played - sum(
            1 for r in rows if r["height"] in session.committed), 0),
        ("round_nonzero", round_nonzero, 0),
        ("block_hash_diff", block_hash_diff, 0),
        ("stored_commit_diff", commit_diff, 0),
        ("app_state_diff", app_state_diff, 0),
        ("app_hash_diff", app_hash_diff, 0),
        ("votes_unhandled", abs(c["intake_votes_handled"]
                                - result["facts"]["votes_sent"]), 0),
        ("intake_lanes_off", abs(lanes - distinct), 0),
        ("commit_lanes_missed", c["sigcache_misses_commit"], 0),
        ("commit_hits_short", max(
            0, session.n_peers * played - c["sigcache_hits_commit"]), 0),
        ("device_lanes_off", abs(c["batch_lanes"]
                                 - c["intake_device_lanes"])
         if on_device else 0, 0),
        ("wal_peer_votes_off", abs(wal["peer_votes"] - votes_all), 0),
        ("wal_own_votes_off", abs(wal["own_votes"] - 2 * heights_all), 0),
        ("wal_end_heights_off", abs(wal["ends"] - heights_all), 0),
        ("timeouts_moved_a_step", max(0, wal["timeouts"] - 1), 0),
        ("window_compiles", compiles, 0),
        ("pallas_degraded", c["pallas_degraded"], 0),
        ("canary_trips", c["canary_trips"], 0),
        ("dispatch_gap", abs(c["dispatches"] - c["implied_chunks"]), 0),
    ] + sorted((name, value, 0) for name, value in probe.items())
