"""Generator `vote_stream`: a whole signed height stream for ONE validator
of a chain whose other validators the driver plays: per height the
block (as its encoded parts), the proposer's signed proposal, every
other validator's prevote and precommit signature, and the order, bursts,
redeliveries and peer ids in which the driver delivers them.

Built as `fresh_chain` builds a chain (blocks by `State.make_block`,
executed by the real `BlockExecutor` with `verified=True`, so nothing here
reaches the process-wide verified-signature cache, trap 1 of ISSUE 25),
with what a live validator's traffic needs besides:

- votes are signed by the plain reference (`cryptography` wheel) over
  sign-bytes from the benchmark's own CanonicalVote encoders
  (`reference/canonical_vote.py`, `reference/vote_tally.py`); a height's
  precommit signatures are also the `last_commit` of the next block, as
  on a real chain, so the node finds them in its sigcache when it
  validates that block;
- the node under test has the lowest voting power and is therefore last
  in the set; its own lane is ABSENT in every pre-built `last_commit`
  (its precommit carries a run-time timestamp). The rotation gives every
  validator one turn as proposer in a chain's first rounds, the
  lowest-powered last (height 150 of 150), and the node's next turn is
  14,901 rounds away: so the chain's `history`, the heights up to and
  with that turn, is handed over as blocks and commits, which the driver
  applies as a node that has synced to there, and the live stream starts
  behind it. The node never proposes inside the live stream: asserted
  below with the program's own proposer rotation, which is what lets
  every block be signed beforehand;
- the payload is plain data (bytes, numbers, lists): no object of the
  program, so no memo of it can ride along, and the node decodes every
  block from its parts as it would from the wire.

Every seed gives the same sizes: heights, validators, deliveries a step
(the redeliveries are drawn as one fixed count a step). Parameters
(traffic file): `heights_per_window_second`, `warmup_heights`,
`probe_heights`, `burst_log2_max`, `redelivered_share`, `peers`. From the
configuration: `validators`, `voting_power_others`, `voting_power_node`,
`txs_per_block`."""

from __future__ import annotations

import hashlib
import math
import random

from benchmark.reference import ed25519_ref, vote_tally

BASE_TIME = 1_700_000_000


def _digest(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


def step_bursts(rng: random.Random, n_votes: int, redelivered: int,
                log2_max: int, peers: int) -> list:
    """One step's deliveries, cut into bursts: each of the `n_votes`
    validators once, in a drawn order, and `redelivered` of them once
    more, from another peer, in a LATER burst than their first
    delivery. A burst's size is floor(2^u), u uniform on [0, log2_max),
    cut to what is left. Returns a list of bursts, each a list of
    (validator index, peer number)."""
    order = list(range(n_votes))
    rng.shuffle(order)
    again = set(rng.sample(order, redelivered))
    first_peer, waiting, bursts = {}, [], []
    turn = rng.randrange(peers)
    while order or waiting:
        size = min(int(2 ** rng.uniform(0, log2_max)),
                   len(order) + len(waiting))
        # of the redeliveries that wait, each joins this burst with
        # probability 1/2 (all of them once the first deliveries are out)
        joins = [i for i in waiting if not order or rng.random() < 0.5]
        joins = joins[:size if not order else max(0, size - 1)]
        fresh = order[:size - len(joins)]
        del order[:len(fresh)]
        waiting = [i for i in waiting if i not in joins]
        burst = []
        for i in fresh + joins:
            turn = (turn + 1) % peers
            if first_peer.get(i) == turn:           # another peer's copy
                turn = (turn + 1) % peers
            first_peer.setdefault(i, turn)
            burst.append((i, turn))
        rng.shuffle(burst)
        bursts.append(burst)
        waiting += [i for i in fresh if i in again]
    return bursts


def build_stream(chain_id: str, n_live: int, cfg: dict, mix: dict,
                 key_tag: str, rng: random.Random) -> dict:
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import GenesisDoc, State
    from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, BlockID,
                                          Commit, CommitSig)
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.vote import Proposal

    n_val = cfg["validators"]
    seeds = [_digest(key_tag, "validator", i) for i in range(n_val - 1)]
    signers = [ed25519_ref.Signer(s) for s in seeds]
    node_seed = _digest(key_tag, "node-under-test")
    node = ed25519_ref.Signer(node_seed)
    vals = [Validator(Ed25519PubKey(s.pub), cfg["voting_power_others"])
            for s in signers]
    vals.append(Validator(Ed25519PubKey(node.pub), cfg["voting_power_node"]))
    genesis = GenesisDoc(chain_id=chain_id, validators=vals,
                         genesis_time=Timestamp(BASE_TIME, 0))
    state = State.from_genesis(genesis)
    # the set's own order: power descending, then address; the node,
    # with the lowest power, is last
    members = state.validators.validators
    by_address = {v.address: (s, seed) for v, s, seed
                  in zip(vals, signers, seeds)}
    node_index = n_val - 1
    if members[node_index].pub_key.bytes_() != node.pub:
        raise RuntimeError("the node under test is not last in the set")
    ordered = [by_address[v.address] for v in members[:node_index]]
    app = KVStoreApplication()
    app.init_chain(chain_id, genesis.initial_height, [], b"")
    executor = BlockExecutor(app)

    redelivered = round(mix["redelivered_share"] * node_index)
    rows, live_from = [], None
    last_commit = Commit()
    h = 0
    while live_from is None or len(rows) - live_from < n_live:
        h += 1
        live = live_from is not None
        proposer = state.validators.get_proposer()
        if proposer.address == members[node_index].address:
            if live:
                raise RuntimeError(
                    f"the node under test proposes height {h}, inside the "
                    "live stream: its block cannot be signed beforehand")
            live_from = h       # the next height is the first live one
        txs = [f"k{h}-{i}=v{h}-{i}".encode()
               for i in range(cfg["txs_per_block"])]
        block = state.make_block(h, txs, last_commit, proposer.address,
                                 timestamp=Timestamp(BASE_TIME + h, 0))
        parts = block.make_part_set()
        block_id = BlockID(block.hash(), parts.header)
        ref_block = (block_id.hash, parts.header.total, parts.header.hash)

        def sign_all(type_):
            return [signer.sign(vote_tally.vote_sign_bytes(
                chain_id, type_, h, 0, ref_block, BASE_TIME + h, i))
                for i, (signer, _seed) in enumerate(ordered)]
        precommit_sigs = sign_all(vote_tally.PRECOMMIT)
        state, _ = executor.apply_block(state, block_id, block,
                                        verified=True)
        row = {"height": h, "block_hash": block_id.hash,
               "parts_total": parts.header.total,
               "parts_hash": parts.header.hash,
               "parts": [p.encode() for p in parts.parts],
               "seconds": BASE_TIME + h, "precommit_sigs": precommit_sigs,
               "txs": txs, "app_hash": state.app_hash}
        if live:
            proposal = Proposal(height=h, round=0, pol_round=-1,
                                block_id=block_id,
                                timestamp=block.header.time)
            row.update(
                proposal_seconds=block.header.time.seconds,
                proposal_nanos=block.header.time.nanos,
                proposal_signature=by_address[proposer.address][0].sign(
                    proposal.sign_bytes(chain_id)),
                prevote_sigs=sign_all(vote_tally.PREVOTE),
                prevote_bursts=step_bursts(
                    rng, node_index, redelivered, mix["burst_log2_max"],
                    mix["peers"]),
                precommit_bursts=step_bursts(
                    rng, node_index, redelivered, mix["burst_log2_max"],
                    mix["peers"]))
        rows.append(row)
        last_commit = Commit(
            height=h, round=0, block_id=block_id,
            signatures=[CommitSig(BLOCK_ID_FLAG_COMMIT, v.address,
                                  Timestamp(BASE_TIME + h, i), sig)
                        for i, (v, sig) in enumerate(zip(
                            members, precommit_sigs))]
            + [CommitSig.absent()])
    return {"chain_id": chain_id, "genesis_seconds": BASE_TIME,
            "pubs": [v.pub_key.bytes_() for v in members],
            "powers": [v.voting_power for v in members],
            "signer_seeds": [seed for _signer, seed in ordered],
            "node_seed": node_seed, "node_index": node_index,
            "history": rows[:live_from], "heights": rows[live_from:]}


def make(params: dict) -> dict:
    cfg, mix, seed = params["config"], params["traffic"], params["seed"]
    n = (mix["warmup_heights"]
         + math.ceil(params["seconds"] * mix["heights_per_window_second"])
         + mix["probe_heights"])
    out = build_stream(f"bench-validator-{seed}", n, cfg, mix,
                       key_tag=str(seed), rng=random.Random(seed))
    out["warmup_heights"] = mix["warmup_heights"]
    out["probe_heights"] = mix["probe_heights"]
    return out
