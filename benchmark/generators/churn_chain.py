"""Generator `churn_chain`: valid chains that no process has verified and
whose validator set changes as the reference's e2e manifests change it
(`[validator_update.<height>]`: the kvstore's `val:<pubkey>!<power>`
transactions, in force two heights later).

Made as `fresh_chain` makes its chains, and for the same reasons: blocks
by `State.make_block`, executed by the real `BlockExecutor` with
`verified=True` (nothing here reaches `types.validation.verify_commit`,
so not one signature reaches the process-wide sigcache), precommits
signed by the plain reference over sign-bytes from the benchmark's own
CanonicalVote encoder, one block more than the chain's length, no
header's hash memo left on the blocks. What is new is the schedule of
updates, which is the configuration's:

- every `update_period` heights, first in block `first_update_block`, one
  update: turn 0, 2, 4, ... a power change of one validator, from
  `voting_power` to one more and back on its next turn; turn 1, 3, 5, ...
  a swap, one member leaves (power 0) and a key never seen joins at
  `voting_power`. The kvstore's grammar holds one key a transaction, so a
  swap is two `val:` transactions in one block;
- the set stays at `validators` members and every member signs every
  commit, so every commit has `validators` lanes; the power change moves
  one member to the head of the set's order and back, the swap puts a
  new address into it, so lane indices shift at every change.

Which member changes and which leaves is drawn from the seed; the sizes,
the heights and the kinds never are: every seed gives the same sizes.

Parameters (traffic file): `blocks_per_window_second`, `warmup_blocks`,
`bad_height`, `bad_index` (handed on to the driver, whose peer does the
lying). From the configuration: `validators`, `voting_power`,
`txs_per_block`, `tile_size`, `update_period`, `first_update_block`."""

from __future__ import annotations

import hashlib
import random

from benchmark.generators.fresh_chain import (BASE_TIME,
                                              forget_header_hashes,
                                              window_blocks)
from benchmark.reference import canonical_vote, ed25519_ref


def _signer(tag: str) -> ed25519_ref.Signer:
    return ed25519_ref.Signer(hashlib.sha256(tag.encode()).digest())


def _val_tx(pub: bytes, power: int) -> bytes:
    return b"val:" + pub.hex().encode() + b"!" + str(power).encode()


def build_chain(chain_id: str, n_blocks: int, n_validators: int,
                key_tag: str, power: int, txs_per_block: int,
                update_period: int, first_update_block: int):
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import GenesisDoc, State
    from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, BlockID,
                                          Commit, CommitSig)
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator

    rng = random.Random(f"{key_tag}/updates")
    signers = [_signer(f"{key_tag}/validator/{i}")
               for i in range(n_validators)]
    by_pub = {s.pub: s for s in signers}
    members = [s.pub for s in signers]      # who is in the newest set
    swinger = rng.choice(members)           # its power goes up and down
    genesis = GenesisDoc(
        chain_id=chain_id, genesis_time=Timestamp(BASE_TIME, 0),
        validators=[Validator(Ed25519PubKey(s.pub), power)
                    for s in signers])
    state = State.from_genesis(genesis)
    app = KVStoreApplication()
    app.init_chain(chain_id, genesis.initial_height, [], b"")
    executor = BlockExecutor(app)

    blocks, block_ids, tx_lists, update_blocks = [], [], [], []
    last_commit = Commit()
    app_hash = b""
    turn = 0
    for h in range(1, n_blocks + 2):
        txs = [f"k{h}-{i}=v{h}-{i}".encode() for i in range(txs_per_block)]
        if (h <= n_blocks and h >= first_update_block
                and (h - first_update_block) % update_period == 0):
            if turn % 2 == 0:
                txs.append(_val_tx(swinger, power + (turn // 2 + 1) % 2))
            else:
                leaver = rng.choice([m for m in members if m != swinger])
                joiner = _signer(f"{key_tag}/joiner/{turn}")
                by_pub[joiner.pub] = joiner
                members[members.index(leaver)] = joiner.pub
                txs += [_val_tx(leaver, 0), _val_tx(joiner.pub, power)]
            update_blocks.append(h)
            turn += 1
        block = state.make_block(
            h, txs, last_commit, state.validators.get_proposer().address,
            timestamp=Timestamp(BASE_TIME + h, 0))
        parts = block.make_part_set().header
        block_id = BlockID(block.hash(), parts)
        sigs = []
        if h <= n_blocks:
            for i, val in enumerate(state.validators.validators):
                msg = canonical_vote.precommit_sign_bytes(
                    chain_id, h, 0, block_id.hash, parts.total, parts.hash,
                    BASE_TIME + h, i)
                sigs.append(CommitSig(
                    BLOCK_ID_FLAG_COMMIT, val.address,
                    Timestamp(BASE_TIME + h, i),
                    by_pub[val.pub_key.bytes_()].sign(msg)))
        state, _ = executor.apply_block(state, block_id, block,
                                        verified=True)
        blocks.append(block)
        block_ids.append(block_id)
        if h <= n_blocks:
            tx_lists.append(txs)
            app_hash = state.app_hash
            last_commit = Commit(height=h, round=0, block_id=block_id,
                                 signatures=sigs)
    return {"chain_id": chain_id, "genesis": genesis, "n_blocks": n_blocks,
            "blocks": forget_header_hashes(blocks), "block_ids": block_ids,
            "tx_lists": tx_lists, "app_hash": app_hash,
            "n_validators": n_validators,
            "genesis_members": [(s.pub, power) for s in signers],
            "update_blocks": update_blocks}


def make(params: dict) -> dict:
    cfg, mix, seed = params["config"], params["traffic"], params["seed"]
    n = window_blocks(params["seconds"], mix["blocks_per_window_second"],
                      cfg["tile_size"])
    if not 1 <= mix["bad_height"] <= n:
        raise ValueError(f"bad_height {mix['bad_height']} is outside the "
                         f"chain of {n} blocks")
    common = dict(n_validators=cfg["validators"],
                  power=cfg["voting_power"],
                  txs_per_block=cfg["txs_per_block"],
                  update_period=cfg["update_period"],
                  first_update_block=cfg["first_update_block"])
    return {
        "main": build_chain(f"bench-{seed}", n, key_tag=f"{seed}/main",
                            **common),
        # one set change and no lie, so that the barrier, the synchronous
        # route and their threads are paid for in set-up
        "warmup": build_chain(f"bench-warm-{seed}", mix["warmup_blocks"],
                              key_tag=f"{seed}/warm", **common),
        "bad_height": mix["bad_height"],
        "bad_index": mix["bad_index"],
    }
