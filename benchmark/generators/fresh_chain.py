"""Generator `fresh_chain`: valid chains that no process has verified.

Copied in outline from the program's `engine.chain_gen.generate_chain`
(blocks are built by `State.make_block` and executed by the real
`BlockExecutor`, because a peer serves a node the program's own block
types), with four differences that matter to a measurement:

- the precommits are signed by the plain reference (`cryptography`
  wheel) over sign-bytes from the benchmark's own CanonicalVote encoder,
  so the program's encoder and verifier are checked against bytes they
  did not make;
- blocks are applied with `verified=True`: nothing here goes through
  `types.validation.verify_commit`, so not one signature reaches the
  process-wide `pipeline.cache.shared_cache()` (trap 1 of ISSUE 25) —
  whichever process runs this;
- one more block than the chain's length is made: block N+1 carries the
  commit that seals block N, as a peer at height N+1 would serve it;
- no memo of the program rides the payload: the blocks come back
  without the `Header._hash_memo` that this generator's own
  `block.hash()` calls set (`forget_header_hashes`), so the node under
  test pays its first header hashes inside the window, as it pays its
  first `CommitSig` encodings, sign-bytes templates and addresses (those
  memos the program itself keeps out of a pickle).

Every seed gives the same sizes: the seed changes keys, hashes and
signatures, never the number of blocks, validators or lanes.

Parameters (traffic file): `blocks_per_window_second`, `warmup_blocks`,
`probe_blocks`, `probe_bad_height`, `probe_bad_index`. From the
configuration: `validators`, `voting_power`, `txs_per_block`,
`tile_size`."""

from __future__ import annotations

import hashlib
import math

from benchmark.reference import canonical_vote, ed25519_ref

BASE_TIME = 1_700_000_000


def window_blocks(seconds: float, per_second: float, tile: int) -> int:
    """Chain length for a window: whole tiles, so that every tile of
    the window has the same lanes."""
    return max(tile, math.ceil(seconds * per_second / tile) * tile)


def forget_header_hashes(blocks: list) -> list:
    """`blocks`, each header without the hash that `Header.hash`
    memoised on it: a frozen instance's `__dict__` entry, which pickle
    would carry to the node under test."""
    for block in blocks:
        block.header.__dict__.pop("_hash_memo", None)
    return blocks


def build_chain(chain_id: str, n_blocks: int, n_validators: int,
                key_tag: str, power: int = 10, txs_per_block: int = 2):
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import GenesisDoc, State
    from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, BlockID,
                                          Commit, CommitSig)
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator

    signers = [ed25519_ref.Signer(hashlib.sha256(
        f"{key_tag}/validator/{i}".encode()).digest())
        for i in range(n_validators)]
    vals = [Validator(Ed25519PubKey(s.pub), power) for s in signers]
    by_address = {v.address: s for v, s in zip(vals, signers)}
    genesis = GenesisDoc(chain_id=chain_id, validators=vals,
                         genesis_time=Timestamp(BASE_TIME, 0))
    state = State.from_genesis(genesis)
    app = KVStoreApplication()
    app.init_chain(chain_id, genesis.initial_height, [], b"")
    executor = BlockExecutor(app)

    blocks, block_ids, tx_lists = [], [], []
    last_commit = Commit()
    app_hash = b""
    for h in range(1, n_blocks + 2):
        txs = [f"k{h}-{i}=v{h}-{i}".encode() for i in range(txs_per_block)]
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
                    by_address[val.address].sign(msg)))
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
            "n_validators": n_validators}


def make(params: dict) -> dict:
    cfg, mix, seed = params["config"], params["traffic"], params["seed"]
    n = window_blocks(params["seconds"], mix["blocks_per_window_second"],
                      cfg["tile_size"])
    common = dict(n_validators=cfg["validators"],
                  power=cfg["voting_power"],
                  txs_per_block=cfg["txs_per_block"])
    return {
        "main": build_chain(f"bench-{seed}", n, key_tag=f"{seed}/main",
                            **common),
        "warmup": build_chain(f"bench-warm-{seed}", mix["warmup_blocks"],
                              key_tag=f"{seed}/warm", **common),
        "probe": build_chain(f"bench-probe-{seed}", mix["probe_blocks"],
                             key_tag=f"{seed}/probe", **common),
        "probe_bad_height": mix["probe_bad_height"],
        "probe_bad_index": mix["probe_bad_index"],
    }
