"""End-to-end blocksync benchmark at the QA valset scale
(BASELINE.json "blocksync catch-up" config; reference
internal/blocksync/reactor.go:540-544 logs blocks/s the same way).

Generates an N-block chain with a V-validator set (default 175 — the
QA-testnet valset, CometBFT-QA-v1.md), then times a fresh node
blocksyncing it through the real executor + TiledCommitVerifier,
reporting blocks/s and verified sigs/s. The tile flushes through the RLC
device kernel on the TPU this process owns; with no TPU the script
exits non-zero. `--batch 0` times the native per-signature path on the
same host, for comparison.

Usage:
    python tools/bench_blocksync.py [--blocks 64] [--validators 175]
        [--tile 32] [--batch auto|0|N] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--validators", type=int, default=175)
    ap.add_argument("--tile", type=int, default=32)
    ap.add_argument("--batch", default="auto",
                    help="auto: 8192-lane device batch; 0: native "
                         "per-signature; N: device batch N")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.engine.chain_gen import (
        LocalChainSource, generate_chain)
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore

    # measured on the chip or not at all (exits non-zero with no TPU);
    # --batch 0 is the native per-signature reference ON that host
    from bench import require_tpu
    device = require_tpu()
    batch = 8192 if args.batch == "auto" else int(args.batch)

    t0 = time.monotonic()
    print(f"[bench_blocksync] generating {args.blocks} blocks x "
          f"{args.validators} validators...", file=sys.stderr, flush=True)
    chain = generate_chain(n_blocks=args.blocks,
                           n_validators=args.validators)
    gen_s = time.monotonic() - t0
    print(f"[bench_blocksync] chain in {gen_s:.1f}s; syncing "
          f"(batch={batch})...", file=sys.stderr, flush=True)

    app = KVStoreApplication()
    app.init_chain(chain.chain_id, 1, [], b"")
    db = MemDB()
    executor = BlockExecutor(app, state_store=StateStore(db),
                             block_store=BlockStore(db))
    state = State.from_genesis(chain.genesis)
    reactor = BlocksyncReactor(
        executor, BlockStore(db), LocalChainSource(chain),
        chain.chain_id, tile_size=args.tile, batch_size=batch)

    t1 = time.monotonic()
    state = reactor.sync(state)
    dt = time.monotonic() - t1
    assert state.last_block_height == args.blocks

    sigs = reactor.stats.sigs_verified
    rec = {
        "metric": "blocksync_throughput",
        "blocks_per_sec": round(args.blocks / dt, 2),
        "sigs_per_sec": round(sigs / dt, 1),
        "unit": "blocks/s",
        "blocks": args.blocks,
        "validators": args.validators,
        "tile": args.tile,
        "batch": batch,
        "device": device,
        "sync_seconds": round(dt, 2),
    }
    if args.json:
        print(json.dumps(rec))
    else:
        print(f"blocksync: {rec['blocks_per_sec']} blocks/s, "
              f"{rec['sigs_per_sec']:,.0f} sigs/s "
              f"({args.blocks} blocks x {args.validators} validators, "
              f"tile {args.tile}, batch {batch}, {dt:.2f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
