"""Device-side vote-ingest side script: the ≤100µs/vote amortized budget
(tests/test_vote_perf.py defers its wall-clock assertion here, since the
budget is a DEVICE number — this host's single core verifies at ~400µs
per signature even through OpenSSL).

THE MEASUREMENT OF RECORD for vote intake is the benchmark cell
`hub-validator-150.vote-intake` (BENCHMARK.json, PERF.md §4): one real
`ConsensusState` taking a 150-validator chain's votes through its inbox,
whose batched intake (consensus/state.py `_intake`) calls the same
`types.vote_set.preverify_lanes` that `VoteSet.add_votes` calls here.
This script stays as a quick probe of that function alone.

Default mode measures `VoteSet.add_votes` — the consensus addVote hot
path (reference state.go:2341 addVote → types/vote_set.go:158, per-vote
Verify at types/vote.go:235) — batched through the device kernel for a
200-validator precommit wave.

Prints ONE JSON line:
  {"metric": "vote_ingest_amortized", "value": <µs/vote>, "unit": "us",
   "budget_us": 100, "within_budget": bool, "backend": "..."}

`--sweep` instead times n lanes natively (`crypto.keys.verify_native`)
against the same n lanes through ONE `crypto.batch` flush, n = 8 … 192,
median of SWEEP_REPS (default 7) each, and prints one JSON line with
both columns and the first n at which the flush wins: the sizing of
`types.validation.BATCH_VERIFY_THRESHOLD` (ROADMAP.md Queue 1 item 2).

Env knobs: VOTES (default 200), ROUNDS (default 4). The budget is a
device number: with no TPU the script exits non-zero.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


BUDGET_US = 100.0


def _valset(n, seed=5):
    import random
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    rng = random.Random(seed)
    keys = [Ed25519PrivKey(bytes(rng.randrange(256) for _ in range(32)))
            for _ in range(n)]
    vals = ValidatorSet([Validator(k.pub_key(), 10) for k in keys])
    by_addr = {k.pub_key().address(): k for k in keys}
    return vals, [by_addr[v.address] for v in vals.validators]


SWEEP_LANES = (8, 16, 32, 48, 64, 96, 128, 192)


def sweep(device):
    """n lanes natively against n lanes through one flush of the seam,
    on the kernel `Node._prewarm_kernels` warms."""
    import statistics
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto.keys import (Ed25519PubKey, kernel_width,
                                          verify_native)
    from cometbft_tpu.ops.ed25519 import prewarm_verify_kernels
    from cometbft_tpu.types.block import BlockID, PartSetHeader
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.vote import Vote, PRECOMMIT_TYPE
    reps = int(os.environ.get("SWEEP_REPS", "7"))
    prewarm_verify_kernels(batch_size=kernel_width())
    _vals, keys = _valset(max(SWEEP_LANES))
    bid = BlockID(b"\x77" * 32, PartSetHeader(1, b"\x88" * 32))

    def lanes(n, height):
        out = []
        for i, k in enumerate(keys[:n]):
            v = Vote(type_=PRECOMMIT_TYPE, height=height, round=0,
                     block_id=bid, timestamp=Timestamp(100, i),
                     validator_address=k.pub_key().address(),
                     validator_index=i)
            sb = v.sign_bytes("perf-chain")
            out.append((k.pub_key().bytes_(), sb, k.sign(sb)))
        return out

    def flush(batch):
        bv, _ok = crypto_batch.create_batch_verifier(
            Ed25519PubKey(batch[0][0]))
        for pub, msg, sig in batch:
            bv.add(Ed25519PubKey(pub), msg, sig)
        return bv.verify()[1]

    flush(lanes(8, 1))      # the device path's first transfer
    rows, height = [], 2
    for n in SWEEP_LANES:
        native, flushed = [], []
        for _ in range(reps):
            batch = lanes(n, height)
            height += 1
            t0 = time.perf_counter()
            ok_native = verify_native(*zip(*batch))
            t1 = time.perf_counter()
            ok_flush = flush(batch)
            t2 = time.perf_counter()
            assert all(ok_native) and all(ok_flush), "sweep lane refused"
            native.append((t1 - t0) * 1e3)
            flushed.append((t2 - t1) * 1e3)
        rows.append({"lanes": n,
                     "native_ms": round(statistics.median(native), 4),
                     "flush_ms": round(statistics.median(flushed), 4)})
    wins = [r["lanes"] for r in rows if r["flush_ms"] < r["native_ms"]]
    print(json.dumps({
        "metric": "native_vs_flush_sweep", "reps": reps, "rows": rows,
        "first_lanes_where_flush_wins": wins[0] if wins else None,
        "backend": device["platform"], "device": device}))
    return 0


def main():
    from bench import require_tpu

    n_votes = int(os.environ.get("VOTES", "200"))
    rounds = int(os.environ.get("ROUNDS", "4"))

    # the budget is a device number: exits non-zero with no TPU
    device = require_tpu()
    if "--sweep" in sys.argv[1:]:
        return sweep(device)

    from cometbft_tpu.types.block import BlockID, PartSetHeader
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.vote import Vote, PRECOMMIT_TYPE
    from cometbft_tpu.types.vote_set import VoteSet

    chain = "perf-chain"
    bid = BlockID(b"\x77" * 32, PartSetHeader(1, b"\x88" * 32))
    vals, keys = _valset(n_votes)

    def wave(height):
        votes = []
        for i, k in enumerate(keys):
            v = Vote(type_=PRECOMMIT_TYPE, height=height, round=0,
                     block_id=bid, timestamp=Timestamp(100, i),
                     validator_address=k.pub_key().address(),
                     validator_index=i)
            v.signature = k.sign(v.sign_bytes(chain))
            votes.append(v)
        return votes

    # warm the kernel bucket out-of-band
    warm = VoteSet(chain, 1, 0, PRECOMMIT_TYPE, vals)
    warm.add_votes(wave(1)[:4])

    total, counted = 0.0, 0
    for r in range(rounds):
        votes = wave(2 + r)
        vs = VoteSet(chain, 2 + r, 0, PRECOMMIT_TYPE, vals)
        t0 = time.perf_counter()
        res = vs.add_votes(votes)
        total += time.perf_counter() - t0
        assert all(x is True for x in res), "ingest failed"
        counted += len(votes)

    us_per_vote = total / counted * 1e6
    print(json.dumps({
        "metric": "vote_ingest_amortized",
        "value": round(us_per_vote, 2),
        "unit": "us",
        "budget_us": BUDGET_US,
        "within_budget": us_per_vote <= BUDGET_US,
        "backend": device["platform"], "device": device,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
