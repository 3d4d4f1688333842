"""Device-side vote-ingest benchmark: the ≤100µs/vote amortized budget
(tests/test_vote_perf.py defers its wall-clock assertion here, since the
budget is a DEVICE number — this host's single core verifies at ~400µs
per signature even through OpenSSL).

Measures `VoteSet.add_votes` — the consensus addVote hot path (reference
state.go:2341 addVote → types/vote_set.go:158, per-vote Verify at
types/vote.go:235) — batched through the device kernel for a
200-validator precommit wave.

Prints ONE JSON line:
  {"metric": "vote_ingest_amortized", "value": <µs/vote>, "unit": "us",
   "budget_us": 100, "within_budget": bool, "backend": "..."}

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


def main():
    from bench import require_tpu

    n_votes = int(os.environ.get("VOTES", "200"))
    rounds = int(os.environ.get("ROUNDS", "4"))

    # the budget is a device number: exits non-zero with no TPU
    device = require_tpu()

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
