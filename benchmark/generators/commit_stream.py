"""Generator `commit_stream`: a stream of distinct commits by one
validator set, each for a fresh height and block id, signed by the plain
reference over the benchmark's own CanonicalVote bytes. Plain data only:
no type of the program is made here, so nothing can have been verified
(trap 1 of ISSUE 25), and the driver builds the program's `Commit`s.

Every seed gives the same sizes. Validators are listed in the order a
CometBFT validator set keeps them: voting power descending, then address
(the first 20 bytes of SHA-256 of the key) ascending.

Parameters (traffic file): `commits_per_window_second`, `warmup_commits`,
`probe_commits`. From the configuration: `validators`, `voting_power`."""

from __future__ import annotations

import hashlib
import math
import random

from benchmark.reference import canonical_vote, ed25519_ref

BASE_TIME = 1_700_000_000


def _digest(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


def make_commit(chain_id, signers, seed, kind, index, height) -> dict:
    block_hash = _digest(seed, kind, "block", index)
    parts_hash = _digest(seed, kind, "parts", index)
    sigs = [s.sign(canonical_vote.precommit_sign_bytes(
        chain_id, height, 0, block_hash, 1, parts_hash,
        BASE_TIME + height, i)) for i, s in enumerate(signers)]
    return {"height": height, "block_hash": block_hash, "parts_total": 1,
            "parts_hash": parts_hash, "seconds": BASE_TIME + height,
            "sigs": sigs}


def make(params: dict) -> dict:
    cfg, mix, seed = params["config"], params["traffic"], params["seed"]
    chain_id = f"bench-commits-{seed}"
    signers = [ed25519_ref.Signer(_digest(seed, "validator", i))
               for i in range(cfg["validators"])]
    signers.sort(key=lambda s: hashlib.sha256(s.pub).digest()[:20])
    n = math.ceil(params["seconds"] * mix["commits_per_window_second"])
    rng = random.Random(seed)
    height = 1
    out = {"chain_id": chain_id, "pubs": [s.pub for s in signers],
           "voting_power": cfg["voting_power"]}
    for kind, count in (("warmup", mix["warmup_commits"]), ("stream", n),
                        ("probes", mix["probe_commits"])):
        rows = []
        for i in range(count):
            rows.append(make_commit(chain_id, signers, seed, kind, i,
                                    height))
            height += 1
        out[kind] = rows
    # which signature each probe commit has altered, drawn from the seed
    out["probe_bad_index"] = [rng.randrange(len(signers))
                              for _ in out["probes"]]
    return out
