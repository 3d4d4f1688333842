"""Generator `light_chain`: chains of signed headers that no process has
verified, as a light client's provider serves them (reference
light/client_benchmark_test.go `BenchmarkSequence`: a mock chain, every
header verified with the adjacent rule).

A light client needs no blocks: a chain here is its headers, the commit
that seals each, and the validator set. Made as `fresh_chain` makes its
chains, and for the same reasons: the precommits are signed by the plain
reference (`cryptography` wheel) over sign-bytes from the benchmark's own
CanonicalVote encoder; nothing here reaches `types.validation`, so not
one signature reaches the process-wide sigcache; the headers come back
without the `_hash_memo` that this generator's own `Header.hash` calls
set (`fresh_chain.forget_header_hashes`), so the client pays its header
hashes inside the window. The validator set is plain data, (public key,
power) in the set's order: the driver builds the program's
`ValidatorSet`, a fresh one a light block.

The set is the configuration's: `validators` members, power
floor(`power_scale` * rank^-`power_exponent`), constant over the chain;
EVERY member signs every commit (as a provider serves them), whatever
the rule then takes. A header's hashes that no light client checks
(`last_commit_hash`, `data_hash`, `consensus_hash`, `app_hash`,
`last_results_hash`, `evidence_hash`) are digests of the seed.

Every seed gives the same sizes: the seed changes keys, hashes and
signatures, never the number of headers, members or lanes.

The probes are three chains of `probe_headers`, each honest but for
header `probe_bad_height`: `altered` (lane `probe_bad_lane`, one the rule
takes, has its signature's s altered), `beyond` (the same in lane
`probe_beyond_lane`, behind the rule's stop), `short` (the
`probe_absent_heaviest` heaviest lanes absent: under 2/3 of the power).

Parameters (traffic file): `chain_headers`, `warmup_headers`,
`probe_headers`, `probe_bad_height`, `probe_bad_lane`,
`probe_beyond_lane`, `probe_absent_heaviest`. From the configuration:
`validators`, `power_scale`, `power_exponent`."""

from __future__ import annotations

import hashlib
from types import SimpleNamespace

from benchmark.generators.fresh_chain import BASE_TIME, forget_header_hashes
from benchmark.reference import canonical_vote, ed25519_ref, valset_replay


def _digest(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


def powers(n: int, scale: int, exponent: float) -> list:
    """Voting power by rank 1..n: floor(scale * rank^-exponent)."""
    return [int(scale * rank ** -exponent) for rank in range(1, n + 1)]


def build_chain(chain_id: str, n_headers: int, key_tag: str, cfg: dict,
                alter=None) -> dict:
    """Headers 1..n. `alter` = (height, function of that height's list of
    `CommitSig`s) changes one commit after it was signed."""
    from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, BlockID,
                                          Commit, CommitSig, Header,
                                          PartSetHeader)
    from cometbft_tpu.types.proto import Timestamp

    signers = {s.pub: s for s in (
        ed25519_ref.Signer(_digest(key_tag, "validator", i))
        for i in range(cfg["validators"]))}
    members = valset_replay.ordered(dict(zip(signers, powers(
        cfg["validators"], cfg["power_scale"], cfg["power_exponent"]))))
    addresses = [valset_replay.address(pub) for pub, _p in members]
    vals_hash = valset_replay.validators_hash(members)

    headers, commits, hashes = [], [], []
    last = BlockID()
    for h in range(1, n_headers + 1):
        header = Header(
            version_block=11, chain_id=chain_id, height=h,
            time=Timestamp(BASE_TIME + h, 0), last_block_id=last,
            last_commit_hash=_digest(key_tag, "last_commit", h),
            data_hash=_digest(key_tag, "data", h),
            validators_hash=vals_hash, next_validators_hash=vals_hash,
            consensus_hash=_digest(key_tag, "consensus"),
            app_hash=_digest(key_tag, "app", h),
            last_results_hash=_digest(key_tag, "results", h),
            evidence_hash=_digest(key_tag, "evidence", h),
            proposer_address=addresses[h % len(addresses)])
        parts = PartSetHeader(1, _digest(key_tag, "parts", h))
        last = BlockID(header.hash(), parts)
        sigs = [CommitSig(
            BLOCK_ID_FLAG_COMMIT, addresses[i], Timestamp(BASE_TIME + h, i),
            signers[pub].sign(canonical_vote.precommit_sign_bytes(
                chain_id, h, 0, last.hash, parts.total, parts.hash,
                BASE_TIME + h, i)))
            for i, (pub, _power) in enumerate(members)]
        if alter is not None and alter[0] == h:
            sigs = alter[1](sigs)
        headers.append(header)
        hashes.append(last.hash)
        commits.append(Commit(height=h, round=0, block_id=last,
                              signatures=sigs))
    forget_header_hashes([SimpleNamespace(header=h) for h in headers])
    return {"chain_id": chain_id, "n_headers": n_headers,
            "members": members, "validators_hash": vals_hash,
            "headers": headers, "commits": commits, "hashes": hashes,
            "now_seconds": BASE_TIME + n_headers + 60}


def _altered_lane(idx: int):
    def alter(sigs):
        from cometbft_tpu.types.block import CommitSig
        cs = sigs[idx]
        return sigs[:idx] + [CommitSig(
            cs.block_id_flag, cs.validator_address, cs.timestamp,
            ed25519_ref.tamper(cs.signature))] + sigs[idx + 1:]
    return alter


def _absent_heaviest(count: int):
    def alter(sigs):
        from cometbft_tpu.types.block import (BLOCK_ID_FLAG_ABSENT,
                                              CommitSig)
        from cometbft_tpu.types.proto import Timestamp
        return [CommitSig(BLOCK_ID_FLAG_ABSENT, b"", Timestamp(), b"")
                for _ in range(count)] + sigs[count:]
    return alter


def make(params: dict) -> dict:
    cfg, mix, seed = params["config"], params["traffic"], params["seed"]
    bad = mix["probe_bad_height"]
    probes = {
        "altered": _altered_lane(mix["probe_bad_lane"]),
        "beyond": _altered_lane(mix["probe_beyond_lane"]),
        "short": _absent_heaviest(mix["probe_absent_heaviest"]),
    }
    return {
        "main": build_chain(f"bench-light-{seed}", mix["chain_headers"],
                            f"{seed}/main", cfg),
        "warmup": build_chain(f"bench-light-warm-{seed}",
                              mix["warmup_headers"], f"{seed}/warm", cfg),
        "probes": {name: build_chain(
            f"bench-light-{name}-{seed}", mix["probe_headers"],
            f"{seed}/probe/{name}", cfg, alter=(bad, alter))
            for name, alter in probes.items()},
        "probe_bad_height": bad,
        "probe_bad_lane": mix["probe_bad_lane"],
        "probe_beyond_lane": mix["probe_beyond_lane"],
    }
