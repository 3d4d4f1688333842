"""Generator `light_skip_chain`: a chain whose validator set rotates by one
member a header, built only where a skipping light client looks
(reference light/client_benchmark_test.go `BenchmarkBisection` over
light/helpers_test.go `genMockNode`: 100 validators of power 2, the
oldest key leaving and a fresh one joining at every header, a header a
minute).

A chain here is `updates` updates of `update_headers` headers each: the
update i takes a client that trusts height 1 + update_headers x (i - 1)
to the one update_headers higher. Only the heights the plain reference's
skipping rule (`reference/light_bisect.py`) fetches there are made, each
with its true `validators_hash` and `next_validators_hash`, its set as
plain data and its commit signed by every member; the driver's provider
refuses any other height. A rule that needs no signature decides which
heights those are (the schedule depends on the sets alone), so the
schedule is computed first and the signatures made afterwards. Height 1,
the trust root, is made too.

The precommits are signed by the plain reference (`cryptography` wheel)
over sign-bytes from the benchmark's own CanonicalVote encoder, for the
block hash from the benchmark's own header encoder
(`reference/header_hash.py`); the program's `Header.hash` is never
called here, so it is first asked when the client checks a header
against its commit, and there it has to give the same hash. Nothing
here reaches `types.validation`, so not one signature reaches the
process-wide sigcache. A header's hashes
that no skipping client checks (`last_block_id`, `last_commit_hash`,
`data_hash`, `consensus_hash`, `app_hash`, `last_results_hash`,
`evidence_hash`) are digests of the seed.

Updates are made in segments, in parallel worker processes where there
are more than one (spawned, each deriving its keys from the seed): a key
is the seed's digest of its index, so a segment needs nothing of
another. Every seed gives the same sizes and the same heights: the seed
changes keys, hashes and signatures.

The probes are chains of one update each, honest but for one lane:
`trusting_lane` (the first lane the trusting check of step
`probe_trusting_step` takes), `own_lane` (the first lane of the target
that only its +2/3 check takes), `behind_stops` (the target's last lane
that neither check takes).

Parameters (traffic file): `updates`, `update_headers`,
`warmup_updates`, `probe_update_headers`, `probe_trusting_step`. From the
configuration: `validators`, `voting_power`, `rotation_per_header`,
`header_interval_s`, `trust_level`."""

from __future__ import annotations

import hashlib
import importlib
import multiprocessing
import os

from benchmark.generators.fresh_chain import BASE_TIME
from benchmark.reference import (canonical_vote, ed25519_ref, header_hash,
                                 light_bisect, valset_replay)

# updates a worker process makes at a time
SEGMENT_UPDATES = 32


def _digest(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


class _Sets:
    """The set in force at every height: members (public key, power) in
    the set's order, and its hash, each made once."""

    def __init__(self, key_tag: str, cfg: dict):
        self.tag, self.cfg = key_tag, cfg
        self._signers, self._members, self._hashes = {}, {}, {}

    def signer(self, j: int):
        if j not in self._signers:
            self._signers[j] = ed25519_ref.Signer(
                _digest(self.tag, "validator", j))
        return self._signers[j]

    def members(self, h: int) -> list:
        if h not in self._members:
            first = self.cfg["rotation_per_header"] * (h - 1)
            self._members[h] = valset_replay.ordered({
                self.signer(j).pub: self.cfg["voting_power"]
                for j in range(first, first + self.cfg["validators"])})
        return self._members[h]

    def hash(self, h: int) -> bytes:
        if h not in self._hashes:
            self._hashes[h] = valset_replay.validators_hash(self.members(h))
        return self._hashes[h]

    def plan_header(self, h: int) -> dict:
        """Height h as the reference's schedule reads it: every member
        signs, and which lanes a check takes depends on nothing else."""
        members = self.members(h)
        return {"members": members, "validators_hash": self.hash(h),
                "next_validators_hash": self.hash(h + 1),
                "lanes": [()] * len(members)}


def _make_header(chain_id: str, h: int, sets: _Sets, key_tag: str,
                 cfg: dict, alter=None):
    """(header, commit) of height h, every member's precommit signed;
    `alter` = lane index whose signature is altered."""
    from cometbft_tpu.types.block import (BLOCK_ID_FLAG_COMMIT, BlockID,
                                          Commit, CommitSig, Header,
                                          PartSetHeader)
    from cometbft_tpu.types.proto import Timestamp
    members = sets.members(h)
    seconds = BASE_TIME + cfg["header_interval_s"] * h
    addresses = [valset_replay.address(pub) for pub, _p in members]
    last = (_digest(key_tag, "block", h - 1), 1,
            _digest(key_tag, "parts", h - 1))
    hashes = {"last_commit_hash": _digest(key_tag, "last_commit", h),
              "data_hash": _digest(key_tag, "data", h),
              "validators_hash": sets.hash(h),
              "next_validators_hash": sets.hash(h + 1),
              "consensus_hash": _digest(key_tag, "consensus"),
              "app_hash": _digest(key_tag, "app", h),
              "last_results_hash": _digest(key_tag, "results", h),
              "evidence_hash": _digest(key_tag, "evidence", h)}
    proposer = addresses[h % len(addresses)]
    header = Header(
        version_block=11, chain_id=chain_id, height=h,
        time=Timestamp(seconds, 0),
        last_block_id=BlockID(last[0], PartSetHeader(last[1], last[2])),
        proposer_address=proposer, **hashes)
    parts = PartSetHeader(1, _digest(key_tag, "parts", h))
    # the hash every precommit signs is the benchmark's own; the program
    # computes its own when it checks the header against its commit
    bid = BlockID(header_hash.header_hash(chain_id, h, (seconds, 0), last,
                                          hashes, proposer), parts)
    by_pub = {sets.signer(j).pub: sets.signer(j) for j in range(
        cfg["rotation_per_header"] * (h - 1),
        cfg["rotation_per_header"] * (h - 1) + cfg["validators"])}
    sigs = []
    for i, (pub, _power) in enumerate(members):
        sig = by_pub[pub].sign(canonical_vote.precommit_sign_bytes(
            chain_id, h, 0, bid.hash, parts.total, parts.hash, seconds, i))
        if i == alter:
            sig = ed25519_ref.tamper(sig)
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, addresses[i],
                              Timestamp(seconds, i), sig))
    return header, Commit(height=h, round=0, block_id=bid, signatures=sigs)


def segment(chain_id: str, key_tag: str, cfg: dict, span: int, first: int,
            count: int, alter=None) -> dict:
    """Updates first..first+count-1 (0-based) of a chain: their schedules
    and the heights those fetch, made. `alter` = (height, lane index)."""
    sets = _Sets(key_tag, cfg)
    trust = tuple(cfg["trust_level"])
    out = {"schedules": [], "headers": {}, "commits": {}, "members": {}}
    heights = [1] if first == 0 else []
    for i in range(first, first + count):
        sched = light_bisect.schedule(1 + span * i, 1 + span * (i + 1),
                                      sets.plan_header, trust)
        if sched["refused"] is not None:
            raise RuntimeError(f"the honest schedule refuses: {sched}")
        out["schedules"].append(sched)
        heights += [step["height"] for step in sched["steps"]]
    for h in heights:
        bad = alter[1] if alter is not None and alter[0] == h else None
        header, commit = _make_header(chain_id, h, sets, key_tag, cfg, bad)
        out["headers"][h], out["commits"][h] = header, commit
        out["members"][h] = sets.members(h)
    return out


def _segment_star(args):
    return segment(*args)


def build_chain(chain_id: str, key_tag: str, cfg: dict, span: int,
                updates: int, alter=None) -> dict:
    jobs = [(chain_id, key_tag, cfg, span, first,
             min(SEGMENT_UPDATES, updates - first), alter)
            for first in range(0, updates, SEGMENT_UPDATES)]
    workers = min(len(jobs), max(1, (os.cpu_count() or 1) - 2))
    if workers > 1:
        # a function the spawned workers can import by its package path
        me = importlib.import_module("benchmark.generators.light_skip_chain")
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            parts = pool.map(me._segment_star, jobs)
    else:
        parts = [segment(*job) for job in jobs]
    chain = {"chain_id": chain_id, "span": span, "updates": updates,
             "header_interval_s": cfg["header_interval_s"],
             "schedules": [], "headers": {}, "commits": {}, "members": {}}
    for part in parts:
        chain["schedules"] += part["schedules"]
        for key in ("headers", "commits", "members"):
            chain[key].update(part[key])
    chain["hashes"] = {h: c.block_id.hash
                       for h, c in chain["commits"].items()}
    return chain


def _probe_alter(tag: str, cfg: dict, span: int, name: str,
                 trusting_step: int) -> tuple:
    """(height, lane index) that probe `name` alters, from the honest
    schedule of its own keys."""
    plan = light_bisect.schedule(1, 1 + span, _Sets(tag, cfg).plan_header,
                                 tuple(cfg["trust_level"]))
    if name == "trusting_lane":
        step = plan["steps"][trusting_step - 1]
        return step["height"], step["trusting"][0]
    step = plan["steps"][-1]
    if name == "own_lane":
        return step["height"], next(i for i in step["own"]
                                    if i not in step["trusting"])
    return step["height"], max(set(range(cfg["validators"]))
                               - set(step["own"]) - set(step["trusting"]))


def make(params: dict) -> dict:
    cfg, mix, seed = params["config"], params["traffic"], params["seed"]
    span = mix["update_headers"]
    probes, alters = {}, {}
    for name in ("trusting_lane", "own_lane", "behind_stops"):
        tag = f"{seed}/probe/{name}"
        alters[name] = _probe_alter(tag, cfg, mix["probe_update_headers"],
                                    name, mix["probe_trusting_step"])
        probes[name] = build_chain(f"bench-skip-{name}-{seed}", tag, cfg,
                                   mix["probe_update_headers"], 1,
                                   alter=alters[name])
    return {
        "main": build_chain(f"bench-skip-{seed}", f"{seed}/main", cfg,
                            span, mix["updates"]),
        "warmup": build_chain(f"bench-skip-warm-{seed}", f"{seed}/warm",
                              cfg, span, mix["warmup_updates"]),
        "probes": probes,
        "probe_alters": alters,
        "probe_trusting_step": mix["probe_trusting_step"],
    }
