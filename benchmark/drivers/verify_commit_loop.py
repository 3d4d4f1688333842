"""Driver `verify_commit_loop`: one caller, closed loop, each call
`types.validation.verify_commit` on the next of a stream of distinct
commits, the next call issued when the last returns, until the window's
seconds have passed (or the stream, sized at about 1.5 times what the
window holds, runs out: printed, and a later benchmark PR resizes it).

On a TPU a 150-signature commit takes the `crypto.batch` seam to one
512-lane dispatch; on a CPU backend `Ed25519BatchVerifier` verifies
natively above 64 lanes (trap 2 of ISSUE 25), so a CPU rehearsal shows
control flow and implies no dispatch."""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

from benchmark.drivers import node_boot
from benchmark.harness import stats
from benchmark.reference import canonical_vote, ed25519_ref

SIGCACHE_PATH = "commit"
warm = node_boot.boot


class Session:
    def __init__(self, config: dict, payload: dict, batch: int, seed: int):
        from cometbft_tpu.crypto.keys import Ed25519PubKey
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        self.config, self.payload = config, payload
        self.batch, self.seed = batch, seed
        self.chain_id = payload["chain_id"]
        self.valset = ValidatorSet([
            Validator(Ed25519PubKey(p), payload["voting_power"])
            for p in payload["pubs"]])
        order = [v.pub_key.bytes_() for v in self.valset.validators]
        if order != payload["pubs"]:
            raise RuntimeError("the validator set orders its members "
                               "otherwise than the generator did")
        self.stream = [self.commit(row) for row in payload["stream"]]

    def commit(self, row: dict, bad_index=None, absent=()):
        """(block id, height, Commit) of a generated row; `bad_index`
        gets its signature altered, `absent` indices are marked absent."""
        from cometbft_tpu.types.block import (
            BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BlockID, Commit,
            CommitSig, PartSetHeader)
        from cometbft_tpu.types.proto import Timestamp
        bid = BlockID(row["block_hash"],
                      PartSetHeader(row["parts_total"], row["parts_hash"]))
        sigs = []
        for i, (val, sig) in enumerate(zip(self.valset.validators,
                                           row["sigs"])):
            if i in absent:
                sigs.append(CommitSig(BLOCK_ID_FLAG_ABSENT, b"",
                                      Timestamp(0, 0), b""))
                continue
            if i == bad_index:
                sig = ed25519_ref.tamper(sig)
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address,
                                  Timestamp(row["seconds"], i), sig))
        return bid, row["height"], Commit(height=row["height"], round=0,
                                          block_id=bid, signatures=sigs)

    def verify(self, bid, height, commit) -> None:
        from cometbft_tpu.types import validation
        with TraceAnnotation("bench.verify_commit"):
            validation.verify_commit(self.chain_id, self.valset, bid,
                                     height, commit)


def build(config: dict, traffic: dict, payload: dict, boot: dict,
          seed: int) -> Session:
    session = Session(config, payload, boot["batch"], seed)
    for row in payload["warmup"]:
        session.verify(*session.commit(row))
    return session


def window(session: Session, seconds: float) -> dict:
    latencies, failed = [], 0
    before = node_boot.device_counters()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    for bid, height, commit in session.stream:
        t = time.perf_counter()
        if t >= deadline:
            break
        try:
            session.verify(bid, height, commit)
        except Exception as exc:  # noqa: BLE001 — counted, judged
            failed += 1
            print(f"[window] call {len(latencies)} raised {exc!r}",
                  flush=True)
        latencies.append(time.perf_counter() - t)
    elapsed = time.perf_counter() - t0
    calls = len(latencies)
    counters = node_boot.delta(before, node_boot.device_counters(),
                               SIGCACHE_PATH)
    n_val = len(session.valset.validators)
    counters["implied_chunks"] = node_boot.implied_chunks(
        [n_val] * calls, session.batch)
    counters["stream_exhausted"] = int(calls == len(session.stream))
    row = session.payload["stream"][0]
    msg_len = len(canonical_vote.precommit_sign_bytes(
        session.chain_id, row["height"], 0, row["block_hash"], 1,
        row["parts_hash"], row["seconds"], 0))
    ms = [s * 1e3 for s in latencies]
    return {
        "end_to_end": {"commit_verify_p50_ms": stats.percentile(ms, 50),
                       "commit_verify_p95_ms": stats.percentile(ms, 95)},
        "attempted": calls, "failed": failed, "counters": counters,
        "facts": {"window_s": elapsed, "lanes": calls * n_val,
                  "hash_blocks": calls * n_val
                  * node_boot.hash_blocks(msg_len),
                  "calls": calls, "commits_per_s": calls / elapsed},
    }


def _reference_lanes(session: Session, row: dict, bad_index=None):
    for i, (pub, sig) in enumerate(zip(session.payload["pubs"],
                                       row["sigs"])):
        msg = canonical_vote.precommit_sign_bytes(
            session.chain_id, row["height"], 0, row["block_hash"],
            row["parts_total"], row["parts_hash"], row["seconds"], i)
        yield i, pub, msg, (ed25519_ref.tamper(sig) if i == bad_index
                            else sig)


def judge(session: Session, result: dict, compiles: int) -> list:
    """Every number compared, as (name, value, limit); exact comparisons,
    limit 0. The probes go through the same entry after the window:
    exact per-signature attribution (the index `verify_commit` blames is
    the one the plain reference rejects) and the +2/3 rule."""
    import random
    from cometbft_tpu.types import validation
    c, calls = result["counters"], result["attempted"]
    rng = random.Random(session.seed)
    ref_rejects = 0
    for _ in range(4):          # four whole commits of the window
        row = session.payload["stream"][rng.randrange(max(1, calls))]
        ref_rejects += sum(not ed25519_ref.verify(p, m, s)
                           for _i, p, m, s in _reference_lanes(session, row))
    accepted = misattributed = ref_accepts = 0
    for row, bad in zip(session.payload["probes"],
                        session.payload["probe_bad_index"]):
        ref_bad = [i for i, p, m, s in _reference_lanes(session, row, bad)
                   if not ed25519_ref.verify(p, m, s)]
        ref_accepts += ref_bad != [bad]
        try:
            session.verify(*session.commit(row, bad_index=bad))
            accepted += 1
        except validation.ErrWrongSignature as exc:
            misattributed += exc.idx != bad
        except validation.CommitVerificationError:
            misattributed += 1
    # a commit that a third of the power plus one did not sign
    n_val = len(session.valset.validators)
    absent = set(range(0, n_val // 3 + 1))
    quorum_accepted = 0
    try:
        session.verify(*session.commit(session.payload["probes"][0],
                                       absent=absent))
        quorum_accepted = 1
    except validation.ErrNotEnoughVotingPowerSigned:
        pass
    except validation.CommitVerificationError:
        quorum_accepted = 1      # refused, but for another reason
    return [
        ("calls_failed", result["failed"], 0),
        ("ref_rejects", ref_rejects, 0),
        ("window_compiles", compiles, 0),
        ("sigcache_hits", c["sigcache_hits"], 0),
        ("pallas_degraded", c["pallas_degraded"], 0),
        ("canary_trips", c["canary_trips"], 0),
        ("dispatch_gap", abs(c["dispatches"] - c["implied_chunks"]), 0),
        ("tamper_accepted", accepted, 0),
        ("tamper_misattributed", misattributed, 0),
        ("tamper_ref_accepts", ref_accepts, 0),
        ("quorum_accepted", quorum_accepted, 0),
    ]


# --- faults for the control runs (tools/control_runs.py) -------------------------

def _plant_batch_verify(make):
    from cometbft_tpu.crypto.keys import Ed25519BatchVerifier
    real = Ed25519BatchVerifier.verify
    Ed25519BatchVerifier.verify = make(real)

    def undo():
        Ed25519BatchVerifier.verify = real
    return undo


def _accept_all(real):
    return lambda self: (True, [True] * len(self))


def _half_lanes(real):
    def half(self):
        k = len(self) // 2
        rest = len(self) - k
        self._pubs, self._msgs, self._sigs = (
            self._pubs[:k], self._msgs[:k], self._sigs[:k])
        ok, oks = real(self)
        return ok, list(oks) + [True] * rest
    return half


PLANTS = {
    # the control: every signature of a commit taken for good
    "accept_all": lambda: _plant_batch_verify(_accept_all),
    # the upper half of the validator set left out of the verification
    "half_lanes": lambda: _plant_batch_verify(_half_lanes),
}
