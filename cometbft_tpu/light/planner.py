"""Commit planning: expand one commit check into signature-lane work
items, entirely host-side. The ONE planner of the light client's tiled
sequential walk (light/client.py `_verify_sequential`) and of the farm's
bisection schedules (farm/planner.py `plan_update`).

The enabling observation: both light-client threshold rules are pure
functions of ADDRESSES and voting power — `verify_commit_light_trusting`
tallies the power of trusted-set members who signed, and
`verify_commit_light` tallies claimed-set power — so whether a commit
CAN pass is decided before any signature is cryptographically verified.
types/validation.py's own batch path works the same way: it tallies
optimistically while ADDING lanes to the batch verifier, early-exits
the scan at the threshold, and only then verifies the added lanes (a
false lane fails the whole check afterwards). The planner mirrors that
exact semantics, which is what makes a verdict over planned lanes equal
to a LightClient verdict lane for lane.

So the lanes of many commits can be gathered first and verified LATER,
in one flush: the light client's across the headers of a tile, the
farm's across every session (farm/batcher.py). `path` is the caller's
SigCache attribution label ("light", "farm").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

from ..pipeline.cache import SigCache
from ..types.block import Commit
from ..types.validation import (CommitVerificationError,
                                ErrNotEnoughVotingPowerSigned, Fraction)
from ..types.validator import ValidatorSet


@dataclass
class Lane:
    """One pending signature verification: a device batch lane."""
    pub: bytes          # raw pubkey bytes (device wire form)
    msg: bytes          # canonical vote sign-bytes
    sig: bytes
    pk: object          # crypto PubKey (CPU-fallback verify)
    sig_index: int      # index into the commit's signature list
    key: bytes          # SigCache key, from the lane's lookup


@dataclass
class PlannedCheck:
    """One VerifyCommitLight / VerifyCommitLightTrusting whose
    threshold already passed host-side; `lanes` await verification."""
    kind: str                     # "light" | "trusting"
    commit: Commit
    lanes: List[Lane] = field(default_factory=list)
    tallied: int = 0              # power tallied at early-exit
    total: int = 0                # total power of the tallying set
    needed: int = 0               # strict floor (accept iff tallied >)
    cache_hits: int = 0           # lanes skipped via SigCache


def plan_commit_light(chain_id: str, vals: ValidatorSet, block_id,
                      height: int, commit: Commit, cache: SigCache,
                      path: str) -> PlannedCheck:
    """Lane plan for types/validation.verify_commit_light (+2/3 of the
    header's OWN claimed set, early-exit at the threshold). Raises the
    same structural/power errors; signature verdicts come later."""
    _basic(vals, commit, height, block_id)
    total = vals.total_voting_power()
    needed = total * 2 // 3
    planned = PlannedCheck("light", commit, total=total, needed=needed)
    taken = []
    try:
        for idx, cs in enumerate(commit.signatures):
            if not cs.for_block():
                continue
            _validate_sig(cs, idx)
            val = vals.get_by_index(idx)
            taken.append((idx, val, cs))
            planned.tallied += val.voting_power
            if planned.tallied > needed:
                break
        if planned.tallied <= needed:
            raise ErrNotEnoughVotingPowerSigned(planned.tallied, needed)
    finally:
        _add_lanes(planned, chain_id, commit, taken, cache, path)
    return planned


def plan_commit_trusting(chain_id: str, vals: ValidatorSet,
                         commit: Commit, trust_level: Fraction,
                         cache: SigCache, path: str) -> PlannedCheck:
    """Lane plan for verify_commit_light_trusting (trust_level of the
    TRUSTED set, matched by address, double votes rejected)."""
    if vals is None:
        raise CommitVerificationError("nil validator set")
    if commit is None:
        raise CommitVerificationError("nil commit")
    if trust_level.denominator == 0:
        raise CommitVerificationError("trustLevel has zero denominator")
    total = vals.total_voting_power()
    needed = (total * trust_level.numerator) // trust_level.denominator
    planned = PlannedCheck("trusting", commit, total=total, needed=needed)
    seen: Dict[int, int] = {}
    taken = []
    try:
        for idx, cs in enumerate(commit.signatures):
            if not cs.for_block():
                continue
            _validate_sig(cs, idx)
            val_idx, val = vals.get_by_address(cs.validator_address)
            if val is None:
                # signer outside the trusted set: no vouching power
                continue
            if val_idx in seen:
                raise CommitVerificationError(
                    f"double vote from validator {val_idx} "
                    f"({seen[val_idx]} and {idx})")
            seen[val_idx] = idx
            taken.append((idx, val, cs))
            planned.tallied += val.voting_power
            if planned.tallied > needed:
                break
        if planned.tallied <= needed:
            raise ErrNotEnoughVotingPowerSigned(planned.tallied, needed)
    finally:
        _add_lanes(planned, chain_id, commit, taken, cache, path)
    return planned


def _basic(vals: ValidatorSet, commit: Commit, height: int,
           block_id) -> None:
    """types/validation._verify_basic, restated (it is private there)."""
    if vals is None:
        raise CommitVerificationError("nil validator set")
    if commit is None:
        raise CommitVerificationError("nil commit")
    if len(vals) != len(commit.signatures):
        raise CommitVerificationError(
            f"validator set size {len(vals)} != "
            f"{len(commit.signatures)} sigs")
    if height != commit.height:
        raise CommitVerificationError(
            f"invalid commit height: want {height}, got {commit.height}")
    if block_id != commit.block_id:
        raise CommitVerificationError("invalid commit -- wrong block ID")


def _validate_sig(cs, idx: int) -> None:
    try:
        cs.validate_basic()
    except ValueError as e:
        raise CommitVerificationError(
            f"invalid signature at index {idx}: {e}") from e


def _add_lanes(planned: PlannedCheck, chain_id: str, commit: Commit,
               taken, cache: SigCache, path: str) -> None:
    """Look the (index, validator, CommitSig) the tally took up in the
    cache in one batch; a miss becomes a lane that keeps its key. Run
    for a refused plan too, so that the lanes it took count on `path`
    as they always have."""
    triples = [(val.pub_key.bytes_(), commit.vote_sign_bytes(chain_id, idx),
                cs.signature) for idx, val, cs in taken]
    keys, cached = cache.lookup(triples, path)
    for (idx, val, _cs), (pkb, msg, sig), key, hit in zip(
            taken, triples, keys, cached):
        if hit:
            planned.cache_hits += 1  # previously verified TRUE: no lane
        else:
            planned.lanes.append(Lane(pkb, msg, sig, val.pub_key, idx, key))


def _add_lane(planned: PlannedCheck, chain_id: str, commit: Commit,
              idx: int, val, cs, cache: SigCache, path: str) -> None:
    """`_add_lanes` of one signature."""
    _add_lanes(planned, chain_id, commit, [(idx, val, cs)], cache, path)
