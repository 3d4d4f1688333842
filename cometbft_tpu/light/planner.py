"""Commit and schedule planning: expand one commit check, and one whole
bisection, into signature-lane work items, entirely host-side. The ONE
planner of the light client's tiled sequential walk (light/client.py
`_verify_sequential`), of its planned skipping walk (`_verify_skipping`)
and of the farm's bisection schedules (farm/planner.py, by import).

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
in one flush: the light client's across the headers of a tile or the
hops of a bisection, the farm's across every session (farm/batcher.py).
A whole bisection schedule (`plan_update`: every pivot, every threshold
decision) costs only provider fetches and hashing. `path` is the
caller's SigCache attribution label ("light", "farm").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional

from ..pipeline.cache import SigCache
from ..types.agg_commit import AggregatedCommit
from ..types.block import Commit
from ..types.proto import Timestamp
from ..types.validation import (CommitVerificationError,
                                ErrNotEnoughVotingPowerSigned, Fraction)
from ..types.validator import ValidatorSet
from . import verifier
from .types import LightBlock


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


# --- the schedule of one update ----------------------------------------------


@dataclass
class VerifyStep:
    """One header acceptance: the checks must ALL verify for `lb` to
    become trusted (none where an aggregate seal was verified whole while
    planning); `record` is the decision in the vocabulary
    tools/check_light_spec.check_decisions validates (a bisection's);
    `reused`, whether `lb`'s set took the hash of the header before's
    (the sequential walk's)."""
    lb: LightBlock
    adjacent: bool
    checks: List[PlannedCheck]
    record: Optional[Dict] = None
    reused: bool = False


def plan_update(chain_id: str, trusted: LightBlock, target: LightBlock,
                fetch: Callable[[int], LightBlock], now: Timestamp,
                trusting_period_s: int, trust_level: Fraction,
                cache: SigCache, path: str,
                max_fetches: Optional[int] = None,
                whole_seals: bool = False) -> Iterator[VerifyStep]:
    """The reference's skipping verification (light/client.go:705-772
    verifySkipping) with every signature deferred: yields, in order,
    the steps from `trusted` to `target`, each planned against the step
    before it (trusted, or only planned). The pivots are the reference's:
    where the trusted set's power does not reach `trust_level` the
    header at mid = (trusted + candidate) // 2 is fetched (`fetch`, the
    provider's) and tried first, the pivots kept on a stack. Raises, at
    the step where it stops, what `verifier.verify` raises there for
    whatever no signature decides (expiry, heights and times, the
    validators_hash binding, power, structure); more than `max_fetches`
    pivots (None: no bound) raise `verifier.PlanBudgetExceeded`. A hop
    sealed by an aggregate (one pairing check, not lanes) is verified
    whole while planning where `whole_seals` (the light client's), and
    refused as a commit with no signature lanes elsewhere (the farm's,
    whose batcher verifies lanes only)."""
    cur, pivots, fetches = trusted, [target], 0
    while pivots:
        candidate = pivots[-1]
        adjacent = candidate.height == cur.height + 1
        try:
            checks = _plan_hop(chain_id, cur, candidate, adjacent, now,
                               trusting_period_s, trust_level, cache, path,
                               whole_seals)
        except verifier.ErrNewValSetCantBeTrusted:
            # the trusted set cannot vouch: bisect toward it
            if max_fetches is not None and fetches >= max_fetches:
                raise verifier.PlanBudgetExceeded(
                    f"bisection exceeded {max_fetches} fetches")
            fetches += 1
            lb = fetch((cur.height + candidate.height) // 2)
            lb.validate_basic(chain_id)
            pivots.append(lb)
            continue
        yield VerifyStep(candidate, adjacent, checks, _record(
            cur, candidate, adjacent, checks, trust_level))
        cur = candidate
        pivots.pop()


def _plan_hop(chain_id: str, cur: LightBlock, candidate: LightBlock,
              adjacent: bool, now: Timestamp, trusting_period_s: int,
              trust_level: Fraction, cache: SigCache, path: str,
              whole_seals: bool) -> List[PlannedCheck]:
    """`verifier.verify` of one hop with its lanes left unverified: the
    trusting check (a jump) and the +2/3 check of the candidate's own
    set, in that order."""
    if whole_seals and isinstance(candidate.signed_header.commit,
                                  AggregatedCommit):
        # an aggregate seal is one pairing check, not lanes: the hop is
        # verified here, whole, through the validation path
        verifier.verify(chain_id, cur, candidate, trusting_period_s, now,
                        trust_level)
        return []
    if adjacent:
        verifier.check_adjacent(chain_id, cur, candidate,
                                trusting_period_s, now)
        return [verifier.plan_own_commit(chain_id, candidate, cache, path)]
    verifier.check_non_adjacent(chain_id, cur, candidate,
                                trusting_period_s, now)
    return [verifier.plan_trusting_commit(chain_id, cur, candidate,
                                          trust_level, cache, path),
            verifier.plan_own_commit(chain_id, candidate, cache, path)]


def _record(cur: LightBlock, candidate: LightBlock, adjacent: bool,
            checks: List[PlannedCheck], trust_level: Fraction) -> Dict:
    """Decision record: the acceptance restated as the power tallies
    tools/check_light_spec.check_decisions re-judges (0 where the hop's
    aggregate seal was verified whole)."""
    own = checks[-1] if checks else None
    trusting = checks[0] if len(checks) == 2 else None
    return {
        "height": candidate.height,
        "from_height": cur.height,
        "adjacent": adjacent,
        "valhash_bound": adjacent,  # checked for adjacent steps
        "own_signed": own.tallied if own else 0,
        "own_total": own.total if own else 0,
        "trusted_signed": trusting.tallied if trusting else 0,
        "trusted_total": trusting.total if trusting else 0,
        "trust_num": trust_level.numerator,
        "trust_den": trust_level.denominator,
        "hash": candidate.header.hash().hex(),
    }
