"""Bisection planning: expand one client's update into signature-lane
work items, entirely host-side.

The commit planner itself (`Lane`, `PlannedCheck`, `plan_commit_light`,
`plan_commit_trusting`, `_add_lane`: why a threshold tally never needs
the device) lives in light/planner.py since the light client's tiled
sequential walk plans its commits with it too; this module keeps the
names, bound to the farm's SigCache label, and what is the farm's own:
the per-client schedule. types/validation.py's batch path tallies
optimistically while ADDING lanes and verifies them afterwards; the
planner mirrors that exact semantics, which is what makes farm verdicts
equal to LightClient verdicts lane for lane.

So a whole bisection schedule — every pivot, every threshold decision —
costs only provider fetches and hashing; the signature lanes it emits
are verified LATER, coalesced with every other session's lanes in one
shared device batch (batcher.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional

from ..light import planner as commit_planner
from ..light import verifier
from ..light.planner import Lane, PlannedCheck, _add_lane  # noqa: F401
from ..light.types import LightBlock
from ..pipeline.cache import SigCache
from ..types.proto import Timestamp
from ..types.validation import (DEFAULT_TRUST_LEVEL,
                                ErrNotEnoughVotingPowerSigned, Fraction)

# SigCache attribution label for farm lanes; the light client's own
# lanes, planned by the same light/planner.py, are labelled "light"
CACHE_PATH = "farm"

plan_commit_light = functools.partial(commit_planner.plan_commit_light,
                                      path=CACHE_PATH)
plan_commit_trusting = functools.partial(
    commit_planner.plan_commit_trusting, path=CACHE_PATH)


class PlanBudgetExceeded(verifier.VerificationError):
    """The bisection needed more provider fetches than the farm's
    per-request budget allows — a byzantine target (or a pathological
    valset-rotation chain) must not let one client pin the service."""


# --- the per-client schedule --------------------------------------------------


@dataclass
class VerifyStep:
    """One header acceptance: the checks must ALL verify for `lb` to
    become trusted; `record` is the decision in the vocabulary
    tools/check_light_spec.check_decisions validates."""
    lb: LightBlock
    adjacent: bool
    checks: List[PlannedCheck]
    record: Dict


def plan_update(chain_id: str, trusted: LightBlock, target: LightBlock,
                provider, now: Timestamp, trusting_period_s: int,
                trust_level: Fraction = DEFAULT_TRUST_LEVEL,
                cache: Optional[SigCache] = None,
                max_fetches: int = 128,
                max_drift_s: int = verifier.MAX_CLOCK_DRIFT_SECONDS
                ) -> List[VerifyStep]:
    """The light/client.py `_verify_skipping` loop with verification
    deferred: returns the ordered steps (pivot chain) whose checks the
    batcher verifies in shared batches. Raises the verifier/validation
    errors for every host-side rejection (expiry, time/height ordering,
    valset-hash binding, insufficient power, bisection stall)."""
    cache = cache if cache is not None else SigCache(0)  # 0 = disabled
    steps: List[VerifyStep] = []
    cur = trusted
    pivots = [target]
    fetches = 0
    while pivots:
        candidate = pivots[-1]
        adjacent = candidate.height == cur.height + 1
        if verifier._expired(cur, trusting_period_s, now):
            raise verifier.ErrOldHeader("trusted header expired")
        verifier._validate_untrusted(chain_id, cur, candidate, now,
                                     max_drift_s)
        trusting: Optional[PlannedCheck] = None
        if adjacent:
            if candidate.header.validators_hash != \
                    cur.header.next_validators_hash:
                raise verifier.ErrInvalidHeader(
                    "untrusted validators_hash != trusted "
                    "next_validators_hash")
        else:
            try:
                trusting = plan_commit_trusting(
                    chain_id, cur.validator_set,
                    candidate.signed_header.commit, trust_level, cache)
            except ErrNotEnoughVotingPowerSigned:
                # the trusted set cannot vouch: bisect toward it
                # (light/client.py:180-188)
                mid = (cur.height + candidate.height) // 2
                if mid in (cur.height, candidate.height):
                    raise verifier.ErrInvalidHeader(
                        "bisection cannot make progress")
                if fetches >= max_fetches:
                    raise PlanBudgetExceeded(
                        f"bisection exceeded {max_fetches} fetches")
                fetches += 1
                lb = provider.light_block(mid)
                lb.validate_basic(chain_id)
                pivots.append(lb)
                continue
        own = plan_commit_light(
            chain_id, candidate.validator_set,
            candidate.signed_header.commit.block_id, candidate.height,
            candidate.signed_header.commit, cache)
        checks = [own] if trusting is None else [trusting, own]
        steps.append(VerifyStep(candidate, adjacent, checks, _record(
            cur, candidate, adjacent, trusting, own, trust_level)))
        cur = candidate
        pivots.pop()
    return steps


def _record(cur: LightBlock, candidate: LightBlock, adjacent: bool,
            trusting: Optional[PlannedCheck], own: PlannedCheck,
            trust_level: Fraction) -> Dict:
    """Decision record — the farm's acceptance restated as the power
    tallies tools/check_light_spec.check_decisions re-judges."""
    return {
        "height": candidate.height,
        "from_height": cur.height,
        "adjacent": adjacent,
        "valhash_bound": adjacent,  # checked above for adjacent steps
        "own_signed": own.tallied,
        "own_total": own.total,
        "trusted_signed": trusting.tallied if trusting else 0,
        "trusted_total": trusting.total if trusting else 0,
        "trust_num": trust_level.numerator,
        "trust_den": trust_level.denominator,
        "hash": candidate.header.hash().hex(),
    }
