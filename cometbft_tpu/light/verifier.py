"""Stateless light-client verification core (reference
light/verifier.go:30-145).

Two modes:
- `verify_adjacent` (heights differ by 1): the untrusted header's
  validators_hash must equal the trusted header's next_validators_hash —
  then one VerifyCommitLight over the known set.
- `verify_non_adjacent` (bisection jumps): the TRUSTED set must have
  signed with >= trust_level (default 1/3) power (VerifyCommitLightTrusting),
  AND the untrusted set must have +2/3 on its own commit.

Both go through the same batch-verify seam as consensus/blocksync — on
bulk catch-up the signatures tile onto the TPU kernel.
"""

from __future__ import annotations

from ..types import validation
from ..types.proto import Timestamp
from . import planner
from .types import LightBlock, LightBlockError

# reference light/verifier.go defaultMaxClockDrift
MAX_CLOCK_DRIFT_SECONDS = 10
# SigCache attribution label of the light client's planned lanes
CACHE_PATH = "light"


class VerificationError(Exception):
    pass


class ErrOldHeader(VerificationError):
    pass


class ErrNewValSetCantBeTrusted(VerificationError):
    """Not enough trusted power signed the new header — bisect."""


class ErrInvalidHeader(VerificationError):
    pass


class PlanBudgetExceeded(VerificationError):
    """The bisection needed more provider fetches than the caller's
    budget allows (`planner.plan_update`'s `max_fetches`): a byzantine
    target, or a pathological set-rotation chain, must not let one
    client pin a shared service."""


def _expired(trusted: LightBlock, trusting_period_s: int,
             now: Timestamp) -> bool:
    """reference light/verifier.go:204 HeaderExpired."""
    t = trusted.header.time
    return t.seconds + trusting_period_s < now.seconds


def _validate_untrusted(chain_id: str, trusted: LightBlock,
                        untrusted: LightBlock, now: Timestamp,
                        max_drift_s: int) -> None:
    """reference light/verifier.go:149-201 verifyNewHeaderAndVals."""
    try:
        untrusted.validate_basic(chain_id)
    except LightBlockError as e:
        raise ErrInvalidHeader(str(e)) from e
    if untrusted.height <= trusted.height:
        raise ErrInvalidHeader(
            f"untrusted height {untrusted.height} <= trusted "
            f"{trusted.height}")
    if untrusted.header.time <= trusted.header.time:
        raise ErrInvalidHeader("untrusted header time not after trusted")
    if untrusted.header.time.seconds > now.seconds + max_drift_s:
        raise ErrInvalidHeader("untrusted header is from the future")


def check_adjacent(chain_id: str, trusted: LightBlock,
                   untrusted: LightBlock, trusting_period_s: int,
                   now: Timestamp,
                   max_drift_s: int = MAX_CLOCK_DRIFT_SECONDS) -> None:
    """The half of `verify_adjacent` that needs no signature: adjacency,
    expiry of the trusted header, the untrusted header's structure and
    times, and the binding of its set to the trusted header's
    `next_validators_hash`. The sequential client runs it against a
    header that is itself only planned (light/client.py): nothing here
    makes `untrusted` trusted."""
    if untrusted.height != trusted.height + 1:
        raise ErrInvalidHeader("headers must be adjacent in height")
    if _expired(trusted, trusting_period_s, now):
        raise ErrOldHeader("trusted header expired")
    _validate_untrusted(chain_id, trusted, untrusted, now, max_drift_s)
    if untrusted.header.validators_hash != \
            trusted.header.next_validators_hash:
        raise ErrInvalidHeader(
            "untrusted validators_hash != trusted next_validators_hash")


def check_non_adjacent(chain_id: str, trusted: LightBlock,
                       untrusted: LightBlock, trusting_period_s: int,
                       now: Timestamp,
                       max_drift_s: int = MAX_CLOCK_DRIFT_SECONDS) -> None:
    """The part of `verify_non_adjacent` that needs no signature and no
    tally: a jump, expiry of the trusted header, the untrusted header's
    structure and times."""
    if untrusted.height == trusted.height + 1:
        raise ErrInvalidHeader("use verify_adjacent for adjacent headers")
    if _expired(trusted, trusting_period_s, now):
        raise ErrOldHeader("trusted header expired")
    _validate_untrusted(chain_id, trusted, untrusted, now, max_drift_s)


def verify_own_commit(chain_id: str, untrusted: LightBlock) -> None:
    """+2/3 of the header's own set signed its commit."""
    try:
        validation.verify_commit_light(
            chain_id, untrusted.validator_set,
            untrusted.signed_header.commit.block_id,
            untrusted.height, untrusted.signed_header.commit)
    except validation.CommitVerificationError as e:
        raise ErrInvalidHeader(f"invalid commit: {e}") from e


def plan_own_commit(chain_id: str, untrusted: LightBlock, cache,
                    path: str = CACHE_PATH) -> planner.PlannedCheck:
    """`verify_own_commit` with the verification deferred: the lanes
    the rule takes, none verified; raises what it raises for whatever
    no signature decides."""
    try:
        return planner.plan_commit_light(
            chain_id, untrusted.validator_set,
            untrusted.signed_header.commit.block_id,
            untrusted.height, untrusted.signed_header.commit, cache,
            path=path)
    except validation.CommitVerificationError as e:
        raise ErrInvalidHeader(f"invalid commit: {e}") from e


def plan_trusting_commit(chain_id: str, trusted: LightBlock,
                         untrusted: LightBlock,
                         trust_level: validation.Fraction, cache,
                         path: str = CACHE_PATH) -> planner.PlannedCheck:
    """The trusting check of `verify_non_adjacent` with the verification
    deferred: the lanes it takes, none verified; raises what it raises
    for whatever no signature decides (ErrNewValSetCantBeTrusted where
    the trusted set's power does not reach `trust_level`)."""
    try:
        return planner.plan_commit_trusting(
            chain_id, trusted.validator_set,
            untrusted.signed_header.commit, trust_level, cache, path=path)
    except validation.ErrNotEnoughVotingPowerSigned as e:
        raise ErrNewValSetCantBeTrusted(str(e)) from e
    except validation.CommitVerificationError as e:
        raise ErrInvalidHeader(f"trusting verify failed: {e}") from e


# what each check raises over a signature that does not verify
_CHECK_PREFIX = {"light": "invalid commit",
                 "trusting": "trusting verify failed"}


def wrong_signature(lane: planner.Lane,
                    kind: str = "light") -> ErrInvalidHeader:
    """What `verify_own_commit` (`kind` "light") or the trusting check
    of `verify_non_adjacent` ("trusting") raises for a planned lane
    that failed."""
    cause = validation.ErrWrongSignature(lane.sig_index, lane.sig)
    err = ErrInvalidHeader(f"{_CHECK_PREFIX[kind]}: {cause}")
    err.__cause__ = cause
    return err


def verify_adjacent(chain_id: str, trusted: LightBlock,
                    untrusted: LightBlock, trusting_period_s: int,
                    now: Timestamp,
                    max_drift_s: int = MAX_CLOCK_DRIFT_SECONDS) -> None:
    """reference light/verifier.go:91-143 VerifyAdjacent."""
    check_adjacent(chain_id, trusted, untrusted, trusting_period_s, now,
                   max_drift_s)
    verify_own_commit(chain_id, untrusted)


def verify_non_adjacent(chain_id: str, trusted: LightBlock,
                        untrusted: LightBlock, trusting_period_s: int,
                        now: Timestamp,
                        trust_level: validation.Fraction =
                        validation.DEFAULT_TRUST_LEVEL,
                        max_drift_s: int = MAX_CLOCK_DRIFT_SECONDS) -> None:
    """reference light/verifier.go:30-88 VerifyNonAdjacent."""
    check_non_adjacent(chain_id, trusted, untrusted, trusting_period_s,
                       now, max_drift_s)
    try:
        validation.verify_commit_light_trusting(
            chain_id, trusted.validator_set,
            untrusted.signed_header.commit, trust_level)
    except validation.ErrNotEnoughVotingPowerSigned as e:
        raise ErrNewValSetCantBeTrusted(str(e)) from e
    except validation.CommitVerificationError as e:
        raise ErrInvalidHeader(f"trusting verify failed: {e}") from e
    verify_own_commit(chain_id, untrusted)


def verify(chain_id: str, trusted: LightBlock, untrusted: LightBlock,
           trusting_period_s: int, now: Timestamp,
           trust_level: validation.Fraction =
           validation.DEFAULT_TRUST_LEVEL) -> None:
    """reference light/verifier.go Verify: dispatch on adjacency."""
    if untrusted.height == trusted.height + 1:
        verify_adjacent(chain_id, trusted, untrusted, trusting_period_s,
                        now)
    else:
        verify_non_adjacent(chain_id, trusted, untrusted,
                            trusting_period_s, now, trust_level)
