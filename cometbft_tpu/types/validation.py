"""Commit verification — single, batch, and trusting forms
(reference types/validation.go).

The batch path feeds the TPU kernel through the same plugin seam the
reference uses (crypto/batch.create_batch_verifier); because the kernel is
lane-parallel it returns per-signature verdicts, so failure attribution
needs no second pass (reference falls back to per-sig loops,
types/validation.go:306-315).

The cross-commit tiling form (many commits → one device batch) lives in
engine/blocksync; these functions are the per-commit semantics they must
agree with.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from ..crypto import batch as crypto_batch
from ..trace import shared_tracer
from .block import Commit, CommitSig, BlockID
from .validator import ValidatorSet

# Minimum signature count before the device batch path pays for itself.
# The reference sets 2 (types/validation.go:13) because its batch verifier
# is a cheap same-thread CPU MSM; here "batch" means a TPU kernel dispatch
# (and a one-time jit compile), so small commits — consensus rounds, tiny
# validator sets — go through the ~50µs native single-sig path instead,
# and the kernel serves the bulk tiles (blocksync, light client) it was
# built for.
BATCH_VERIFY_THRESHOLD = 64


class CommitVerificationError(Exception):
    pass


class ErrInvalidCommitSignatures(CommitVerificationError):
    pass


class ErrNotEnoughVotingPowerSigned(CommitVerificationError):
    def __init__(self, got: int, needed: int):
        super().__init__(f"insufficient voting power: got {got}, "
                         f"needed more than {needed}")
        self.got = got
        self.needed = needed


class ErrWrongSignature(CommitVerificationError):
    def __init__(self, idx: int, sig: bytes):
        super().__init__(f"wrong signature (#{idx}): {sig.hex()}")
        self.idx = idx


@dataclass(frozen=True)
class Fraction:
    """reference libs/math/fraction.go."""
    numerator: int
    denominator: int


DEFAULT_TRUST_LEVEL = Fraction(1, 3)


def _verify_basic(vals: ValidatorSet, commit: Commit, height: int,
                  block_id: BlockID) -> None:
    """reference types/validation.go:408-431."""
    if vals is None:
        raise CommitVerificationError("nil validator set")
    if commit is None:
        raise CommitVerificationError("nil commit")
    if len(vals) != len(commit.signatures):
        raise ErrInvalidCommitSignatures(
            f"validator set size {len(vals)} != {len(commit.signatures)} sigs")
    if height != commit.height:
        raise CommitVerificationError(
            f"invalid commit height: want {height}, got {commit.height}")
    if block_id != commit.block_id:
        raise CommitVerificationError("invalid commit -- wrong block ID")


def _should_batch_verify(vals: ValidatorSet, missing: int) -> bool:
    """Whether `missing` lanes, the ones the verified-signature cache
    did not answer, are worth one flush through the batch seam. Asked of
    the lanes that MISS, not of the commit's size: a validator's commits
    are mostly hits, and three misses of 150 are three native checks,
    not a 512-lane dispatch."""
    prop = vals.get_proposer()
    if prop is None:
        return False
    threshold = BATCH_VERIFY_THRESHOLD
    if prop.pub_key.type_() == "bls12_381":
        # BLS per-sig verification is pairing-bound (two Miller loops
        # plus a final exponentiation EACH); the multi-pairing batch
        # shares one final exponentiation across the whole set, so it
        # pays for itself at the reference's own threshold of 2
        # (types/validation.go:13) — no device dispatch involved.
        threshold = 2
    return (missing >= threshold
            and crypto_batch.supports_batch_verifier(prop.pub_key))


def _verify_commit_core(chain_id: str, vals: ValidatorSet, commit: Commit,
                        voting_power_needed: int,
                        ignore: Callable[[CommitSig], bool],
                        count: Callable[[CommitSig], bool],
                        count_all: bool, lookup_by_index: bool) -> None:
    """Shared body of the batch and single paths
    (reference types/validation.go:218-322 and :331-405; one body here
    because attribution is free with per-lane verdicts)."""
    from .agg_commit import AggregatedCommit
    if isinstance(commit, AggregatedCommit):
        # the BLS aggregate seal: one multi-pairing check for the whole
        # commit (aggsig/verify.py), same ignore/count semantics and
        # exception vocabulary, whole-aggregate verdict SigCache-keyed
        from ..aggsig import verify as aggsig_verify
        from ..pipeline.cache import shared_cache as _shared_cache
        aggsig_verify.verify_aggregated_commit(
            chain_id, vals, commit, voting_power_needed,
            ignore=ignore, count=count, count_all=count_all,
            lookup_by_index=lookup_by_index, cache=_shared_cache())
        return
    with shared_tracer().start("commit.verify") as span:
        _verify_commit_lanes(chain_id, vals, commit, voting_power_needed,
                             ignore, count, count_all, lookup_by_index,
                             span)


def _verify_commit_lanes(chain_id, vals, commit, voting_power_needed,
                         ignore, count, count_all, lookup_by_index,
                         span) -> None:
    # verified-signature cache (pipeline/cache): commits re-checked by
    # the light client or blocksync's respeculation path, and a
    # validator's `last_commit`s, whose signatures it took in as votes,
    # skip signatures a previous pass already verified TRUE; cached
    # lanes never reach a verifier and failed lanes are never cached,
    # so verdicts are byte-identical with the uncached path
    from ..pipeline.cache import shared_cache
    cache = shared_cache()

    # first the walk: the structural checks, the tally and the cache
    # lookups. The route is chosen afterwards, from the lanes that
    # missed; what the walk refuses is held back until then, because the
    # two routes have always raised in different orders (below)
    tallied = 0
    seen = {}
    walked = []     # (idx, pub key, its bytes, sign-bytes, signature)
    refused = None
    try:
        for idx, cs in enumerate(commit.signatures):
            if ignore(cs):
                continue
            try:
                cs.validate_basic()
            except ValueError as e:
                raise CommitVerificationError(
                    f"invalid signature at index {idx}: {e}") from e

            if lookup_by_index:
                val = vals.get_by_index(idx)
            else:
                val_idx, val = vals.get_by_address(cs.validator_address)
                if val is None:
                    continue
                if val_idx in seen:
                    raise CommitVerificationError(
                        f"double vote from validator {val_idx} "
                        f"({seen[val_idx]} and {idx})")
                seen[val_idx] = idx

            walked.append((idx, val.pub_key, val.pub_key.bytes_(),
                           commit.vote_sign_bytes(chain_id, idx),
                           cs.signature))

            if count(cs):
                tallied += val.voting_power
            if not count_all and tallied > voting_power_needed:
                break
        if tallied <= voting_power_needed:
            raise ErrNotEnoughVotingPowerSigned(tallied,
                                                voting_power_needed)
    except CommitVerificationError as e:
        refused = e
    # one lookup of the lanes walked; a hit previously verified TRUE and
    # is no work on either route. A miss keeps its key for the insert
    keys, cached = cache.lookup([lane[2:] for lane in walked],
                                path="commit")
    missing = [(idx, pub_key, key, msg, sig)    # key: the lane's cache key
               for (idx, pub_key, _pkb, msg, sig), key, hit
               in zip(walked, keys, cached) if not hit]
    hits = len(walked) - len(missing)

    bv = None
    if _should_batch_verify(vals, len(missing)):
        if len({v.pub_key.type_() for v in vals.validators}) > 1:
            # heterogeneous valset: a proposer-keyed single-curve
            # verifier would TypeError on the first foreign-curve
            # lane; the mixed dispatcher buckets per curve (batched
            # where supported, per-sig singles otherwise) with exact
            # per-lane attribution
            bv = crypto_batch.MixedBatchVerifier()
        else:
            bv, _ok = crypto_batch.create_batch_verifier(
                vals.get_proposer().pub_key)
    span.set_attr("lanes", hits + len(missing))
    span.set_attr("cache_hits", hits)
    span.set_attr("device_lanes", len(missing) if bv is not None else 0)
    span.set_attr("native_lanes", 0 if bv is not None else len(missing))

    if bv is None:
        # the native route verifies in index order and names the first
        # signature that fails, before anything the walk refused at or
        # after it (reference verifyCommitSingle)
        oks = []
        for _idx, pub_key, _key, msg, sig in missing:
            oks.append(bool(pub_key.verify_signature(msg, sig)))
            if not oks[-1]:
                break
    else:
        # the batch route flushes only what passed the walk (reference
        # verifyCommitBatch: the tally is checked before the batch
        # verifies)
        if refused is not None:
            raise refused
        for _idx, pub_key, _key, msg, sig in missing:
            bv.add(pub_key, msg, sig)
        _all_ok, oks = bv.verify()
        # fail-closed: a lane counts as verified only on its own
        # verdict; a verifier that answers for fewer lanes than it was
        # given has refused the rest
        oks = [bool(ok) for ok in oks] + \
            [False] * (len(missing) - len(oks))
    # the lanes that verified true, in one insert
    cache.insert([lane[2] for lane, ok in zip(missing, oks) if ok])
    for (idx, _pk, _key, _msg, sig), ok in zip(missing, oks):
        if not ok:
            raise ErrWrongSignature(idx, sig)
    if refused is not None:
        raise refused


def verify_commit(chain_id: str, vals: ValidatorSet, block_id: BlockID,
                  height: int, commit: Commit) -> None:
    """+2/3 signed, checking ALL signatures
    (reference types/validation.go:26-53). Raises on failure."""
    _verify_basic(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    _verify_commit_core(
        chain_id, vals, commit, needed,
        ignore=lambda c: c.absent_(),
        count=lambda c: c.for_block(),
        count_all=True, lookup_by_index=True)


def verify_commit_light(chain_id: str, vals: ValidatorSet, block_id: BlockID,
                        height: int, commit: Commit,
                        count_all: bool = False) -> None:
    """+2/3 signed, early-exit once the threshold is reached — blocksync /
    light-client form (reference types/validation.go:61-116)."""
    _verify_basic(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    _verify_commit_core(
        chain_id, vals, commit, needed,
        ignore=lambda c: not c.for_block(),
        count=lambda _: True,
        count_all=count_all, lookup_by_index=True)


def verify_commit_light_trusting(chain_id: str, vals: ValidatorSet,
                                 commit: Commit,
                                 trust_level: Fraction = DEFAULT_TRUST_LEVEL,
                                 count_all: bool = False) -> None:
    """trustLevel of a TRUSTED validator set signed this commit — validators
    matched by address, unknown signers skipped, double votes rejected
    (reference types/validation.go:118-215)."""
    if vals is None:
        raise CommitVerificationError("nil validator set")
    if commit is None:
        raise CommitVerificationError("nil commit")
    if trust_level.denominator == 0:
        raise CommitVerificationError("trustLevel has zero denominator")
    needed = (vals.total_voting_power()
              * trust_level.numerator) // trust_level.denominator
    _verify_commit_core(
        chain_id, vals, commit, needed,
        ignore=lambda c: not c.for_block(),
        count=lambda _: True,
        count_all=count_all, lookup_by_index=False)
