"""VoteSet: 2/3-majority vote accounting for one (height, round, type)
(reference types/vote_set.go:158-473).

Semantics reproduced exactly:
- `votes` keeps one canonical vote per validator (the first seen; votes
  for the 2/3-majority block take priority once one exists),
- `votes_by_block` tracks per-block tallies; conflicting votes are only
  retained for blocks a peer claimed has a 2/3 majority (memory-bounded
  double-sign tracking, the DoS argument at vote_set.go:26-56),
- quorum = total_power * 2/3 + 1, first quorum latches `maj23`.

Single-threaded by design: the consensus engine serializes all mutations
through its event loop (SURVEY §2.3: the single-writer receiveRoutine),
so the reference's mutex has no analog here.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from ..libs.bits import BitArray
from ..trace import shared_tracer
from .block import BlockID, Commit, CommitSig
from .vote import Vote, PRECOMMIT_TYPE

MAX_VOTES_COUNT = 10000  # DoS bound, reference types/vote_set.go:14-17
_TRACER = shared_tracer()


class VoteError(Exception):
    pass


class ErrVoteUnexpectedStep(VoteError):
    pass


class ErrVoteInvalidValidatorIndex(VoteError):
    pass


class ErrVoteInvalidValidatorAddress(VoteError):
    pass


class ErrVoteInvalidSignature(VoteError):
    pass


class ErrVoteNonDeterministicSignature(VoteError):
    """Same validator, same block, different signature bytes."""


class ErrVoteConflictingVotes(VoteError):
    """Double-sign: same validator voted for two different blocks.

    Carries both votes — the raw material of DuplicateVoteEvidence
    (reference types/vote_set.go NewConflictingVoteError)."""

    def __init__(self, existing: Vote, new: Vote, added: bool):
        super().__init__(
            f"conflicting votes from validator "
            f"{new.validator_address.hex()}")
        self.vote_a = existing
        self.vote_b = new
        self.added = added


class _BlockVotes:
    """Votes for one particular block (reference vote_set.go:675-705)."""

    __slots__ = ("peer_maj23", "bit_array", "votes", "sum")

    def __init__(self, peer_maj23: bool, num_validators: int):
        self.peer_maj23 = peer_maj23
        self.bit_array = BitArray(num_validators)
        self.votes: List[Optional[Vote]] = [None] * num_validators
        self.sum = 0

    def add_verified_vote(self, vote: Vote, voting_power: int) -> None:
        idx = vote.validator_index
        if self.votes[idx] is None:
            self.bit_array.set_index(idx, True)
            self.votes[idx] = vote
            self.sum += voting_power

    def get_by_index(self, idx: int) -> Optional[Vote]:
        return self.votes[idx]


class VoteSet:
    def __init__(self, chain_id: str, height: int, round_: int,
                 signed_msg_type: int, val_set, extensions_enabled=False):
        if height == 0:
            raise ValueError("cannot make VoteSet for height 0")
        self.chain_id = chain_id
        self.height = height
        self.round = round_
        self.signed_msg_type = signed_msg_type
        self.val_set = val_set
        self.extensions_enabled = extensions_enabled
        n = len(val_set)
        self.votes_bit_array = BitArray(n)
        self.votes: List[Optional[Vote]] = [None] * n
        self.sum = 0
        self.maj23: Optional[BlockID] = None
        self.votes_by_block: Dict[bytes, _BlockVotes] = {}
        self.peer_maj23s: Dict[str, BlockID] = {}

    def size(self) -> int:
        return len(self.val_set)

    # --- adding votes --------------------------------------------------------

    def add_vote(self, vote: Optional[Vote]) -> bool:
        """Returns True if added, False for exact duplicates; raises
        VoteError otherwise (reference vote_set.go:158 AddVote)."""
        val = self._precheck(vote)
        if val is None:
            return False  # exact duplicate
        self._check_signature(vote, val)
        return self._finish_add(vote, val)

    def _check_signature(self, vote: Vote, val) -> None:
        """The per-vote hot path (types/vote.go:235, and with vote
        extensions `VerifyVoteAndExtension`); raises on failure. A
        batched intake ahead of it (`preverify_lanes`) shows here as a
        cache hit, and as nothing else: the vote's signature is looked up
        on path `vote`, an extension's on path `ext`, and only what
        misses is verified natively, once."""
        addr = vote.validator_address
        pub_key = val.pub_key
        pkb = pub_key.bytes_()
        if self.extensions_enabled:
            if vote.block_id.is_nil() and \
                    (vote.extension or vote.extension_signature):
                # reference Vote.ValidateBasic: extensions only ride
                # non-nil precommits — unsigned bytes on a nil vote
                # would be stored and re-gossiped otherwise
                raise VoteError("extension data on nil precommit")
            extended = self.signs_extension(vote)
            ok = (not extended or bool(vote.extension_signature)) and \
                verify_cached(pub_key, pkb, vote.sign_bytes(self.chain_id),
                              vote.signature, "vote", vote.height)[1]
            if ok and extended:
                ok = verify_cached(
                    pub_key, pkb, vote.extension_sign_bytes(self.chain_id),
                    vote.extension_signature, "ext", vote.height)[1]
            if not ok:
                raise ErrVoteInvalidSignature(
                    f"failed to verify extended vote from {addr.hex()}")
            return
        # re-gossiped votes hit the verified-signature cache instead of
        # re-running the ~400µs verify (or burning a device lane); only
        # verified-TRUE signatures are ever cached, so a hit can't flip a
        # verdict. _precheck pinned addr == val.address, so Vote.verify's
        # address check is redundant here
        if not verify_cached(pub_key, pkb, vote.sign_bytes(self.chain_id),
                             vote.signature, "vote", vote.height)[1]:
            raise ErrVoteInvalidSignature(
                f"failed to verify vote from {addr.hex()}")
        if vote.extension or vote.extension_signature:
            raise VoteError("unexpected vote extension data")

    def signs_extension(self, vote: Vote) -> bool:
        """Whether `add_vote` checks a second signature of this vote, over
        its extension: a non-nil precommit in a set with vote extensions
        (reference types/vote.go VerifyVoteAndExtension)."""
        return (self.extensions_enabled and vote.type_ == PRECOMMIT_TYPE
                and not vote.block_id.is_nil())

    def lane_validator(self, vote: Optional[Vote]):
        """The validator whose key `add_vote(vote)` would look this
        vote's signatures up under in the verified-signature cache, or
        None where it never gets that far or the lookups are not all of
        the check: a vote `_precheck` refuses, an exact duplicate, a vote
        carrying extension data it should not, a non-nil precommit of a
        set with vote extensions that lacks its extension signature.
        What a batched intake may verify ahead of `add_vote`, and nothing
        else: the vote's signature, and its extension's where
        `signs_extension`."""
        try:
            val = self._precheck(vote)
        except VoteError:
            return None
        if val is None:
            return None
        if self.signs_extension(vote):
            return val if vote.extension_signature else None
        if vote.extension or vote.extension_signature:
            return None
        return val

    def lanes(self, vote: Vote, val) -> list:
        """The (public key, sign-bytes, signature, cache path) lanes of a
        vote `lane_validator` gave `val` for: its own, and its
        extension's where `signs_extension`."""
        out = [(val.pub_key, vote.sign_bytes(self.chain_id), vote.signature,
                "vote")]
        if self.signs_extension(vote):
            out.append((val.pub_key, vote.extension_sign_bytes(self.chain_id),
                        vote.extension_signature, "ext"))
        return out

    def add_votes(self, votes: List[Vote]) -> List:
        """Batched ingest: the signatures of the whole list that the
        cache does not hold are verified in ONE flush through the
        crypto/batch seam where they are worth one (`preverify_lanes`),
        then every vote is added as `add_vote` adds it, which finds its
        signature verified or verifies it natively (reference
        crypto/ed25519/ed25519.go:208-241 batches the same way for
        commits; here it is applied to vote ingest). Consensus does the
        same over the run of votes queued in its inbox
        (consensus/state.py `_intake`).

        Returns one entry per vote: True (added), False (exact
        duplicate), or the VoteError instance that add_vote would have
        raised (conflicts carry both votes).
        """
        preverify_lanes([
            lane for v in votes
            if (val := self.lane_validator(v)) is not None
            for lane in self.lanes(v, val)])
        out: List = []
        for v in votes:
            try:
                out.append(self.add_vote(v))
            except VoteError as e:
                out.append(e)
        return out

    def _precheck(self, vote: Optional[Vote]):
        """Everything before the signature check (reference
        vote_set.go:158-240): returns the validator, or None for an
        exact duplicate; raises VoteError."""
        if vote is None:
            raise VoteError("nil vote")
        idx = vote.validator_index
        addr = vote.validator_address
        block_key = vote.block_id.key()

        if idx < 0:
            raise ErrVoteInvalidValidatorIndex(f"index {idx} < 0")
        if not addr:
            raise ErrVoteInvalidValidatorAddress("empty address")
        if (vote.height != self.height or vote.round != self.round
                or vote.type_ != self.signed_msg_type):
            raise ErrVoteUnexpectedStep(
                f"expected {self.height}/{self.round}/{self.signed_msg_type},"
                f" got {vote.height}/{vote.round}/{vote.type_}")

        val = self.val_set.get_by_index(idx)
        if val is None:
            raise ErrVoteInvalidValidatorIndex(
                f"no validator at index {idx} in set of "
                f"{len(self.val_set)}")
        if addr != val.address:
            raise ErrVoteInvalidValidatorAddress(
                f"vote address {addr.hex()} != validator {idx} address "
                f"{val.address.hex()}")

        existing = self._get_vote(idx, block_key)
        if existing is not None:
            if existing.signature == vote.signature:
                return None  # exact duplicate
            raise ErrVoteNonDeterministicSignature(
                f"existing vote: {existing}; new vote: {vote}")
        return val

    def _finish_add(self, vote: Vote, val) -> bool:
        added, conflicting = self._add_verified_vote(
            vote, vote.block_id.key(), val.voting_power)
        if conflicting is not None:
            raise ErrVoteConflictingVotes(conflicting, vote, added)
        if not added:
            raise AssertionError("expected to add non-conflicting vote")
        return added

    def _get_vote(self, idx: int, block_key: bytes) -> Optional[Vote]:
        v = self.votes[idx]
        if v is not None and v.block_id.key() == block_key:
            return v
        bv = self.votes_by_block.get(block_key)
        if bv is not None:
            return bv.get_by_index(idx)
        return None

    def _add_verified_vote(self, vote: Vote, block_key: bytes,
                           voting_power: int):
        """reference vote_set.go:260-329 addVerifiedVote."""
        idx = vote.validator_index
        conflicting = None

        existing = self.votes[idx]
        if existing is not None:
            if existing.block_id == vote.block_id:
                raise AssertionError("unexpected duplicate vote")
            conflicting = existing
            # replace only if the new vote is for the latched maj23 block
            if self.maj23 is not None and self.maj23.key() == block_key:
                self.votes[idx] = vote
                self.votes_bit_array.set_index(idx, True)
        else:
            self.votes[idx] = vote
            self.votes_bit_array.set_index(idx, True)
            self.sum += voting_power

        bv = self.votes_by_block.get(block_key)
        if bv is not None:
            if conflicting is not None and not bv.peer_maj23:
                return False, conflicting
        else:
            if conflicting is not None:
                # not tracking this block: forget the conflicting vote
                return False, conflicting
            bv = _BlockVotes(False, len(self.val_set))
            self.votes_by_block[block_key] = bv

        orig_sum = bv.sum
        quorum = self.val_set.total_voting_power() * 2 // 3 + 1
        bv.add_verified_vote(vote, voting_power)

        if orig_sum < quorum <= bv.sum and self.maj23 is None:
            self.maj23 = vote.block_id
            for i, v in enumerate(bv.votes):
                if v is not None:
                    self.votes[i] = v
        return True, conflicting

    def set_peer_maj23(self, peer_id: str, block_id: BlockID) -> None:
        """A peer claims 2/3 majority for block_id: start tracking
        conflicting votes for it (reference vote_set.go:335-368)."""
        block_key = block_id.key()
        existing = self.peer_maj23s.get(peer_id)
        if existing is not None:
            if existing == block_id:
                return
            raise VoteError(
                f"conflicting maj23 claim from peer {peer_id}")
        self.peer_maj23s[peer_id] = block_id

        bv = self.votes_by_block.get(block_key)
        if bv is not None:
            bv.peer_maj23 = True
        else:
            self.votes_by_block[block_key] = _BlockVotes(
                True, len(self.val_set))

    # --- queries -------------------------------------------------------------

    def bit_array(self) -> BitArray:
        return self.votes_bit_array.copy()

    def bit_array_by_block_id(self, block_id: BlockID
                              ) -> Optional[BitArray]:
        bv = self.votes_by_block.get(block_id.key())
        return bv.bit_array.copy() if bv is not None else None

    def get_by_index(self, idx: int) -> Optional[Vote]:
        return self.votes[idx]

    def get_by_address(self, addr: bytes) -> Optional[Vote]:
        idx, val = self.val_set.get_by_address(addr)
        if val is None:
            return None
        return self.votes[idx]

    def list_votes(self) -> List[Vote]:
        return [v for v in self.votes if v is not None]

    def has_two_thirds_majority(self) -> bool:
        return self.maj23 is not None

    def is_commit(self) -> bool:
        return (self.signed_msg_type == PRECOMMIT_TYPE
                and self.maj23 is not None)

    def has_two_thirds_any(self) -> bool:
        return self.sum > self.val_set.total_voting_power() * 2 // 3

    def has_all(self) -> bool:
        return self.sum == self.val_set.total_voting_power()

    def two_thirds_majority(self) -> Optional[BlockID]:
        """The latched 2/3-majority block, or None."""
        return self.maj23

    # --- commit construction -------------------------------------------------

    def _make_commit_plain(self) -> Commit:
        """Per-lane-signature commit assembly (reference
        MakeExtendedCommit vote_set.go:635 + ExtendedCommit.ToCommit):
        one CommitSig slot per validator, absent where no usable vote."""
        if self.signed_msg_type != PRECOMMIT_TYPE:
            raise VoteError("cannot make commit from non-precommit VoteSet")
        if self.maj23 is None:
            raise VoteError("cannot make commit without +2/3 majority")
        sigs = []
        for v in self.votes:
            if v is None:
                sigs.append(CommitSig.absent())
                continue
            cs = v.commit_sig()
            # votes for a different (non-maj23) block are marked absent
            if cs.for_block() and v.block_id != self.maj23:
                cs = CommitSig.absent()
            sigs.append(cs)
        return Commit(height=self.height, round=self.round,
                      block_id=self.maj23, signatures=sigs)

    def make_commit(self) -> Commit:
        """Commit assembly. When the validator set is uniformly BLS
        with registered proofs of possession, the for-block signatures
        fold into the AggregatedCommit seal (one 96B aggregate + a
        signer bitmap — types/agg_commit.py); every other valset gets
        the plain per-lane form, byte-for-byte as before."""
        from .agg_commit import maybe_aggregate
        return maybe_aggregate(self._make_commit_plain(), self.val_set)

    def make_extended_commit(self) -> "ExtendedCommit":
        """Commit + the vote extensions that rode each precommit
        (reference vote_set.go:635 MakeExtendedCommit). Always the
        plain per-lane form: extensions pair with individual
        signatures, never with the aggregate seal."""
        from .extended_commit import ExtendedCommit, ExtendedCommitSig
        commit = self._make_commit_plain()
        ext_sigs = []
        for cs, v in zip(commit.signatures, self.votes):
            if cs.for_block() and v is not None:
                ext_sigs.append(ExtendedCommitSig(
                    cs, v.extension, v.extension_signature))
            else:
                ext_sigs.append(ExtendedCommitSig(cs))
        return ExtendedCommit(height=commit.height, round=commit.round,
                              block_id=commit.block_id,
                              signatures=ext_sigs)

    def __repr__(self) -> str:
        voted = self.votes_bit_array.num_true_bits()
        return (f"VoteSet{{H:{self.height} R:{self.round} "
                f"T:{self.signed_msg_type} {voted}/{len(self.val_set)} "
                f"maj23:{self.maj23 is not None}}}")


def verify_cached(pub_key, pkb: bytes, sign_bytes: bytes, sig: bytes,
                  path: str, height: int) -> tuple:
    """(hit, ok) of one signature: looked up in the verified-signature
    cache on `path`; on a miss verified natively and added where true.
    With tracing on the native check is a `vote.verify` span: a root,
    which the readers place by its `height`; `path` says whose
    signature it is (a vote's, `vote`, or its extension's, `ext`)."""
    from ..pipeline.cache import shared_cache
    cache = shared_cache()
    (key,), (hit,) = cache.lookup([(pkb, sign_bytes, sig)], path)
    if hit:
        return True, True
    if not _TRACER.enabled:
        ok = pub_key.verify_signature(sign_bytes, sig)
    else:
        with _TRACER.start("vote.verify", height=height, path=path):
            ok = pub_key.verify_signature(sign_bytes, sig)
    if ok:
        cache.insert([key])
    return False, ok


def preverify_lanes(lanes) -> dict:
    """The batched half of vote intake. `lanes` are (public key,
    sign-bytes, signature, cache path) of votes about to go through
    `add_vote`, one after the other (`VoteSet.lanes`: a vote's own on
    path `vote`, and an extension's on path `ext`). Each is looked up in
    the verified-signature cache on its path; where the lanes that MISS
    reach `BATCH_VERIFY_THRESHOLD` and are all of one key type the seam
    batches, they are verified in ONE flush through `crypto.batch` (the
    device, on a TPU; lanes of both lengths together) and those that
    verified true are added to the cache, where `_check_signature` finds
    them. Below the threshold nothing is verified here: the native
    single check beats a dispatch (same rule as commit verification,
    types/validation.py).

    Fail-closed by construction: this only ever ADDS verified-true
    signatures to the cache, each on its own lane's verdict. A lane that
    failed, a lane a short verdict list left out, a lane never handed in:
    `add_vote` verifies it natively and raises what it raises.

    Returns {path: [cache hits, lanes flushed, lanes left to the native
    check]} for each path the lanes name."""
    from ..crypto import batch as crypto_batch
    from ..pipeline.cache import shared_cache
    from .validation import BATCH_VERIFY_THRESHOLD
    counts = {path: [0, 0, 0] for *_lane, path in lanes}
    if len(lanes) < BATCH_VERIFY_THRESHOLD:
        for *_lane, path in lanes:  # cannot reach it: not even looked up
            counts[path][2] += 1
        return counts
    cache = shared_cache()
    unique, asked = [], set()
    for pub_key, sign_bytes, sig, path in lanes:
        triple = (pub_key.bytes_(), sign_bytes, sig)
        if triple in asked:
            continue                # the same vote twice in one list
        asked.add(triple)
        unique.append((pub_key, triple, path))
    keys, cached = cache.lookup([triple for _pk, triple, _p in unique],
                                [path for _pk, _t, path in unique])
    missing = []    # (public key, cache key, sign-bytes, signature, path)
    for (pub_key, (_pkb, sign_bytes, sig), path), key, hit in zip(
            unique, keys, cached):
        if hit:
            counts[path][0] += 1
        else:
            missing.append((pub_key, key, sign_bytes, sig, path))
    bv, ok = None, False
    if len(missing) >= BATCH_VERIFY_THRESHOLD and \
            len({lane[0].type_() for lane in missing}) == 1:
        bv, ok = crypto_batch.create_batch_verifier(missing[0][0])
    if not ok:
        for *_lane, path in missing:
            counts[path][2] += 1
        return counts
    for pub_key, _key, sign_bytes, sig, path in missing:
        bv.add(pub_key, sign_bytes, sig)
        counts[path][1] += 1
    _all_ok, lane_oks = bv.verify()
    cache.insert([lane[1] for lane, lane_ok in zip(missing, lane_oks)
                  if lane_ok])
    return counts
