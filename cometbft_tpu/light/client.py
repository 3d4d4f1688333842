"""Light client: sequential + skipping (bisection) verification with
witness cross-checking (reference light/client.go:473,612,705,
light/detector.go).

The third north-star call site: on a 10k-header catch-up, each header's
commit flows through the same batch-verify seam the blocksync tile uses,
so bulk light verification rides the TPU kernel.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List, Optional

from ..crypto import batch as crypto_batch
from ..crypto.keys import kernel_width
from ..pipeline.cache import insert_span_attrs, shared_cache
from ..trace import shared_tracer
from ..types.agg_commit import AggregatedCommit
from ..types.block import SIG_TS_PREFIX
from ..types.proto import Timestamp
from ..types import validation
from . import verifier
from .provider import Provider, ProviderError
from .store import LightStore
from .types import LightBlock, LightBlockError


# A tile of the sequential walk holds the headers whose planned lanes fit
# TILE_CHUNKS chunks of the device's lane bucket (`kernel_width()`): whole
# chunks, so a tile pads by less than one header's lanes. At most
# `ops.ed25519._MAX_UNREAD_CHUNKS` (16), so that one flush is dispatched
# whole before any verdict is read. Found by a sweep on the chip (PR 36,
# `benchmark/tools/light_tile_sweep.py`, one TPU v5e, 150 validators of
# which the rule takes 42, 1,535 headers a run, headers/s of three
# repetitions): 1 chunk 372 / 370 / 365, 2: 323 / 382 / 393, 4: 316 / 391 /
# 404, 8: 323 / 396 / 406, 16: 412 / 398 / 415; 16 read highest in every
# repetition, 1 lowest by 7-10 %. The host's work a header (3 ms) is nine
# tenths of a tile whatever its size; what a larger tile saves is the
# flush's fixed part.
TILE_CHUNKS = 16

# The tiled walk's counters, process-wide like the verified-signature
# cache it fills: headers trusted, tiles settled, flushes through the
# crypto.batch seam, and of the lanes the rule took those a flush
# verified, those verified natively and (beside them) those the cache
# answered; and of the headers trusted, those whose set took the hash
# of the header before's (`ValidatorSet.adopt_hash_of`); and of the
# CommitSigs the saves encoded in a commit's one pass, those whose
# timestamp's seconds field came from an earlier lane of that pass
# (`types/block.SIG_TS_PREFIX`). One thread's delta is exact.
_tile_counts = {"headers": 0, "tiles": 0, "flushes": 0, "lanes": 0,
                "device_lanes": 0, "native_lanes": 0, "cache_hits": 0,
                "set_hashes_reused": 0, "sig_encodings": 0,
                "sig_ts_prefix_reused": 0}
_tile_lock = threading.Lock()


def tile_stats() -> dict:
    """Snapshot of the sequential walk's counters."""
    with _tile_lock:
        return dict(_tile_counts)


def _verify_lanes(vals, lanes) -> tuple:
    """Verdicts of a tile's planned lanes, one a lane, and whether they
    went through the crypto.batch seam in one flush (the device, on a
    TPU): the rule, the seam and the reading of the answer are
    `types/validation._verify_commit_lanes`'s. Fail-closed: a lane
    counts as verified only on its own verdict, and a verifier that
    answers for fewer lanes than it was given has refused the rest."""
    if not validation._should_batch_verify(vals, len(lanes)):
        return [lane.pk.verify_signature(lane.msg, lane.sig)
                for lane in lanes], False
    if len({lane.pk.type_() for lane in lanes}) > 1:
        bv = crypto_batch.MixedBatchVerifier()
    else:
        bv, _ok = crypto_batch.create_batch_verifier(lanes[0].pk)
    for lane in lanes:
        bv.add(lane.pk, lane.msg, lane.sig)
    _all_ok, oks = bv.verify()
    return ([bool(ok) for ok in oks]
            + [False] * (len(lanes) - len(oks))), True


class LightClientError(Exception):
    pass


class ErrNoWitnesses(LightClientError):
    pass


@dataclass
class ConflictingHeadersError(LightClientError):
    """A witness returned a different header for a verified height — the
    divergence the detector reports as a light-client attack (reference
    light/detector.go:21-92). Carries the constructed
    LightClientAttackEvidence (reference detector.go
    newLightClientAttackEvidence → provider ReportEvidence)."""
    primary: LightBlock
    witness: LightBlock
    witness_index: int
    evidence: object = None

    def __str__(self) -> str:
        return (f"witness {self.witness_index} disagrees at height "
                f"{self.primary.height}")


@dataclass
class TrustOptions:
    """reference light/client.go:58-90."""
    period_seconds: int
    height: int
    hash: bytes

    def validate(self) -> None:
        if self.period_seconds <= 0:
            raise LightClientError("trusting period must be positive")
        if self.height <= 0:
            raise LightClientError("trusted height must be positive")
        if len(self.hash) != 32:
            raise LightClientError("trusted hash must be 32 bytes")


class LightClient:
    """reference light/client.go Client (sequential=False selects
    skipping/bisection, the default)."""

    def __init__(self, chain_id: str, trust_options: TrustOptions,
                 primary: Provider, witnesses: List[Provider],
                 store: LightStore, sequential: bool = False,
                 trust_level: validation.Fraction =
                 validation.DEFAULT_TRUST_LEVEL,
                 now_fn=Timestamp.now):
        trust_options.validate()
        self.chain_id = chain_id
        self.trusting_period = trust_options.period_seconds
        self.primary = primary
        self.witnesses = list(witnesses)
        self.store = store
        self.sequential = sequential
        self.trust_level = trust_level
        self._now = now_fn
        self._initialize(trust_options)

    def _initialize(self, opts: TrustOptions) -> None:
        """Fetch + pin the trust root (reference client.go:388-470
        initializeWithTrustOptions)."""
        existing = self.store.light_block(opts.height)
        if existing is not None:
            if existing.header.hash() != opts.hash:
                raise LightClientError(
                    "trusted hash does not match stored header")
            return
        lb = self.primary.light_block(opts.height)
        lb.validate_basic(self.chain_id)
        if lb.header.hash() != opts.hash:
            raise LightClientError(
                f"primary returned header hash "
                f"{lb.header.hash().hex()[:16]} != trusted "
                f"{opts.hash.hex()[:16]}")
        # the set that signed must be the one committed to by the header
        validation.verify_commit_light(
            self.chain_id, lb.validator_set,
            lb.signed_header.commit.block_id, lb.height,
            lb.signed_header.commit)
        self.store.save_light_block(lb)

    # --- public API -----------------------------------------------------------

    def trusted_light_block(self, height: int) -> Optional[LightBlock]:
        return self.store.light_block(height)

    def latest_trusted(self) -> Optional[LightBlock]:
        return self.store.latest()

    def update(self, now: Optional[Timestamp] = None) -> LightBlock:
        """Verify the primary's latest header (reference client.go:506)."""
        latest = self.primary.light_block(0)
        return self.verify_light_block_at_height(latest.height, now)

    def verify_light_block_at_height(self, height: int,
                                     now: Optional[Timestamp] = None
                                     ) -> LightBlock:
        """reference light/client.go:473-504."""
        now = now or self._now()
        got = self.store.light_block(height)
        if got is not None:
            return got
        latest = self.store.latest()
        if latest is None:
            raise LightClientError("store empty — client not initialized")
        if height < latest.height:
            # backwards verification (reference client.go:934): walk the
            # hash links down from the closest trusted header
            return self._verify_backwards(height)
        lb = self.primary.light_block(height)
        lb.validate_basic(self.chain_id)
        if self.sequential:
            self._verify_sequential(latest, lb, now)
        else:
            self._verify_skipping(latest, lb, now)
        self._cross_check(lb)
        self.store.save_light_block(lb)
        return lb

    # --- verification strategies ----------------------------------------------

    def _verify_sequential(self, trusted: LightBlock, target: LightBlock,
                           now: Timestamp) -> None:
        """reference light/client.go:612-668: fetch and verify EVERY
        header between trusted and target; here in tiles, as blocksync
        verifies blocks. A tile's headers are first taken through
        everything that needs no signature, each against the header
        before it (trusted or, inside the tile, only planned), and
        their commits planned: the lanes the +2/3 rule takes. The lanes
        of the whole tile are then verified in ONE flush, and only then
        are its headers trusted, in order. No header enters the store
        before every lane the rule takes of it, and every header before
        it, has verified true; what fails at header j leaves the store
        as a walk one header at a time would: everything below j
        trusted, j and everything after it not."""
        # whole chunks of the device's lane bucket; 0 where lanes verify
        # natively: a tile is then one header
        budget = TILE_CHUNKS * kernel_width()
        cache = shared_cache()
        tracer = shared_tracer()
        cur, held = trusted, None       # held: planned, not yet in a tile
        h = trusted.height + 1
        while held is not None or h <= target.height:
            with tracer.start("light.tile") as span:
                tile, room, refused = [], budget, None
                with tracer.start("light.plan", parent=span):
                    while room > 0 or not tile:
                        if held is None:
                            if h > target.height:
                                break
                            try:
                                held = self._plan_header(cur, h, target,
                                                         now, cache)
                            except Exception as e:
                                # the headers planned before it are
                                # still verified and saved first
                                refused = e
                                break
                            cur, h = held[0], h + 1
                        # a header with every lane cached still takes
                        # room, so that no tile grows without end; a
                        # commit that cannot be planned takes a tile
                        planned = held[1]
                        need = (max(1, len(planned.lanes))
                                if planned is not None else max(1, budget))
                        if tile and need > room:
                            break
                        tile.append(held)
                        room, held = room - need, None
                if tile:
                    self._settle_tile(tile, span)
                if refused is not None:
                    raise refused

    def _plan_header(self, cur: LightBlock, height: int,
                     target: LightBlock, now: Timestamp, cache):
        """(light block, planned commit, whether its set's hash was
        reused) of the next header: everything `verify_adjacent` checks
        of it short of its signatures. The plan is None for a commit
        whose lanes cannot be planned (an aggregate seal is one pairing
        check, not lanes). A set equal, member for member, to the header
        before's takes that set's hash instead of its own merkle;
        `validate_basic` still binds it to this header's
        `validators_hash`."""
        nxt = (target if height == target.height
               else self.primary.light_block(height))
        reused = (nxt.validator_set is not None and
                  nxt.validator_set.adopt_hash_of(cur.validator_set))
        nxt.validate_basic(self.chain_id)
        verifier.check_adjacent(self.chain_id, cur, nxt,
                                self.trusting_period, now)
        if isinstance(nxt.signed_header.commit, AggregatedCommit):
            return nxt, None, reused
        return (nxt, verifier.plan_own_commit(self.chain_id, nxt, cache),
                reused)

    def _settle_tile(self, tile, span) -> None:
        """Verify the planned lanes of a tile's headers in one flush,
        then trust the headers in order up to the first whose lanes did
        not all verify true, and raise for that one what the walk one
        header at a time raises."""
        tracer = shared_tracer()
        plans = [planned for _lb, planned, _r in tile if planned is not None]
        lanes = [lane for planned in plans for lane in planned.lanes]
        hits = sum(planned.cache_hits for planned in plans)
        oks, flushed = [], False
        with tracer.start("light.verify", parent=span,
                          lanes=len(lanes)) as vspan:
            if lanes:
                oks, flushed = _verify_lanes(tile[0][0].validator_set,
                                             lanes)
            if len(plans) < len(tile):      # the one unplanned commit
                verifier.verify_own_commit(self.chain_id, tile[0][0])
            device = len(lanes) if flushed else 0
            vspan.set_attr("device_lanes", device)
            vspan.set_attr("native_lanes", len(lanes) - device)
        cache = shared_cache()
        failed, saved, at = None, 0, 0
        encoded, ts_reused = SIG_TS_PREFIX
        with tracer.start("light.save", parent=span) as sspan, \
                insert_span_attrs(cache, sspan):
            for lb, planned, _r in tile:
                mine = planned.lanes if planned is not None else ()
                failed = next((lane for lane, ok in zip(
                    mine, oks[at:at + len(mine)]) if not ok), None)
                at += len(mine)
                if failed is not None:
                    break
                self.store.save_light_block(lb)
                saved += 1
            # each lane on its own verdict, as everywhere: the true lanes
            # of the headers saved and of the one that failed, in one
            # insert with their lookups' keys
            cache.insert([lane.key for lane, ok in zip(lanes[:at], oks)
                          if ok])
            encoded = SIG_TS_PREFIX[0] - encoded
            ts_reused = SIG_TS_PREFIX[1] - ts_reused
            sspan.set_attr("sig_encodings", encoded)
            sspan.set_attr("sig_ts_prefix_reused", ts_reused)
        span.set_attr("first_height", tile[0][0].height)
        span.set_attr("headers", saved)
        span.set_attr("lanes", len(lanes))
        span.set_attr("cache_hits", hits)
        with _tile_lock:
            for key, n in (("headers", saved), ("tiles", 1),
                           ("flushes", int(flushed)),
                           ("lanes", len(lanes)),
                           ("device_lanes", device),
                           ("native_lanes", len(lanes) - device),
                           ("cache_hits", hits),
                           ("set_hashes_reused",
                            sum(r for _lb, _p, r in tile[:saved])),
                           ("sig_encodings", encoded),
                           ("sig_ts_prefix_reused", ts_reused)):
                _tile_counts[key] += n
        if failed is not None:
            raise verifier.wrong_signature(failed)

    def _verify_skipping(self, trusted: LightBlock, target: LightBlock,
                         now: Timestamp) -> None:
        """Bisection (reference light/client.go:705-772 verifySkipping):
        try the jump; when the trusted set can't vouch (<1/3 overlap),
        bisect toward the trusted header until it can."""
        cur = trusted
        pivots = [target]
        while pivots:
            candidate = pivots[-1]
            try:
                if candidate.height == cur.height + 1:
                    verifier.verify_adjacent(
                        self.chain_id, cur, candidate,
                        self.trusting_period, now)
                else:
                    verifier.verify_non_adjacent(
                        self.chain_id, cur, candidate,
                        self.trusting_period, now, self.trust_level)
            except verifier.ErrNewValSetCantBeTrusted:
                mid = (cur.height + candidate.height) // 2
                if mid in (cur.height, candidate.height):
                    raise LightClientError(
                        "bisection cannot make progress")
                lb = self.primary.light_block(mid)
                lb.validate_basic(self.chain_id)
                pivots.append(lb)
                continue
            self.store.save_light_block(candidate)
            cur = candidate
            pivots.pop()

    def _verify_backwards(self, height: int) -> LightBlock:
        """Hash-linked walk down from the closest trusted header above
        (client.go:934-988)."""
        cur = self.store.lowest_above(height)
        while cur is not None and cur.height > height:
            prev = self.primary.light_block(cur.height - 1)
            prev.validate_basic(self.chain_id)
            if cur.header.last_block_id.hash != prev.header.hash():
                raise LightClientError(
                    f"backwards hash mismatch at {prev.height}")
            self.store.save_light_block(prev)
            cur = prev
        if cur is None or cur.height != height:
            raise LightClientError(f"cannot reach height {height}")
        return cur

    # --- detector ---------------------------------------------------------------

    def _cross_check(self, lb: LightBlock) -> None:
        """Compare the verified header against every witness (reference
        light/detector.go:21-92, compareNewHeaderWithWitness). On
        divergence, build LightClientAttackEvidence against the highest
        trusted (common) header below the conflict and report it to the
        witnesses that can act on it (detector.go ReportEvidence)."""
        for i, w in enumerate(self.witnesses):
            try:
                other = w.light_block(lb.height)
            except ProviderError:
                continue  # witness lagging — reference retries/drops
            if other.header.hash() != lb.header.hash():
                # the disputed header must not stay trusted: the verify
                # strategies saved it before this cross-check ran, and a
                # stored block short-circuits all future verification
                self.store.delete(lb.height)
                # the anchor must be a header BOTH sides share — recent
                # stored headers came from the (possibly lying) primary,
                # so walk down until the witness agrees, evicting every
                # primary-only header passed on the way (the reference
                # detector walks its trace the same way,
                # detector.go examineConflictingHeaderAgainstTrace)
                common = self._common_anchor(w, lb.height)
                ev_witness = self._make_attack_evidence(other, common,
                                                        counterpart=lb)
                ev_primary = self._make_attack_evidence(lb, common,
                                                        counterpart=other)
                self._report(self.primary, ev_witness)
                self._report(w, ev_primary)
                raise ConflictingHeadersError(lb, other, i,
                                              evidence=ev_witness)

    def _common_anchor(self, witness: Provider,
                       below: int) -> Optional[LightBlock]:
        """Highest stored block below `below` whose hash the witness
        confirms; stored blocks the witness disputes (headers only the
        primary vouched for) are evicted rather than trusted."""
        while True:
            cand = self.store.highest_below(below)
            if cand is None:
                return None
            try:
                theirs = witness.light_block(cand.height)
                if theirs.header.hash() == cand.header.hash():
                    return cand
            except ProviderError:
                return cand  # witness can't say; keep the stored anchor
            self.store.delete(cand.height)
            below = cand.height

    @staticmethod
    def _report(provider, evidence) -> None:
        if evidence is None:
            return
        report = getattr(provider, "report_evidence", None)
        if report is not None:
            try:
                report(evidence)
            except ProviderError:
                pass

    def _make_attack_evidence(self, conflicting: LightBlock, common,
                              counterpart: LightBlock = None):
        """Evidence anchored at the highest trusted height below the
        conflict (the common header, detector.go:169).

        The byzantine list MUST use the same per-attack-style formula
        full nodes verify with (evidence/pool.py
        expected_byzantine_validators — lunatic / equivocation /
        amnesia, reference types/evidence.go:250-300): a list built
        with the lunatic formula for a non-lunatic attack would fail
        every pool's completeness check and the genuine evidence would
        be dropped network-wide. `counterpart` is the block the honest
        side holds at the same height (classifies the style)."""
        from ..evidence.pool import expected_byzantine_validators
        from ..types.evidence import LightClientAttackEvidence
        if common is None:
            return None
        ev = LightClientAttackEvidence(
            conflicting_block=conflicting,
            common_height=common.height,
            byzantine_validators=[],
            total_voting_power=common.validator_set.total_voting_power(),
            timestamp=common.header.time)
        byz = expected_byzantine_validators(
            ev, common.validator_set,
            counterpart.header if counterpart is not None else None,
            counterpart.signed_header.commit
            if counterpart is not None else None)
        if byz is None:
            # style undeterminable (no counterpart): fall back to the
            # lunatic formula — verifiers without the trusted block
            # skip completeness too
            signers = {cs.validator_address for cs in
                       conflicting.signed_header.commit.signatures
                       if cs.for_block()}
            byz = [v for v in common.validator_set.validators
                   if v.address in signers]
        ev.byzantine_validators = byz
        return ev
