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
from . import planner, verifier
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

# A bisection settles (one flush, then its steps trusted in order)
# whenever its unsettled planned lanes reach BISECT_LANES, a sequential
# tile's on a TPU (16 chunks of 512), on every platform. A primary that
# forges a pivot whose tallies pass, and above it headers whose sets
# vouch only for the next, makes the client fetch and hold that many
# lanes' steps at most before the forgery is refused, and not every
# height of the gap. An honest update of `light-skip-100` plans 1,616.
BISECT_LANES = 8192

# The walks' counters, process-wide like the verified-signature cache
# they fill, one set a walk, each keeping the keys of its own (`_count`).
# The sequential walk's: headers trusted, tiles settled, flushes through
# the crypto.batch seam, and of the lanes the rule took those a flush
# verified, those verified natively and (beside them) those the cache
# answered; of the headers trusted, those whose set took the hash of the
# header before's (`ValidatorSet.adopt_hash_of`); and of the CommitSigs
# the saves encoded in a commit's one pass, those whose timestamp's
# seconds field came from an earlier lane of that pass
# (`types/block.SIG_TS_PREFIX`). The planned skipping walk's: updates
# (calls that planned a bisection), steps trusted, pivots fetched,
# flushes, the lanes the checks took and the cache did not answer
# (`lanes`, a signature two checks of a step take counted twice), of
# those the distinct ones (`lanes_distinct`), of which a flush verified
# `device_lanes` and the rest were verified natively (`native_lanes`),
# and the lanes the cache answered. One thread's delta is exact.
_counts = {
    "tile": dict.fromkeys(
        ("headers", "tiles", "flushes", "lanes", "device_lanes",
         "native_lanes", "cache_hits", "set_hashes_reused",
         "sig_encodings", "sig_ts_prefix_reused"), 0),
    "bisect": dict.fromkeys(
        ("updates", "steps", "fetches", "flushes", "lanes",
         "lanes_distinct", "device_lanes", "native_lanes", "cache_hits"),
        0)}
_counts_lock = threading.Lock()


def _count(walk: str, numbers: dict) -> None:
    """Add to `walk`'s counters those of `numbers` it keeps."""
    with _counts_lock:
        counts = _counts[walk]
        for key, n in numbers.items():
            if key in counts:
                counts[key] += n


def tile_stats() -> dict:
    """Snapshot of the sequential walk's counters."""
    with _counts_lock:
        return dict(_counts["tile"])


def bisect_stats() -> dict:
    """Snapshot of the planned skipping walk's counters."""
    with _counts_lock:
        return dict(_counts["bisect"])


def _verify_lanes(vals, lanes) -> tuple:
    """Verdicts of planned lanes (a tile's, or a bisection's), one a
    lane, and whether they went through the crypto.batch seam in one
    flush (the device, on a TPU): the rule, the seam and the reading of
    the answer are `types/validation._verify_commit_lanes`'s.
    Fail-closed: a lane counts as verified only on its own verdict, and
    a verifier that answers for fewer lanes than it was given has
    refused the rest."""
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


def _room(step) -> int:
    """The lanes a step takes of a flush's budget: at least one, so that
    a step whose every lane the cache answered still counts."""
    return max(1, sum(len(check.lanes) for check in step.checks))


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
        are its headers trusted, in order (`_settle`). No header enters
        the store before every lane the rule takes of it, and every
        header before it, has verified true; what fails at header j
        leaves the store as a walk one header at a time would:
        everything below j trusted, j and everything after it not."""
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
                            cur, h = held.lb, h + 1
                        # a header with every lane cached still takes
                        # room, so that no tile grows without end
                        need = _room(held)
                        if tile and need > room:
                            break
                        tile.append(held)
                        room, held = room - need, None
                if tile:
                    numbers, failed = self._settle(tile, span, "tile")
                    span.set_attr("first_height", tile[0].lb.height)
                    for key in ("headers", "lanes", "cache_hits"):
                        span.set_attr(key, numbers[key])
                    if failed is not None:
                        raise failed
                if refused is not None:
                    raise refused

    def _plan_header(self, cur: LightBlock, height: int,
                     target: LightBlock, now: Timestamp,
                     cache) -> planner.VerifyStep:
        """The next header as a step: everything `verify_adjacent`
        checks of it short of its signatures, and the lanes its +2/3
        check takes; a commit sealed by an aggregate (one pairing check,
        not lanes) is verified here, whole, and takes none. A set equal,
        member for member, to the header before's takes that set's hash
        instead of its own merkle; `validate_basic` still binds it to
        this header's `validators_hash`."""
        nxt = (target if height == target.height
               else self.primary.light_block(height))
        reused = (nxt.validator_set is not None and
                  nxt.validator_set.adopt_hash_of(cur.validator_set))
        nxt.validate_basic(self.chain_id)
        verifier.check_adjacent(self.chain_id, cur, nxt,
                                self.trusting_period, now)
        if isinstance(nxt.signed_header.commit, AggregatedCommit):
            verifier.verify_own_commit(self.chain_id, nxt)
            checks = []
        else:
            checks = [verifier.plan_own_commit(self.chain_id, nxt, cache)]
        return planner.VerifyStep(nxt, True, checks, reused=reused)

    def _verify_skipping(self, trusted: LightBlock, target: LightBlock,
                         now: Timestamp) -> None:
        """Bisection (reference light/client.go:705-772 verifySkipping),
        planned: the schedule from the trusted header to the target is
        built with no signature verified (`planner.plan_update`: the
        reference's pivots, every check that needs no signature, the
        lanes each check takes), the lanes of every check of its steps
        are verified in ONE flush, and only then are the steps trusted,
        in order (`_settle`); a schedule whose lanes pass BISECT_LANES
        settles in parts of that size. Power is tallied before any
        signature is verified, whatever the lane count (the reference's
        batch route). No header enters the store before every lane of
        every check of its step, and of every step before it, has
        verified true; what the plan refuses at step j is raised after
        the steps before it are verified and trusted, as the walk one
        hop at a time raises it."""
        tracer = shared_tracer()
        fetched = []

        def fetch(height: int) -> LightBlock:
            fetched.append(height)
            return self.primary.light_block(height)

        plan = planner.plan_update(
            self.chain_id, trusted, target, fetch, now,
            self.trusting_period, self.trust_level, shared_cache(),
            verifier.CACHE_PATH, whole_seals=True)
        totals = dict.fromkeys(("steps", "lanes", "lanes_distinct",
                                "cache_hits"), 0)
        refused = failed = None
        planned_all = False
        with tracer.start("light.bisect", from_height=trusted.height,
                          height=target.height) as span:
            while not planned_all and failed is None:
                steps, room = [], BISECT_LANES
                with tracer.start("light.bisect.plan", parent=span):
                    try:
                        while room > 0:
                            step = next(plan, None)
                            if step is None:
                                planned_all = True
                                break
                            steps.append(step)
                            room -= _room(step)
                    except Exception as e:
                        # the steps planned before it are still
                        # verified and saved first
                        refused, planned_all = e, True
                if steps:
                    numbers, failed = self._settle(steps, span, "bisect")
                    for key in totals:
                        totals[key] += numbers[key]
            span.set_attr("pivots_fetched", len(fetched))
            for key, n in totals.items():
                span.set_attr(key, n)
        _count("bisect", {"updates": 1, "fetches": len(fetched)})
        if failed is not None:
            raise failed
        if refused is not None:
            raise refused

    def _settle(self, steps, span, walk: str) -> tuple:
        """Verify the planned lanes of `steps` (a tile's headers, or a
        bisection's steps) in one flush, a signature that two checks
        take (same key, sign-bytes and signature: the cache key) as one
        lane whose verdict both read; then trust the steps in order up
        to the first with a check whose lane did not verify true.
        Counts on `walk`'s counters; returns the numbers counted and
        what the walk one step at a time raises for the step that
        failed (None where none did)."""
        tracer = shared_tracer()
        name = "light" if walk == "tile" else "light.bisect"
        lanes, slot, ends = [], {}, []
        for step in steps:
            for check in step.checks:
                for lane in check.lanes:
                    if lane.key not in slot:
                        slot[lane.key] = len(lanes)
                        lanes.append(lane)
            ends.append(len(lanes))
        oks, flushed = [], False
        with tracer.start(f"{name}.verify", parent=span,
                          lanes=len(lanes)) as vspan:
            if lanes:
                oks, flushed = _verify_lanes(steps[0].lb.validator_set,
                                             lanes)
            device = len(lanes) if flushed else 0
            vspan.set_attr("device_lanes", device)
            vspan.set_attr("native_lanes", len(lanes) - device)
        cache = shared_cache()
        failed, saved = None, 0
        encoded, ts_reused = SIG_TS_PREFIX
        with tracer.start(f"{name}.save", parent=span) as sspan, \
                insert_span_attrs(cache, sspan):
            for step in steps:
                failed = next((verifier.wrong_signature(lane, check.kind)
                               for check in step.checks
                               for lane in check.lanes
                               if not oks[slot[lane.key]]), None)
                if failed is not None:
                    break
                self.store.save_light_block(step.lb)
                saved += 1
            # each lane on its own verdict, as everywhere: the true lanes
            # of the steps saved and of the one that failed, in one
            # insert with their lookups' keys
            at = ends[saved] if failed is not None else len(lanes)
            cache.insert([lane.key for lane, ok in zip(lanes[:at], oks)
                          if ok])
            encoded = SIG_TS_PREFIX[0] - encoded
            ts_reused = SIG_TS_PREFIX[1] - ts_reused
            sspan.set_attr("sig_encodings", encoded)
            sspan.set_attr("sig_ts_prefix_reused", ts_reused)
        checks = [check for step in steps for check in step.checks]
        numbers = {"headers": saved, "steps": saved, "tiles": 1,
                   "flushes": int(flushed),
                   "lanes": sum(len(check.lanes) for check in checks),
                   "lanes_distinct": len(lanes), "device_lanes": device,
                   "native_lanes": len(lanes) - device,
                   "cache_hits": sum(check.cache_hits for check in checks),
                   "set_hashes_reused": sum(step.reused
                                            for step in steps[:saved]),
                   "sig_encodings": encoded,
                   "sig_ts_prefix_reused": ts_reused}
        _count(walk, numbers)
        return numbers, failed

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
