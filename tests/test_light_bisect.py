"""The light client's planned skipping walk (`LightClient._verify_skipping`,
`light/planner.plan_update`) against a walk one hop at a time written
here from `verifier.verify`, and against the plain reference
`benchmark/reference/light_bisect.py`, on seeded chains of 8-16
validators whose set rotates: the store's contents, the heights fetched
and the exception (type, message, cause) are compared, case by case.

The hop-at-a-time walk verifies each hop through `types/validation.py`.
Its batch route (`verifyCommitBatch`: power tallied, then the lanes
verified) is the planned walk's rule; its native route, taken below
`BATCH_VERIFY_THRESHOLD` lanes, verifies while it tallies. The two differ
in one case only, pinned below: a pivot with too little trusted power
AND a bad lane, which the native route refuses and the planned walk
bisects past.

The hop-at-a-time walk stops at the first step whose lane fails; the
planned walk has fetched every pivot of its schedule by then, so where a
step fails the first walk's fetches are the start of the second's."""

import random
from dataclasses import replace

import pytest

from benchmark.reference import light_bisect
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.engine.chain_gen import sign_commit
from cometbft_tpu.light import client as light_client
from cometbft_tpu.light import verifier
from cometbft_tpu.light.client import (ConflictingHeadersError, LightClient,
                                       TrustOptions)
from cometbft_tpu.light.store import LightStore
from cometbft_tpu.light.types import LightBlock, LightBlockError, SignedHeader
from cometbft_tpu.pipeline.cache import reset_shared_cache
from cometbft_tpu.types import validation
from cometbft_tpu.types.block import (BlockID, CommitSig, Header,
                                      PartSetHeader)
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet

BASE = 1_700_000_000
PERIOD = 10**6
SEEDS = range(4)


class Chain:
    """Headers 1..n of a chain whose set rotates: after the first height
    `rotate` members (a seed-drawn count in that range) leave and as many
    fresh keys of power 1-10 join. `turn` = (height, kept): there every
    member but the `kept` of lowest power is replaced."""

    def __init__(self, seed: int, n: int = 40, rotate=(0, 2), turn=None):
        rng = random.Random(seed)
        self.chain_id, self.n = f"bisect-{seed}", n
        size = rng.randrange(8, 17)

        def fresh():
            key = Ed25519PrivKey(rng.randbytes(32))
            return key.pub_key(), (key, rng.randrange(1, 11))
        members = dict(fresh() for _ in range(size))   # pub -> (key, power)
        self.keys, self.sets = {}, []
        for h in range(1, n + 2):
            if h > 1:
                order = sorted(members, key=lambda pub: (members[pub][1],
                                                         pub.bytes_()))
                gone = (order[turn[1]:] if turn and h == turn[0] else
                        rng.sample(order, rng.randrange(rotate[0],
                                                        rotate[1] + 1)))
                for pub in gone:
                    del members[pub]
                members.update(fresh() for _ in gone)
            self.keys.update({pub.address(): key
                              for pub, (key, _p) in members.items()})
            self.sets.append(ValidatorSet([
                Validator(pub, p) for pub, (_k, p) in members.items()]))
        self.blocks, last = [], BlockID()
        for h in range(1, n + 1):
            vals = self.sets[h - 1]
            header = Header(
                version_block=11, chain_id=self.chain_id, height=h,
                time=Timestamp(BASE + h, 0), last_block_id=last,
                validators_hash=vals.hash(),
                next_validators_hash=self.sets[h].hash(),
                proposer_address=vals.validators[0].address)
            last = BlockID(header.hash(), PartSetHeader(1, bytes([h]) * 32))
            self.blocks.append((header, sign_commit(
                self.chain_id, h, 0, last, vals, self.keys)))

    def light_block(self, h: int, alter=None) -> LightBlock:
        header, commit = self.blocks[h - 1]
        if alter is not None:
            header, commit = alter(header, commit)
        return LightBlock(SignedHeader(header, commit),
                          self.sets[h - 1].copy())

    def ref_header(self, h: int, alter=None) -> dict:
        """Height h as the plain reference reads it."""
        lb = self.light_block(h, alter)
        bid = lb.signed_header.commit.block_id
        return {
            "members": [(v.pub_key.bytes_(), v.voting_power)
                        for v in lb.validator_set.validators],
            "validators_hash": lb.header.validators_hash,
            "next_validators_hash": lb.header.next_validators_hash,
            "block": (bid.hash, bid.parts.total, bid.parts.hash),
            "lanes": [None if cs.absent_() else
                      (cs.timestamp.seconds, cs.timestamp.nanos,
                       cs.signature)
                      for cs in lb.signed_header.commit.signatures]}


class Provider:
    """The chain, with `alter` applied at the heights it names, and a
    log of the heights fetched."""

    def __init__(self, chain: Chain, alter=None):
        self.chain, self.alter, self.fetched = chain, alter or {}, []

    def light_block(self, h: int) -> LightBlock:
        self.fetched.append(h)
        return self.chain.light_block(h, self.alter.get(h))


def bad_lane(idx: int):
    """A commit whose lane `idx` carries a signature that does not
    verify (one bit of s flipped)."""
    def alter(header, commit):
        cs = commit.signatures[idx]
        sig = bytearray(cs.signature)
        sig[40] ^= 1
        sigs = list(commit.signatures)
        sigs[idx] = CommitSig(cs.block_id_flag, cs.validator_address,
                              cs.timestamp, bytes(sig))
        return header, replace(commit, signatures=sigs)
    return alter


def _now(chain):
    return Timestamp(BASE + chain.n + 5, 0)


def _stored(store, chain):
    return [h for h in range(1, chain.n + 1)
            if store.light_block(h) is not None]


def _outcome(raised):
    if raised is None:
        return None
    cause = raised.__cause__
    return (type(raised), str(raised),
            None if cause is None else (type(cause), str(cause)))


def planned(chain, alter=None, witnesses=(), period=PERIOD, provider=None,
            tip=None):
    """The client as `cmd_light` builds it (skipping, the default)."""
    reset_shared_cache()
    provider = provider or Provider(chain, alter)
    tip = tip or chain.n
    store = LightStore(MemDB())
    client = LightClient(
        chain.chain_id, TrustOptions(period, 1, chain.blocks[0][0].hash()),
        provider, list(witnesses), store,
        now_fn=lambda: Timestamp(BASE + tip + 5, 0))
    provider.fetched.clear()
    raised = None
    try:
        client.verify_light_block_at_height(tip)
    except Exception as exc:        # compared by type, text and cause
        raised = exc
    return ([h for h in range(1, tip + 1) if store.light_block(h)],
            provider.fetched, _outcome(raised))


def hop_at_a_time(chain, alter=None, period=PERIOD, batch_route=True,
                  provider=None, tip=None):
    """The reference's verifySkipping one hop at a time: each hop checked
    whole by `verifier.verify` before the next is looked at."""
    reset_shared_cache()
    provider = provider or Provider(chain, alter)
    tip = tip or chain.n
    store = LightStore(MemDB())
    root = chain.light_block(1)
    store.save_light_block(root)
    threshold = validation.BATCH_VERIFY_THRESHOLD
    if batch_route:
        validation.BATCH_VERIFY_THRESHOLD = 1
    raised = None
    try:
        target = provider.light_block(tip)
        target.validate_basic(chain.chain_id)
        cur, pivots = root, [target]
        while pivots:
            cand = pivots[-1]
            try:
                verifier.verify(chain.chain_id, cur, cand, period,
                                Timestamp(BASE + tip + 5, 0))
            except verifier.ErrNewValSetCantBeTrusted:
                lb = provider.light_block((cur.height + cand.height) // 2)
                lb.validate_basic(chain.chain_id)
                pivots.append(lb)
                continue
            store.save_light_block(cand)
            cur = cand
            pivots.pop()
    except Exception as exc:
        raised = exc
    finally:
        validation.BATCH_VERIFY_THRESHOLD = threshold
    return ([h for h in range(1, tip + 1) if store.light_block(h)],
            provider.fetched, _outcome(raised))


def reference(chain, alter=None):
    """(schedule, heights trusted with the root, failure) by the plain
    reference."""
    alter = alter or {}

    def header(h):
        return chain.ref_header(h, alter.get(h))
    sched = light_bisect.schedule(1, chain.n, header)
    trusted, failure = light_bisect.trust(chain.chain_id, sched["steps"],
                                          header)
    return sched, [1] + trusted, failure


_PREFIX = {"trusting": "trusting verify failed", "light": "invalid commit"}


def assert_same(chain, alter=None, **kw):
    """The planned walk equals the hop-at-a-time walk (the heights that
    walk fetched are the start of the planned walk's) and, where the
    chain's faults are lanes, the plain reference; returns the planned
    walk's (store, fetched, exception)."""
    got = planned(chain, alter, **kw)
    hop = hop_at_a_time(chain, alter, **kw)
    assert (got[0], got[2]) == (hop[0], hop[2])
    assert got[1][:len(hop[1])] == hop[1]
    if got[2] is None:
        assert got[1] == hop[1]
    sched, trusted, failure = reference(chain, alter)
    if sched["refused"] is not None:
        assert got[2] is not None
        assert got[0] == [1] + [s["height"] for s in sched["steps"]]
    elif "period" not in kw:
        assert got[0] == trusted
        assert got[1] == [chain.n] + sched["pivots"]
        if failure is None:
            assert got[2] is None
        else:
            _h, check, idx = failure
            assert got[2][0] is verifier.ErrInvalidHeader
            assert got[2][1].startswith(
                f"{_PREFIX[check]}: wrong signature (#{idx})")
            assert got[2][2][0] is validation.ErrWrongSignature
    return got


def _rotating(seed):
    """A chain on which the walk bisects and takes three steps or more."""
    chain = Chain(seed)
    sched = light_bisect.schedule(1, chain.n, chain.ref_header)
    assert len(sched["steps"]) >= 3 and sched["refused"] is None
    return chain, sched


# --- honest chains ----------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_an_honest_chain_is_trusted_at_the_references_pivots(seed):
    chain, sched = _rotating(seed)
    stored, fetched, raised = assert_same(chain)
    assert raised is None and chain.n in stored
    assert len(stored) == 1 + len(sched["steps"])


def test_an_adjacent_step():
    """The whole set changes at height 20: no jump across it can be
    trusted, so the walk comes down to the hop 19 -> 20, which the
    validators_hash binding decides."""
    chain = Chain(7, rotate=(0, 0), turn=(20, 0))
    sched = light_bisect.schedule(1, chain.n, chain.ref_header)
    assert any(s["from"] == 19 and s["height"] == 20
               and s["trusting"] is None for s in sched["steps"])
    stored, _fetched, raised = assert_same(chain)
    assert raised is None and {19, 20} <= set(stored)


# --- a lane that does not verify -------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_a_bad_lane_in_a_trusting_check(seed):
    chain, sched = _rotating(seed)
    step = sched["steps"][1]
    stored, _fetched, raised = assert_same(
        chain, {step["height"]: bad_lane(step["trusting"][0])})
    assert raised[1].startswith("trusting verify failed: wrong signature")
    assert stored == [1, sched["steps"][0]["height"]]


@pytest.mark.parametrize("seed", SEEDS)
def test_a_bad_lane_in_a_two_thirds_check(seed):
    chain, sched = _rotating(seed)
    step = sched["steps"][-1]
    idx = next(i for i in step["own"] if i not in step["trusting"])
    stored, _fetched, raised = assert_same(chain, {chain.n: bad_lane(idx)})
    assert raised[1] == f"invalid commit: {raised[2][1]}"
    assert stored == [1] + [s["height"] for s in sched["steps"][:-1]]


@pytest.mark.parametrize("seed", SEEDS)
def test_a_bad_lane_behind_both_stops_is_never_looked_at(seed):
    chain, sched = _rotating(seed)
    step = sched["steps"][-1]
    behind = [i for i in range(len(chain.sets[chain.n - 1]))
              if i not in step["own"] and i not in step["trusting"]]
    assert behind
    stored, _fetched, raised = assert_same(chain,
                                           {chain.n: bad_lane(behind[-1])})
    assert raised is None and chain.n in stored


# --- what needs no signature -----------------------------------------------


def test_an_expired_trusted_header_is_refused_before_any_lane():
    chain, _sched = _rotating(0)
    before = light_client.bisect_stats()
    stored, fetched, raised = assert_same(chain, period=3)
    assert raised[:2] == (verifier.ErrOldHeader, "trusted header expired")
    assert stored == [1] and fetched == [chain.n]
    assert light_client.bisect_stats()["lanes"] == before["lanes"]


@pytest.mark.parametrize("seed", SEEDS)
def test_a_refusal_at_a_later_step_trusts_the_steps_before(seed):
    """The last pivot's header names another set: refused when it is
    fetched, once the steps planned before it are verified and
    trusted."""
    chain, sched = _rotating(seed)
    pivot = sched["pivots"][-1]
    alter = {pivot: lambda h, c: (replace(h, validators_hash=b"\x02" * 32),
                                  c)}
    stored, fetched, raised = assert_same(chain, alter)
    assert raised[0] is LightBlockError and fetched[-1] == pivot
    done = [s["height"] for s in sched["steps"]]
    assert 1 < len(stored) and stored == [1] + done[:len(stored) - 1]


# --- one lane a signature, and the accepted difference -----------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_a_signature_two_checks_take_is_verified_once(seed, monkeypatch):
    chain, sched = _rotating(seed)
    flushed = []
    real = light_client._verify_lanes

    def spy(vals, lanes):
        flushed.append([(lane.pub, lane.msg, lane.sig) for lane in lanes])
        return real(vals, lanes)
    monkeypatch.setattr(light_client, "_verify_lanes", spy)
    before = light_client.bisect_stats()
    _stored_, _fetched, raised = planned(chain)
    delta = {k: v - before[k] for k, v in light_client.bisect_stats().items()}
    assert raised is None and len(flushed) == 1
    want = light_bisect.distinct_lanes(chain.chain_id, sched["steps"],
                                       chain.ref_header)
    shared = sum(len(set(s["trusting"] or ()) & set(s["own"]))
                 for s in sched["steps"])
    assert shared > 0
    assert sorted(flushed[0]) == sorted(want)
    assert delta == {"updates": 1, "steps": len(sched["steps"]),
                     "fetches": len(sched["pivots"]), "flushes": 0,
                     "lanes": len(want) + shared,
                     "lanes_distinct": len(want), "device_lanes": 0,
                     "native_lanes": len(want), "cache_hits": 0}


@pytest.mark.parametrize("seed", SEEDS)
def test_too_little_trusted_power_and_a_bad_lane_bisects_past_it(seed):
    """The accepted difference from the native route, pinned. All but
    the lightest member leave at height 20, so the jump from the root to
    the target has too little trusted power, and that member's lane in
    the target's commit, which the jump's trusting tally takes, is bad.
    The native route refuses there; the planned walk (the batch route)
    bisects, and then refuses the target on that lane in a check of a
    later jump that takes it, or trusts the target."""
    chain = Chain(seed, rotate=(0, 0), turn=(20, 1))
    root, target = chain.ref_header(1), chain.ref_header(chain.n)
    among = dict(root["members"])
    assert light_bisect.taken(target["members"], target["lanes"],
                              sum(among.values()) // 3, among) is None
    idx = next(i for i, (pub, _p) in enumerate(target["members"])
               if pub in among)
    alter = {chain.n: bad_lane(idx)}
    got = assert_same(chain, alter)
    native = hop_at_a_time(chain, alter, batch_route=False)
    assert native[0] == [1] and native[1] == [chain.n]
    assert native[2][1].startswith(
        f"trusting verify failed: wrong signature (#{idx})")
    assert got[1] != native[1] and len(got[0]) > 1


# --- the witness ------------------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_a_witness_that_disagrees(seed):
    chain, _sched = _rotating(seed)
    lying = Provider(chain, {chain.n: lambda h, c: (
        replace(h, app_hash=b"\x66" * 32), c)})
    stored, _fetched, raised = planned(chain, witnesses=[lying])
    assert raised[0] is ConflictingHeadersError
    walked, _f, _r = hop_at_a_time(chain)
    assert stored == [h for h in walked if h != chain.n]


# --- a primary that forges a pivot -------------------------------------------------


class Forgery:
    """A primary that serves the root of `chain` and, above it up to
    `tip`, a chain of its own. At the first pivot, a header whose set
    holds the root's members and a key of the primary's with more than
    2/3 of the power, its commit carrying the members' signatures made
    with keys that are not theirs; at every other height, a set of one
    key of the primary's. Each header names the next height's set. The
    root's members vouch for the pivot by tally alone, and no set above
    it vouches for any header but the next, so a walk past the pivot
    comes down to adjacent steps, every one of them signed."""

    def __init__(self, chain: Chain, tip: int):
        rng = random.Random(tip)
        self.chain, self.pivot, self.fetched = chain, (1 + tip) // 2, []
        root = chain.sets[0]
        self.keys = {v.address: Ed25519PrivKey(rng.randbytes(32))
                     for v in root.validators}         # not theirs
        self.sets = {}
        for h in range(2, tip + 2):
            key = Ed25519PrivKey(rng.randbytes(32))
            self.keys[key.pub_key().address()] = key
            if h == self.pivot:
                self.sets[h] = ValidatorSet(
                    [Validator(key.pub_key(), 10 * root.total_voting_power())]
                    + [Validator(v.pub_key, v.voting_power)
                       for v in root.validators])
            else:
                self.sets[h] = ValidatorSet([Validator(key.pub_key(), 1)])

    def light_block(self, h: int) -> LightBlock:
        self.fetched.append(h)
        if h == 1:
            return self.chain.light_block(1)
        vals = self.sets[h]
        header = Header(
            version_block=11, chain_id=self.chain.chain_id, height=h,
            time=Timestamp(BASE + h, 0), last_block_id=BlockID(),
            validators_hash=vals.hash(),
            next_validators_hash=self.sets[h + 1].hash(),
            proposer_address=vals.validators[0].address)
        bid = BlockID(header.hash(), PartSetHeader(1, bytes([h % 256]) * 32))
        return LightBlock(SignedHeader(header, sign_commit(
            self.chain.chain_id, h, 0, bid, vals, self.keys)), vals.copy())


def test_a_forged_pivot_is_refused_after_a_bounded_plan(monkeypatch):
    """The walk one hop at a time refuses the forged pivot at its
    trusting check's first lane, having fetched the target and the
    pivot. The planned walk refuses it with the same exception and the
    same store, once the steps it has planned reach BISECT_LANES: it
    fetched at most the heights of that many one-lane steps above the
    pivot, and the pivots between them. Without the bound it fetches
    every height between the pivot and the target first."""
    chain, tip, budget = Chain(0), 400, 16
    monkeypatch.setattr(light_client, "BISECT_LANES", budget)
    hop = hop_at_a_time(chain, provider=Forgery(chain, tip), tip=tip)
    got = planned(chain, provider=Forgery(chain, tip), tip=tip)
    pivot = (1 + tip) // 2
    assert hop[1] == [tip, pivot] and hop[0] == [1]
    assert (got[0], got[2]) == (hop[0], hop[2])
    assert got[2][1].startswith("trusting verify failed: wrong signature")
    assert got[1][:2] == hop[1]
    assert len(got[1]) <= 2 + tip.bit_length() + budget
    monkeypatch.setattr(light_client, "BISECT_LANES", 10**9)
    unbounded = planned(chain, provider=Forgery(chain, tip), tip=tip)
    assert unbounded[2] == hop[2]
    assert set(range(pivot, tip + 1)) <= set(unbounded[1])


# --- an aggregate seal ------------------------------------------------------------


def test_a_chain_of_aggregate_seals_is_verified_whole_at_each_hop():
    """A BLS chain sealed by aggregates: its hops carry no lanes, each is
    checked whole while the schedule is planned."""
    from cometbft_tpu.engine.chain_gen import (ChainLightProvider,
                                               generate_chain)
    chain = generate_chain(n_blocks=4, n_validators=4, txs_per_block=1,
                           chain_id="bisect-agg", seed=7,
                           key_type="bls12_381", aggregate=True)
    reset_shared_cache()
    store = LightStore(MemDB())
    client = LightClient(chain.chain_id, TrustOptions(
        PERIOD, 1, chain.blocks[0].hash()), ChainLightProvider(chain), [],
        store, now_fn=lambda: Timestamp(BASE + 100, 0))
    before = light_client.bisect_stats()
    lb = client.verify_light_block_at_height(3)
    assert lb.header.hash() == chain.blocks[2].hash()
    after = light_client.bisect_stats()
    assert after["steps"] - before["steps"] == 1
    assert after["lanes"] == before["lanes"]


# --- a verifier that accepts every lane is caught -----------------------------------


@pytest.mark.parametrize("case", [
    test_a_bad_lane_in_a_trusting_check,
    test_a_bad_lane_in_a_two_thirds_check,
])
def test_a_planted_verifier_that_accepts_every_lane_fails_them(
        case, monkeypatch):
    monkeypatch.setattr(light_client, "_verify_lanes",
                        lambda vals, lanes: ([True] * len(lanes), False))
    with pytest.raises(AssertionError):
        case(0)
