"""The sequential light client's tiled walk against the rule applied a
header at a time.

The reference is what `_verify_sequential` was before it gathered lanes:
`verifier.verify_adjacent` per header, `kernel_width()` 0, each header
saved as it verifies. The tiled walk is forced on the CPU: a stub lane
width, the batch threshold lowered to one header's lanes, and a
`crypto.batch` verifier that answers natively and keeps every flush.
Chains are seeded, 8 validators with power floor(10^6 * rank^-0.8): the
rule takes the 4 heaviest lanes of a header, so a tile of 32 lanes is 8
headers."""

import dataclasses
import hashlib

import pytest

from cometbft_tpu import trace
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.engine.chain_gen import sign_commit
from cometbft_tpu.farm import planner as farm_planner
from cometbft_tpu.light import client as light_client
from cometbft_tpu.light import planner as light_planner
from cometbft_tpu.light import verifier
from cometbft_tpu.light.client import LightClient, TrustOptions, tile_stats
from cometbft_tpu.light.provider import ErrLightBlockNotFound
from cometbft_tpu.light.store import LightStore
from cometbft_tpu.light.types import LightBlock, SignedHeader
from cometbft_tpu.pipeline.cache import reset_shared_cache, shared_cache
from cometbft_tpu.types import validation
from cometbft_tpu.types.block import (BLOCK_ID_FLAG_ABSENT, BlockID, Commit,
                                      CommitSig, Header, PartSetHeader)
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet

CHAIN = "tiled-chain"
BASE = 1_700_000_000
PERIOD = 30 * 86400
WIDTH, TAKEN = 4, 4         # stub lane bucket; lanes the rule takes a header
CHUNKS = 8                  # what the tests set TILE_CHUNKS to
TILE = CHUNKS * WIDTH // TAKEN      # headers a tile


def _digest(*parts) -> bytes:
    return hashlib.sha256("/".join(map(str, parts)).encode()).digest()


def _keys(seed, n, tag="v"):
    return [Ed25519PrivKey(_digest(seed, tag, i)) for i in range(n)]


def _members(keys):
    """[(key, power)], power floor(10^6 * rank^-0.8)."""
    return [(k, int(10**6 * (i + 1) ** -0.8)) for i, k in enumerate(keys)]


def _valset(members) -> ValidatorSet:
    return ValidatorSet([Validator(k.pub_key(), p) for k, p in members])


class Chain:
    """Headers 1..n with their commits, every member signing; `sets[h]`
    the members in force at h (`change_at`: from that height on another
    key holds rank 2 and the powers of ranks 3 and 4 are swapped; from
    `back_at` the first set again). `forged_at`: that header's
    `validators_hash`, and the one before's `next_validators_hash`, name
    no set at all, and both are signed as they stand."""

    def __init__(self, seed: int, n: int, change_at: int = 0,
                 back_at: int = 0, forged_at: int = 0):
        keys = _keys(seed, 8)
        first = _members(keys)
        after = list(first)
        after[1] = (_keys(seed, 1, "joiner")[0], first[1][1])
        after[2], after[3] = (first[2][0], first[3][1]), \
            (first[3][0], first[2][1])
        self.n = n
        self.sets = {h: after if change_at <= h < (back_at or n + 2)
                     and change_at else first for h in range(1, n + 2)}
        forged = {forged_at: _digest(seed, "forged")} if forged_at else {}
        self.headers, self.commits = {}, {}
        last = BlockID()
        for h in range(1, n + 1):
            vals, nxt = _valset(self.sets[h]), _valset(self.sets[h + 1])
            header = Header(
                chain_id=CHAIN, height=h, time=Timestamp(BASE + h, 0),
                last_block_id=last,
                validators_hash=forged.get(h, vals.hash()),
                next_validators_hash=forged.get(h + 1, nxt.hash()),
                app_hash=_digest(seed, "app", h),
                proposer_address=vals.validators[0].address)
            last = BlockID(header.hash(),
                           PartSetHeader(1, _digest(seed, "parts", h)))
            by_address = {k.pub_key().address(): k for k, _p in self.sets[h]}
            self.headers[h] = header
            self.commits[h] = sign_commit(CHAIN, h, 0, last, vals,
                                          by_address, base_time=BASE)

    def light_block(self, h: int) -> LightBlock:
        return LightBlock(SignedHeader(self.headers[h], self.commits[h]),
                          _valset(self.sets[h]))


class Provider:
    """Hands out a fresh light block a call; `altered` maps a height to
    a function of the honest light block."""

    def __init__(self, chain: Chain, altered=None):
        self.chain, self.altered = chain, altered or {}

    def chain_id(self) -> str:
        return CHAIN

    def light_block(self, height: int) -> LightBlock:
        if not 1 <= height <= self.chain.n:
            raise ErrLightBlockNotFound(f"no light block at {height}")
        lb = self.chain.light_block(height)
        alter = self.altered.get(height)
        return alter(lb) if alter else lb


def _tamper(sig: bytes) -> bytes:
    return sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]


def _with_sigs(lb: LightBlock, change) -> LightBlock:
    c = lb.signed_header.commit
    sigs = [change(i, cs) for i, cs in enumerate(c.signatures)]
    return LightBlock(
        SignedHeader(lb.header, Commit(c.height, c.round, c.block_id, sigs)),
        lb.validator_set)


def bad_lane(idx: int):
    return lambda lb: _with_sigs(lb, lambda i, cs: CommitSig(
        cs.block_id_flag, cs.validator_address, cs.timestamp,
        _tamper(cs.signature)) if i == idx else cs)


def absent(*idxs):
    return lambda lb: _with_sigs(lb, lambda i, cs: CommitSig(
        BLOCK_ID_FLAG_ABSENT, b"", Timestamp(), b"") if i in idxs else cs)


def unbound(lb: LightBlock) -> LightBlock:
    """A header (consistently hashed and signed by its own set) whose
    set is not the one the header before it announced."""
    members = _members(_keys(99, 8, "stranger"))
    vals = _valset(members)
    header = dataclasses.replace(lb.header, validators_hash=vals.hash())
    bid = BlockID(header.hash(), lb.signed_header.commit.block_id.parts)
    by_address = {k.pub_key().address(): k for k, _p in members}
    return LightBlock(SignedHeader(header, sign_commit(
        CHAIN, header.height, 0, bid, vals, by_address, base_time=BASE)),
        vals)


def _store_rows(store: LightStore) -> list:
    return list(store._db.iterate(b""))


def _outcome(call) -> tuple:
    try:
        lb = call()
        return "ok", lb.height, lb.header.hash()
    except Exception as e:      # compared by type and text
        return type(e).__name__, str(e), type(e.__cause__).__name__


def reference(provider: Provider, tip: int, now: Timestamp):
    """The rule a header at a time: the walk as it was before tiles."""
    reset_shared_cache()
    store = LightStore(MemDB())
    store.save_light_block(provider.light_block(1))

    def walk():
        cur = store.light_block(1)
        target = provider.light_block(tip)
        target.validate_basic(CHAIN)
        for h in range(2, tip + 1):
            nxt = target if h == tip else provider.light_block(h)
            nxt.validate_basic(CHAIN)
            verifier.verify_adjacent(CHAIN, cur, nxt, PERIOD, now)
            store.save_light_block(nxt)
            cur = nxt
        return cur
    return _outcome(walk), _store_rows(store)


class StubVerifier:
    """A `crypto.batch` verifier that answers natively and keeps the
    lane count of every flush; `answer` alters the verdict list."""

    def __init__(self, flushes, answer):
        self.lanes, self.flushes, self.answer = [], flushes, answer

    def __len__(self):
        return len(self.lanes)

    def add(self, pk, msg, sig):
        self.lanes.append((pk, msg, sig))

    def verify(self):
        self.flushes.append(len(self.lanes))
        oks = self.answer([pk.verify_signature(m, s)
                           for pk, m, s in self.lanes])
        return all(oks), oks


def tiled(monkeypatch, provider: Provider, tip: int, now: Timestamp,
          answer=lambda oks: oks):
    """The client's own walk with tiles forced. Returns the outcome, the
    store's rows, the lanes of every flush and the counters' delta."""
    flushes, honest = [], [lambda oks: oks]
    with monkeypatch.context() as m:
        m.setattr(light_client, "kernel_width", lambda: WIDTH)
        m.setattr(light_client, "TILE_CHUNKS", CHUNKS)
        m.setattr(validation, "BATCH_VERIFY_THRESHOLD", TAKEN)
        m.setattr(crypto_batch, "create_batch_verifier",
                  lambda pk: (StubVerifier(flushes, honest[-1]), True))
        reset_shared_cache()
        store = LightStore(MemDB())
        root = provider.chain.light_block(1)
        lc = LightClient(
            CHAIN, TrustOptions(PERIOD, 1, root.header.hash()), provider, [],
            store, sequential=True, now_fn=lambda: now)
        before = tile_stats()
        flushes.clear()     # the root's own commit
        honest.append(answer)
        outcome = _outcome(lambda: lc.verify_light_block_at_height(tip))
        after = tile_stats()
    reset_shared_cache()
    return outcome, _store_rows(store), flushes, {
        k: after[k] - before[k] for k in after}


def _now(chain: Chain) -> Timestamp:
    return Timestamp(BASE + chain.n + 5, 0)


@pytest.fixture(scope="module")
def chain():
    return Chain(seed=7, n=2 * TILE + 4)


def test_the_rule_takes_four_of_eight_lanes(chain):
    plan = light_planner.plan_commit_light(
        CHAIN, _valset(chain.sets[2]), chain.commits[2].block_id, 2,
        chain.commits[2], shared_cache(), path="light")
    assert [lane.sig_index for lane in plan.lanes] == list(range(TAKEN))
    # one flush is dispatched whole before any verdict is read
    from cometbft_tpu.ops.ed25519 import _MAX_UNREAD_CHUNKS
    assert 1 <= light_client.TILE_CHUNKS <= _MAX_UNREAD_CHUNKS


@pytest.mark.parametrize("headers", [1, TILE - 3, TILE, TILE + 1,
                                     2 * TILE + 3])
def test_sound_chain_equal_store_and_return(monkeypatch, chain, headers):
    """Chains shorter than, equal to and one longer than a tile."""
    tip, now = 1 + headers, _now(chain)
    provider = Provider(chain)
    want = reference(provider, tip, now)
    outcome, rows, flushes, delta = tiled(monkeypatch, provider, tip, now)
    assert (outcome, rows) == want and outcome[0] == "ok"
    assert len(rows) == tip
    full, rest = divmod(headers, TILE)
    assert flushes == [TILE * TAKEN] * full + [rest * TAKEN] * bool(rest)
    assert delta == {"headers": headers, "tiles": len(flushes),
                     "flushes": len(flushes), "lanes": headers * TAKEN,
                     "device_lanes": headers * TAKEN, "native_lanes": 0,
                     "cache_hits": 0,
                     # all but the target, whose set was hashed when it
                     # was fetched
                     "set_hashes_reused": headers - 1,
                     # the walk a header at a time encoded these commits
                     # first: every lane is served from its memo
                     "sig_encodings": 0, "sig_ts_prefix_reused": 0}


def test_without_a_lane_width_a_tile_is_one_header(monkeypatch, chain):
    """`kernel_width()` 0, as on every CPU: today's route."""
    tip, now = TILE + 2, _now(chain)
    provider = Provider(chain)
    want = reference(provider, tip, now)
    reset_shared_cache()
    store = LightStore(MemDB())
    lc = LightClient(CHAIN, TrustOptions(PERIOD, 1, chain.headers[1].hash()),
                     provider, [], store, sequential=True,
                     now_fn=lambda: now)
    before = tile_stats()
    outcome = _outcome(lambda: lc.verify_light_block_at_height(tip))
    delta = {k: v - before[k] for k, v in tile_stats().items()}
    assert (outcome, _store_rows(store)) == want
    assert delta["tiles"] == delta["headers"] == tip - 1
    assert delta["native_lanes"] == (tip - 1) * TAKEN
    assert delta["flushes"] == delta["device_lanes"] == 0


@pytest.mark.parametrize("change_at", [2, 5, TILE + 1, TILE + 2])
def test_a_set_change_inside_a_tile(monkeypatch, change_at):
    chain = Chain(seed=11, n=2 * TILE, change_at=change_at)
    provider, now = Provider(chain), _now(chain)
    want = reference(provider, chain.n, now)
    outcome, rows, flushes, delta = tiled(monkeypatch, provider, chain.n, now)
    assert (outcome, rows) == want and outcome[0] == "ok"
    assert delta["headers"] == chain.n - 1 and len(flushes) <= 3


@pytest.mark.parametrize("lane", [0, TAKEN - 1])
@pytest.mark.parametrize("height", [2, 2 + TILE // 2, 1 + TILE, 2 + TILE])
def test_an_altered_signature_the_rule_takes(monkeypatch, chain, height,
                                             lane):
    """At the first, a middle and the last header of a tile (and the
    first of the next), in the first and the last lane the rule takes:
    the exception of the walk a header at a time, and its store."""
    provider, now = Provider(chain, {height: bad_lane(lane)}), _now(chain)
    want = reference(provider, chain.n, now)
    outcome, rows, _flushes, delta = tiled(monkeypatch, provider, chain.n,
                                           now)
    assert (outcome, rows) == want
    assert outcome[0] == "ErrInvalidHeader" and outcome[2] == \
        "ErrWrongSignature" and f"(#{lane})" in outcome[1]
    assert len(rows) == height - 1 == 1 + delta["headers"]
    # the tile's headers planned after it took a hash and were not
    # trusted: a reuse counts with its header's trust
    assert delta["set_hashes_reused"] == delta["headers"]


def test_two_altered_signatures_name_the_first_in_header_order(monkeypatch,
                                                               chain):
    provider = Provider(chain, {4: bad_lane(3), 3: bad_lane(2),
                                6: bad_lane(0)})
    now = _now(chain)
    want = reference(provider, chain.n, now)
    outcome, rows, _f, _d = tiled(monkeypatch, provider, chain.n, now)
    assert (outcome, rows) == want and "(#2)" in outcome[1]
    assert len(rows) == 2


@pytest.mark.parametrize("height", [3, 1 + TILE])
def test_an_altered_signature_beyond_the_cut_is_accepted(monkeypatch, chain,
                                                         height):
    """The early exit is the rule's: both accept."""
    provider = Provider(chain, {height: bad_lane(TAKEN + 1)})
    now = _now(chain)
    want = reference(provider, chain.n, now)
    outcome, rows, _f, _d = tiled(monkeypatch, provider, chain.n, now)
    assert (outcome, rows) == want and outcome[0] == "ok"
    assert len(rows) == chain.n


def _missing(lb):
    raise ErrLightBlockNotFound("the provider has lost it")


def _bad_structure(lb):
    return LightBlock(SignedHeader(lb.header, lb.signed_header.commit),
                      _valset(_members(_keys(5, 8, "other"))))


@pytest.mark.parametrize("fault, kind", [
    (absent(0, 1), "ErrInvalidHeader"),         # under 2/3 signing
    (unbound, "ErrInvalidHeader"),              # validators_hash binding
    (_bad_structure, "LightBlockError"),        # validate_basic
    (_missing, "ErrLightBlockNotFound"),        # the provider itself
])
@pytest.mark.parametrize("height", [2, 2 + TILE // 2, 1 + TILE])
def test_refused_without_a_signature(monkeypatch, chain, fault, kind,
                                     height):
    """Raised before any lane of that header is flushed; the headers
    before it verified and saved, as the untiled walk leaves them."""
    provider, now = Provider(chain, {height: fault}), _now(chain)
    want = reference(provider, chain.n, now)
    outcome, rows, flushes, delta = tiled(monkeypatch, provider, chain.n,
                                          now)
    assert (outcome, rows) == want and outcome[0] == kind
    assert len(rows) == height - 1
    assert sum(flushes) == (height - 2) * TAKEN == delta["lanes"]


def _one_power_off(lb):
    """The honest header under a set whose one power differs."""
    return LightBlock(lb.signed_header, ValidatorSet([
        Validator(v.pub_key, v.voting_power + (i == TAKEN - 1))
        for i, v in enumerate(lb.validator_set.validators)]))


@pytest.mark.parametrize("forged", [True, False],
                         ids=["forged-hash-equal-set", "one-power-off"])
@pytest.mark.parametrize("height", [2, 2 + TILE // 2, 1 + TILE])
def test_a_set_bound_to_no_header_is_refused_at_its_own(monkeypatch, chain,
                                                        height, forged):
    """A set equal, member for member, to the header before's, under a
    header (and a header before it) that name another root: the hash is
    adopted, and the binding still refuses it. A set one power off under
    an honest header: no adoption, its own root refuses it. Both at that
    header, before any of its lanes is flushed, as the walk a header at
    a time refuses them; the refused header's reuse is not counted."""
    if forged:
        chain = Chain(seed=13, n=chain.n, forged_at=height)
    provider = Provider(chain, {} if forged else {height: _one_power_off})
    now = _now(chain)
    want = reference(provider, chain.n, now)
    adopted, adopt = [], ValidatorSet.adopt_hash_of
    monkeypatch.setattr(ValidatorSet, "adopt_hash_of", lambda vs, other: (
        adopted.append(adopt(vs, other)) or adopted[-1]))
    outcome, rows, flushes, delta = tiled(monkeypatch, provider, chain.n,
                                          now)
    assert (outcome, rows) == want
    assert outcome[0] == "LightBlockError" and \
        "validators_hash" in outcome[1]
    assert adopted[-1] is forged and all(adopted[:-1])
    assert len(rows) == height - 1
    assert sum(flushes) == (height - 2) * TAKEN == delta["lanes"]
    assert delta["set_hashes_reused"] == height - 2 == delta["headers"]


def test_a_hash_is_reused_wherever_the_set_did_not_change(monkeypatch):
    """Two set changes: every header planned takes the hash of the one
    before but the two whose set changed and the target, whose set was
    hashed when it was fetched."""
    chain = Chain(seed=17, n=2 * TILE, change_at=5, back_at=TILE + 3)
    provider, now = Provider(chain), _now(chain)
    want = reference(provider, chain.n, now)
    outcome, rows, _f, delta = tiled(monkeypatch, provider, chain.n, now)
    assert (outcome, rows) == want and outcome[0] == "ok"
    changed = [h for h in range(2, chain.n + 1)
               if chain.sets[h] != chain.sets[h - 1]]
    assert changed == [5, TILE + 3]
    assert delta["set_hashes_reused"] == chain.n - 2 - len(changed)


def test_a_header_from_the_future_mid_tile(monkeypatch, chain):
    now = Timestamp(BASE + 5 - verifier.MAX_CLOCK_DRIFT_SECONDS, 0)
    provider = Provider(chain)
    want = reference(provider, chain.n, now)
    outcome, rows, flushes, _d = tiled(monkeypatch, provider, chain.n, now)
    assert (outcome, rows) == want and "from the future" in outcome[1]
    assert len(rows) == 5
    assert sum(flushes) == (len(rows) - 1) * TAKEN


def test_an_expired_trusted_header(monkeypatch, chain):
    now = Timestamp(BASE + 1 + PERIOD + 1, 0)
    provider = Provider(chain)
    want = reference(provider, chain.n, now)
    outcome, rows, flushes, _d = tiled(monkeypatch, provider, chain.n, now)
    assert (outcome, rows) == want and outcome[0] == "ErrOldHeader"
    assert len(rows) == 1 and flushes == []


@pytest.mark.parametrize("answered", [0, 9, TILE * TAKEN - 1])
def test_a_short_verdict_list_trusts_nothing_beyond_it(monkeypatch, chain,
                                                       answered):
    outcome, rows, flushes, delta = tiled(
        monkeypatch, Provider(chain), chain.n, _now(chain),
        answer=lambda oks: oks[:answered])
    done = answered // TAKEN        # headers with every lane answered
    assert outcome[0] == "ErrInvalidHeader" and \
        f"(#{answered % TAKEN})" in outcome[1]
    assert len(rows) == 1 + done == 1 + delta["headers"]
    assert flushes == [TILE * TAKEN]


def test_verified_lanes_reach_the_cache_under_their_own_label(monkeypatch,
                                                              chain):
    """A second client on the same chain finds every lane verified."""
    provider, now = Provider(chain), _now(chain)
    flushes = []
    monkeypatch.setattr(light_client, "kernel_width", lambda: WIDTH)
    monkeypatch.setattr(light_client, "TILE_CHUNKS", CHUNKS)
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", TAKEN)
    monkeypatch.setattr(
        crypto_batch, "create_batch_verifier",
        lambda pk: (StubVerifier(flushes, lambda oks: oks), True))
    reset_shared_cache()
    try:
        for again in (False, True):
            lc = LightClient(
                CHAIN, TrustOptions(PERIOD, 1, chain.headers[1].hash()),
                provider, [], LightStore(MemDB()), sequential=True,
                now_fn=lambda: now)
            before, n = tile_stats(), len(flushes)
            lc.verify_light_block_at_height(chain.n)
            delta = {k: v - before[k] for k, v in tile_stats().items()}
            assert delta["cache_hits"] == again * (chain.n - 1) * TAKEN
            assert (len(flushes) == n) == again
        cache = shared_cache()
        with cache._lock:
            assert cache.hits.get("light") == (chain.n - 1) * TAKEN
            assert "farm" not in cache.hits
    finally:
        reset_shared_cache()


def test_the_save_inserts_true_lanes_never_the_false_one(monkeypatch,
                                                        chain):
    """A false lane mid-tile: the save inserts the true lanes of the
    headers before it and of its own header, each with the key its
    lookup computed, and neither the false lane nor the lanes of the
    headers planned after it."""
    height, bad = 2 + TILE // 2, 1
    provider, now = Provider(chain, {height: bad_lane(bad)}), _now(chain)
    monkeypatch.setattr(light_client, "kernel_width", lambda: WIDTH)
    monkeypatch.setattr(light_client, "TILE_CHUNKS", CHUNKS)
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", TAKEN)
    monkeypatch.setattr(
        crypto_batch, "create_batch_verifier",
        lambda pk: (StubVerifier([], lambda oks: oks), True))
    reset_shared_cache()
    try:
        lc = LightClient(
            CHAIN, TrustOptions(PERIOD, 1, chain.headers[1].hash()),
            provider, [], LightStore(MemDB()), sequential=True,
            now_fn=lambda: now)
        cache = shared_cache()
        cache.clear()       # the root's own commit
        with pytest.raises(verifier.ErrInvalidHeader, match=f"#{bad}"):
            lc.verify_light_block_at_height(chain.n)
        lanes = {}
        for h in range(2, height + 1):
            lb = provider.light_block(h)
            commit = lb.signed_header.commit
            for i in range(TAKEN):
                lanes[h, i] = (
                    lb.validator_set.get_by_index(i).pub_key.bytes_(),
                    commit.vote_sign_bytes(CHAIN, i),
                    commit.signatures[i].signature)
        false = lanes.pop((height, bad))
        assert len(cache) == len(lanes)
        assert all(cache.seen(*t, path="x") for t in lanes.values())
        assert not cache.seen(*false, path="x")
        assert cache.insert_counts() == (len(lanes), len(lanes))
    finally:
        reset_shared_cache()


def test_spans_of_a_tile(monkeypatch, chain):
    trace.enable(seed=3)
    try:
        tiled(monkeypatch, Provider(chain), 2 + TILE, _now(chain))
        spans = trace.shared_recorder().snapshot()
    finally:
        trace.disable()
    tiles = [s for s in spans if s["name"] == "light.tile"]
    assert [(t["attrs"]["first_height"], t["attrs"]["headers"],
             t["attrs"]["lanes"], t["attrs"]["cache_hits"])
            for t in tiles] == [(2, TILE, TILE * TAKEN, 0), (2 + TILE, 1,
                                                             TAKEN, 0)]
    for t in tiles:
        kids = [s for s in spans if s["pid"] == t["sid"]]
        assert [k["name"] for k in kids] == ["light.plan", "light.verify",
                                             "light.save"]
        assert all(t["t0"] <= k["t0"] <= k["t1"] <= t["t1"] for k in kids)
        v = kids[1]["attrs"]
        assert v["lanes"] == v["device_lanes"] + v["native_lanes"] == \
            t["attrs"]["lanes"]


def test_save_counts_the_commit_lanes_it_encodes(monkeypatch):
    """A chain never encoded before, one second a commit: each header's
    save builds all its 8 lanes in one pass, and all but the first take
    the seconds field the first built. The tile's `light.save` span
    carries the same counts."""
    chain = Chain(seed=19, n=TILE + 3)
    trace.enable(seed=5)
    try:
        outcome, rows, _f, delta = tiled(monkeypatch, Provider(chain),
                                         chain.n, _now(chain))
        spans = trace.shared_recorder().snapshot()
    finally:
        trace.disable()
    lanes, saved = len(chain.sets[1]), chain.n - 1
    assert outcome[0] == "ok" and len(rows) == chain.n
    assert delta["headers"] == saved
    assert delta["sig_encodings"] == lanes * saved
    assert delta["sig_ts_prefix_reused"] == (lanes - 1) * saved
    saves = [s["attrs"] for s in spans if s["name"] == "light.save"]
    tiles = [s["attrs"] for s in spans if s["name"] == "light.tile"]
    assert [(a["sig_encodings"], a["sig_ts_prefix_reused"]) for a in saves] \
        == [(lanes * t["headers"], (lanes - 1) * t["headers"])
            for t in tiles]


def test_the_farm_plans_as_before_the_move(chain):
    """One planner: the farm's names are the light client's, bound to
    the farm's cache label; a plan is the same lanes and tallies."""
    assert farm_planner.Lane is light_planner.Lane
    assert farm_planner.PlannedCheck is light_planner.PlannedCheck
    assert farm_planner._add_lane is light_planner._add_lane
    assert farm_planner.CACHE_PATH == "farm" and \
        verifier.CACHE_PATH == "light"
    reset_shared_cache()
    try:
        cache = shared_cache()
        vals, commit = _valset(chain.sets[3]), chain.commits[3]
        args = (CHAIN, vals, commit.block_id, 3, commit, cache)
        farm = farm_planner.plan_commit_light(*args)
        light = light_planner.plan_commit_light(*args, path="light")
        assert farm == light and farm.kind == "light"
        assert (farm.tallied, farm.total, farm.needed) == (
            sum(p for _k, p in chain.sets[3][:TAKEN]),
            vals.total_voting_power(), vals.total_voting_power() * 2 // 3)
        trusting = farm_planner.plan_commit_trusting(
            CHAIN, vals, commit, validation.DEFAULT_TRUST_LEVEL, cache)
        assert trusting.kind == "trusting" and len(trusting.lanes) == 2
        with cache._lock:
            assert cache.misses == {"farm": TAKEN + 2, "light": TAKEN}
        with pytest.raises(validation.ErrNotEnoughVotingPowerSigned):
            farm_planner.plan_commit_light(
                CHAIN, vals, commit.block_id, 3,
                absent(0, 1)(chain.light_block(3)).signed_header.commit,
                cache)
    finally:
        reset_shared_cache()
