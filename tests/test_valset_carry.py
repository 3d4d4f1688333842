"""What apply carries from one height to the next of a validator set
(PR 32): the key's address memo (crypto/keys.py), the address index a
`ValidatorSet.copy()` shares, and the set's stored encoding
(`ValidatorSet._json_memo`, state/state.py `_valset_to_json`). None of
it may show: the store's bytes, the proposer rotation and the
independence of a copy are held against plain re-implementations here,
which share no code with what they check."""

import json
import pickle

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.crypto.keys import Ed25519PubKey, address_from_pubkey_bytes
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.state import state as state_mod
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.state import (GenesisDoc, State, StateStore,
                                      _valset_from_json, _valset_to_json)
from cometbft_tpu.types.block import BlockID, Commit
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet

N = 200


def _key(i: int) -> Ed25519PubKey:
    # any 32 bytes are a key to everything below: nothing here verifies
    return Ed25519PubKey(i.to_bytes(4, "big") * 8)


def _set(powers) -> ValidatorSet:
    return ValidatorSet([Validator(_key(i), p) for i, p in enumerate(powers)])


# --- the plain encoders: the store's format, written out once more ---------

def plain_valset_json(vs: ValidatorSet) -> bytes:
    prop = vs.proposer
    return json.dumps({
        "validators": [
            {"pub_key": v.pub_key.raw.hex(), "type": "ed25519",
             "power": v.voting_power, "priority": v.proposer_priority}
            for v in vs.validators],
        "proposer": prop.pub_key.raw.hex() if prop else None,
        "proposer_type": "ed25519" if prop else None,
    }).encode()


def plain_state_json(s: State) -> bytes:
    p = s.consensus_params
    return json.dumps({
        "chain_id": s.chain_id,
        "initial_height": s.initial_height,
        "last_block_height": s.last_block_height,
        "last_block_id": {
            "hash": s.last_block_id.hash.hex(),
            "total": s.last_block_id.parts.total,
            "parts_hash": s.last_block_id.parts.hash.hex()},
        "last_block_time": [s.last_block_time.seconds,
                            s.last_block_time.nanos],
        "validators": plain_valset_json(s.validators).decode(),
        "next_validators": plain_valset_json(s.next_validators).decode(),
        "last_validators": plain_valset_json(s.last_validators).decode(),
        "last_height_validators_changed": s.last_height_validators_changed,
        "last_results_hash": s.last_results_hash.hex(),
        "app_hash": s.app_hash.hex(),
        "version_block": s.version_block,
        "version_app": s.version_app,
        "consensus_params": {
            "max_block_bytes": p.max_block_bytes,
            "max_gas": p.max_gas,
            "evidence_max_age_num_blocks": p.evidence_max_age_num_blocks,
            "evidence_max_age_seconds": p.evidence_max_age_seconds,
            "evidence_max_bytes": p.evidence_max_bytes,
            "pbts_enable_height": p.pbts_enable_height,
            "vote_extensions_enable_height":
                p.vote_extensions_enable_height,
            "synchrony_precision_ns": p.synchrony_precision_ns,
            "synchrony_message_delay_ns": p.synchrony_message_delay_ns,
        },
    }).encode()


# --- (a) the store's bytes, height by height --------------------------------

BLOCKS = 24
LEAVER, JOINER = _key(17), _key(N + 1)


def _val_tx(key: Ed25519PubKey, power: int) -> bytes:
    return b"val:" + key.raw.hex().encode() + b"!%d" % power


# a power change (in force at 7), a swap (14), the power back (21)
VAL_TXS = {5: [_val_tx(_key(3), 11)],
           12: [_val_tx(LEAVER, 0), _val_tx(JOINER, 10)],
           19: [_val_tx(_key(3), 10)]}


def test_the_state_store_holds_the_plain_encoders_bytes_at_every_height():
    genesis = GenesisDoc(
        chain_id="carry", validators=[Validator(_key(i), 10)
                                      for i in range(N)],
        genesis_time=Timestamp(1_700_000_000, 0))
    app = KVStoreApplication()
    app.init_chain(genesis.chain_id, 1, [], b"")
    db = MemDB()
    store = StateStore(db)
    executor = BlockExecutor(app, state_store=store)
    state = State.from_genesis(genesis)
    hashes = set()
    for h in range(1, BLOCKS + 1):
        before = list(state_mod.VALSET_ENCODINGS)
        block = state.make_block(
            h, [b"k%d=v%d" % (h, h)] + VAL_TXS.get(h, []), Commit(),
            state.validators.get_proposer().address,
            timestamp=Timestamp(1_700_000_000 + h, 0))
        block_id = BlockID(block.hash(), block.make_part_set().header)
        state, _ = executor.apply_block(state, block_id, block,
                                        verified=True)
        # a State saved at this height, under the same two keys
        assert state.last_block_height == h
        assert db.get(b"statestore:state") == plain_state_json(state)
        assert db.get(b"vals:" + (h + 1).to_bytes(8, "big")) == \
            plain_valset_json(state.validators)
        # four encodings asked for, and only next_validators' computed
        # (the first save has nothing to reuse but `validators` itself)
        asked = [a - b for a, b in zip(state_mod.VALSET_ENCODINGS, before)]
        assert sum(asked) == 4
        assert asked[0] == (3 if h == 1 else 1)
        # the restarted node's view
        loaded = store.load()
        assert plain_state_json(loaded) == plain_state_json(state)
        for name in ("validators", "next_validators", "last_validators"):
            a, b = getattr(loaded, name), getattr(state, name)
            assert a.hash() == b.hash()
            assert [a.get_by_address(v.address)[0] for v in b.validators] \
                == list(range(len(b)))
        assert plain_valset_json(store.load_validators(h + 1)) == \
            plain_valset_json(state.validators)
        hashes.add(state.next_validators.hash())
    # the chain did change its set: three changes, three more hashes
    assert len(hashes) == 4
    assert state.validators.has_address(JOINER.address())
    assert not state.validators.has_address(LEAVER.address())
    assert len(state.validators) == N


# --- (b) proposer rotation against a plain one ------------------------------

def _plain_rotation(vals, heights):
    """[address, power, priority] rows through `heights` increments of
    one (reference types/validator_set.go:105-235), yielding the
    proposer's address and every priority after each."""
    total = sum(v[1] for v in vals)
    for _ in range(heights):
        prios = [v[2] for v in vals]
        diff = max(prios) - min(prios)
        if diff > 2 * total:
            ratio = (diff + 2 * total - 1) // (2 * total)
            for v in vals:
                v[2] = abs(v[2]) // ratio * (1 if v[2] >= 0 else -1)
        avg = sum(v[2] for v in vals) // len(vals)
        for v in vals:
            v[2] += v[1] - avg
        top = min(vals, key=lambda v: (-v[2], v[0]))
        top[2] -= total
        yield top[0], [v[2] for v in vals]


@pytest.mark.parametrize("powers", [
    [10] * N,
    [1 + (7 * i) % 23 for i in range(N)],
], ids=["equal-power", "mixed-power"])
def test_rotation_through_copies_equals_a_plain_rotation(powers):
    vs = _set(powers)
    rows = [[v.address, v.voting_power, v.proposer_priority]
            for v in vs.validators]
    proposers = set()
    for want_proposer, want_prios in _plain_rotation(rows, 3 * N):
        # as _update_state moves the set on: a copy, incremented once,
        # its encoding asked for in between as StateStore.save asks
        _valset_to_json(vs)
        vs = vs.copy()
        vs.increment_proposer_priority(1)
        assert vs.get_proposer().address == want_proposer
        assert [v.proposer_priority for v in vs.validators] == want_prios
        proposers.add(want_proposer)
    assert _valset_to_json(vs) == plain_valset_json(vs)
    # a real rotation: with equal power everyone proposed three times
    assert len(proposers) == N or len(set(powers)) > 1


# --- (c) a copy is independent ----------------------------------------------

def _view(vs: ValidatorSet):
    return ([(v.address, v.voting_power, v.proposer_priority)
             for v in vs.validators],
            [vs.get_by_address(_key(i).address())[0] for i in range(N + 2)],
            vs.get_proposer().address, vs.hash(), _valset_to_json(vs))


MUTATIONS = {
    "increment": lambda vs: vs.increment_proposer_priority(3),
    "power-change": lambda vs: vs.update_with_change_set(
        [Validator(_key(3), 25)]),
    "swap": lambda vs: vs.update_with_change_set(
        [Validator(LEAVER, 0), Validator(JOINER, 10)]),
}


@pytest.mark.parametrize("mutate", MUTATIONS.values(), ids=list(MUTATIONS))
@pytest.mark.parametrize("side", ["original", "copy"])
def test_a_copy_and_its_original_do_not_see_each_others_changes(side, mutate):
    original = _set([10] * N)
    original.increment_proposer_priority(5)
    _valset_to_json(original)           # the memo a copy carries
    cp = original.copy()
    assert _view(cp) == _view(original)
    assert not {id(v) for v in cp.validators} & {
        id(v) for v in original.validators}
    changed, kept = (original, cp) if side == "original" else (cp, original)
    before = _view(kept)
    mutate(changed)
    assert _view(kept) == before
    assert _view(kept)[4] == plain_valset_json(kept)
    assert _view(changed) != before
    assert _view(changed)[4] == plain_valset_json(changed)
    # the changed set's index is its own list's
    assert all(changed.get_by_address(v.address) == (i, v)
               for i, v in enumerate(changed.validators))


# --- (d) every mutator drops the encoding ------------------------------------

@pytest.mark.parametrize("mutate", [
    lambda vs: vs.increment_proposer_priority(1),
    lambda vs: vs.rescale_priorities(1),
    lambda vs: vs._shift_by_avg_proposer_priority(),
    lambda vs: vs.update_with_change_set([Validator(_key(3), 25)]),
], ids=["increment_proposer_priority", "rescale_priorities",
        "_shift_by_avg_proposer_priority", "update_with_change_set"])
def test_every_mutator_drops_the_encoding_memo(mutate):
    vs = _set([1 + i % 5 for i in range(N)])
    for v in vs.validators[:7]:
        v.proposer_priority += 1300     # an average to shift by
    vs._json_memo = None                # (written past the mutators)
    stale = _valset_to_json(vs)
    assert vs._json_memo is stale == plain_valset_json(vs)
    mutate(vs)
    assert vs._json_memo is None
    fresh = _valset_to_json(vs)
    assert fresh == plain_valset_json(vs) != stale


def test_a_set_built_from_its_encoding_has_no_memo_of_it():
    vs = _set([10] * 4)
    back = _valset_from_json(_valset_to_json(vs))
    assert back._json_memo is None      # the class's default, by __new__
    assert _valset_to_json(back) == plain_valset_json(vs)


# --- (e) the address memo stays with its holder ------------------------------

def test_a_pickled_key_carries_no_address_memo():
    key = _key(5)
    assert "_address_memo" not in vars(key)
    address = key.address()
    assert address == address_from_pubkey_bytes(key.raw)
    assert key.address() is address             # asked twice, hashed once
    back = pickle.loads(pickle.dumps(key))
    assert vars(back) == {"raw": key.raw}
    assert back == key and hash(back) == hash(key)
    assert back.address() == address
    # a set's worth, as the benchmark's generator hands over a genesis
    vals = pickle.loads(pickle.dumps(_set([10] * 4).validators))
    assert all("_address_memo" not in vars(v.pub_key) for v in vals)
