"""The plain reference `benchmark/reference/valset_replay.py` held against
the program on data neither made for the other: change sets and
transactions that this test draws from a seed, at 8 validators. The
reference imports nothing of the program; where the two disagree, one of
them is wrong about the rules."""

import ast
import hashlib
import os
import random

import pytest

from conftest import REPO
from benchmark.reference import ed25519_ref, valset_replay


def _pub(tag: str) -> bytes:
    return ed25519_ref.Signer(hashlib.sha256(tag.encode()).digest()).pub


def _members(valset) -> list:
    return [(v.pub_key.bytes_(), v.voting_power) for v in valset.validators]


def _draw_updates(rng, powers: dict, fresh: list) -> list:
    """One block's updates: a power change, a swap, a removal, a join, or
    two of them at once."""
    updates = {}
    for _ in range(rng.choice((1, 1, 2))):
        kind = rng.choice(("power", "swap", "leave", "join"))
        inside = [p for p in powers if p not in updates]
        if kind in ("power", "swap", "leave") and len(inside) < 3:
            kind = "join"
        if kind == "power":
            updates[rng.choice(inside)] = rng.choice((1, 7, 10, 11, 2**40))
        if kind in ("swap", "leave"):
            updates[rng.choice(inside)] = 0
        if kind in ("swap", "join"):
            updates[fresh.pop()] = rng.choice((3, 10, 10, 12))
    return list(updates.items())


@pytest.mark.parametrize("seed", [1, 29, 2**31 + 3])
def test_replay_agrees_with_update_with_change_set(seed):
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    rng = random.Random(seed)
    fresh = [_pub(f"{seed}/joiner/{i}") for i in range(200)]
    powers = {_pub(f"{seed}/genesis/{i}"): 10 for i in range(8)}
    valset = ValidatorSet([Validator(Ed25519PubKey(p), w)
                           for p, w in powers.items()])
    assert _members(valset) == valset_replay.ordered(powers)
    for _round in range(60):
        updates = _draw_updates(rng, powers, fresh)
        powers = valset_replay.apply_updates(powers, updates)
        valset.update_with_change_set(
            [Validator(Ed25519PubKey(p), w) for p, w in updates])
        members = valset_replay.ordered(powers)
        assert _members(valset) == members
        assert valset.hash() == valset_replay.validators_hash(members)
        assert valset.total_voting_power() == sum(powers.values())
        for v, (pub, _w) in zip(valset.validators, members):
            assert v.address == valset_replay.address(pub)


@pytest.mark.parametrize("updates, why", [
    ([("gone", 0)], "removing a validator that is not there"),
    ([("in", 5), ("in", 6)], "a key twice in one block"),
    ([("in", -1)], "negative power"),
    ([("in", 0), ("also-in", 0)], "the set would be empty"),
    ([("in", valset_replay.MAX_TOTAL_VOTING_POWER)], "over the cap"),
])
def test_both_refuse_what_the_rules_refuse(updates, why):
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    powers = {_pub("in"): 10, _pub("also-in"): 10}
    updates = [(_pub(name), power) for name, power in updates]
    with pytest.raises(ValueError):
        valset_replay.apply_updates(powers, updates)
    with pytest.raises(ValueError):
        ValidatorSet([Validator(Ed25519PubKey(p), w)
                      for p, w in powers.items()]).update_with_change_set(
            [Validator(Ed25519PubKey(p), w) for p, w in updates])


@pytest.mark.parametrize("seed", [7, 2**31 + 29])
def test_replay_agrees_with_the_state_over_a_chain(seed):
    """40 blocks through the real `BlockExecutor` and kvstore: the set
    the program has in force at every height (`State.validators` after
    the block before), the header's `validators_hash` and
    `next_validators_hash`, and the application's state."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import GenesisDoc, State
    from cometbft_tpu.types.block import BlockID, Commit
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validator import Validator
    rng = random.Random(seed)
    fresh = [_pub(f"{seed}/joiner/{i}") for i in range(100)]
    genesis = [(_pub(f"{seed}/genesis/{i}"), 10) for i in range(8)]
    powers = dict(genesis)
    tx_lists = []
    for h in range(1, 41):
        txs = [f"k{h}={rng.randrange(10**6)}".encode(),
               f"shared={h}".encode()]
        if h % 3 == 0:
            updates = _draw_updates(rng, powers, fresh)
            powers = valset_replay.apply_updates(powers, updates)
            txs[1:1] = [b"val:" + p.hex().encode() + b"!%d" % w
                        for p, w in updates]
        tx_lists.append(txs)
    replayed = valset_replay.replay(genesis, tx_lists)

    doc = GenesisDoc(chain_id="replay", genesis_time=Timestamp(1_700_000_000, 0),
                     validators=[Validator(Ed25519PubKey(p), w)
                                 for p, w in genesis])
    state = State.from_genesis(doc)
    app = KVStoreApplication()
    app.init_chain("replay", 1, [], b"")
    executor = BlockExecutor(app)
    last_commit = Commit()
    for h, txs in enumerate(tx_lists, start=1):
        assert _members(state.validators) == replayed.members(h)
        assert _members(state.next_validators) == replayed.members(h + 1)
        block = state.make_block(
            h, txs, last_commit, state.validators.get_proposer().address,
            timestamp=Timestamp(1_700_000_000 + h, 0))
        assert block.header.validators_hash == replayed.validators_hash(h)
        assert block.header.next_validators_hash == \
            replayed.validators_hash(h + 1)
        block_id = BlockID(block.hash(), block.make_part_set().header)
        state, _ = executor.apply_block(state, block_id, block,
                                        verified=True)
        last_commit = Commit(height=h, round=0, block_id=block_id,
                             signatures=[])
    assert _members(state.validators) == replayed.members(41)
    assert _members(state.next_validators) == replayed.members(42)
    assert app.state == replayed.app_state
    assert replayed.total_power(41) == state.validators.total_voting_power()
    # a change is in force two heights after its block, and only there
    changed = [h + 2 for h, txs in enumerate(tx_lists, start=1)
               if any(tx.startswith(b"val:") for tx in txs)]
    assert [h for h in replayed.change_heights() if h <= 41] == \
        [h for h in changed if h <= 41
         and replayed.members(h) != replayed.members(h - 1)]
    assert len(replayed.change_heights()) >= 10


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(REPO, "benchmark", "reference", "valset_replay.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "hashlib"}
