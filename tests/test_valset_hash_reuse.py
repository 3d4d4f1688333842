"""`ValidatorSet.adopt_hash_of`: a set takes another's hash only where
that is its own root, the same members in the same order, and copies
nothing else. Every case ends on the set's hash against a merkle
computed afresh from its own members."""

import hashlib
from dataclasses import dataclass

import pytest

from cometbft_tpu.crypto import merkle
from cometbft_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
from cometbft_tpu.state.state import _valset_from_json, _valset_to_json
from cometbft_tpu.types.validator import Validator, ValidatorSet


def _key(i: int) -> Ed25519PubKey:
    return Ed25519PrivKey(hashlib.sha256(b"reuse/%d" % i).digest()).pub_key()


@dataclass(frozen=True)
class OtherKey:
    """32 key bytes under another key type: sr25519, the repo's other
    32-byte type, has no leaf encoding, so a stub hashes as secp256k1."""
    raw: bytes

    def address(self) -> bytes:
        return hashlib.sha256(self.raw).digest()[:20]

    def bytes_(self) -> bytes:
        return self.raw

    def type_(self) -> str:
        return "secp256k1"


MEMBERS = [(_key(i), int(10**6 * (i + 1) ** -0.8)) for i in range(8)]


def _set(members=MEMBERS) -> ValidatorSet:
    return ValidatorSet([Validator(k, p) for k, p in members])


def _fresh(vs: ValidatorSet) -> bytes:
    return merkle.hash_from_byte_slices([v.bytes_() for v in vs.validators])


def _replace(i, key=None, power=None):
    members = list(MEMBERS)
    k, p = members[i]
    members[i] = (key or k, power or p)
    return members


def _priorities_moved() -> ValidatorSet:
    vs = _set()
    vs.increment_proposer_priority(5)
    return vs


def _swapped() -> ValidatorSet:
    """Two members in each other's place, built as `_valset_from_json`
    builds a set: with `__new__`, in the stored order, no sort."""
    vs = _valset_from_json(_valset_to_json(_set()))
    vals = vs.validators
    vals[2], vals[3] = vals[3], vals[2]
    vs._by_address = {v.address: i for i, v in enumerate(vals)}
    return vs


@pytest.mark.parametrize("make, adopted", [
    (_set, True),
    (_priorities_moved, True),
    (lambda: _valset_from_json(_valset_to_json(_set())), True),
    (lambda: _set(_replace(4, power=MEMBERS[4][1] + 1)), False),
    (lambda: _set(_replace(0, key=_key(99))), False),
    # the same 32 bytes under another key type is another leaf
    (lambda: _set(_replace(5, key=OtherKey(MEMBERS[5][0].raw))), False),
    (_swapped, False),
    (lambda: _set(MEMBERS[:-1]), False),
    (lambda: _set(MEMBERS + [(_key(8), 1)]), False),
], ids=["equal", "priorities-only", "decoded-from-json", "one-power",
        "one-key", "other-key-type", "two-swapped", "one-fewer",
        "one-more"])
def test_adopted_only_where_the_root_is_the_same(make, adopted):
    other = _set()
    other.hash()
    vs = make()
    assert vs.adopt_hash_of(other) is adopted
    assert (vs._hash is not None) is adopted
    assert vs.hash() == _fresh(vs)
    assert (vs.hash() == other.hash()) is adopted


def test_the_other_sets_hash_is_computed_where_it_has_none():
    """A trusted set decoded from the store has no memo yet."""
    other = _valset_from_json(_valset_to_json(_set()))
    vs = _set()
    assert other._hash is None
    assert vs.adopt_hash_of(other)
    assert vs._hash == other._hash == _fresh(vs)


def test_a_set_with_its_own_hash_is_left_untouched():
    vs, other = _set(), _set()
    mark = b"\x00" * 32
    vs._hash = mark
    assert not vs.adopt_hash_of(other)
    assert vs._hash is mark
    assert other._hash is None


def test_nothing_but_the_hash_is_copied():
    other = _priorities_moved()
    other.total_voting_power()
    _valset_to_json(other)          # fills other._json_memo
    assert other._json_memo is not None and other._total is not None
    vs = _valset_from_json(_valset_to_json(_set()))
    by_address = vs._by_address
    assert vs.adopt_hash_of(other)
    assert vs._json_memo is None and vs._total is None
    assert vs._by_address is by_address
    assert [v.proposer_priority for v in vs.validators] != \
        [v.proposer_priority for v in other.validators]
    assert _valset_to_json(vs) != _valset_to_json(other)


def test_keys_without_value_equality_never_adopt():
    """A BLS12-381 key compares by identity: the same set with its keys
    decoded afresh computes its own root, the same one."""
    from cometbft_tpu.crypto import bls12381
    keys = [bls12381.Bls12381PrivKey.generate(b"reuse/bls/%d" % i +
                                              bytes(22)).pub_key()
            for i in range(3)]
    other = ValidatorSet([Validator(k, 10 - i) for i, k in enumerate(keys)])
    vs = ValidatorSet([Validator(bls12381.Bls12381PubKey(k.bytes_()), 10 - i)
                       for i, k in enumerate(keys)])
    assert not vs.adopt_hash_of(other)
    assert vs._hash is None
    assert vs.hash() == _fresh(vs) == other.hash()
