"""The plain reference of a chain whose validator set changes: the
kvstore's transactions replayed into the application's state AND into
the validator set in force at every height, written from the reference's
rules and from nothing of the program.

Transactions (the kvstore application's grammar, as the blocks carry it):

    key=value                       store a pair
    val:<pubkey hex>!<power>        set that ed25519 validator's power;
                                    power 0 removes it, an unknown key joins

The rules (reference state/execution.go:597-672 `updateState`,
types/validator_set.go `UpdateWithChangeSet`):

- the updates of block H are applied to the set that was going to be in
  force at H+1 and give the set in force at H+2; heights 1 and 2 have the
  genesis set;
- within one block a key may appear once; power is never negative; a key
  removed must be in the set; the set may not become empty; the total
  power may not pass MaxTotalVotingPower = (2^63 - 1) / 8;
- the set is ordered by power, larger first, then by address, smaller
  first (`ValidatorsByVotingPower`); the address is the first 20 bytes of
  SHA-256 of the key (crypto/tmhash `SumTruncated`);
- `validators_hash` is the RFC 6962 Merkle root (SHA-256, leaf prefix
  0x00, inner prefix 0x01, split at the largest power of two below n)
  over each validator's `SimpleValidator` encoding in that order:
  field 1 the `PublicKey` message (its field 1, the 32 ed25519 bytes),
  field 2 the power as a varint (types/validator.go:118-133).

Proposer priorities are not replayed: they are in no hash and no
signature that the benchmark compares."""

from __future__ import annotations

import hashlib

VAL_PREFIX = b"val:"
MAX_TOTAL_VOTING_POWER = (2**63 - 1) // 8


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:20]


def ordered(powers: dict) -> list:
    """[(pub, power)] in the set's order."""
    return sorted(powers.items(), key=lambda kv: (-kv[1], address(kv[0])))


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def simple_validator(pub: bytes, power: int) -> bytes:
    key = b"\x0a" + _uvarint(len(pub)) + pub            # PublicKey.ed25519
    return (b"\x0a" + _uvarint(len(key)) + key          # pub_key
            + (b"\x10" + _uvarint(power) if power else b""))  # voting_power


def merkle_root(leaves: list) -> bytes:
    if not leaves:
        return hashlib.sha256(b"").digest()
    if len(leaves) == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    k = 1 << ((len(leaves) - 1).bit_length() - 1)
    return hashlib.sha256(b"\x01" + merkle_root(leaves[:k])
                          + merkle_root(leaves[k:])).digest()


def validators_hash(members: list) -> bytes:
    return merkle_root([simple_validator(pub, power)
                        for pub, power in members])


def parse_update(tx: bytes):
    """(pub, power) of a `val:` transaction."""
    body = tx[len(VAL_PREFIX):].decode()
    key_hex, power = body.split("!", 1)
    pub = bytes.fromhex(key_hex)
    if len(pub) != 32:
        raise ValueError("not an ed25519 key")
    return pub, int(power)


def apply_updates(powers: dict, updates: list) -> dict:
    """The set after one block's updates, by the rules above."""
    if len({pub for pub, _p in updates}) != len(updates):
        raise ValueError("a key appears twice in one block's updates")
    after = dict(powers)
    for pub, power in updates:
        if power < 0:
            raise ValueError("negative voting power")
        if power == 0:
            if pub not in powers:
                raise ValueError("removing a validator that is not there")
            del after[pub]
        else:
            after[pub] = power
    if not after:
        raise ValueError("the updates would empty the set")
    if sum(after.values()) > MAX_TOTAL_VOTING_POWER:
        raise ValueError("total voting power over the cap")
    return after


class Replay:
    """What the replay gives: the application's state, and for every
    height 1..n+2 the set in force (members in order, total power, hash)."""

    def __init__(self, app_state: dict, sets: list):
        self.app_state = app_state
        self._sets = sets           # index h-1 -> members in order
        self._hashes: dict = {}

    def members(self, height: int) -> list:
        return self._sets[height - 1]

    def total_power(self, height: int) -> int:
        return sum(power for _pub, power in self.members(height))

    def validators_hash(self, height: int) -> bytes:
        members = self.members(height)
        key = id(members)           # consecutive heights share a list
        if key not in self._hashes:
            self._hashes[key] = validators_hash(members)
        return self._hashes[key]

    def change_heights(self) -> list:
        """Heights whose set differs from the height before."""
        return [h for h in range(2, len(self._sets) + 1)
                if self._sets[h - 1] is not self._sets[h - 2]]


def replay(genesis: list, tx_lists) -> Replay:
    """`genesis`: [(pub, power)] of the genesis file; `tx_lists`: the
    transactions of blocks 1..n in order."""
    app_state: dict = {}
    powers = dict(genesis)
    if len(powers) != len(genesis):
        raise ValueError("a key appears twice in the genesis set")
    first = ordered(powers)
    sets = [first, first]           # heights 1 and 2
    for txs in tx_lists:            # block H gives the set of H+2
        updates = []
        for tx in txs:
            if tx.startswith(VAL_PREFIX):
                updates.append(parse_update(tx))
            else:
                k, v = tx.split(b"=", 1)
                app_state[k.decode()] = v.decode()
        members = sets[-1]
        if updates:
            powers = apply_updates(powers, updates)
            after = ordered(powers)
            if after != members:
                members = after
        sets.append(members)        # the same list where nothing changed
    return Replay(app_state, sets)
