"""The bytes a validator signs for its precommit's vote extension:
CometBFT's `CanonicalVoteExtension`, varint-length-delimited (reference
types/vote.go `VoteExtensionSignBytes`, proto/cometbft/types/v1/
canonical.proto), written from the wire format; what the benchmark's
extending kvstore puts into an extension; and the reference's verdict on
an extended precommit. Imports nothing of the program.

    CanonicalVoteExtension  1 extension (bytes)   2 height (sfixed64)
                            3 round (sfixed64)    4 chain_id (string)

proto3: a field that is zero or empty is left out (see canonical_vote.py).

The extension of a validator at a height is `size` bytes of SHAKE-256
over "vote-extension|<height>|" and the validator's address, the first 20
bytes of SHA-256 of its public key (reference crypto/ed25519 `Address`).

The verdict, as the reference takes an extended precommit in
`VoteSet.AddVote` (`VerifyVoteAndExtension`) behind `State.addVote`
(`VerifyExtension`, then the app's `VerifyVoteExtension`): a precommit
for a block counts only if it carries an extension signature, its own
signature verifies, its extension's signature verifies over the bytes
above, and the app accepts the extension; a nil precommit never carries
extension data (`Vote.ValidateBasic`)."""

from __future__ import annotations

import hashlib

from benchmark.reference import ed25519_ref
from benchmark.reference.canonical_vote import (_bytes_field, _sfixed64_field,
                                                _uvarint)


def extension_sign_bytes(chain_id: str, height: int, round_: int,
                         extension: bytes) -> bytes:
    body = (_bytes_field(1, extension) + _sfixed64_field(2, height)
            + _sfixed64_field(3, round_)
            + _bytes_field(4, chain_id.encode("utf-8")))
    return _uvarint(len(body)) + body


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:20]


def extension(height: int, addr: bytes, size: int) -> bytes:
    return hashlib.shake_256(b"vote-extension|%d|" % height
                             + addr).digest(size)


def accepts(chain_id: str, pub: bytes, vote_sign_bytes: bytes,
            signature: bytes, height: int, round_: int, for_block: bool,
            ext: bytes, ext_signature: bytes, size: int) -> bool:
    """The reference's verdict on one precommit of a chain with vote
    extensions on, its own sign-bytes given (reference/vote_tally.py)."""
    if not for_block:
        return not ext and not ext_signature and ed25519_ref.verify(
            pub, vote_sign_bytes, signature)
    return (bool(ext_signature)
            and ed25519_ref.verify(pub, vote_sign_bytes, signature)
            and ed25519_ref.verify(pub, extension_sign_bytes(
                chain_id, height, round_, ext), ext_signature)
            and ext == extension(height, address(pub), size))
