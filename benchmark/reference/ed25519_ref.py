"""The plain ed25519 reference: sign and verify through the
`cryptography` wheel (OpenSSL), which shares no code with the program's
kernels. OpenSSL verifies by RFC 8032; on honestly made signatures and on
a signature whose scalar was altered, RFC 8032 and ZIP-215 give the same
verdict, and those are the only lanes the benchmark's traffic holds."""

from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey, Ed25519PublicKey)
from cryptography.hazmat.primitives import serialization


class Signer:
    def __init__(self, seed32: bytes):
        self._key = Ed25519PrivateKey.from_private_bytes(seed32)
        self.pub = self._key.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw)

    def sign(self, msg: bytes) -> bytes:
        return self._key.sign(msg)


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def tamper(sig: bytes) -> bytes:
    """Flip one low bit of s: the signature stays structurally valid
    (R decodes, s < L), only the verification equation fails."""
    return sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
