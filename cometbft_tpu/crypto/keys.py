"""Key interfaces and the ed25519 implementation.

Mirrors the reference plugin surface (crypto/crypto.go:22-54: PubKey,
PrivKey, BatchVerifier) so every call site — vote verification, commit
batch verification, light client — goes through the same seam the
reference uses, with the TPU kernel slotted in behind it
(crypto/batch/batch.go:11-35 is re-created in `batch.py`).

Single-signature verification uses ZIP-215 semantics, identical to the
batch path (reference crypto/ed25519/ed25519.go:181-188) — verdict parity
between single and batch verification is what makes batch-failure
attribution sound.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import List, Optional, Protocol, Sequence, Tuple, runtime_checkable

from . import ref_ed25519 as ref

ADDRESS_SIZE = 20  # reference crypto/tmhash/hash.go:78 (sha256, truncated)

ED25519_KEY_TYPE = "ed25519"

try:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey as _CEd25519PublicKey)
    from cryptography.exceptions import InvalidSignature as _CInvalidSig

    def _native_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
        if len(sig) != 64 or len(pub) != 32:
            return False
        try:
            _CEd25519PublicKey.from_public_bytes(pub).verify(sig, msg)
            return True
        except (_CInvalidSig, ValueError):
            return False
except ImportError:  # pragma: no cover
    def _native_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
        return False


def address_from_pubkey_bytes(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()[:ADDRESS_SIZE]


@runtime_checkable
class PubKey(Protocol):
    def address(self) -> bytes: ...
    def bytes_(self) -> bytes: ...
    def verify_signature(self, msg: bytes, sig: bytes) -> bool: ...
    def type_(self) -> str: ...


@runtime_checkable
class PrivKey(Protocol):
    def sign(self, msg: bytes) -> bytes: ...
    def pub_key(self) -> PubKey: ...
    def bytes_(self) -> bytes: ...
    def type_(self) -> str: ...


class BatchVerifier(Protocol):
    """reference crypto/crypto.go:46-54."""

    def add(self, pk: PubKey, msg: bytes, sig: bytes) -> None: ...
    def verify(self) -> Tuple[bool, List[bool]]: ...


@dataclass(frozen=True)
class Ed25519PubKey:
    raw: bytes

    def __post_init__(self):
        if len(self.raw) != 32:
            raise ValueError(f"ed25519 pubkey must be 32B, got {len(self.raw)}")

    def address(self) -> bytes:
        return address_from_pubkey_bytes(self.raw)

    def bytes_(self) -> bytes:
        return self.raw

    def type_(self) -> str:
        return ED25519_KEY_TYPE

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        """Single-signature ZIP-215 verify — the consensus addVote hot
        path (reference types/vote.go:235, crypto/ed25519/ed25519.go:181).

        Fast path: the native C verifier (~50µs). It implements strict
        cofactorless RFC 8032, which ACCEPTS a strict subset of ZIP-215:
        an accept is always ZIP-215-valid (the cofactorless equation
        implies the cofactored one; s<L and point validity are enforced),
        but a reject may still be ZIP-215-valid (non-canonical encodings,
        small-order/mixed-order components), so rejects re-check against
        the full ZIP-215 oracle. Honest traffic never hits the slow path.
        """
        fast = _native_verify(self.raw, msg, sig)
        if fast:
            return True
        return ref.verify(self.raw, msg, sig, zip215=True)


@dataclass(frozen=True)
class Ed25519PrivKey:
    seed: bytes

    def __post_init__(self):
        if len(self.seed) != 32:
            raise ValueError("ed25519 seed must be 32B")

    @classmethod
    def generate(cls, rng=None) -> "Ed25519PrivKey":
        import secrets
        return cls(secrets.token_bytes(32) if rng is None
                   else bytes(rng.randrange(256) for _ in range(32)))

    def sign(self, msg: bytes) -> bytes:
        # fast native signer when available; identical RFC 8032 output
        try:
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PrivateKey)
            return Ed25519PrivateKey.from_private_bytes(self.seed).sign(msg)
        except ImportError:  # pragma: no cover
            return ref.sign(self.seed, msg)

    def pub_key(self) -> Ed25519PubKey:
        return Ed25519PubKey(ref.pubkey_from_seed(self.seed))

    def bytes_(self) -> bytes:
        return self.seed

    def type_(self) -> str:
        return ED25519_KEY_TYPE


class Ed25519BatchVerifier:
    """Accumulate-and-flush batch verifier backed by the TPU kernel
    (replaces curve25519-voi's CPU batch, reference
    crypto/ed25519/ed25519.go:208-241).

    Unlike the reference — whose batch returns one bool plus a per-sig
    attribution vector only on failure — the lane-parallel kernel always
    produces per-signature verdicts, so `verify()` is exact attribution
    with no fallback re-verification pass (types/validation.go:306-315).
    """

    def __init__(self, batch_size: Optional[int] = None):
        self._pubs: List[bytes] = []
        self._msgs: List[bytes] = []
        self._sigs: List[bytes] = []
        self._batch_size = batch_size

    def __len__(self) -> int:
        return len(self._pubs)

    def add(self, pk: PubKey, msg: bytes, sig: bytes) -> None:
        if pk.type_() != ED25519_KEY_TYPE:
            raise TypeError(f"ed25519 batch verifier got {pk.type_()} key")
        self._pubs.append(pk.bytes_())
        self._msgs.append(msg)
        self._sigs.append(sig)

    def verify(self) -> Tuple[bool, List[bool]]:
        if not self._pubs:
            return False, []
        n = len(self._pubs)
        eff = self._batch_size or 1 << (n - 1).bit_length()
        from ..libs.jax_cache import is_device_platform, ledger
        on_device = is_device_platform()
        if on_device:
            # pad up to the pallas lane tile: a sub-TILE batch would
            # take the XLA kernel (ops/ed25519._rlc_dispatch alignment
            # check) and pay a separate multi-minute compile per
            # width, where the TILE bucket is the one blocksync
            # already keeps warm
            from ..ops.pallas_verify import TILE
            eff = -(-eff // TILE) * TILE
        if not on_device and eff > 64 \
                and not ledger().warm_in_process("ed25519-rlc", eff):
            # CPU backend: jitting the RLC kernel at batch >= 256
            # takes minutes and can crash the XLA:CPU compiler
            # (docs/PERF.md); a >64-lane flush on a CPU node runs the
            # native per-sig verify instead — the same clamp blocksync
            # applies (engine/blocksync.py:79-89). The clamp LIFTS
            # when this process already compiled the bucket (node
            # prewarm, or an earlier flush through this verifier): the
            # warm jit cache makes the wide kernel the cheaper path
            # (ROADMAP item-5 residual). Process-local warmth only —
            # XLA:CPU executables are never persisted, so another
            # process's ledger entry predicts a full recompile, not a
            # reload (libs/jax_cache.warm_in_process).
            oks = [Ed25519PubKey(p).verify_signature(m, s)
                   for p, m, s in zip(self._pubs, self._msgs,
                                      self._sigs)]
            return all(oks), oks
        from ..ops.ed25519 import verify_batch
        with ledger().compile_guard("ed25519-rlc", eff):
            out = verify_batch(self._pubs, self._msgs, self._sigs,
                               batch_size=eff)
        oks = [bool(v) for v in out]
        return all(oks), oks


def privkey_from_type_bytes(key_type: str, raw: bytes) -> PrivKey:
    """Private-key factory by wire type string — the decode side of
    FilePV state files, which persist (type, raw) so a BLS validator
    key round-trips as BLS instead of being re-typed ed25519."""
    if key_type == ED25519_KEY_TYPE:
        return Ed25519PrivKey(raw)
    if key_type == "bls12_381":
        from .bls12381 import Bls12381PrivKey
        return Bls12381PrivKey(raw)
    raise ValueError(f"unsupported privval key type {key_type!r}")


def pubkey_from_type_bytes(key_type: str, raw: bytes) -> PubKey:
    """Key factory by wire type string (reference
    crypto/encoding/codec.go:119 PubKeyFromTypeAndBytes)."""
    if key_type == ED25519_KEY_TYPE:
        return Ed25519PubKey(raw)
    if key_type == "secp256k1":
        from .secp256k1 import Secp256k1PubKey
        return Secp256k1PubKey(raw)
    if key_type == "sr25519":
        from .sr25519 import Sr25519PubKey
        return Sr25519PubKey(raw)
    if key_type == "bls12_381":
        # pure-Python curve (reference gates this type behind a blst
        # build tag, crypto/bls12381/key_bls12381.go:1)
        from .bls12381 import Bls12381PubKey
        return Bls12381PubKey(raw)
    raise ValueError(f"unknown key type {key_type!r}")
