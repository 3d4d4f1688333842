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
from typing import List, Protocol, Sequence, Tuple, runtime_checkable

import numpy as np

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
        """Memoized per instance, as CommitSig.encode is: the key is
        frozen, and proposer rotation asks a 200-member set for two to
        four addresses a comparison, every height. The memo is not a
        field and does not travel through pickle/copy
        (`__getstate__`): whoever holds the key pays its one hash."""
        memo = self.__dict__.get("_address_memo")
        if memo is None:
            memo = address_from_pubkey_bytes(self.raw)
            object.__setattr__(self, "_address_memo", memo)
        return memo

    def __getstate__(self):
        return {"raw": self.raw}

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


def kernel_bucket() -> int:
    """The ONE lane bucket every ed25519 kernel dispatch of this tree
    rides: the Pallas lane tile, which `prewarm_verify_kernels` compiles
    before traffic. Not a function of the lane count:
    `ops.ed25519.verify_batch` cuts a wider batch into bucket-sized
    chunks and pads a narrower one up, so every chunk of every caller
    is the warmed executable, and a sub-tile or odd width never pays a
    compile of its own (minutes) or falls to the XLA kernel
    (`ops/ed25519._rlc_dispatch`'s alignment check). Messages longer
    than a vote's need a longer SHA-512 axis: the batch verifier sends
    their lanes to the kernel only where that shape is warm
    (`ops.ed25519.verify_batch_warm`). Read through the module at call
    time: the canary tests shrink the tile."""
    from ..ops import pallas_verify
    return pallas_verify.TILE


def kernel_width() -> int:
    """Where this process's ed25519 lanes verify, and how wide: the
    kernel bucket on a device platform, 0 = natively everywhere else.
    A process without a device never jits a verify kernel on a route
    the program chooses (XLA:CPU takes minutes a bucket and crashes at
    256 lanes and more, docs/PERF.md); verdicts cannot differ, because
    `Ed25519PubKey.verify_signature` is ZIP-215 too. Every in-process
    caller asks here: this module's batch verifier, blocksync through
    `Node._device_batch_size`, the farm's and ingest's fallback."""
    from ..libs.jax_cache import is_device_platform
    return kernel_bucket() if is_device_platform() else 0


def verify_native(pubs: Sequence[bytes], msgs: Sequence[bytes],
                  sigs: Sequence[bytes]) -> np.ndarray:
    """Per-lane verdicts by the native single-signature verify (~50µs a
    lane, never a jit): the width-0 route, and what a drained, cold or
    canary-failed device batch falls back to. The one such loop over
    byte triples in the tree; a key of the wrong length is a reject,
    not an exception."""
    return np.array([
        len(p) == 32 and Ed25519PubKey(p).verify_signature(m, s)
        for p, m, s in zip(pubs, msgs, sigs)], dtype=bool)


class Ed25519BatchVerifier:
    """Accumulate-and-flush batch verifier backed by the TPU kernel
    (replaces curve25519-voi's CPU batch, reference
    crypto/ed25519/ed25519.go:208-241).

    Unlike the reference — whose batch returns one bool plus a per-sig
    attribution vector only on failure — the lane-parallel kernel always
    produces per-signature verdicts, so `verify()` is exact attribution
    with no fallback re-verification pass (types/validation.go:306-315).
    """

    def __init__(self):
        self._pubs: List[bytes] = []
        self._msgs: List[bytes] = []
        self._sigs: List[bytes] = []

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
        width = kernel_width()
        if width == 0:
            out = verify_native(self._pubs, self._msgs, self._sigs)
        else:
            from ..ops.ed25519 import verify_batch_warm
            out = verify_batch_warm(self._pubs, self._msgs, self._sigs,
                                    width)
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
