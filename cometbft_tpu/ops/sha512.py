"""Batched SHA-512 as JAX ops (uint32-pair emulation of 64-bit words).

The verify hot path needs k = SHA512(R || A || M) mod L per signature
(reference: RFC 8032 §5.1.7 as implemented by curve25519-voi behind
crypto/ed25519/ed25519.go). Messages here are CometBFT vote sign-bytes
(~122 B) plus 64 B of R||A — short, so the whole digest runs on-device to
avoid a host round-trip per batch.

TPU has no native u64: every 64-bit word is an (hi, lo) uint32 pair; adds
propagate an explicit carry, rotations stitch the halves. Batched over
arbitrary leading dims; the block loop is a `lax.scan` with a per-message
block-count mask so one compiled kernel serves variable-length inputs up
to a static maximum.

Host-side `pad_messages` performs the MD-strengthening padding (the byte
shuffling is cheap; the 80-round compression is the part worth lanes).
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np
import jax.numpy as jnp
from jax import lax


def _icbrt(n: int) -> int:
    x = int(round(n ** (1 / 3)))
    while x**3 > n:
        x -= 1
    while (x + 1)**3 <= n:
        x += 1
    return x


def _primes(n: int):
    ps, c = [], 2
    while len(ps) < n:
        if all(c % p for p in ps if p * p <= c):
            ps.append(c)
        c += 1
    return ps


# round constants: frac(cbrt(p)) and init state frac(sqrt(p)), low 64 bits
_K64 = [_icbrt(p << 192) & ((1 << 64) - 1) for p in _primes(80)]
_H64 = [math.isqrt(p << 128) & ((1 << 64) - 1) for p in _primes(8)]

# host-side numpy, NOT jnp: a module-level jnp.asarray builds a device
# array at import, which INITIALIZES THE BACKEND — and so takes the
# chip for any process that merely imports cometbft_tpu.ops.ed25519
# (a launcher that leaves the chip to a child must be able to). They
# become trace-time constants inside jit regardless.
K_HI = np.array([k >> 32 for k in _K64], dtype=np.uint32)
K_LO = np.array([k & 0xFFFFFFFF for k in _K64], dtype=np.uint32)
H_HI = np.array([h >> 32 for h in _H64], dtype=np.uint32)
H_LO = np.array([h & 0xFFFFFFFF for h in _H64], dtype=np.uint32)

W64 = Tuple[jnp.ndarray, jnp.ndarray]  # (hi, lo) uint32 pair


def _add2(a: W64, b: W64) -> W64:
    lo = a[1] + b[1]
    carry = (lo < a[1]).astype(jnp.uint32)
    return a[0] + b[0] + carry, lo


def _add(*xs: W64) -> W64:
    acc = xs[0]
    for x in xs[1:]:
        acc = _add2(acc, x)
    return acc


def _rotr(x: W64, n: int) -> W64:
    hi, lo = x
    if n == 32:
        return lo, hi
    if n < 32:
        return ((hi >> n) | (lo << (32 - n)),
                (lo >> n) | (hi << (32 - n)))
    m = n - 32
    return ((lo >> m) | (hi << (32 - m)),
            (hi >> m) | (lo << (32 - m)))


def _shr(x: W64, n: int) -> W64:
    hi, lo = x
    if n < 32:
        return hi >> n, (lo >> n) | (hi << (32 - n))
    return jnp.zeros_like(hi), hi >> (n - 32)


def _xor3(a: W64, b: W64, c: W64) -> W64:
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def _big_sigma0(x):
    return _xor3(_rotr(x, 28), _rotr(x, 34), _rotr(x, 39))


def _big_sigma1(x):
    return _xor3(_rotr(x, 14), _rotr(x, 18), _rotr(x, 41))


def _small_sigma0(x):
    return _xor3(_rotr(x, 1), _rotr(x, 8), _shr(x, 7))


def _small_sigma1(x):
    return _xor3(_rotr(x, 19), _rotr(x, 61), _shr(x, 6))


def _ch(e: W64, f: W64, g: W64) -> W64:
    return ((e[0] & f[0]) ^ (~e[0] & g[0]),
            (e[1] & f[1]) ^ (~e[1] & g[1]))


def _maj(a: W64, b: W64, c: W64) -> W64:
    return ((a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
            (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]))


def _compress(state_hi, state_lo, w_hi, w_lo):
    """One SHA-512 compression: state (..., 8) pairs, block words (..., 16).

    80 rounds as a lax.scan carrying the (a..h) registers and a 16-word
    message-schedule ring buffer.
    """
    def round_fn(carry, xs):
        regs_hi, regs_lo, ring_hi, ring_lo = carry
        t, k_hi, k_lo = xs
        idx = t % 16
        # schedule: for t>=16, w = s1(w[t-2]) + w[t-7] + s0(w[t-15]) + w[t-16]
        def ring_at(off):
            j = (t + off) % 16
            return (jnp.take(ring_hi, j, axis=-1),
                    jnp.take(ring_lo, j, axis=-1))
        w_cur = ring_at(0)
        w_new = _add(_small_sigma1(ring_at(14)), ring_at(9),
                     _small_sigma0(ring_at(1)), w_cur)
        use_new = t >= 16
        w_hi_t = jnp.where(use_new, w_new[0], w_cur[0])
        w_lo_t = jnp.where(use_new, w_new[1], w_cur[1])
        ring_hi = ring_hi.at[..., idx].set(w_hi_t)
        ring_lo = ring_lo.at[..., idx].set(w_lo_t)

        a, b, c, d, e, f, g, h = [
            (regs_hi[..., i], regs_lo[..., i]) for i in range(8)]
        k = (jnp.broadcast_to(k_hi, a[0].shape),
             jnp.broadcast_to(k_lo, a[0].shape))
        t1 = _add(h, _big_sigma1(e), _ch(e, f, g), k, (w_hi_t, w_lo_t))
        t2 = _add2(_big_sigma0(a), _maj(a, b, c))
        new = [_add2(t1, t2), a, b, c, _add2(d, t1), e, f, g]
        regs_hi = jnp.stack([x[0] for x in new], axis=-1)
        regs_lo = jnp.stack([x[1] for x in new], axis=-1)
        return (regs_hi, regs_lo, ring_hi, ring_lo), None

    ts = jnp.arange(80, dtype=jnp.int32)
    (regs_hi, regs_lo, _, _), _ = lax.scan(
        round_fn, (state_hi, state_lo, w_hi, w_lo), (ts, K_HI, K_LO))
    lo = state_lo + regs_lo
    carry = (lo < state_lo).astype(jnp.uint32)
    hi = state_hi + regs_hi + carry
    return hi, lo


def _block_words(block: jnp.ndarray):
    """(..., 128) uint8 big-endian -> (..., 16) uint32 hi/lo pairs."""
    b = block.astype(jnp.uint32).reshape(*block.shape[:-1], 16, 8)
    hi = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    lo = (b[..., 4] << 24) | (b[..., 5] << 16) | (b[..., 6] << 8) | b[..., 7]
    return hi, lo


def sha512_blocks(blocks: jnp.ndarray, nblocks: jnp.ndarray) -> jnp.ndarray:
    """SHA-512 over pre-padded blocks.

    blocks:  (..., B, 128) uint8 — already MD-padded (see pad_messages)
    nblocks: (...,) int32 — how many of the B blocks are real per message
    returns: (..., 64) uint8 digest
    """
    batch = blocks.shape[:-2]
    nb = blocks.shape[-2]
    # derive the initial state from the input (+0) so its sharding/varying
    # axes match the loop output under shard_map's vma check
    zero = (blocks[..., 0, 0] * 0).astype(jnp.uint32)[..., None]
    st_hi = jnp.asarray(H_HI) + zero
    st_lo = jnp.asarray(H_LO) + zero

    def body(carry, xs):
        st_hi, st_lo = carry
        block, bidx = xs
        w_hi, w_lo = _block_words(block)
        nhi, nlo = _compress(st_hi, st_lo, w_hi, w_lo)
        live = (bidx < nblocks)[..., None]
        st_hi = jnp.where(live, nhi, st_hi)
        st_lo = jnp.where(live, nlo, st_lo)
        return (st_hi, st_lo), None

    # scan over the block axis: move it to the front
    blocks_t = jnp.moveaxis(blocks, -2, 0)
    (st_hi, st_lo), _ = lax.scan(
        body, (st_hi, st_lo),
        (blocks_t, jnp.arange(nb, dtype=jnp.int32)))

    def be_bytes(w):
        return jnp.stack([(w >> s) & 0xFF for s in (24, 16, 8, 0)],
                         axis=-1).astype(jnp.uint8)
    out = jnp.concatenate(
        [be_bytes(st_hi)[..., :, None, :], be_bytes(st_lo)[..., :, None, :]],
        axis=-2)
    return out.reshape(*batch, 64)


def pad_messages(msgs, max_blocks: int, prefix: np.ndarray | None = None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Host helper: list of bytes -> (N, max_blocks, 128) uint8 + (N,) int32.

    Standard SHA-512 padding: 0x80, zeros, 128-bit big-endian bit length.
    `prefix`, an (N, K) uint8 array, puts row i in front of message i.

    One `join` fills the whole array: what follows a message (the
    padding, and zeros up to `max_blocks`) depends on its length alone
    and is built once a distinct length, so a batch of one length and
    a batch of N lengths take the same path.
    """
    n = len(msgs)
    k = 0 if prefix is None else prefix.shape[1]
    width = max_blocks * 128
    lens = list(map(len, msgs))
    tails = {}
    for ln in dict.fromkeys(lens):
        total = k + ln
        nb = (total + 17 + 127) // 128
        if nb > max_blocks:
            raise ValueError(
                f"message {total}B needs {nb} blocks > {max_blocks}")
        tails[ln] = (b"\x80" + bytes(nb * 128 - total - 17)
                     + (8 * total).to_bytes(16, "big")
                     + bytes(width - nb * 128)), nb
    gap = bytes(k)
    out = np.frombuffer(
        bytearray().join([piece for m, ln in zip(msgs, lens)
                          for piece in (gap, m, tails[ln][0])]),
        dtype=np.uint8).reshape(n, width)
    if k:
        out[:, :k] = prefix
    nblocks = np.array([tails[ln][1] for ln in lens], dtype=np.int32)
    return out.reshape(n, max_blocks, 128), nblocks
