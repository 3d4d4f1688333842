"""Batched ed25519 verification — the TPU data plane for the north-star
hot path (reference: verifyCommitBatch types/validation.go:218-322 →
crypto/ed25519/ed25519.go:208-241 → curve25519-voi batch verify).

Per-signature-parallel formulation: every lane independently evaluates the
cofactored ZIP-215 equation

    [8]([s]B - R - [k]A) == identity,   k = SHA512(R || A || M) mod L

with shared doublings between the two scalar mults (Straus). This keeps a
per-signature validity verdict — so a failing batch needs NO re-verification
pass for attribution (the reference must fall back to per-sig verify on
batch failure, types/validation.go:306-315; here attribution is free).

Static-shape contract (XLA compiles one kernel per (batch, max_blocks)
bucket): callers pad batches to fixed sizes via `prepare_batch`; padded
lanes carry a canonical valid dummy signature so the mask is the only
difference.
"""

from __future__ import annotations

import functools
import threading
from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import edwards as ed
from .scalar import (bytes_to_limbs, sc_dot_mod_l, sc_lt_l, sc_mul,
                     sc_nibbles, sc_reduce_wide)
from .sha512 import sha512_blocks, pad_messages
from ..crypto import ref_ed25519 as ref
from ..trace import shared_tracer


def verify_core(pub: jnp.ndarray, sig: jnp.ndarray,
                hblocks: jnp.ndarray, hnblocks: jnp.ndarray,
                zip215: bool = True) -> jnp.ndarray:
    # staticcheck: assume(pub, 0, 255, shape=(N, 32), dtype=uint8)
    # staticcheck: assume(sig, 0, 255, shape=(N, 64), dtype=uint8)
    # staticcheck: assume(hblocks, 0, 255, shape=(N, B, 128), dtype=uint8)
    # staticcheck: assume(hnblocks, 1, 32767, shape=(N,), dtype=int32)
    # staticcheck: assume(B, 1, 4096)
    """Core batched verify (trace-through form — used directly inside
    shard_map by parallel.verify; jitted entry below).

    pub:      (N, 32) uint8 public keys
    sig:      (N, 64) uint8 signatures (R || s)
    hblocks:  (N, B, 128) uint8 SHA-512-padded R||A||M blocks
    hnblocks: (N,) int32 live block counts
    returns:  (N,) bool validity

    Host-facing arrays are batch-leading; the kernel transposes once at
    the boundary to the device-native byte/limb-leading layout (batch on
    the minor/lane axis — see field.py's layout rationale).
    """
    sig_b = jnp.moveaxis(sig, -1, 0)                   # (64, N)
    r_enc, s_enc = sig_b[:32], sig_b[32:]
    s = bytes_to_limbs(s_enc.astype(jnp.int32))
    s_ok = sc_lt_l(s)

    a_pt, a_ok = ed.pt_decompress(jnp.moveaxis(pub, -1, 0), zip215=zip215)
    r_pt, r_ok = ed.pt_decompress(r_enc, zip215=zip215)

    digest = jnp.moveaxis(sha512_blocks(hblocks, hnblocks), -1, 0)
    k = sc_reduce_wide(bytes_to_limbs(digest.astype(jnp.int32)))

    # [s]B + [k](-A), then subtract R, then clear the cofactor
    neg_a_tab = ed.window_table(ed.pt_neg(a_pt))
    acc = ed.straus_double_mul(s, k, neg_a_tab)
    acc = ed.pt_add(acc, ed.pt_neg(r_pt))
    acc = ed.pt_double(ed.pt_double(ed.pt_double(acc)))
    return s_ok & a_ok & r_ok & ed.pt_is_identity(acc)


verify_kernel = jax.jit(verify_core, static_argnames=("zip215",))


ZWIN = 32  # radix-16 windows covering the 128-bit random coefficients


def verify_rlc_core(pub: jnp.ndarray, sig: jnp.ndarray,
                    hblocks: jnp.ndarray, hnblocks: jnp.ndarray,
                    z: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # staticcheck: assume(pub, 0, 255, shape=(N, 32), dtype=uint8)
    # staticcheck: assume(sig, 0, 255, shape=(N, 64), dtype=uint8)
    # staticcheck: assume(hblocks, 0, 255, shape=(N, B, 128), dtype=uint8)
    # staticcheck: assume(hnblocks, 1, 32767, shape=(N,), dtype=int32)
    # staticcheck: assume(B, 1, 4096)
    # staticcheck: assume(z, 0, 65535, shape=(N, 8), dtype=int32)
    """Random-linear-combination batch verify — ONE combined equation for
    the whole tile (the batch equation curve25519-voi evaluates with a
    Pippenger MSM, reference crypto/ed25519/ed25519.go:239-241 →
    types/validation.go:218):

        [8]( [Σ z_i·s_i]B − Σ z_i·R_i − Σ (z_i·k_i)·A_i ) == identity

    with z_i 128-bit random coefficients (soundness 2^-128, matching
    voi's batch semantics — cofactored, ZIP-215 compatible).

    pub/sig/hblocks/hnblocks as in `verify_core` (batch-leading at the
    host boundary); z (N, 8) int32 limbs.
    Returns (batch_ok scalar bool, struct_ok (N,) bool). Structurally
    invalid lanes (bad point/scalar encodings) have their z zeroed — they
    drop out of all three sums — and report False in struct_ok. If
    batch_ok is True, every struct_ok lane holds a valid signature; if
    False, at least one lane is bad and the caller attributes via the
    per-lane `verify_core` fallback (the reference must do the same
    fallback pass, types/validation.go:306-315).

    Cost shape: per lane ~2 decompressions + 2×15 table adds + one add
    per window into each window's lane-tree (ZWIN + 64 windows), vs ~252
    doublings + 128 adds for per-lane Straus — and every stage is a wide
    vectorized op over the batch.
    """
    w, s_sum, struct_ok = rlc_local_stage(pub, sig, hblocks, hnblocks, z)
    return rlc_finish_stage(w, s_sum), struct_ok


def rlc_local_stage(pub: jnp.ndarray, sig: jnp.ndarray,
                    hblocks: jnp.ndarray, hnblocks: jnp.ndarray,
                    z: jnp.ndarray
                    ) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray,
                               jnp.ndarray]:
    """The lane-local portion of the RLC equation: everything up to ONE
    point per radix-16 window of the local lanes' −R/−A content, plus
    the local partial of Σ z_i·s_i mod L.

    This is the shard-local body of the multi-chip path
    (parallel/verify.verify_rlc_sharded): window sums and scalar
    partials are the only cross-device state — 64 points + one scalar
    per device (~25KB), all_gathered over ICI and tree-combined, then
    finished once by `rlc_finish_stage`. Single-device verify_rlc_core
    is exactly finish(local(...)).

    Returns (w: 64-window Point coords (16, 64) each, s_partial (16,),
    struct_ok (N,))."""
    sig_b = jnp.moveaxis(sig, -1, 0)                   # (64, N)
    r_enc, s_enc = sig_b[:32], sig_b[32:]
    s = bytes_to_limbs(s_enc.astype(jnp.int32))        # (16, N)
    s_ok = sc_lt_l(s)

    a_pt, a_ok = ed.pt_decompress(jnp.moveaxis(pub, -1, 0), zip215=True)
    r_pt, r_ok = ed.pt_decompress(r_enc, zip215=True)

    digest = jnp.moveaxis(sha512_blocks(hblocks, hnblocks), -1, 0)
    k = sc_reduce_wide(bytes_to_limbs(digest.astype(jnp.int32)))  # (16, N)

    struct_ok = s_ok & a_ok & r_ok                     # (N,)
    zl = jnp.moveaxis(z, -1, 0)                        # (8, N) limb-leading
    zl = zl * struct_ok[None].astype(zl.dtype)         # drop bad lanes

    # scalar side: S = Σ z_i s_i mod L; per-lane t_i = z_i k_i mod L
    s_sum = sc_dot_mod_l(zl, s)                         # (16,)
    z16 = jnp.concatenate([zl, jnp.zeros_like(zl)], axis=0)  # (16, N)
    t = sc_mul(z16, k)                                  # (16, N)

    # point side: per-window lane-trees over −R (z digits) and −A (t digits)
    tab_r = ed.window_table(ed.pt_neg(r_pt))
    tab_a = ed.window_table(ed.pt_neg(a_pt))
    sel_r = ed.lookup_windows(tab_r, sc_nibbles(z16)[:ZWIN])
    sel_a = ed.lookup_windows(tab_a, sc_nibbles(t))     # (L, 64, N)
    w_r = ed.pt_tree_sum(sel_r)                         # (L, ZWIN)
    w_a = ed.pt_tree_sum(sel_a)                         # (L, 64)
    lo = ed.pt_add(tuple(c[:, :ZWIN] for c in w_a), w_r)
    w = tuple(jnp.concatenate([cl, ca[:, ZWIN:]], axis=1)
              for cl, ca in zip(lo, w_a))
    return w, s_sum, struct_ok


def rlc_finish_stage(w: Tuple[jnp.ndarray, ...],
                     s_sum: jnp.ndarray) -> jnp.ndarray:
    """Fold [S]B into the (globally combined) windows via the shared
    base table, Horner the windows, clear the cofactor, test identity.
    Runs once per batch — replicated per device on the mesh path (the
    work is 64 single-point ops, nothing to shard)."""
    b_tab = jnp.asarray(ed.small_base_table())
    w = ed.pt_add(w, ed._lookup_shared(b_tab, sc_nibbles(s_sum)))
    acc = ed.horner_windows(w)
    acc = ed.pt_double(ed.pt_double(ed.pt_double(acc)))  # clear cofactor
    return ed.pt_is_identity(acc)


verify_rlc_kernel = jax.jit(verify_rlc_core)


def verify_rlc_core_pallas(pub: jnp.ndarray, sig: jnp.ndarray,
                           hblocks: jnp.ndarray, hnblocks: jnp.ndarray,
                           z: jnp.ndarray,
                           interpret: bool = False
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # staticcheck: assume(pub, 0, 255, shape=(N, 32), dtype=uint8)
    # staticcheck: assume(sig, 0, 255, shape=(N, 64), dtype=uint8)
    # staticcheck: assume(hblocks, 0, 255, shape=(N, B, 128), dtype=uint8)
    # staticcheck: assume(hnblocks, 1, 32767, shape=(N,), dtype=int32)
    # staticcheck: assume(B, 1, 4096)
    # staticcheck: assume(z, 0, 65535, shape=(N, 8), dtype=int32)
    """`verify_rlc_core` with the dominant point stage (window tables +
    digit selects + lane trees) in a fused Pallas kernel
    (ops/pallas_verify.rlc_window_sums) that keeps every point
    intermediate in VMEM. Same equation, same verdict semantics; the
    XLA share is reduced to decompression, scalar work, a (96, G*TAIL)
    fold, the shared-base [S]B windows, and the Horner.

    Motivation: on the chip the XLA-composed point ops run 40-150x
    below their fe_mul content (docs/PERF.md) — past a few hundred
    HLOs the fuser stops fusing and intermediates round-trip HBM.
    """
    from .field import fe_neg
    from .pallas_verify import (TAIL, pt_decompress_tiled,
                                rlc_window_sums)

    def neg_packed(p):
        return jnp.stack([fe_neg(p[0]), p[1], p[2], fe_neg(p[3])])

    sig_b = jnp.moveaxis(sig, -1, 0)                   # (64, N)
    r_enc, s_enc = sig_b[:32], sig_b[32:]
    s = bytes_to_limbs(s_enc.astype(jnp.int32))        # (16, N)
    s_ok = sc_lt_l(s)

    # tiled pallas decompression (2x 12.4ms per verify via XLA on the
    # chip — the next bottleneck after the window stage)
    a_pt, a_ok = pt_decompress_tiled(jnp.moveaxis(pub, -1, 0),
                                     interpret=interpret)
    r_pt, r_ok = pt_decompress_tiled(r_enc, interpret=interpret)

    digest = jnp.moveaxis(sha512_blocks(hblocks, hnblocks), -1, 0)
    k = sc_reduce_wide(bytes_to_limbs(digest.astype(jnp.int32)))

    struct_ok = s_ok & a_ok & r_ok                     # (N,)
    zl = jnp.moveaxis(z, -1, 0)                        # (8, N)
    zl = zl * struct_ok[None].astype(zl.dtype)

    s_sum = sc_dot_mod_l(zl, s)                        # (16,)
    z16 = jnp.concatenate([zl, jnp.zeros_like(zl)], axis=0)
    t = sc_mul(z16, k)                                 # (16, N)

    # fused point stage: per-(tile, window) partial sums of -A and -R
    out = rlc_window_sums(
        neg_packed(a_pt), neg_packed(r_pt),
        sc_nibbles(t), sc_nibbles(z16)[:ZWIN], interpret=interpret)
    g = out.shape[0]
    # (G, 96, 4, 16, TAIL) -> coords (4, 16, 96, G*TAIL); the epilogue
    # kernel folds lanes, combines the R windows, adds the shared-base
    # [S]B windows, Horners, clears the cofactor, and tests identity —
    # all point math stays in VMEM (tiny-shape pt ops are latency-bound
    # in XLA on the chip)
    from .pallas_verify import rlc_epilogue
    folded = jnp.transpose(out, (2, 3, 1, 0, 4)).reshape(
        4, 16, out.shape[1], g * TAIL)
    batch_ok = rlc_epilogue(
        folded, jnp.asarray(ed.small_base_table()),
        sc_nibbles(s_sum), interpret=interpret)
    return batch_ok, struct_ok


verify_rlc_kernel_pallas = jax.jit(verify_rlc_core_pallas,
                                   static_argnames=("interpret",))


def use_pallas_rlc() -> bool:
    """Pallas point-stage on a TPU backend; XLA path on CPU (the
    mosaic kernels target the chip; interpret mode is for tests)."""
    from ..libs.jax_cache import is_device_platform
    return is_device_platform()


def make_rlc_coefficients(n: int, rng=None) -> np.ndarray:
    """(n, 8) int32 16-bit limbs of 128-bit random coefficients.

    Defaults to OS entropy; an adversary who can predict z_i can craft a
    bad batch that passes the combined check."""
    if rng is None:
        import secrets
        raw = np.frombuffer(secrets.token_bytes(16 * n), dtype=np.uint8)
    else:
        raw = rng.integers(0, 256, size=16 * n, dtype=np.uint8)
    b = raw.reshape(n, 16).astype(np.int32)
    return b[:, 0::2] | (b[:, 1::2] << 8)


# A known-good (pub, sig, msg) used to pad partial batches: generated once
# from the oracle so padded lanes exercise the same code path.
@functools.lru_cache(maxsize=None)
def _dummy() -> Tuple[bytes, bytes, bytes]:
    seed = b"\x42" * 32
    msg = b"cometbft-tpu pad lane"
    return ref.pubkey_from_seed(seed), ref.sign(seed, msg), msg


@functools.lru_cache(maxsize=8)
def _padding_rows(batch_size: int, max_blocks: int):
    """What every padding lane of one (batch_size, max_blocks) bucket
    carries, built once a process: the dummy's key, its signature, its
    padded hash blocks and their count, each broadcast (read-only, no
    memory of its own) to `batch_size` rows."""
    dpub, dsig, dmsg = _dummy()
    hblocks, hnblocks = pad_messages([dsig[:32] + dpub + dmsg], max_blocks)
    return tuple(
        np.broadcast_to(row, (batch_size,) + row.shape)
        for row in (np.frombuffer(dpub, dtype=np.uint8),
                    np.frombuffer(dsig, dtype=np.uint8),
                    hblocks[0], hnblocks[0]))


def prepare_batch(pubs: Sequence[bytes], msgs: Sequence[bytes],
                  sigs: Sequence[bytes], batch_size: int,
                  max_msg_len: int = 256
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
                             np.ndarray]:
    """Host-side marshalling: pad to `batch_size` lanes and build the
    SHA-512 input blocks for k = H(R || A || M).

    Oversized or malformed inputs are mapped to the dummy lane and masked
    invalid host-side (they cannot be valid signatures; the reference
    rejects malformed keys/sigs before batching, types/validation.go).
    Returns (pub[N,32], sig[N,64], hblocks[N,B,128], hnblocks[N], ok[N])
    where ok marks real lanes that were well-formed; malformed lanes run
    the dummy on-device but report False.
    """
    n = len(pubs)
    if not (n == len(msgs) == len(sigs)):
        raise ValueError("pubs/msgs/sigs length mismatch")
    if n > batch_size:
        raise ValueError(f"{n} signatures exceed batch_size {batch_size}")
    max_blocks = (64 + max_msg_len + 17 + 127) // 128

    ok = np.zeros((batch_size,), dtype=bool)
    ok[:n] = True
    if not (set(map(len, pubs)) <= {32} and set(map(len, sigs)) <= {64}
            and max(map(len, msgs), default=0) <= max_msg_len):
        dpub, dsig, dmsg = _dummy()
        pubs, msgs, sigs = list(pubs), list(msgs), list(sigs)
        for i in range(n):
            if (len(pubs[i]) != 32 or len(sigs[i]) != 64
                    or len(msgs[i]) > max_msg_len):
                ok[i] = False
                pubs[i], msgs[i], sigs[i] = dpub, dmsg, dsig
    pub_r = np.frombuffer(b"".join(pubs), dtype=np.uint8).reshape(n, 32)
    sig_r = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
    hb_r, hn_r = pad_messages(
        msgs, max_blocks,
        prefix=np.concatenate([sig_r[:, :32], pub_r], axis=1))
    # concatenate copies: what jax is handed is never the shared rows
    pub_a, sig_a, hblocks, hnblocks = (
        np.concatenate([real, rows[n:]]) for real, rows in zip(
            (pub_r, sig_r, hb_r, hn_r),
            _padding_rows(batch_size, max_blocks)))
    return pub_a, sig_a, hblocks, hnblocks, ok


def verify_batch(pubs: Sequence[bytes], msgs: Sequence[bytes],
                 sigs: Sequence[bytes], batch_size: int | None = None,
                 zip215: bool = True, rlc: bool = True) -> np.ndarray:
    """Convenience host API: returns (len(pubs),) bool array.

    batch_size defaults to the next power of two (one compiled kernel per
    bucket; production callers pick fixed tile sizes — see crypto.batch).
    Inputs larger than batch_size are verified in batch_size-sized chunks.

    The default path evaluates ONE random-linear-combination equation per
    chunk (`verify_rlc_core`); a failing chunk falls back to the per-lane
    Straus kernel for attribution — so the honest-traffic fast path does
    ~4x less group arithmetic and adversarial batches degrade to exactly
    the round-1 behavior, never worse (the reference's fallback shape,
    types/validation.go:306-315). Strict RFC-8032 mode (zip215=False) is
    per-lane only.
    """
    dispatch = _rlc_dispatch if (rlc and zip215) else None
    fallback = functools.partial(verify_kernel, zip215=zip215)
    return _verify_batch_loop(pubs, msgs, sigs, batch_size,
                              dispatch, fallback)


# chunks of one call that may be on the device with their verdicts not
# yet read: a flush of tens of thousands of lanes cannot pile its
# buffers there (a tile holds 7)
_MAX_UNREAD_CHUNKS = 16


def hash_blocks_needed(msg_len: int) -> int:
    """SHA-512 blocks of H(R || A || M) for a message of `msg_len`
    bytes: 64 bytes of R and A, the message, 0x80 and a 16-byte length."""
    return (64 + msg_len + 17 + 127) // 128


def hash_block_bucket(msg_len: int) -> int:
    """The block axis of a chunk whose longest message has `msg_len`
    bytes: 2 up to 175 bytes (every vote's and commit's sign-bytes, the
    shape every node warms), above that the blocks the message needs
    rounded up to four significant bits, so that a lane computes at most
    an eighth more blocks than it needs and a few lengths share one
    compiled program (a 2 KiB vote extension's ~2,090 sign-bytes: 17
    blocks needed, 18 computed)."""
    need = hash_blocks_needed(msg_len)
    if need <= 2:
        return 2
    step = 1 << max(0, need.bit_length() - 4)
    return -(-need // step) * step


def msg_cap_of(n_blocks: int) -> int:
    """The longest message that `n_blocks` SHA-512 blocks hold."""
    return n_blocks * 128 - 64 - 17


def _plan_chunks(msgs, batch_size: int) -> list:
    """(first lane, end lane, hash blocks, the blocks its lanes' messages
    need) of each chunk of a call: cut in order into `batch_size` chunks,
    each at the block axis `hash_block_bucket` gives its longest message.
    A chunk that mixes lengths (a flush's vote and vote-extension lanes)
    runs every lane at the longest's axis: at a chunk's fixed lane count
    the kernel's time goes by the axis, so one mixed chunk costs what the
    long lanes alone would, where two chunks cut by length cost both."""
    plan = []
    for lo in range(0, len(msgs), batch_size):
        lens = list(map(len, msgs[lo:lo + batch_size]))
        shortest, longest = min(lens), max(lens)
        need = hash_blocks_needed(shortest)
        real = (need * len(lens) if need == hash_blocks_needed(longest)
                else sum(map(hash_blocks_needed, lens)))
        plan.append((lo, lo + len(lens), hash_block_bucket(longest), real))
    return plan


def _verify_batch_loop(pubs, msgs, sigs, batch_size, dispatch, fallback
                       ) -> np.ndarray:
    """The shared host-side chunking protocol behind every batch-verify
    entry point (single-device `verify_batch` here; the mesh-sharded
    `parallel.verify.verify_batch_mesh`): pad each chunk to the fixed
    `batch_size` bucket and its SHA-512 block axis to
    `hash_block_bucket` of its longest message (`_plan_chunks`), try ONE
    RLC equation per chunk via `dispatch(pub, sig, hb, hn, z)`, and
    attribute failed chunks (or serve strict mode, dispatch=None) via
    the per-lane `fallback(pub, sig, hb, hn)`.

    Dispatch all, read back once: the chunks of a call (at most
    `_MAX_UNREAD_CHUNKS` at a time) are prepared and dispatched one
    after the other and what `dispatch` returned is kept UNREAD, so the
    device runs chunk k while the host prepares chunk k+1 (JAX dispatch
    is asynchronous); only then are the verdicts read, in order, and a
    chunk whose equation failed sent to `fallback` with the arrays it
    was dispatched with. Their coefficients come from ONE draw of OS
    entropy, a row a lane, padding lanes included, none used twice. A
    call of one chunk is "dispatch, then read" as ever."""
    n = len(pubs)
    if n == 0:
        return np.zeros((0,), dtype=bool)
    if batch_size is None:
        batch_size = 1 << (n - 1).bit_length()
    tracer = shared_tracer()
    chunks = _plan_chunks(msgs, batch_size)
    outs = []
    for first in range(0, len(chunks), _MAX_UNREAD_CHUNKS):
        window = chunks[first:first + _MAX_UNREAD_CHUNKS]
        if dispatch is not None:
            z = make_rlc_coefficients(batch_size * len(window))
        unread = []
        for i, (lo, hi, blocks, real_blocks) in enumerate(window):
            # tiles flush on the dispatch thread, single commits on the
            # caller's: there the host's share of a chunk can be read
            with tracer.start("ed25519.prepare", lanes=hi - lo,
                              batch_size=batch_size):
                pub_a, sig_a, hb, hn, ok_mask = prepare_batch(
                    pubs[lo:hi], msgs[lo:hi], sigs[lo:hi], batch_size,
                    msg_cap_of(blocks))
            verdict = None
            if dispatch is not None:
                verdict = dispatch(
                    pub_a, sig_a, hb, hn,
                    z[i * batch_size:(i + 1) * batch_size])
            unread.append((hi - lo, (pub_a, sig_a, hb, hn), ok_mask,
                           verdict, real_blocks))
        # from the last chunk's dispatch to the last verdict read
        # (strict mode dispatched nothing: it has 0 chunks to read back)
        with tracer.start("ed25519.readback",
                          chunks=len(unread) if dispatch is not None else 0,
                          lanes=sum(u[0] for u in unread)) as rspan:
            attributed = 0
            for lanes, arrays, ok_mask, verdict, real_blocks in unread:
                out = None
                if verdict is not None:
                    batch_ok, struct_ok = verdict
                    if bool(batch_ok):
                        out = np.asarray(struct_ok)
                failed = verdict is not None and out is None
                attributed += failed
                # either thread may be here: the counters are shared
                with _batch_lock:
                    _batch["chunks"] += 1
                    _batch["lanes"] += lanes
                    _batch["hash_blocks_real"] += real_blocks
                    _batch["hash_blocks_dispatched"] += (
                        arrays[2].shape[0] * arrays[2].shape[1])
                    if failed:
                        _batch["attributed_chunks"] += 1
                        _batch["attributed_lanes"] += lanes
                if out is None:  # attribution fallback / strict mode
                    out = np.asarray(fallback(*arrays))
                outs.append(out[:lanes] & ok_mask[:lanes])
            rspan.set_attr("attributed_chunks", attributed)
    return np.concatenate(outs)


_pallas_broken = False

# Mosaic miscompile canary (reference posture: attribution safety,
# types/validation.go:306-315 — a batch verifier may NEVER accept what
# per-signature verification would reject). A pallas kernel that fails
# to lower, compile or run RAISES (see _rlc_dispatch); a kernel that
# silently MISCOMPILES and returns batch_ok=True on a batch containing
# an invalid signature would accept a forgery. So every CANARY_INTERVAL-th
# aligned dispatch (including the very first — node prewarm and
# device/server._warm both route here) first re-runs the pallas kernel
# on the same batch with one lane's s deliberately corrupted: the
# verdict MUST be False. If the kernel claims True, it is accepting a
# known-invalid signature — trip the sticky XLA fallback and count it.
_CANARY_INTERVAL = 16
_canary = {"runs": 0, "trips": 0}
_dispatches = 0
# aligned dispatches by (lanes, hash blocks): the canary's cadence is
# counted per shape, so every compiled program is checked from its first
# dispatch on, whichever shapes a node interleaves
_shape_dispatches: dict = {}
# bucket-wide chunks and real lanes through `_verify_batch_loop`, and of
# those the ones whose RLC equation failed and went to the per-lane
# fallback for attribution (strict mode, which has no RLC pass, counts
# under the first pair only); the SHA-512 blocks the real lanes' messages
# need, and those the kernel computed: every lane of every chunk, padding
# in, at its chunk's block axis (`hash_block_bucket`). Beside the loop,
# the lanes `verify_batch_warm` verified natively for want of a warm shape
_batch = {"chunks": 0, "lanes": 0,
          "attributed_chunks": 0, "attributed_lanes": 0,
          "hash_blocks_real": 0, "hash_blocks_dispatched": 0,
          "cold_shape_lanes": 0}
_batch_lock = threading.Lock()


def canary_stats() -> dict:
    """Snapshot of mosaic-canary counters ({"runs", "trips"}) — wired
    into the Prometheus registry as callback gauges (node/node.py)."""
    return dict(_canary)


def batch_stats() -> dict:
    """Snapshot of the batch loop's counters: {"chunks", "lanes"} for
    everything it verified, {"attributed_chunks", "attributed_lanes"}
    for the chunks a failed RLC equation sent to the per-lane kernel,
    {"hash_blocks_real", "hash_blocks_dispatched"}: Σ over the real lanes
    of the SHA-512 blocks each message needs, and Σ over the chunks of
    lanes × block axis (padding lanes compute their chunk's blocks too),
    {"cold_shape_lanes"}: lanes `verify_batch_warm` sent to the native
    check because no kernel of their shape was warm."""
    with _batch_lock:
        return dict(_batch)


def pallas_degraded() -> bool:
    """True once a canary trip has latched this process onto the XLA
    kernel for good — exported beside the canary counters."""
    return _pallas_broken


@functools.lru_cache(maxsize=8)
def _canary_batch(batch_size: int, n_blocks: int):
    """Constant canary inputs for one (batch, hash-blocks) bucket: every
    lane carries the known-good dummy signature — structurally valid BY
    CONSTRUCTION, so zeroing-out of struct-bad lanes can never mask the
    tamper — except the last lane, whose s has bit 0 flipped (dummy s is
    nowhere near L, so the lane stays canonical and the batch EQUATION
    must fail). Input data is fixed, so an adversary cannot steer the
    canary; shapes match the production bucket, so the very same
    compiled executable is exercised."""
    pub, sig, msg = _dummy()
    bad = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    assert int.from_bytes(bad[32:64], "little") < ref.L
    pubs = [pub] * batch_size
    msgs = [msg] * batch_size
    sigs = [sig] * (batch_size - 1) + [bad]
    pub_a, sig_a, hb, hn, _ = prepare_batch(pubs, msgs, sigs, batch_size,
                                            msg_cap_of(n_blocks))
    z = make_rlc_coefficients(batch_size)
    return pub_a, sig_a, hb, hn, z


def _run_canary(batch_size: int, n_blocks: int) -> None:
    """Execute the tampered-lane canary against the pallas kernel;
    trips `_pallas_broken` on a silent-accept miscompile. Costs one
    extra kernel execution (same shapes — same compiled executable) on
    canary rounds; never a per-lane fallback."""
    global _pallas_broken
    pub_a, sig_a, hb, hn, z = _canary_batch(batch_size, n_blocks)
    _canary["runs"] += 1
    batch_ok, _ = verify_rlc_kernel_pallas(pub_a, sig_a, hb, hn, z)
    if bool(batch_ok):
        _canary["trips"] += 1
        _pallas_broken = True
        import sys
        print("ed25519: PALLAS CANARY TRIPPED — mosaic kernel returned "
              "batch_ok=True on a batch with a known-invalid lane; "
              "degrading permanently to the XLA kernel", file=sys.stderr,
              flush=True)


def _rlc_dispatch(pub_a, sig_a, hb, hn, z):
    """RLC verify via the pallas point-stage on a TPU backend.

    The ONE degradation to the XLA kernel is the canary's: a kernel
    caught accepting a known-invalid lane is never trusted again
    (`_pallas_broken`, counted in canary_stats). A pallas kernel that
    fails to lower, compile or run is a bug in the tree, not a
    condition to route around — the exception propagates, so a device
    host can never end up measuring or serving the XLA kernel under
    the pallas path's name. Batches not aligned to the pallas lane
    tile take the XLA kernel by design (a small one-off verify)."""
    global _dispatches
    from .pallas_verify import TILE
    aligned = pub_a.shape[0] % TILE == 0
    if use_pallas_rlc() and aligned and not _pallas_broken:
        shape = (pub_a.shape[0], hb.shape[1])
        seen = _shape_dispatches.get(shape, 0)
        if seen % _CANARY_INTERVAL == 0:
            _run_canary(*shape)
        _shape_dispatches[shape] = seen + 1
        _dispatches += 1
        if not _pallas_broken:
            return verify_rlc_kernel_pallas(pub_a, sig_a, hb, hn, z)
    return verify_rlc_kernel(pub_a, sig_a, hb, hn, z)


# the message capacity of a vote's or a commit's sign-bytes (~107 bytes),
# and its SHA-512 block axis: the shape every node warms at boot
VOTE_MSG_CAP = 128
VOTE_BLOCKS = 2


def rlc_kernel_name(n_blocks: int) -> str:
    """The compile ledger's name of the RLC kernel at `n_blocks` SHA-512
    blocks (its bucket is the lane count): "ed25519-rlc" at the vote
    shape, as ever, the block axis beside it at any other."""
    if n_blocks == VOTE_BLOCKS:
        return "ed25519-rlc"
    return f"ed25519-rlc@{n_blocks}b"


def shape_warm(batch_size: int, n_blocks: int) -> bool:
    """Whether a chunk of `batch_size` lanes at `n_blocks` SHA-512
    blocks dispatches without a compile: the vote shape, which every
    device process warms before its first flush (`crypto.keys
    .kernel_bucket`), or a shape `prewarm_verify_kernels` warmed in this
    process (a chain's vote extensions, `Node._warm_shapes`)."""
    from ..libs.jax_cache import ledger
    return n_blocks == VOTE_BLOCKS or ledger().warm_in_process(
        rlc_kernel_name(n_blocks), batch_size)


def verify_batch_warm(pubs: Sequence[bytes], msgs: Sequence[bytes],
                      sigs: Sequence[bytes], batch_size: int) -> np.ndarray:
    """`verify_batch` for a live path, which never compiles: a lane
    whose SHA-512 bucket (`hash_block_bucket`) is not `shape_warm` is
    verified natively, the rest through the kernel. So a vote extension
    of a length the node did not warm (an external app's, or one longer
    than `[base] vote_extension_size`) costs a native check, not minutes
    of compile on the consensus receive path."""
    cold = {ln for ln in set(map(len, msgs))
            if not shape_warm(batch_size, hash_block_bucket(ln))}
    if not cold:
        return verify_batch(pubs, msgs, sigs, batch_size=batch_size)
    from ..crypto.keys import verify_native
    is_cold = np.fromiter((len(m) in cold for m in msgs), dtype=bool,
                          count=len(msgs))
    out = np.zeros((len(msgs),), dtype=bool)
    for mask, verify in ((is_cold, verify_native), (~is_cold, functools
                         .partial(verify_batch, batch_size=batch_size))):
        idx = np.flatnonzero(mask)
        if idx.size:
            out[idx] = verify([pubs[i] for i in idx], [msgs[i] for i in idx],
                              [sigs[i] for i in idx])
    with _batch_lock:
        _batch["cold_shape_lanes"] += int(is_cold.sum())
    return out


def prewarm_verify_kernels(batch_size: int = 4096,
                           msg_cap: int = VOTE_MSG_CAP) -> None:
    """Compile the RLC fast path AND the per-lane attribution fallback
    of the (batch, `hash_block_bucket(msg_cap)`) shape, the one
    `_verify_batch_loop` dispatches messages of up to `msg_cap` bytes
    at, before live traffic, so neither cold jit lands mid-blocksync
    (the device server does the same at start, device/server.py:_warm;
    this is the in-process caller's analog).

    The tampered lane corrupts a LOW byte of s: the signature stays
    structurally valid, the RLC batch EQUATION fails, and the fallback
    kernel genuinely compiles — corrupting R instead fails at
    decompression, which the structural mask attributes WITHOUT the
    fallback, leaving it cold until the first live failed batch."""
    from ..libs.jax_cache import ledger
    pub, sig, msg = _dummy()
    bad = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
    n_blocks = hash_block_bucket(msg_cap)
    msg_cap = msg_cap_of(n_blocks)
    pub_a, sig_a, hb, hn, _ = prepare_batch([pub], [msg], [sig],
                                            batch_size, msg_cap)
    z = make_rlc_coefficients(batch_size)
    # warm the kernel the live path will actually dispatch to (pallas
    # on a TPU backend, behind its miscompile canary). The
    # compile guard attributes the warm in the ledger AND marks the
    # shape process-warm, which `shape_warm` and mesh/executor's
    # single-shard view read as "no cold compile on a live flush".
    with ledger().compile_guard(rlc_kernel_name(n_blocks), batch_size):
        _rlc_dispatch(pub_a, sig_a, hb, hn, z)
        pub_a, sig_a, hb, hn, _ = prepare_batch([pub], [msg], [bad],
                                                batch_size, msg_cap)
        verify_kernel(pub_a, sig_a, hb, hn, zip215=True)
