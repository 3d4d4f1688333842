"""Pallas TPU kernels for the RLC batch-verify point pipeline.

Why these exist: the XLA-composed point ops run 40-150x slower on the
chip than their fe_mul content (docs/PERF.md per-stage TPU profile —
fe_mul 1.8us at N=8192 vs pt_add 941us): past a few hundred HLOs the
fuser stops fusing and every field-op intermediate round-trips HBM. A
Pallas kernel holds a lane-tile of the whole pipeline in VMEM (~16MB
per core), so the only HBM traffic is the tile in and the window sums
out.

Layout contract matches ops/field.py: limb axis leading, batch (lanes)
minor. A point here is a single (4, 16, T) int32 array (coord, limb,
lane) rather than the 4-tuple, so one ref covers it.

Kernels:
- `pt_add_tiled`: standalone complete addition over lane tiles (the
  A/B de-risk kernel; same math as edwards.pt_add).
- `rlc_window_sums`: the fused hot stage of `verify_rlc_core` — per
  lane-tile, build the 16-entry window tables of -A and -R in VMEM,
  select per-window entries by scalar digits (compare-accumulate), and
  tree-reduce across the tile's lanes; emits per-tile per-window
  partial sums that a tiny XLA epilogue folds and Horners. Replaces
  the `window_table` + `lookup_windows` + `pt_tree_sum` sequence
  (215ms of the 192ms/8192-sig RLC iteration on the chip).

CPU tests run the same kernels with interpret=True (tests/test_pallas.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .field import MASK, LIMB_BITS, FOUR_P_LIMBS
from .scalar import bytes_to_limbs

# lanes per grid program. 512 int32 lanes x (2 tables of 16 entries x
# 4 coords x 16 limbs) = 4MB of table scratch, well under the ~16MB
# VMEM budget including pt_add temporaries. Env-tunable so a VMEM
# overflow on some chip generation degrades to a smaller tile instead
# of a dead kernel; malformed/nonpositive overrides fall back to the
# default (libs/env.py) instead of raising at import.
from ..libs.env import env_int
TILE = env_int("COMETBFT_TPU_PALLAS_TILE", 512, minimum=1)

A_WINDOWS = 64   # radix-16 digits of t_i = z_i * k_i (256-bit)
R_WINDOWS = 32   # radix-16 digits of the 128-bit z_i
N_WINDOWS = A_WINDOWS + R_WINDOWS
TAIL = 8         # lanes left unreduced per (tile, window) — folded by
#                  the epilogue kernel
LANES = 128      # vreg lane width: the narrowest row a kernel stores


# --- field/point helpers on (16, T) arrays, traced INSIDE kernels ---------
# These mirror ops/field.py (same bounds proofs) but avoid the per-row
# list/stack pattern: inside a Pallas kernel everything is VMEM-resident
# so op count, not materialization, is what matters.

def _carry(x: jnp.ndarray) -> jnp.ndarray:
    """fe_carry on (16, T): limbs [0, 2^27) -> strictly [0, 2^16).
    Same structure/proof as field.fe_carry (ripple, fold 38, ripple,
    2-limb mini-cascade)."""
    c = jnp.zeros_like(x[0])
    rows = []
    for i in range(16):
        v = x[i] + c
        rows.append(v & MASK)
        c = v >> LIMB_BITS
    rows[0] = rows[0] + 38 * c
    c = jnp.zeros_like(rows[0])
    for i in range(16):
        v = rows[i] + c
        rows[i] = v & MASK
        c = v >> LIMB_BITS
    t0 = rows[0] + 38 * c
    rows[0] = t0 & MASK
    rows[1] = rows[1] + (t0 >> LIMB_BITS)
    return jnp.stack(rows)


def _bcast(c: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """Right-pad a limb constant ((16,) or (16,1...)) with singleton
    batch dims to `like`'s rank — the limb axis is LEADING, so plain
    trailing-aligned numpy broadcasting would misalign it."""
    if c.ndim < like.ndim:
        return c.reshape(c.shape[0], *([1] * (like.ndim - 1)))
    return c


def _mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """fe_mul on (16, *batch) with the same exactness bounds as
    field.spread_mul (strict 16-bit limbs in, one uint32 outer product,
    lo/hi split, schoolbook shift-add, fold 2^256=38, carry). Operands
    of unequal rank are limb-axis-aligned first."""
    a, b = _bcast(a, b), _bcast(b, a)
    au = a.astype(jnp.uint32)
    bu = b.astype(jnp.uint32)
    p = au[:, None] * bu[None]                     # (16, 16, ...) exact
    lo = (p & MASK).astype(jnp.int32)
    hi = (p >> LIMB_BITS).astype(jnp.int32)
    # schoolbook as pad-shift-add (field.spread_mul's form): row i of
    # the outer product lands at limb offset i (lo) / i+1 (hi) of a
    # 32-limb accumulator — whole (16, ...) rows at a time, so a
    # multiply is ~100 equations to trace and lower, not ~1,750
    zeros = jnp.zeros_like(lo[0])                  # (16, ...)

    def shifted(row, off):
        wide = jnp.concatenate([row, zeros], axis=0)       # (32, ...)
        return pltpu.roll(wide, off, 0) if off else wide

    acc = shifted(lo[0], 0)
    for i in range(16):
        if i:
            acc = acc + shifted(lo[i], i)
        acc = acc + shifted(hi[i], i + 1)
    return _carry(acc[:16] + 38 * acc[16:])


# Pallas kernels may not close over constant arrays — the field
# constants ride in as a (6, 16) input:
# row 0 = 4p, 1 = 2d, 2 = p, 3 = d, 4 = sqrt(-1), 5 = 1.
N_CONSTS = 6


def _consts_array() -> jnp.ndarray:
    from .edwards import D_LIMBS, SQRT_M1_LIMBS, TWO_D_LIMBS
    from .field import P_LIMBS, limbs_from_int
    import numpy as np
    return jnp.asarray(np.stack([FOUR_P_LIMBS, TWO_D_LIMBS, P_LIMBS,
                                 D_LIMBS, SQRT_M1_LIMBS,
                                 limbs_from_int(1)]),
                       dtype=jnp.int32)


def _add(a, b):
    return _carry(a + b)


def _sub(a, b, four_p):
    return _carry(a + _bcast(four_p, a) - b)


def _pt_add(p: jnp.ndarray, q: jnp.ndarray, four_p, two_d) -> jnp.ndarray:
    """add-2008-hwcd-3 on (4, 16, *batch) packed points (same formula
    as edwards.pt_add). four_p/two_d: (16,)-leading constants, rank-
    normalized internally."""
    x1, y1, z1, t1 = p[0], p[1], p[2], p[3]
    x2, y2, z2, t2 = q[0], q[1], q[2], q[3]
    a = _mul(_sub(y1, x1, four_p), _sub(y2, x2, four_p))
    b = _mul(_add(y1, x1), _add(y2, x2))
    c = _mul(_mul(t1, two_d), t2)
    d = _carry(2 * _mul(z1, z2))
    e = _sub(b, a, four_p)
    f = _sub(d, c, four_p)
    g = _add(d, c)
    h = _add(b, a)
    return jnp.stack([_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h)])


def _pt_double(p: jnp.ndarray, four_p) -> jnp.ndarray:
    """dbl-2008-hwcd on a packed point (edwards.pt_double)."""
    x1, y1, z1 = p[0], p[1], p[2]
    a = _mul(x1, x1)
    b = _mul(y1, y1)
    c = _carry(2 * _mul(z1, z1))
    h = _add(a, b)
    xy = _add(x1, y1)
    e = _sub(h, _mul(xy, xy), four_p)
    g = _sub(a, b, four_p)
    f = _add(c, g)
    return jnp.stack([_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h)])


def _one_like(x: jnp.ndarray, one_limbs) -> jnp.ndarray:
    """The field element 1 broadcast to x's (16, *batch) shape. The
    constant limb row rides in with the consts block: a scatter
    (`.at[0].set(1)`) has no Mosaic lowering."""
    return jnp.zeros_like(x) + _bcast(one_limbs, x)


def _pt_identity(like: jnp.ndarray, one_limbs) -> jnp.ndarray:
    """Identity point shaped like the packed point `like` (4, 16, T)."""
    z = jnp.zeros_like(like[0])
    one = _one_like(z, one_limbs)
    return jnp.stack([z, one, one, z])


# --- decompress helpers (mirror field.py/edwards.py with consts
# passed in; same bounds proofs) -------------------------------------------

def _cond_sub_p(x: jnp.ndarray, p_limbs) -> jnp.ndarray:
    """Subtract p when x >= p (x fully carried); one borrow pass
    decides both (field._cond_sub_p)."""
    d = x - _bcast(p_limbs, x)
    c = jnp.zeros_like(d[0])
    rows = []
    for i in range(16):
        v = d[i] + c
        rows.append(v & MASK)
        c = v >> LIMB_BITS
    sub = jnp.stack(rows)
    return jnp.where((c == 0)[None], sub, x)


def _canonical(x: jnp.ndarray, p_limbs) -> jnp.ndarray:
    x = _carry(x)
    x = _cond_sub_p(x, p_limbs)
    return _cond_sub_p(x, p_limbs)


def _eq(a, b, four_p, p_limbs) -> jnp.ndarray:
    d = _canonical(_sub(a, b, four_p), p_limbs)
    return jnp.all(d == 0, axis=0)


def _neg(a, four_p):
    return _carry(_bcast(four_p, a) - a)


def _nsq(x, n):
    def step(_, c):
        return _mul(c, c)
    return jax.lax.fori_loop(0, n, step, x)


def _pow2523(z: jnp.ndarray) -> jnp.ndarray:
    """z^(2^252 - 3), the ref10 chain (field.fe_pow2523) with
    fori_loops for the long square runs."""
    t0 = _mul(z, z)
    t1 = _nsq(t0, 2)
    t1 = _mul(z, t1)
    t0 = _mul(t0, t1)
    t0 = _mul(t0, t0)
    t0 = _mul(t1, t0)
    t1 = _nsq(t0, 5)
    t0 = _mul(t1, t0)
    t1 = _nsq(t0, 10)
    t1 = _mul(t1, t0)
    t2 = _nsq(t1, 20)
    t1 = _mul(t2, t1)
    t1 = _nsq(t1, 10)
    t0 = _mul(t1, t0)
    t1 = _nsq(t0, 50)
    t1 = _mul(t1, t0)
    t2 = _nsq(t1, 100)
    t1 = _mul(t2, t1)
    t1 = _nsq(t1, 50)
    t0 = _mul(t1, t0)
    t0 = _nsq(t0, 2)
    return _mul(t0, z)


def _decompress(enc: jnp.ndarray, consts):
    """(16, T) int32 16-bit limbs of the 32-byte encoding (bit 255 =
    the x sign, still in limb 15) -> packed point (4, 16, T), valid
    (T,). ZIP-215 semantics, mirroring edwards.pt_decompress. The
    bytes are paired into limbs on the XLA side of the call: a strided
    value slice lowers to a gather Mosaic refuses."""
    four_p = consts[0]
    p_limbs = consts[2]
    d_limbs = consts[3]
    sqrt_m1 = consts[4]

    sign = (enc[15] >> 15) & 1
    y = jnp.stack([enc[i] for i in range(15)] + [enc[15] & 0x7FFF])

    yy = _mul(y, y)
    one = _one_like(y, consts[5])
    u = _sub(yy, one, four_p)
    v = _add(_mul(yy, d_limbs), one)
    v3 = _mul(_mul(v, v), v)
    v7 = _mul(_mul(v3, v3), v)
    x = _mul(_mul(u, v3), _pow2523(_mul(u, v7)))
    vxx = _mul(v, _mul(x, x))
    ok_direct = _eq(vxx, u, four_p, p_limbs)
    ok_twisted = _eq(vxx, _neg(u, four_p), four_p, p_limbs)
    x = jnp.where(ok_twisted[None], _mul(x, sqrt_m1), x)
    valid = ok_direct | ok_twisted
    parity = _canonical(x, p_limbs)[0] & 1
    x = jnp.where((parity != sign)[None], _neg(x, four_p), x)
    return jnp.stack([x, y, one, _mul(x, y)]), valid


# --- kernel 1: standalone tiled pt_add (A/B de-risk) ----------------------

def _pt_add_kernel(c_ref, p_ref, q_ref, o_ref):
    o_ref[:] = _pt_add(p_ref[:], q_ref[:], c_ref[0], c_ref[1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def pt_add_tiled(p: jnp.ndarray, q: jnp.ndarray,
                 interpret: bool = False) -> jnp.ndarray:
    # staticcheck: assume(p, 0, 65535, shape=(4, 16, N), dtype=int32)
    # staticcheck: assume(q, 0, 65535, shape=(4, 16, N), dtype=int32)
    """Complete addition of (4, 16, N) packed points, N % TILE == 0."""
    n = p.shape[-1]
    grid = (n // TILE,)
    spec = pl.BlockSpec((4, 16, TILE), lambda i: (0, 0, i),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _pt_add_kernel,
        out_shape=jax.ShapeDtypeStruct(p.shape, jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((N_CONSTS, 16), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
                  spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(_consts_array(), p, q)


# --- kernel 2: fused table-build + select + lane-tree ----------------------

def _tree_to_tail(pt: jnp.ndarray, four_p, two_d) -> jnp.ndarray:
    """(4, 16, T) -> (4, 16, min(T, LANES)) pairwise-halving point
    reduction; lanes [0, TAIL) of the result hold the TAIL partial
    sums, the lanes above them are scratch.

    Down to one vreg width the halves are lane-aligned slices. Below
    it a half is not a slice Mosaic can take for free, so the upper
    half is ROTATED onto the lower instead (lane i picks up lane i+h —
    the same operand pairing, so the sums are bit-identical to the
    slicing tree) and the store stays a full, lane-dense vreg row."""
    n = pt.shape[-1]
    while n > LANES:
        h = n // 2
        pt = _pt_add(pt[..., :h], pt[..., h:], four_p, two_d)
        n = h
    h = n // 2
    while h >= TAIL:
        pt = _pt_add(pt, pltpu.roll(pt, n - h, 2), four_p, two_d)
        h //= 2
    return pt


def _build_table(pt: jnp.ndarray, tab_ref, consts) -> None:
    """tab_ref (16, 4, 16, T) <- [j]pt for j in 0..15 (entry leading)."""
    four_p, two_d = consts[0], consts[1]
    tab_ref[0] = _pt_identity(pt, consts[5])
    tab_ref[1] = pt

    def step(j, acc):
        acc = _pt_add(acc, pt, four_p, two_d)
        tab_ref[j] = acc
        return acc

    jax.lax.fori_loop(2, 16, step, pt)


def _select(tab_ref, dig: jnp.ndarray) -> jnp.ndarray:
    """Compare-accumulate entry select: dig (T,) in 0..15 ->
    (4, 16, T)."""
    acc = jnp.zeros_like(tab_ref[0])
    for e in range(16):
        mask = (dig == e).astype(jnp.int32)[None, None, :]
        acc = acc + tab_ref[e] * mask
    return acc


def _rlc_kernel(c_ref, a_ref, r_ref, tdig_ref, zdig_ref, o_ref,
                tab_a, tab_r):
    four_p, two_d = c_ref[0], c_ref[1]
    _build_table(a_ref[:], tab_a, c_ref)
    _build_table(r_ref[:], tab_r, c_ref)

    def a_window(w, _):
        sel = _select(tab_a, tdig_ref[w])
        o_ref[0, w] = _tree_to_tail(sel, four_p, two_d)
        return 0

    def r_window(w, _):
        sel = _select(tab_r, zdig_ref[w])
        o_ref[0, A_WINDOWS + w] = _tree_to_tail(sel, four_p, two_d)
        return 0

    jax.lax.fori_loop(0, A_WINDOWS, a_window, 0)
    jax.lax.fori_loop(0, R_WINDOWS, r_window, 0)


def rlc_window_sums_impl(a_pt: jnp.ndarray, r_pt: jnp.ndarray,
                         t_dig: jnp.ndarray, z_dig: jnp.ndarray,
                         interpret: bool = False) -> jnp.ndarray:
    # staticcheck: assume(a_pt, 0, 65535, shape=(4, 16, N), dtype=int32)
    # staticcheck: assume(r_pt, 0, 65535, shape=(4, 16, N), dtype=int32)
    # staticcheck: assume(t_dig, 0, 15, shape=(64, N), dtype=int32)
    # staticcheck: assume(z_dig, 0, 15, shape=(32, N), dtype=int32)
    """Per-tile window partial sums for the RLC equation.

    a_pt, r_pt: (4, 16, N) packed -A / -R points (already negated,
    struct-masked z's folded into the digits by the caller).
    t_dig: (64, N) radix-16 digits of t_i = z_i*k_i.
    z_dig: (32, N) radix-16 digits of z_i.
    Returns (G, 96, 4, 16, TAIL) where G = N // TILE: windows 0..63
    are the -A windows, 64..95 the -R windows; the caller folds the
    (G, TAIL) axes (tiny XLA tree) and Horners the 64 combined
    windows exactly as verify_rlc_core does.
    """
    n = a_pt.shape[-1]
    assert n % TILE == 0, (n, TILE)
    g = n // TILE
    # the kernel stores a full lane-dense row per window; only its
    # first TAIL lanes are partial sums (see _tree_to_tail)
    row = min(TILE, LANES)
    pt_spec = pl.BlockSpec((4, 16, TILE), lambda i: (0, 0, i),
                           memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _rlc_kernel,
        out_shape=jax.ShapeDtypeStruct((g, N_WINDOWS, 4, 16, row),
                                       jnp.int32),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((N_CONSTS, 16), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pt_spec, pt_spec,
            pl.BlockSpec((A_WINDOWS, TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((R_WINDOWS, TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, N_WINDOWS, 4, 16, row),
                               lambda i: (i, 0, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((16, 4, 16, TILE), jnp.int32),
            pltpu.VMEM((16, 4, 16, TILE), jnp.int32),
        ],
        interpret=interpret,
    )(_consts_array(), a_pt, r_pt, t_dig, z_dig)
    return out[..., :TAIL]


rlc_window_sums = jax.jit(rlc_window_sums_impl,
                          static_argnames=("interpret",))


# --- kernel 3: tiled ZIP-215 point decompression ---------------------------

def _decompress_kernel(c_ref, enc_ref, pt_ref, ok_ref):
    pt, valid = _decompress(enc_ref[:], c_ref)
    pt_ref[:] = pt
    ok_ref[:] = valid[None].astype(jnp.int32)


def pt_decompress_tiled_impl(enc: jnp.ndarray,
                             interpret: bool = False):
    # staticcheck: assume(enc, 0, 255, shape=(32, N), dtype=int32)
    """ZIP-215 decompression of (32, N) byte-leading encodings on lane
    tiles (the pallas analog of edwards.pt_decompress — 2x 12.4ms per
    RLC verify on the chip via XLA, docs/PERF.md). Returns
    (packed (4,16,N) int32, valid (N,) bool)."""
    n = enc.shape[-1]
    assert n % TILE == 0, (n, TILE)
    limbs = bytes_to_limbs(enc)                        # (16, N) int32
    pt, ok = pl.pallas_call(
        _decompress_kernel,
        out_shape=(jax.ShapeDtypeStruct((4, 16, n), jnp.int32),
                   jax.ShapeDtypeStruct((1, n), jnp.int32)),
        grid=(n // TILE,),
        in_specs=[
            pl.BlockSpec((N_CONSTS, 16), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((16, TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(pl.BlockSpec((4, 16, TILE), lambda i: (0, 0, i),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, TILE), lambda i: (0, i),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
    )(_consts_array(), limbs)
    return pt, ok[0].astype(bool)


pt_decompress_tiled = jax.jit(pt_decompress_tiled_impl,
                              static_argnames=("interpret",))


# --- kernel 4: the RLC epilogue (fold + combine + [S]B + Horner) -----------
#
# After the window stage, everything left is point arithmetic on TINY
# shapes (96 windows x G*TAIL lanes, then a single accumulator point) —
# in XLA on the chip those ops are latency-bound at ~1-2ms each, which
# would cap the whole verify once the wide stages are fused. One
# single-program kernel keeps the entire tail in VMEM.
#
# Layout: the WINDOW index rides the lane axis, padded 96 -> LANES
# (lanes 0..63 the -A windows, 64..95 the -R windows, 96..127 identity
# points), and the M partials per window are M such rows on the
# leading axis. Every access is then a whole vreg-aligned row, and the
# steps that move data ACROSS windows (combine, Horner) are lane
# rotations of a row rather than sub-vreg slices or a dynamic slice of
# a value, neither of which Mosaic lowers. Lanes past the ones a step
# reads carry well-defined scratch (limbs stay < 2^16) that nothing
# consumes.

def _epilogue_kernel(c_ref, w_ref, sel_ref, ok_ref):
    four_p = c_ref[0]
    two_d = c_ref[1]
    p_limbs = c_ref[2]

    # fold the M partials of every window, (M, 4, 16, LANES) -> one
    # row; row by row off the ref, so the live set stays one row wide
    # however many tiles fed the fold (a halving tree over the whole
    # block overflows VMEM at 8192 lanes)
    def fold(j, acc):
        return _pt_add(acc, w_ref[j], four_p, two_d)

    w = jax.lax.fori_loop(1, w_ref.shape[0], fold, w_ref[0])

    # combine: windows 0..31 of -A pick up -R's 32 windows (lanes
    # 64..95 rotate onto 0..31; lanes 32..63 pick up the identity pad)
    w = _pt_add(w, pltpu.roll(w, LANES - A_WINDOWS, 2), four_p, two_d)

    # fold [S]B: sel holds the shared-base table entries the radix-16
    # digits of S select, one window per lane
    w = _pt_add(w, sel_ref[:], four_p, two_d)

    # radix-16 Horner over the 64 windows, most significant first, read
    # off lane 0: each step rotates the next lower window into lane 0
    def step(_, carry):
        acc, wr = carry
        wr = pltpu.roll(wr, 1, 2)
        acc = _pt_double(acc, four_p)
        acc = _pt_double(acc, four_p)
        acc = _pt_double(acc, four_p)
        acc = _pt_double(acc, four_p)
        return _pt_add(acc, wr, four_p, two_d), wr

    top = pltpu.roll(w, LANES - (A_WINDOWS - 1), 2)   # lane 0 <- w[63]
    acc, _ = jax.lax.fori_loop(0, A_WINDOWS - 1, step, (top, top))

    # clear the cofactor, then the projective identity test; the
    # verdict is lane 0 of a lane-dense row
    acc = _pt_double(_pt_double(_pt_double(acc, four_p), four_p), four_p)
    x_zero = jnp.all(_canonical(acc[0], p_limbs) == 0, axis=0)
    yz_eq = jnp.all(
        _canonical(_sub(acc[1], acc[2], four_p), p_limbs) == 0, axis=0)
    ok_ref[:] = (x_zero & yz_eq)[None].astype(jnp.int32)


def rlc_epilogue_impl(folded: jnp.ndarray, b_tab: jnp.ndarray,
                      s_dig: jnp.ndarray,
                      interpret: bool = False) -> jnp.ndarray:
    # staticcheck: assume(folded, 0, 65535, shape=(4, 16, 96, M), dtype=int32)
    # staticcheck: assume(b_tab, 0, 65535, shape=(16, 4, 16), dtype=int32)
    # staticcheck: assume(s_dig, 0, 15, shape=(64,), dtype=int32)
    """folded: (4, 16, 96, M) window partials (M = G*TAIL lanes);
    b_tab: (16, 4, 16) shared [j]B table; s_dig: (64,) radix-16 digits
    of S = sum(z_i s_i). Returns the scalar batch verdict (bool)."""
    from .edwards import _lookup_shared
    m = folded.shape[-1]
    # partials leading, windows onto the lane axis, identity-padded to
    # a full row
    ident = _consts_array()[5]
    zero = jnp.zeros_like(ident)
    pad = jnp.stack([zero, ident, ident, zero])            # (4, 16)
    pad = jnp.broadcast_to(pad[None, :, :, None],
                           (m, 4, 16, LANES - N_WINDOWS))
    w = jnp.concatenate(
        [jnp.transpose(folded, (3, 0, 1, 2)), pad], axis=-1)
    # the [S]B table entries, window per lane; a select by 64 digits
    # of one shared 16-entry table is a lookup, not point math
    sel = jnp.stack(_lookup_shared(b_tab.astype(jnp.int32), s_dig))
    sel = jnp.concatenate(
        [sel, jnp.zeros((4, 16, LANES - A_WINDOWS), jnp.int32)], axis=-1)
    ok = pl.pallas_call(
        _epilogue_kernel,
        out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        in_specs=[
            pl.BlockSpec((N_CONSTS, 16), memory_space=pltpu.VMEM),
            pl.BlockSpec((m, 4, 16, LANES), memory_space=pltpu.VMEM),
            pl.BlockSpec((4, 16, LANES), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, LANES), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(_consts_array(), w, sel)
    return ok[0, 0].astype(bool)


rlc_epilogue = jax.jit(rlc_epilogue_impl,
                       static_argnames=("interpret",))


def pack_point(p) -> jnp.ndarray:
    """edwards 4-tuple (each (16, N)) -> packed (4, 16, N)."""
    return jnp.stack(p)


def unpack_point(a: jnp.ndarray):
    return (a[0], a[1], a[2], a[3])
