"""Work of verifying ed25519 signatures, as a pure function of lanes and
SHA-512 blocks: the integer operations and the bytes of the REFERENCE
algorithm, whatever kernel does the work. Random-linear-combination
batching, windowing, another limb form or a fused kernel change the time,
never this count.

The reference algorithm, per signature (RFC 8032 §5.1.7, the cofactored
equation [8][s]B = [8]R + [8][h]A that ZIP-215 prescribes):

  h = SHA-512(R || A || M) mod L      `blocks` compressions + a reduction
  decompress A, decompress R          one field exponentiation each
  [s]B, [h]A                          plain 253-bit double-and-add
  [8]([s]B - [h]A - R) == identity    2 additions, 3 doublings, 1 check

in field multiplications M (a squaring counts as one):

  decompression       x = u v^3 (u v^7)^((p-5)/8): 251 squarings and 11
                      multiplications of the addition chain, 7 more for
                      u, v, v^3, v^7 and the check v x^2 = ±u   -> 269 M
  point doubling      extended coordinates, 4 squarings + 4 mult ->   8 M
  point addition      extended coordinates, 8 mult + 1 by 2d     ->   9 M
  [k]P, 253 bits      253 doublings + 253/2 additions (half the
                      bits of a uniform scalar are set)  -> 3,162.5 M
  cofactor and check  2 additions + 3 doublings + 2              -> 44 M
  h mod L             512 bits by 253: 32 x 16 limb products,
                      the size of two field multiplications      ->   2 M

  total  2 x 269 + 2 x 3,162.5 + 44 + 2 = 6,909 M per signature

One field multiplication of two 255-bit elements held as 16 limbs of 16
bits is 16 x 16 = 256 int32 multiply-adds, 512 operations as a published
peak counts them (a multiply and an add); the reduction mod p rides free.
One SHA-512 compression is 80 rounds of 26 64-bit operations and 64
message-schedule steps of 13, plus 8 final additions: 2,920 64-bit
operations, counted as 5,840 on 32-bit lanes (two halves each; the extra
shifts of a split rotation ride free).

Bytes a lane must move: 32 (A) + 64 (R, s) + 128 per hash block in, one
verdict byte out."""

from __future__ import annotations

FIELD_MULS_PER_SIGNATURE = 2 * 269 + 2 * 3162.5 + 44 + 2     # 6,909
OPS_PER_FIELD_MUL = 2 * 16 * 16                               # 512
OPS_PER_SHA512_BLOCK = 2 * (80 * 26 + 64 * 13 + 8)            # 5,840
BYTES_PER_LANE = 32 + 64 + 1
BYTES_PER_HASH_BLOCK = 128


def ops(lanes: int, hash_blocks: int) -> float:
    """Integer operations of the reference algorithm for `lanes`
    signatures whose hash inputs fill `hash_blocks` SHA-512 blocks."""
    return (lanes * FIELD_MULS_PER_SIGNATURE * OPS_PER_FIELD_MUL
            + hash_blocks * OPS_PER_SHA512_BLOCK)


def bytes_moved(lanes: int, hash_blocks: int) -> float:
    return lanes * BYTES_PER_LANE + hash_blocks * BYTES_PER_HASH_BLOCK


def least_seconds(lanes: int, hash_blocks: int, peaks: dict):
    """(seconds, which) — the least time this device could take: the
    larger of operations over its integer peak and bytes over its memory
    bandwidth, and which of the two bounds it."""
    by_ops = ops(lanes, hash_blocks) / peaks["int8_ops_per_s"]
    by_bytes = bytes_moved(lanes, hash_blocks) / peaks["hbm_bytes_per_s"]
    return (by_ops, "ops") if by_ops >= by_bytes else (by_bytes, "bytes")
