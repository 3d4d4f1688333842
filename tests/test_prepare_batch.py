"""`ops/ed25519.prepare_batch` and `ops/sha512.pad_messages` against the
per-lane versions they replaced (PR 30), array for array. numpy only:
no kernel is traced or compiled here.

The two `_reference_*` functions are the per-lane code as it stood
before PR 30, kept here as the plain reading of the contract: every
lane, padding lanes included, built by its own Python statements."""

import random

import numpy as np
import pytest

from cometbft_tpu.ops import ed25519 as ed
from cometbft_tpu.ops import sha512 as sh

BUCKET = 512


def _reference_pad_messages(msgs, max_blocks):
    n = len(msgs)
    out = np.zeros((n, max_blocks, 128), dtype=np.uint8)
    nblocks = np.zeros((n,), dtype=np.int32)
    for i, m in enumerate(msgs):
        ln = len(m)
        nb = (ln + 17 + 127) // 128
        if nb > max_blocks:
            raise ValueError(f"message {ln}B needs {nb} blocks > {max_blocks}")
        buf = bytearray(nb * 128)
        buf[:ln] = m
        buf[ln] = 0x80
        buf[-16:] = (8 * ln).to_bytes(16, "big")
        out[i, :nb] = np.frombuffer(bytes(buf),
                                    dtype=np.uint8).reshape(nb, 128)
        nblocks[i] = nb
    return out, nblocks


def _reference_prepare_batch(pubs, msgs, sigs, batch_size, max_msg_len=256):
    n = len(pubs)
    if not (n == len(msgs) == len(sigs)):
        raise ValueError("pubs/msgs/sigs length mismatch")
    if n > batch_size:
        raise ValueError(f"{n} signatures exceed batch_size {batch_size}")
    dpub, dsig, dmsg = ed._dummy()
    max_blocks = (64 + max_msg_len + 17 + 127) // 128
    pub_a = np.zeros((batch_size, 32), dtype=np.uint8)
    sig_a = np.zeros((batch_size, 64), dtype=np.uint8)
    live = np.zeros((batch_size,), dtype=bool)
    forced_bad = np.zeros((batch_size,), dtype=bool)
    hash_inputs = []
    for i in range(batch_size):
        if i < n:
            p, m, sg = pubs[i], msgs[i], sigs[i]
            live[i] = True
            if len(p) != 32 or len(sg) != 64 or len(m) > max_msg_len:
                forced_bad[i] = True
                p, m, sg = dpub, dmsg, dsig
        else:
            p, m, sg = dpub, dmsg, dsig
        pub_a[i] = np.frombuffer(p, dtype=np.uint8)
        sig_a[i] = np.frombuffer(sg, dtype=np.uint8)
        hash_inputs.append(sg[:32] + p + m)
    hblocks, hnblocks = _reference_pad_messages(hash_inputs, max_blocks)
    return pub_a, sig_a, hblocks, hnblocks, live & ~forced_bad


def _lanes(n, msg_len, seed=0):
    """n lanes of random bytes: lane i's message is `msg_len(i)` long."""
    rng = random.Random(seed)
    return ([rng.randbytes(32) for _ in range(n)],
            [rng.randbytes(msg_len(i)) for i in range(n)],
            [rng.randbytes(64) for _ in range(n)])


def _assert_same(got, want):
    assert len(got) == len(want) == 5
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


# message lengths on both sides of a SHA-512 block boundary: with the 64
# bytes of R || A in front, 47 | 48 bytes end the first block | open the
# second, 111 | 112 end the second | open the third
LENGTHS = {
    "one-length": lambda i: 107,
    "two-lengths": lambda i: 107 if i % 3 else 115,
    "every-lane-another-length": lambda i: i % 193,
    "block-boundaries": lambda i: (47, 48, 111, 112)[i % 4],
}


@pytest.mark.parametrize("n", [0, 1, 150, 511, 512])
@pytest.mark.parametrize("lengths", sorted(LENGTHS))
def test_arrays_equal_the_per_lane_version(n, lengths):
    pubs, msgs, sigs = _lanes(n, LENGTHS[lengths], seed=n)
    cap = 64
    while cap < max(map(len, msgs), default=0):
        cap *= 2
    _assert_same(ed.prepare_batch(pubs, msgs, sigs, BUCKET, cap),
                 _reference_prepare_batch(pubs, msgs, sigs, BUCKET, cap))


MALFORMED = {
    "short-key": lambda p, m, s: (p[:31], m, s),
    "long-key": lambda p, m, s: (p + b"\0", m, s),
    "empty-key": lambda p, m, s: (b"", m, s),
    "short-signature": lambda p, m, s: (p, m, s[:63]),
    "long-signature": lambda p, m, s: (p, m, s + b"\0"),
    "long-message": lambda p, m, s: (p, m + bytes(200), s),
}


@pytest.mark.parametrize("kind", sorted(MALFORMED) + ["together"])
def test_malformed_lanes_carry_the_dummy_and_report_false(kind):
    pubs, msgs, sigs = _lanes(150, LENGTHS["two-lengths"], seed=3)
    kinds = sorted(MALFORMED) if kind == "together" else [kind]
    bad = [7 + 11 * j for j in range(len(kinds))]
    for lane, k in zip(bad, kinds):
        pubs[lane], msgs[lane], sigs[lane] = MALFORMED[k](
            pubs[lane], msgs[lane], sigs[lane])
    got = ed.prepare_batch(pubs, msgs, sigs, BUCKET, 128)
    _assert_same(got, _reference_prepare_batch(pubs, msgs, sigs, BUCKET, 128))
    pub_a, sig_a, hb, hn, ok = got
    dpub, dsig, _dmsg = ed._dummy()
    for lane in bad:
        assert not ok[lane]
        assert bytes(pub_a[lane]) == dpub and bytes(sig_a[lane]) == dsig
        assert np.array_equal(hb[lane], hb[-1]) and hn[lane] == hn[-1]
    assert ok[:150].sum() == 150 - len(bad) and not ok[150:].any()
    # the caller's lists are left as they were
    assert len(pubs[bad[0]]) != 32 or len(sigs[bad[0]]) != 64 \
        or len(msgs[bad[0]]) > 128


def test_all_lanes_malformed():
    pubs, msgs, sigs = _lanes(5, LENGTHS["one-length"])
    pubs = [p[:5] for p in pubs]
    _assert_same(ed.prepare_batch(pubs, msgs, sigs, 8, 128),
                 _reference_prepare_batch(pubs, msgs, sigs, 8, 128))


def test_arrays_are_fresh_and_writable():
    pubs, msgs, sigs = _lanes(150, LENGTHS["one-length"])
    first = ed.prepare_batch(pubs, msgs, sigs, BUCKET, 128)
    want = [a.copy() for a in first]
    for a in first:
        assert a.flags.writeable
        a[...] = 1  # a caller that scribbles on its arrays ...
    second = ed.prepare_batch(pubs, msgs, sigs, BUCKET, 128)
    _assert_same(second, want)  # ... spoils nobody else's padding rows
    for a in first:
        for b in second:
            assert not np.shares_memory(a, b)
    for rows in ed._padding_rows(BUCKET, 2):
        assert not rows.flags.writeable
        for a in second:
            assert not np.shares_memory(a, rows)


def test_argument_errors_unchanged():
    pubs, msgs, sigs = _lanes(3, LENGTHS["one-length"])
    with pytest.raises(ValueError, match="length mismatch"):
        ed.prepare_batch(pubs, msgs[:2], sigs, 4, 128)
    with pytest.raises(ValueError, match="exceed batch_size"):
        ed.prepare_batch(pubs, msgs, sigs, 2, 128)


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
def test_pad_messages_equals_the_per_lane_version(lengths):
    _pubs, msgs, _sigs = _lanes(64, LENGTHS[lengths], seed=5)
    msgs += [b"", b"a" * 111, b"a" * 112, b"a" * 239, b"a" * 240]
    for got, want in zip(sh.pad_messages(msgs, 3),
                         _reference_pad_messages(msgs, 3)):
        assert got.dtype == want.dtype and np.array_equal(got, want)
    blocks, nblocks = sh.pad_messages(msgs, 3)
    assert blocks.flags.writeable and nblocks.flags.writeable


def test_pad_messages_prefix_rows_precede_the_messages():
    _pubs, msgs, _sigs = _lanes(9, LENGTHS["block-boundaries"], seed=6)
    prefix = np.arange(9 * 64, dtype=np.uint8).reshape(9, 64)
    joined = [bytes(prefix[i]) + m for i, m in enumerate(msgs)]
    for got, want in zip(sh.pad_messages(msgs, 2, prefix=prefix),
                         _reference_pad_messages(joined, 2)):
        assert np.array_equal(got, want)


def test_pad_messages_empty_and_oversized():
    blocks, nblocks = sh.pad_messages([], 2)
    assert blocks.shape == (0, 2, 128) and nblocks.shape == (0,)
    assert blocks.dtype == np.uint8 and nblocks.dtype == np.int32
    with pytest.raises(ValueError, match="needs 2 blocks > 1"):
        sh.pad_messages([b"ok", b"a" * 112], 1)
