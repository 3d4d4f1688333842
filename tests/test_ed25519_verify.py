"""End-to-end batched ed25519 verification tests.

Covers RFC 8032 §7.1 test vectors, malleability (s >= L), corruption
attribution inside a batch, and ZIP-215 permissive decoding semantics
(reference: crypto/ed25519/ed25519.go:40-42,181-188)."""

import functools

import numpy as np
import pytest
from _kernel_shape import KERNEL_LANES, KERNEL_MSG_CAP

from cometbft_tpu.crypto import ref_ed25519 as ref
from cometbft_tpu.ops import ed25519 as ops_ed25519

# every kernel call of this file at the suite's one compiled shape
# (tests/_kernel_shape.py); the size-less default (next power of two of the
# call) would compile a variant for each test
verify_batch = functools.partial(ops_ed25519.verify_batch,
                                 batch_size=KERNEL_LANES)

# RFC 8032 §7.1: (seed, pub, msg, sig) hex
RFC8032 = [
    ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
     "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
     "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
     "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
     "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
     "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
     "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
    ("c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
     "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
     "af82",
     "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
     "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"),
    ("833fe62409237b9d62ec77587520911e9a759cec1d19755b7da901b96dca3d42",
     "ec172b93ad5e563bf4932c70e1245034c35467ef2efd4d64ebf819683467e2bf",
     "ddaf35a193617abacc417349ae20413112e6fa4e89a97ea20a9eeee64b55d39a"
     "2192992a274fc1a836ba3c23a3feebbd454d4423643ce80e2a9ac94fa54ca49f",
     "dc2a4459e7369633a52b1bf277839a00201009a3efbf3ecb69bea2186c26b589"
     "09351fc9ac90b3ecfdfbc7c66431e0303dca179c138ac17ad9bef1177331a704"),
]


def test_rfc8032_vectors_oracle_and_kernel():
    pubs, msgs, sigs = [], [], []
    for seed_h, pub_h, msg_h, sig_h in RFC8032:
        seed, pub = bytes.fromhex(seed_h), bytes.fromhex(pub_h)
        msg, sig = bytes.fromhex(msg_h), bytes.fromhex(sig_h)
        assert ref.pubkey_from_seed(seed) == pub
        assert ref.sign(seed, msg) == sig
        assert ref.verify(pub, msg, sig)
        pubs.append(pub)
        msgs.append(msg)
        sigs.append(sig)
    got = verify_batch(pubs, msgs, sigs)
    assert got.all(), got


def test_batch_attribution_and_rejections():
    import random
    rng = random.Random(11)
    pubs, msgs, sigs, expect = [], [], [], []
    for i in range(12):  # more than one chunk of KERNEL_LANES
        seed = bytes([rng.randrange(256) for _ in range(32)])
        msg = bytes([rng.randrange(256) for _ in range(
            rng.randrange(1, KERNEL_MSG_CAP + 1))])
        pub, sig = ref.pubkey_from_seed(seed), ref.sign(seed, msg)
        kind = i % 4
        if kind == 1:    # corrupt signature R
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        elif kind == 2:  # corrupt message
            msg = msg + b"x"
        elif kind == 3:  # malleate: s += L (would pass without the s<L gate)
            s = int.from_bytes(sig[32:], "little") + ref.L
            if s < 2**256:
                sig = sig[:32] + s.to_bytes(32, "little")
            else:  # rare; corrupt instead
                sig = sig[:32] + bytes(32)
        pubs.append(pub)
        msgs.append(msg)
        sigs.append(sig)
        expect.append(ref.verify(pub, msg, sig))
        if kind != 0:
            assert not expect[-1]
        else:
            assert expect[-1]
    got = verify_batch(pubs, msgs, sigs)
    assert list(got) == expect


@pytest.mark.parametrize("pub_len,sig_len", [
    (32, 63), (32, 65), (31, 64), (0, 64)],
    ids=["short-sig", "long-sig", "short-key", "empty-key"])
def test_malformed_inputs(pub_len, sig_len):
    """A key or signature of the wrong length is rejected in its own
    lane, between two good ones."""
    seed = b"\x01" * 32
    msg = b"hello"
    pub, sig = ref.pubkey_from_seed(seed), ref.sign(seed, msg)
    got = verify_batch([pub, (pub * 2)[:pub_len], pub], [msg, msg, msg],
                       [sig, (sig * 2)[:sig_len], sig])
    assert list(got) == [True, False, True]


def test_zip215_small_order_and_noncanonical():
    # identity pubkey + identity R + s=0 verifies for any msg (cofactored)
    ident = (1).to_bytes(32, "little")
    sig = ident + bytes(32)
    msg = b"anything"
    assert ref.verify(ident, msg, sig)
    # non-canonical identity encoding y = p+1: zip215 accepts, strict rejects
    ident_nc = (ref.P + 1).to_bytes(32, "little")
    sig_nc = ident_nc + bytes(32)
    assert ref.verify(ident_nc, msg, sig_nc, zip215=True)
    assert not ref.verify(ident_nc, msg, sig_nc, zip215=False)

    got = verify_batch([ident, ident_nc], [msg, msg], [sig, sig_nc])
    assert list(got) == [True, True]


@pytest.mark.slow
def test_strict_mode_kernel_rejects_noncanonical():
    """zip215=False is a static argument of the per-lane kernel: a
    variant of its own, ~85 s of XLA:CPU compile that no other test
    reuses and no path of the program asks for (the strict decoding
    rule itself is tier-1 in test_edwards.py, the strict oracle in the
    test above)."""
    msg = b"anything"
    ident = (1).to_bytes(32, "little")
    ident_nc = (ref.P + 1).to_bytes(32, "little")
    got = verify_batch([ident, ident_nc], [msg, msg],
                       [ident + bytes(32), ident_nc + bytes(32)],
                       zip215=False)
    assert list(got) == [True, False]


def test_empty_batch():
    assert verify_batch([], [], []).shape == (0,)


@pytest.mark.parametrize("n", [
    KERNEL_LANES + 1, 2 * KERNEL_LANES, 2 * KERNEL_LANES + 1],
    ids=["full+padded", "full+full", "full+full+padded"])
def test_oversized_batch_chunks(n):
    """More signatures than batch_size must chunk, not crash."""
    seed = b"\x05" * 32
    pub = ref.pubkey_from_seed(seed)
    msgs = [bytes([i]) for i in range(n)]
    sigs = [ref.sign(seed, m) for m in msgs]
    bad = {3, KERNEL_LANES, n - 1}  # one in every chunk
    for i in bad:
        sigs[i] = bytes(64)
    got = verify_batch([pub] * n, msgs, sigs)
    assert list(got) == [i not in bad for i in range(n)]


# --- the one route: where ed25519 lanes verify, and how wide (crypto/keys) ----

def _one_bad_lane(n):
    """n lanes signed by one key, the middle one's s altered (still
    canonical: the structural mask cannot decide it); want[i] is the
    verdict, checked against the reference on every lane of a small
    batch and on the first, the bad and the last lane of a wide one."""
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    key = Ed25519PrivKey(b"\x07" * 32)
    pub = key.pub_key().raw
    msgs = [b"lane %d" % i for i in range(n)]
    sigs = [key.sign(m) for m in msgs]
    bad = n // 2
    if n:
        sigs[bad] = (sigs[bad][:40] + bytes([sigs[bad][40] ^ 1])
                     + sigs[bad][41:])
    want = [i != bad for i in range(n)]
    for i in (range(n) if n <= 150 else (0, bad, n - 1)):
        assert ref.verify(pub, msgs[i], sigs[i], zip215=True) == want[i]
    return pub, msgs, sigs, want


@pytest.mark.parametrize("platform,n", [
    ("cpu", 0), ("cpu", 1), ("cpu", 64), ("cpu", 150),
    ("tpu", 0), ("tpu", 1), ("tpu", 150), ("tpu", 512), ("tpu", 513),
    ("tpu", 3200)])
def test_lanes_route_by_platform_alone(platform, n, monkeypatch):
    """`kernel_width()` is 0 on a CPU backend and the Pallas lane tile
    on a device, whatever the lane count; `Ed25519BatchVerifier.verify`
    asks it and nothing else: on cpu the native loop and never
    `verify_batch`, on a device ONE `verify_batch` call at the tile,
    which cuts n lanes into ceil(n / tile) chunks of the warmed bucket
    (the real chunking loop runs here under a per-lane stand-in that
    accepts, so the verdicts are the native ones: no kernel is jitted).
    n = 0 is the width rule alone and the empty batch's refusal."""
    from cometbft_tpu.crypto import keys as K
    from cometbft_tpu.libs import jax_cache
    from cometbft_tpu.ops.pallas_verify import TILE

    calls, chunks = [], []

    def accept(pub_a, sig_a, hb, hn):
        chunks.append(pub_a.shape[0])
        return np.ones((pub_a.shape[0],), dtype=bool)

    def counting_verify_batch(pubs, msgs, sigs, batch_size=None):
        calls.append((len(pubs), batch_size))
        shaped = ops_ed25519._verify_batch_loop(
            pubs, msgs, sigs, batch_size, None, accept)
        return shaped & K.verify_native(pubs, msgs, sigs)

    monkeypatch.setattr(jax_cache, "backend_platform", lambda: platform)
    monkeypatch.setattr(ops_ed25519, "verify_batch", counting_verify_batch)
    width = K.kernel_width()
    assert width == (TILE if platform == "tpu" else 0)

    pub, msgs, sigs, want = _one_bad_lane(n)
    bv = K.Ed25519BatchVerifier()
    for m, s in zip(msgs, sigs):
        bv.add(K.Ed25519PubKey(pub), m, s)
    assert bv.verify() == (False, want)  # one bad lane, or none at all
    if platform == "cpu" or n == 0:
        assert calls == [] and chunks == []
    else:
        assert calls == [(n, TILE)]
        assert chunks == [TILE] * -(-n // TILE)


def test_device_layers_import_nothing_above_them():
    """`ops/`, `crypto/`, `parallel/` and `mesh/` sit under the engine,
    the pipeline and the node: no import in them, at any depth of any
    function, names one of those packages."""
    import ast
    import pathlib
    root = pathlib.Path(ops_ed25519.__file__).resolve().parents[1]
    above = {"engine", "pipeline", "node"}
    found = []
    for layer in ("ops", "crypto", "parallel", "mesh"):
        for path in sorted((root / layer).rglob("*.py")):
            depth = len(path.relative_to(root).parts) - 1
            for node in ast.walk(ast.parse(path.read_text())):
                if isinstance(node, ast.ImportFrom):
                    parts = (node.module or "").split(".")
                    if node.level == 0 and parts[0] == "cometbft_tpu":
                        parts = parts[1:]
                    elif node.level != depth + 1:
                        continue  # another distribution, or the layer's own
                    names = parts[:1] if parts and parts[0] else \
                        [a.name for a in node.names]
                elif isinstance(node, ast.Import):
                    names = [a.name.split(".")[1] for a in node.names
                             if a.name.startswith("cometbft_tpu.")]
                else:
                    continue
                found += [f"{path.relative_to(root)}:{node.lineno}"
                          for name in names if name in above]
    assert not found, found


# --- the device sniff and the compile-cache decision (libs/jax_cache) ---------

def test_device_sniff_answers_from_the_backend(monkeypatch):
    """The sniff is the truth of the initialised backend: the suite runs
    on the CPU platform, so it answers cpu / False, and every consumer
    takes its native branch; a TPU backend flips them all."""
    from cometbft_tpu.libs import jax_cache
    from cometbft_tpu.node.node import Node
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.ops.pallas_verify import TILE

    monkeypatch.delenv(jax_cache.DEVICE_SERVER_ENV, raising=False)
    assert jax_cache.backend_platform() == "cpu"
    assert not jax_cache.is_device_platform()
    assert Node._device_batch_size() == 0
    assert not e5.use_pallas_rlc()

    monkeypatch.setattr(jax_cache, "backend_platform", lambda: "tpu")
    assert jax_cache.is_device_platform()
    assert Node._device_batch_size() == TILE
    assert e5.use_pallas_rlc()


@pytest.mark.parametrize("platform,env_dir,want", [
    ("cpu", None, (False, None)),
    ("cpu", "/some/dir", (False, None)),
    ("tpu", None, (True, "DEFAULT")),
    ("tpu", "/some/dir", (True, None)),
])
def test_compile_cache_plan(platform, env_dir, want):
    """On a TPU the persistent cache is ON: where
    JAX_COMPILATION_CACHE_DIR is set JAX's own handling stands and the
    code sets no directory, otherwise the fixed <checkout>/.jax_cache.
    On cpu it stays off."""
    import os
    from cometbft_tpu.libs import jax_cache
    on, directory = jax_cache.compile_cache_plan(platform, env_dir)
    if want[1] == "DEFAULT":
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = (True, os.path.join(root, ".jax_cache"))
    assert (on, directory) == want


def test_enable_compile_cache_leaves_env_dir_alone(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, enable_compile_cache() on a
    TPU backend updates no directory in code; unset, it sets the fixed
    checkout path. (jax.config.update is intercepted — nothing here
    may switch the real suite's cache on.)"""
    import jax
    from cometbft_tpu.libs import jax_cache
    updates = {}
    monkeypatch.setattr(jax_cache, "backend_platform", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    jax_cache.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in updates
    assert "jax_enable_compilation_cache" not in updates  # stays on
    assert jax_cache.cache_dir() == "/some/dir"

    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    jax_cache.enable_compile_cache()
    assert updates["jax_compilation_cache_dir"] == jax_cache.DEFAULT_CACHE_DIR
    assert jax_cache.cache_dir() == jax_cache.DEFAULT_CACHE_DIR
