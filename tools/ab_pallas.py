"""A/B on the real chip: XLA point ops vs the pallas kernels, then the
full RLC verify both ways at batch 8192.

AB_SWEEP="256,512,1024" re-execs this script once per TILE value (the
pallas lane-tile is latched at module import, so each point needs a
fresh interpreter) timing ONLY the full pallas RLC — the TILE tuning
pass of VERDICT r5 item 3. AB_ONLY=pallas skips the per-stage A/B."""
import os, subprocess, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("AB_SWEEP"):
    tiles = [int(t) for t in os.environ["AB_SWEEP"].split(",")]
    print(f"TILE sweep: {tiles}", flush=True)
    for tile in tiles:
        env = dict(os.environ, COMETBFT_TPU_PALLAS_TILE=str(tile),
                   AB_ONLY="pallas")
        env.pop("AB_SWEEP")
        print(f"--- TILE={tile} ---", flush=True)
        try:
            r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, timeout=2400)
            rc = r.returncode
        except subprocess.TimeoutExpired:
            # one hung tile (wedged device mid-run) must not abort the
            # remaining sweep points
            rc = "timeout"
        print(f"--- TILE={tile} rc={rc} ---", flush=True)
    sys.exit(0)
from cometbft_tpu.libs.jax_cache import enable_compile_cache
enable_compile_cache()
import numpy as np
import jax
import jax.numpy as jnp

N = int(os.environ.get("AB_N", "8192"))
print(f"device={jax.devices()[0].platform} N={N}", flush=True)

from cometbft_tpu.ops import edwards as ed
from cometbft_tpu.ops import pallas_verify as pv

rng = np.random.default_rng(0)
limbs = lambda *s: jnp.asarray(
    rng.integers(0, 1 << 16, size=(16, *s), dtype=np.int32))

def t(name, fn, *args, reps=5):
    t0 = time.perf_counter()
    out = fn(*args); jax.block_until_ready(out)
    print(f"{name:34s} compile+1st {time.perf_counter()-t0:7.1f}s",
          flush=True)
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args); jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    print(f"{name:34s} {best*1e3:9.3f} ms", flush=True)
    return out

_only_pallas = os.environ.get("AB_ONLY") == "pallas"
print(f"pallas TILE={pv.TILE}", flush=True)

if not _only_pallas:
    pt = (limbs(N), limbs(N), limbs(N), limbs(N))
    packed = jnp.stack(pt)

    # 1) pt_add: XLA vs pallas
    t("pt_add XLA", jax.jit(ed.pt_add), pt, pt)
    t("pt_add PALLAS tiled", lambda p, q: pv.pt_add_tiled(p, q),
      packed, packed)

    # 2) window stage: XLA table+lookup+tree vs pallas fused
    tdig = jnp.asarray(rng.integers(0, 16, size=(64, N), dtype=np.int32))
    zdig = jnp.asarray(rng.integers(0, 16, size=(32, N), dtype=np.int32))

    @jax.jit
    def xla_stage(a, r, td, zd):
        wa = ed.pt_tree_sum(ed.lookup_windows(ed.window_table(a), td))
        wr = ed.pt_tree_sum(ed.lookup_windows(ed.window_table(r), zd))
        return wa[0] + wr[0]
    t("window stage XLA", xla_stage, pt, pt, tdig, zdig)

    def pallas_stage(a, r, td, zd):
        out = pv.rlc_window_sums(a, r, td, zd)
        folded = jnp.transpose(out, (2, 3, 1, 0, 4)).reshape(
            4, 16, 96, out.shape[0] * pv.TAIL)
        return ed.pt_tree_sum(tuple(folded[i] for i in range(4)))[0]
    t("window stage PALLAS", jax.jit(pallas_stage), packed, packed,
      tdig, zdig)

# 3) full RLC verify both ways on real signatures
from cometbft_tpu.ops.ed25519 import (
    make_rlc_coefficients, prepare_batch,
    verify_rlc_kernel, verify_rlc_kernel_pallas)
from cometbft_tpu.crypto import ref_ed25519 as ref

try:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey)
    from cryptography.hazmat.primitives import serialization
    keys = [Ed25519PrivateKey.generate() for _ in range(200)]
    raw = lambda k: k.public_key().public_bytes(
        serialization.Encoding.Raw, serialization.PublicFormat.Raw)
    msgs = [rng.integers(0, 256, 122, dtype=np.uint8).tobytes()
            for _ in range(N)]
    pubs = [raw(keys[i % 200]) for i in range(N)]
    sigs = [keys[i % 200].sign(m) for i, m in enumerate(msgs)]
except ImportError:
    seeds = [bytes([int(b) for b in rng.integers(0, 256, 32)])
             for _ in range(8)]
    msgs = [b"m" * 100] * N
    pubs = [ref.pubkey_from_seed(seeds[i % 8]) for i in range(N)]
    sigs = [ref.sign(seeds[i % 8], msgs[i]) for i in range(N)]

pub, sig, hb, hn, ok = prepare_batch(pubs, msgs, sigs, N, 128)
assert ok.all()
z = make_rlc_coefficients(N)
dev = jax.devices()[0]
pub, sig, hb, hn = (jax.device_put(x, dev) for x in (pub, sig, hb, hn))

def full(kern, name):
    bok, sok = t(f"RLC full {name}", lambda: kern(pub, sig, hb, hn, z))
    assert bool(bok) and np.asarray(sok).all(), name

full(verify_rlc_kernel_pallas, "PALLAS")
if not _only_pallas:
    full(verify_rlc_kernel, "XLA")
variants = [("PALLAS", verify_rlc_kernel_pallas)]
if not _only_pallas:
    variants.append(("XLA", verify_rlc_kernel))
for name, kern in variants:
    t0 = time.perf_counter()
    iters = 4
    for _ in range(iters):
        z2 = make_rlc_coefficients(N)
        bok, out = kern(pub, sig, hb, hn, z2)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"THROUGHPUT {name}: {N*iters/dt:,.0f} sigs/s "
          f"({dt/iters*1e3:.1f} ms/iter)", flush=True)
