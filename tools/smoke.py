"""Fast pre-commit smoke check (VERDICT r2 #9: a snapshot must never land
with bench.py or dryrun broken again).

Runs on a small virtual CPU mesh in one process, in under ~2 minutes warm:
  1. compile+run the single-chip verify kernel on a 16-sig batch
     (the `entry()` path),
  2. one RLC tile through `verify_rlc_kernel` incl. a corrupted lane
     falling back to attribution,
  3. one sharded `TiledCommitVerifier`-style multi-device step
     (the `dryrun_multichip` path) on a 4-device mesh.

Usage: python tools/smoke.py   (exit 0 = safe to commit)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# the smoke pins a virtual CPU mesh for itself
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)

from cometbft_tpu.libs.jax_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np


def _batch(n, msg_len=40, seed=123):
    import random
    from cometbft_tpu.crypto import ref_ed25519 as ref
    rng = random.Random(seed)
    pubs, msgs, sigs = [], [], []
    for _ in range(n):
        sd = bytes([rng.randrange(256) for _ in range(32)])
        m = bytes([rng.randrange(256) for _ in range(msg_len)])
        pubs.append(ref.pubkey_from_seed(sd))
        msgs.append(m)
        sigs.append(ref.sign(sd, m))
    return pubs, msgs, sigs


def main():
    from cometbft_tpu.ops.ed25519 import (
        make_rlc_coefficients, prepare_batch, verify_batch,
        verify_rlc_kernel)

    # 1. per-lane kernel via the host API (entry() path)
    pubs, msgs, sigs = _batch(16)
    ok = verify_batch(pubs, msgs, sigs, batch_size=16, rlc=False)
    assert ok.all(), f"per-lane kernel rejected valid sigs: {ok}"

    # 2. RLC tile: clean pass, then corrupted lane -> attribution fallback
    pub, sig, hb, hn, mask = prepare_batch(pubs, msgs, sigs, 16, 64)
    assert mask.all()
    z = make_rlc_coefficients(16)
    bok, sok = verify_rlc_kernel(pub, sig, hb, hn, z)
    assert bool(bok) and np.asarray(sok).all(), "RLC clean tile failed"
    bad_sigs = list(sigs)
    bad_sigs[5] = bytes(64)
    ok = verify_batch(pubs, msgs, bad_sigs, batch_size=16)
    want = [True] * 16
    want[5] = False
    assert list(ok) == want, f"attribution failed: {list(ok)}"

    # 3. sharded multi-device tile (dryrun path)
    from cometbft_tpu.parallel.mesh import make_mesh
    from cometbft_tpu.parallel.verify import make_sharded_verifier
    mesh = make_mesh(4)
    C, V = mesh.shape["commit"], 2 * mesh.shape["sig"]
    pubs, msgs, sigs = _batch(C * V)
    pub, sig, hb, hn, mask = prepare_batch(pubs, msgs, sigs, C * V, 64)
    assert mask.all()
    grid = lambda x: x.reshape(C, V, *x.shape[1:])
    power = np.full((C, V), 3.0, dtype=np.float32)
    ok, tally = make_sharded_verifier(mesh)(
        grid(pub), grid(sig), grid(hb), grid(hn), power)
    assert np.asarray(ok).all() and (np.asarray(tally) == 3.0 * V).all()

    print("smoke: ok")


if __name__ == "__main__":
    main()
