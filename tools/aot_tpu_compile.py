"""Compile the main path's kernels for a DESCRIBED TPU v5e, no chip
attached (the rehearsal before a chip call: what the TPU compiler
refuses here would be refused there, at no chip time).

Nothing runs: a pass means "lowers and fits", never "is correct" or
"is fast". The cheap cases (the four Pallas kernels and the jitted
`verify_rlc_kernel_pallas` at the 512-lane node bucket) are also kept
as tests in tests/test_aot_tpu_compile.py; this script adds the widths
and graphs that are too slow for the suite: the same at 8192 lanes, the
XLA RLC kernel and the per-lane attribution kernel (minutes each), and
the sharded RLC verifier on a 4-device mesh.

Usage: python tools/aot_tpu_compile.py [case ...]   (default: all)
       python tools/aot_tpu_compile.py --list
Exit 0 = every selected case compiled. One line per case with its wall
seconds, generated-code size and temporaries.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NODE_BUCKET = 512     # Node._device_batch_size() == pallas TILE
WIDE = 8192
VOTE_BLOCKS = 2       # SHA-512 blocks of R||A||M at the 128-byte msg cap


def sds(sharding):
    """shape -> ShapeDtypeStruct placed by `sharding`."""
    import jax

    def s(shape, dtype="int32"):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return s


def verify_args(s, n, blocks=VOTE_BLOCKS, with_z=True):
    """Shapes of (pub, sig, hblocks, hnblocks[, z]) for an n-lane batch."""
    args = [s((n, 32), "uint8"), s((n, 64), "uint8"),
            s((n, blocks, 128), "uint8"), s((n,))]
    if with_z:
        args.append(s((n, 8)))
    return args


def pallas_cases(s, n):
    """name -> (jittable, arg shapes) for the four pallas_calls and the
    jitted kernel that composes them, at n lanes. Shared with
    tests/test_aot_tpu_compile.py."""
    from cometbft_tpu.ops import pallas_verify as pv
    from cometbft_tpu.ops.ed25519 import verify_rlc_kernel_pallas
    pt = s((4, 16, n))
    m = n // pv.TILE * pv.TAIL
    return {
        "pt_add_tiled": (pv.pt_add_tiled, [pt, pt]),
        "pt_decompress_tiled": (pv.pt_decompress_tiled, [s((32, n))]),
        "rlc_window_sums": (pv.rlc_window_sums,
                            [pt, pt, s((pv.A_WINDOWS, n)),
                             s((pv.R_WINDOWS, n))]),
        "rlc_epilogue": (pv.rlc_epilogue,
                         [s((4, 16, pv.N_WINDOWS, m)), s((16, 4, 16)),
                          s((pv.A_WINDOWS,))]),
        "verify_rlc_kernel_pallas": (verify_rlc_kernel_pallas,
                                     verify_args(s, n)),
    }


def all_cases(topo):
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    from cometbft_tpu.ops import ed25519 as e
    from cometbft_tpu.parallel.mesh import COMMIT_AXIS, SIG_AXIS
    from cometbft_tpu.parallel.verify import make_rlc_sharded_verifier
    import numpy as np

    s = sds(SingleDeviceSharding(topo.devices[0]))
    cases = {}
    for n in (NODE_BUCKET, WIDE):
        for name, case in pallas_cases(s, n).items():
            cases[f"{name}@{n}"] = case
    cases[f"verify_kernel@{NODE_BUCKET}"] = (
        e.verify_kernel, verify_args(s, NODE_BUCKET, with_z=False))
    cases[f"verify_rlc_kernel@{NODE_BUCKET}"] = (
        e.verify_rlc_kernel, verify_args(s, NODE_BUCKET))

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2),
                (COMMIT_AXIS, SIG_AXIS))
    lanes = sds(NamedSharding(mesh, P((COMMIT_AXIS, SIG_AXIS))))
    cases[f"rlc_sharded_mesh2x2@{NODE_BUCKET}"] = (
        make_rlc_sharded_verifier(mesh), verify_args(lanes, NODE_BUCKET))
    return cases


def compile_case(fn, args):
    """(seconds, compiled) for one lowering + compile."""
    import jax
    t0 = time.monotonic()
    compiled = jax.jit(fn).lower(*args).compile()
    return time.monotonic() - t0, compiled


def main(argv):
    os.environ["JAX_PLATFORMS"] = "cpu"      # nothing executes; see above
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from cometbft_tpu.libs.jax_cache import disable_persistent_cache
    disable_persistent_cache()  # a described-device entry never reloads
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cases = all_cases(topo)
    if "--list" in argv:
        print("\n".join(cases))
        return 0
    want = [a for a in argv if not a.startswith("-")] or list(cases)
    failed = 0
    for name in want:
        fn, args = cases[name]
        try:
            secs, compiled = compile_case(fn, args)
        except Exception as exc:  # noqa: BLE001 — report every case
            failed += 1
            print(f"{name}: FAILED {type(exc).__name__}: "
                  f"{str(exc)[:600]}", flush=True)
            continue
        mem = compiled.memory_analysis()
        line = (f"{name}: compiled for {topo.devices[0].device_kind} in "
                f"{secs:.1f}s code={mem.generated_code_size_in_bytes} "
                f"temp={mem.temp_size_in_bytes}")
        if "mesh" in name:
            line += f" all-gather={'all-gather' in compiled.as_text()}"
        print(line, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
