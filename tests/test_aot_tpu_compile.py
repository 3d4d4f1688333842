"""The main path's Pallas kernels must COMPILE for the chip, not just
interpret: each pallas_call of ops/pallas_verify.py, and the jitted
verify_rlc_kernel_pallas that composes them, is lowered and compiled
here for a described (not attached) TPU v5e at the node's 512-lane
bucket. Interpret mode (tests/test_pallas.py) cannot see what
Mosaic refuses — scatters, strided value slices, dynamic slices of
values, VMEM overflow — and before PR 22 three of these four kernels
did not lower at all.

Nothing executes: a pass means "lowers and fits", never "is right" or
"is fast". The slow cases (8192 lanes, the XLA kernels, the 4-device
mesh) are in the hand-run tools/aot_tpu_compile.py, which shares the
case table below.

The topology is described inside a fixture of THIS file only: one
process at a time may load the TPU library, so it must never load while
a module is imported or collected, and never in a child process.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

NODE_BUCKET = 512
KERNELS = ("pt_add_tiled", "pt_decompress_tiled", "rlc_window_sums",
           "rlc_epilogue", "verify_rlc_kernel_pallas")


@pytest.fixture(scope="module")
def v5e():
    """SingleDeviceSharding on chip 0 of a described v5e:2x2, with the
    persistent compile cache off around the compiles (an entry written
    for a described device can never be read back here)."""
    import signal
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    on_term = signal.getsignal(signal.SIGTERM)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        # loading the TPU library installs a SIGTERM handler that dumps a
        # stack trace; a runner that ends an overrunning suite with
        # SIGTERM would get that dump in the middle of pytest's progress
        # line. Put this process's own disposition back.
        signal.signal(signal.SIGTERM, on_term)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", KERNELS)
def test_pallas_kernel_compiles_for_v5e(v5e, kernel):
    import aot_tpu_compile as aot
    from cometbft_tpu.ops import pallas_verify as pv
    assert pv.TILE == NODE_BUCKET  # what Node._device_batch_size() returns
    fn, args = aot.pallas_cases(aot.sds(v5e), NODE_BUCKET)[kernel]
    secs, compiled = aot.compile_case(fn, args)
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    kind = next(iter(v5e.device_set)).device_kind
    print(f"{kernel}@{NODE_BUCKET}: compiled for {kind} in {secs:.1f}s")
