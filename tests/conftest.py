"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's approach of testing multi-node behavior in-process
(reference: internal/consensus/common_test.go, p2p/test_util.go) — here the
"cluster" is a virtual 8-device mesh so sharding/collective code paths run
without TPU hardware. The suite pins the CPU platform for itself
(whatever JAX_PLATFORMS says); a child process a test starts is handed
its platform through the child's environment.
"""

import os
import sys

import jax

# NOTE: on the CPU test platform enable_compile_cache() intentionally
# DISABLES the persistent compile cache — XLA:CPU AOT executables
# reloaded by another process fail the machine-feature check (SIGILL
# risk; mesh executables outright segfault), so every test run
# recompiles its kernels (minutes per variant, per process).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cometbft_tpu.libs.jax_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / perturbation tests")
    config.addinivalue_line(
        "markers", "sim: deterministic simnet scenarios (virtual time)")
    config.addinivalue_line(
        "markers", "pipeline: asynchronous multi-tile verification "
        "pipeline (pipeline/scheduler, watchdog, sig cache)")
