"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's approach of testing multi-node behavior in-process
(reference: internal/consensus/common_test.go, p2p/test_util.go) — here the
"cluster" is a virtual 8-device mesh so sharding/collective code paths run
without TPU hardware. The suite pins the CPU platform for itself
(whatever JAX_PLATFORMS says); a child process a test starts is handed
its platform through the child's environment.
"""

import os
import subprocess
import sys

import jax
import pytest

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

_MESH_HARNESS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "_mesh_harness.py")


@pytest.fixture(scope="module")
def mesh_harness(request):
    """ONE fresh interpreter of tests/_mesh_harness.py for all of the
    requesting module's MESH_MODES (a multi-device XLA:CPU executable
    built in a pytest worker that has compiled many single-device
    kernels segfaults this jaxlib). Returns check(mode): each test
    reads its own "OK <mode>" line, so a mode fails alone."""
    modes = request.module.MESH_MODES
    r = subprocess.run([sys.executable, _MESH_HARNESS, *modes],
                       capture_output=True, text=True,
                       timeout=request.module.MESH_TIMEOUT)

    def check(mode):
        assert f"OK {mode}\n" in r.stdout, (
            f"mesh harness {mode!r} of {modes} rc={r.returncode}\n"
            f"--- stdout ---\n{r.stdout}\n"
            f"--- stderr ---\n{r.stderr[-4000:]}")
    return check


# The head of the queue `--dist loadfile` hands out, one file a worker:
# the four mesh files (minutes each, spent in a child interpreter, so
# the waiting worker costs no core), then the two files that compile the
# single-device kernel pair. Started first they overlap everything else,
# where alphabetically they were the run's tail; and while their compiles
# load every core, the two workers left over are compiling as well, not
# running the consensus and p2p tests, which race the clock.
_FIRST = ("test_parallel_equiv.py", "test_parallel_graft.py",
          "test_parallel_grid.py", "test_parallel_lanes.py",
          "test_device_server.py", "test_ed25519_verify.py")


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


def pytest_configure(config):
    # the order above holds only if xdist does not re-queue the files by
    # their NUMBER of tests, most first (its default, which puts the one-
    # and two-test mesh files, the longest of the suite, at the very end)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers", "slow: multi-process / perturbation tests")
    config.addinivalue_line(
        "markers", "sim: deterministic simnet scenarios (virtual time)")
    config.addinivalue_line(
        "markers", "pipeline: asynchronous multi-tile verification "
        "pipeline (pipeline/scheduler, watchdog, sig cache)")
