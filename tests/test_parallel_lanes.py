"""The lane-sharded verifiers over the virtual 8-device CPU mesh: the
sharded RLC equation with its per-lane attribution fallback, and
blocksync dispatching real tiles to them.

Both modes run in ONE fresh interpreter (tests/_mesh_harness.py through
the `mesh_harness` fixture): `blocksync` verifies through the pair of
executables `rlc` compiled.
"""

import os
import subprocess
import sys

MESH_MODES = ("rlc", "blocksync")
MESH_TIMEOUT = 480


def test_sharded_rlc_fast_path_and_attribution(mesh_harness):
    mesh_harness("rlc")


def test_blocksync_through_mesh(mesh_harness):
    mesh_harness("blocksync")


def test_harness_reports_a_failing_mode_and_goes_on():
    """What lets the modes of a group share an interpreter and still
    fail alone: a mode that raises is reported by name with its
    traceback, the next one still runs, and the interpreter exits 0."""
    harness = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "_mesh_harness.py")
    r = subprocess.run([sys.executable, harness, "no-such-mode", "nor-this"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FAIL no-such-mode\n" in r.stdout
    assert "FAIL nor-this\n" in r.stdout
    assert "KeyError: 'no-such-mode'" in r.stdout
    assert "OK " not in r.stdout
