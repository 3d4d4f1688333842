"""The MeshExecutor over the virtual 8-device CPU mesh against the
single-device kernels, verdict for verdict.

The mode compiles the single-device pair AND a sharded executable —
as much as a whole group of test_parallel_grid.py or
test_parallel_lanes.py — so it has a fresh interpreter
(tests/_mesh_harness.py through the `mesh_harness` fixture) and a file,
hence under `--dist loadfile` a worker, to itself.
"""

MESH_MODES = ("equiv",)
MESH_TIMEOUT = 600


def test_mesh_executor_matches_single_chip(mesh_harness):
    """ISSUE 12 acceptance: sharded and single-chip verdicts identical
    on clean / tampered / valset-change chains, then a pipelined
    catch-up with the MeshExecutor as the real verify backend."""
    mesh_harness("equiv")
