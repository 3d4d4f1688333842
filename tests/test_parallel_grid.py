"""The (commit, sig) grid verifier with its exact power tally over the
virtual 8-device CPU mesh (the in-process stand-in for a real TPU pod
slice, mirroring how the reference tests multi-node behavior in-process
— SURVEY §4), whole and re-factored.

Both modes run in ONE fresh interpreter (tests/_mesh_harness.py through
the `mesh_harness` fixture) and share the (4,2) executable; the other
mesh groups are files of their own (test_parallel_lanes.py,
test_parallel_entry.py), so that `--dist loadfile` can hand each to a
different worker.
"""

MESH_MODES = ("tally", "refactor")
MESH_TIMEOUT = 600


def test_sharded_commit_verify_with_tally(mesh_harness):
    mesh_harness("tally")


def test_mesh_refactor_matrix_exact_tally(mesh_harness):
    """8 -> 6 -> 4 -> 1-device factorings via topology masking: the
    int64 power tally stays bit-exact across every factoring."""
    mesh_harness("refactor")
