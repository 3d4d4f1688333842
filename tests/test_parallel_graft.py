"""The driver's `__graft_entry__` sequence on the virtual 8-device CPU
mesh: entry()'s single-device jit, then dryrun_multichip(8), in ONE
process and in that order — the sequence that used to segfault
in-suite. It is about an order of compiles inside one process, so it
shares its fresh interpreter (tests/_mesh_harness.py through the
`mesh_harness` fixture) with no other mode.
"""

MESH_MODES = ("graft",)
MESH_TIMEOUT = 600


def test_graft_entry(mesh_harness):
    mesh_harness("graft")
