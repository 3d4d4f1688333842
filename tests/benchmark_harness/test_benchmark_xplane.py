"""The trace reduction, on planes built by hand and on a trace recorded
on the chip (PR 25: the traced 1 s window of `hub-live-150.cold-commit`,
cut down to the first three program executions and the benchmark's own
annotations with tensorflow's xplane_pb2, 265 KB)."""

import os

import pytest

from conftest import REPO  # noqa: F401
from benchmark.harness import xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "commit_3calls.xplane.pb")
S = 1e9


def planes(ops, modules, host):
    return [("/device:TPU:0", [(xplane.MODULES_LINE, modules),
                               (xplane.OPS_LINE, ops)]),
            ("/host:CPU", [("python3", host)])]


def test_busy_is_a_union_and_idle_gaps_carry_the_annotation():
    ops = [("%while.1 = s32[] while(...)", 1 * S, 3 * S),
           ("%fusion.2 = s32[] fusion(...)", 1.5 * S, 2.5 * S),
           ("%fusion.2 = s32[] fusion(...)", 6 * S, 7 * S),
           ("%before = s32[] copy(...)", 0, 0.5 * S)]       # outside
    modules = [("jit_verify_rlc_core_pallas(123)", 1 * S, 3 * S),
               ("jit_verify_rlc_core_pallas(123)", 6 * S, 7 * S),
               ("jit_other(9)", 0, 0.5 * S)]
    host = [("bench.traced", 1 * S, 9 * S), ("bench.fetch", 3 * S, 5 * S),
            ("bench.sync", 1 * S, 9 * S), ("SomeRuntimeEvent", 0, 9 * S)]
    s = xplane.reduce_planes(planes(ops, modules, host))
    assert s.window_s == 8.0 and s.busy_s == 3.0 and s.n_device_planes == 1
    assert s.programs == {"jit_verify_rlc_core_pallas": [3.0, 2]}
    assert s.program_seconds(r"verify_rlc_core_pallas") == (3.0, 2)
    assert s.program_seconds(r"no_such_program") is None
    # self time: the while keeps 1 s of its 2, the fusions get 1 + 1
    assert dict(map(tuple, s.device_ops)) == {"fusion.2": 2.0, "while.1": 1.0}
    gaps = dict(map(tuple, s.idle_gaps))
    assert sum(gaps.values()) == pytest.approx(5.0)
    assert any(k.startswith("bench.fetch") for k in gaps)
    assert any(k.startswith("bench.sync") for k in gaps)
    assert s.annotations["bench.fetch"] == [2.0, 1]


def test_a_trace_without_the_window_annotation_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce_planes(planes([], [], [("bench.sync", 0, S)]))


def test_recorded_trace_reduces_to_what_was_looked_at_by_hand():
    s = xplane.reduce_file(RECORDED)
    assert s.window_s == pytest.approx(0.726627998)
    assert s.n_device_planes == 1
    assert s.programs.keys() == {"jit_verify_rlc_core_pallas"}
    seconds, count = s.programs["jit_verify_rlc_core_pallas"]
    assert count == 3 and seconds == pytest.approx(0.008482496)
    # busy time is the union of the programs' spans
    assert s.busy_s == pytest.approx(seconds)
    assert s.annotations["bench.verify_commit"][1] == 60
    names = [n for n, _ in s.device_ops]
    assert names[0].startswith("rlc_window_sums_impl")
    # the ten operations that took most time, by self time: most of the
    # programs' spans and never more
    assert 0.85 * s.busy_s < sum(v for _, v in s.device_ops) <= s.busy_s
    assert all(k.startswith("bench.verify_commit") for k, _ in s.idle_gaps)
    assert "XLA Ops" in xplane.describe(RECORDED, 2)
