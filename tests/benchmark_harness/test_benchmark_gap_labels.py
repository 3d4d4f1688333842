"""The device's idle gaps labelled by the program's spans
(`benchmark/harness/gap_labels.py`) and the tool that takes them from a
traced run (`benchmark/tools/trace_gaps.py`): given no spans the labels
are `xplane.reduce_planes`' own, on hand-built planes and on the trace
recorded on the chip; a program span that is more specific than an
annotation labels the gap; spans on another clock are moved by the
offset measured at the window's ends."""

import os
import time

import pytest

from conftest import REPO  # noqa: F401
from benchmark.harness import gap_labels, runner, xplane
from test_benchmark_xplane import RECORDED, S, planes

OPS = [("%fusion.2 = s32[] fusion(...)", 1 * S, 3 * S)]
MODULES = [("jit_verify_rlc_core_pallas(123)", 1 * S, 3 * S),
           ("jit_verify_rlc_core_pallas(123)", 6 * S, 7 * S)]
HOST = [("bench.traced", 1 * S, 9 * S), ("bench.fetch", 3 * S, 5 * S),
        ("bench.sync", 1 * S, 9 * S)]
EPOCH = 1_800_000_000 * 10**9       # where the program's clock stands


def _span(name, lo, hi, clock=0):
    return {"name": name, "t0": int(lo + clock), "t1": int(hi + clock)}


def test_without_spans_the_labels_are_the_reductions():
    p = planes(OPS, MODULES, HOST)
    got = gap_labels.label(p)
    assert got.idle_gaps == xplane.reduce_planes(p).idle_gaps
    assert got.idle_s == pytest.approx(5.0)
    assert got.program_share == 0.0 and got.offsets_ns is None


def test_the_recorded_trace_reduces_as_before_and_labels_alike():
    loaded = xplane.load(RECORDED)
    assert xplane.reduce_planes(loaded) == xplane.reduce_file(RECORDED)
    got = gap_labels.label(loaded)
    assert got.idle_gaps == xplane.reduce_file(RECORDED).idle_gaps
    assert got.program_share == 0.0


@pytest.mark.parametrize("clock", [0, EPOCH])
def test_a_program_span_labels_a_gap_over_an_annotation(clock):
    """Idle 3-6 s and 7-9 s. `light.save` (1.8 s in all) covers 1.8 s of
    the first gap, more specific than `bench.fetch` (2 s), which covers
    2; nothing of the program covers half of the second, which stays
    `bench.sync`'s. On the program's own clock, the stamps taken at the
    window's ends move the spans back onto the trace's."""
    spans = [_span("light.save", 3.2 * S, 5.0 * S, clock),
             _span("light.tile", 3.0 * S, 7.5 * S, clock)]
    stamps = (10**9 + clock - 20_000, 9 * 10**9 + clock + 30_000)
    got = gap_labels.label(planes(OPS, MODULES, HOST), spans,
                           stamps if clock else None)
    gaps = dict(got.idle_gaps)
    assert gaps == {"light.save (longest 3000.0 ms)": pytest.approx(3.0),
                    "bench.sync (longest 2000.0 ms)": pytest.approx(2.0)}
    assert got.program_share == pytest.approx(60.0)
    if clock:
        assert got.offsets_ns == (20_000 - clock, -30_000 - clock)
        assert got.shift_ns == -clock - 5_000


def test_small_offsets_move_nothing():
    spans = [_span("light.save", 3.2 * S, 5.0 * S)]
    stamps = (10**9 - 40_000, 9 * 10**9 - 90_000)
    got = gap_labels.label(planes(OPS, MODULES, HOST), spans, stamps)
    assert got.offsets_ns == (40_000, 90_000) and got.shift_ns == 0
    assert got.program_share == pytest.approx(60.0)


def test_the_tool_keeps_what_the_run_throws_away(tiny_root, fresh_sigcache,
                                                 monkeypatch):
    """The tool on the tiny light cell, on a CPU that the runner is told
    is a TPU so that it profiles: no device plane, so one gap (the
    window), the spans kept and nothing dropped; the runner is as it was
    afterwards."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_gaps", os.path.join(REPO, "benchmark", "tools",
                                   "trace_gaps.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    real = runner.devmod.cpu_device_info
    monkeypatch.setattr(runner.devmod, "cpu_device_info",
                        lambda: dict(real(), platform="tpu"))
    before = (runner.profiled, runner.xplane)
    out, labels, dropped = tool.traced_run(
        tiny_root, "light-seq-150.tip-catch-up", 2**31 + 3802, 1.0,
        time.perf_counter(), look_for_chip=False, in_process_traffic=True)
    assert (runner.profiled, runner.xplane) == before
    assert out["correct"] and dropped == 0
    # the one gap: the program's spans label it where they cover half of
    # the window (the light client's tiles, on an idle host), else the
    # driver's annotation
    (label, seconds), = labels.idle_gaps
    assert seconds == pytest.approx(labels.idle_s)
    assert labels.program_share == (
        0.0 if label.startswith("bench.") else 100.0)
    assert abs(labels.offsets_ns[1] - labels.offsets_ns[0]) < 10**9
