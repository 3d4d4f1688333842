"""The five readers that split the validator's `consensus.intake` runs
(`benchmark/layer_metrics/_intake_split.py`): sums by height on
hand-built spans, the end-of-height WAL record inside finalize counted
once in the run's self time, the parts adding up to the runs, nothing to
read where the program does not split its runs; and the tiny cell,
traced on a CPU, reports all five."""

import time
import types

import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest
from benchmark.layer_metrics import _intake_split

CELL = "hub-validator-150.vote-intake"
NEW = ("wal_ms_per_height.validator", "native_verify_ms_per_height.validator",
       "sign_ms_per_height.validator", "intake_other_ms_per_height.validator",
       "intake_offcpu_ms_per_height.validator")
MS = 1_000_000


def _span(name, t0, t1, sid=0, pid=0, **attrs):
    return {"name": name, "sid": sid, "pid": pid, "t0": int(t0 * MS),
            "t1": int(t1 * MS), "attrs": attrs}


def _run(t0, t1, height, cpu_ms, sid):
    return _span("consensus.intake", t0, t1, sid=sid, height=height,
                 votes=9, cpu_ns=int(cpu_ms * MS))


# height 7: two runs; the first signs the precommit and finalizes, with
# the end-of-height record fsynced inside finalize. Height 8: one run and,
# outside it, the proposal's record and the prevote's signature. Height 9:
# one short run.
SPANS = [
    _run(0, 10, 7, 6, sid=1),
    _span("consensus.intake.flush", 0, 1, pid=1, height=7),
    _span("consensus.wal", 1, 1.5, pid=1, height=7, sync=0),
    _span("vote.verify", 1.5, 2.5, height=7),
    _span("consensus.wal", 2.5, 3, pid=1, height=7, sync=0),
    _span("privval.sign", 3, 4, pid=1, height=7, type=2),
    _span("consensus.finalize", 5, 9, sid=2, pid=1, height=7),
    _span("consensus.wal", 6, 7, pid=2, height=7, sync=1),
    _run(20, 22, 7, 2, sid=3),
    _span("consensus.intake.flush", 20, 20.5, pid=3, height=7),
    _span("consensus.wal", 20.5, 21, pid=3, height=7, sync=0),
    _run(30, 34, 8, 3, sid=4),
    _span("consensus.intake.flush", 30, 31, pid=4, height=8),
    _span("vote.verify", 31, 33, height=8),
    _span("consensus.wal", 35, 36, height=8, sync=0),
    _span("privval.sign", 36, 39, height=8, type=1),
    _run(40, 41, 9, 1, sid=5),
    _span("consensus.intake.flush", 40, 40.2, pid=5, height=9),
    _span("consensus.wal", 40.2, 40.6, pid=5, height=9, sync=0),
    _span("pipeline.apply", 0, 99),
]


def _ctx(spans):
    return types.SimpleNamespace(spans=list(spans),
                                 result={"counters": {}, "facts": {}})


def _reader(name):
    return Manifest(REPO).layer_reader(name).read


def test_the_split_sums_by_height_and_adds_up_to_the_runs():
    rows = _intake_split.split(SPANS)
    assert set(rows) == {7, 8, 9}
    h7 = rows[7]
    assert h7["intake"] == pytest.approx(12.0)
    assert h7["consensus.wal"] == pytest.approx(2.5)      # 0.5 + 0.5 + 1 + 0.5
    assert h7["vote.verify"] == pytest.approx(1.0)
    assert h7["privval.sign"] == pytest.approx(1.0)
    assert h7["consensus.intake.flush"] == pytest.approx(1.5)
    assert h7["consensus.finalize"] == pytest.approx(4.0)
    # the record inside finalize is covered twice and counted once
    assert h7["overlap"] == pytest.approx(1.0)
    # the runs' 12 ms less the union of their children, 8 + 1
    assert h7["other"] == pytest.approx(3.0)
    # what happens outside the runs is no part of them
    assert rows[8]["consensus.wal"] == 0 and rows[8]["privval.sign"] == 0
    assert _intake_split.outside_ms(SPANS, "privval.sign") == \
        pytest.approx(3.0)
    for row in rows.values():
        parts = sum(row[n] for n in _intake_split.PARTS)
        assert parts + row["other"] - row["overlap"] == \
            pytest.approx(row["intake"])


def test_the_readers_take_the_median_height(capfd):
    ctx = _ctx(SPANS)
    assert _reader("intake_ms_per_height.validator")(ctx) == 4.0
    # heights 7, 8, 9: wal 2.5, 0, 0.4; native 1, 2, 0; sign 1, 0, 0;
    # other 3, 1, 0.4; off the CPU 4 + 0, 1, 0
    assert _reader("wal_ms_per_height.validator")(ctx) == \
        pytest.approx(0.4)
    assert _reader("native_verify_ms_per_height.validator")(ctx) == \
        pytest.approx(1.0)
    assert _reader("sign_ms_per_height.validator")(ctx) == 0.0
    assert _reader("intake_other_ms_per_height.validator")(ctx) == \
        pytest.approx(1.0)
    assert _reader("intake_offcpu_ms_per_height.validator")(ctx) == \
        pytest.approx(1.0)
    out = capfd.readouterr().out
    assert "consensus.wal: of which sync=1 0.000 ms" in out
    assert "privval.sign: inside the runs of 3 heights; outside them " \
           "1.000 ms a height" in out
    assert "sum to the runs within 8.333 % at every height" in out


def test_nothing_to_read_where_the_program_does_not_split_its_runs():
    before = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                             if k != "cpu_ns"}) for s in SPANS
              if s["name"] in ("consensus.intake", "consensus.finalize",
                               "pipeline.apply")]
    for name in NEW:
        assert _reader(name)(_ctx(before)) is None, name
        assert _reader(name)(_ctx([])) is None, name
    # spans of a part but no run: nothing to split
    assert _reader("wal_ms_per_height.validator")(_ctx(
        [_span("consensus.wal", 0, 1, height=3, sync=0)])) is None


def test_the_split_entries_name_their_layer_and_the_cell(doc):
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("ms", "lower", "program_span",
                                "consensus intake", "commit_verify_p50_ms")
        assert CELL in m["workloads"]


def test_the_tiny_cell_reports_the_split(tiny_root, fresh_sigcache,
                                         monkeypatch, capfd):
    from cometbft_tpu.types import validation
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 4)
    out = runner.run_cell(tiny_root, CELL, 2**31 + 3801, 2.0, True,
                          time.perf_counter(), look_for_chip=False,
                          in_process_traffic=True)
    assert out["correct"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in NEW:
        assert m[name] >= 0, name
    assert m["wal_ms_per_height.validator"] > 0
    assert m["native_verify_ms_per_height.validator"] > 0
    assert 0 < m["intake_other_ms_per_height.validator"] < \
        m["intake_ms_per_height.validator"]
    assert "consensus.intake split, medians over" in capfd.readouterr().out
