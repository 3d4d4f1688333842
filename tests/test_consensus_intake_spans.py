"""The spans inside the validator's `consensus.intake` runs
(consensus/state.py, types/vote_set.py): `consensus.wal` around every WAL
append, `vote.verify` around a native signature check on a cache miss,
`privval.sign` around the own vote's signature, `consensus.intake.flush`
around a run's lookups and flush, and the run's `cpu_ns`. One height of
tests/test_consensus_intake.py's sequence, drained by `receive_routine`
on real clocks; with tracing off, none of it."""

import pytest

from cometbft_tpu import trace as program_trace
from cometbft_tpu.libs import timesource
from cometbft_tpu.trace import NOOP_SPAN
from cometbft_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE

from test_consensus_intake import _node, _sequence, chain, stub  # noqa: F401

NEW = ("consensus.wal", "vote.verify", "privval.sign",
       "consensus.intake.flush")


def _drain(chain):
    cs, wal, _store, _steps = _node(chain)
    for entry in _sequence(chain):
        cs.inbox.put(entry)
    cs.inbox.put(None)
    cs.receive_routine()
    return cs, wal


@pytest.fixture
def spans(chain, stub):
    program_trace.enable(seed=0, ring=1 << 12)
    try:
        cs, wal = _drain(chain)
        got = program_trace.shared_recorder().snapshot()
    finally:
        program_trace.disable()
    assert cs.rs.height == 2 and cs._trace_parent is None
    return got, wal


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(span, run):
    return run["t0"] <= span["t0"] and span["t1"] <= run["t1"]


def test_each_new_span_has_its_height_and_its_parent(spans):
    spans, wal = spans
    runs = _named(spans, "consensus.intake")
    by_sid = {s["sid"]: s for s in spans}
    assert len(runs) == 4
    for name in NEW:
        assert _named(spans, name), name
        # the height of the message handled: 7 is the parked vote's
        for s in _named(spans, name):
            assert s["attrs"]["height"] in (1, 2, 7), (name, s["attrs"])

    # one flush span a run, under it, with the run's lookups
    flushes = _named(spans, "consensus.intake.flush")
    assert [by_sid[f["pid"]] for f in flushes] == runs
    assert [f["attrs"]["flushed"] for f in flushes] == [1, 0, 1, 0]
    for f, run in zip(flushes, runs):
        assert set(f["attrs"]) == {"height", "lanes", "cache_hits",
                                   "flushed"}
        assert f["attrs"]["cache_hits"] == run["attrs"]["cache_hits"]
        # a lane the flush refused is neither verified nor left native
        assert f["attrs"]["lanes"] >= run["attrs"]["device_lanes"] + \
            run["attrs"]["native_lanes"] + run["attrs"]["cache_hits"]
        if not f["attrs"]["flushed"]:
            assert f["attrs"]["lanes"] == run["attrs"]["native_lanes"]

    # every WAL append is in a span: a run's peer votes under the run,
    # the end-of-height record fsynced under finalize, the proposal, its
    # parts, the timeouts and the own prevote outside any run as roots
    appends = _named(spans, "consensus.wal")
    assert len(appends) == len(wal.records)
    assert [a["attrs"]["sync"] for a in appends] == [
        int(kind == "sync") for kind, _rec in wal.records]
    (fin,) = _named(spans, "consensus.finalize")
    assert by_sid[fin["pid"]] is runs[2]
    (end,) = [a for a in appends if a["pid"] == fin["sid"]]
    assert end["attrs"] == {"height": 1, "sync": 1} and _inside(end, fin)
    for a in appends:
        parent = by_sid.get(a["pid"])
        if parent is None:
            assert a["pid"] == 0
            assert not any(_inside(a, run) for run in runs)
        else:
            assert parent["name"] in ("consensus.intake",
                                      "consensus.finalize")
            assert any(_inside(a, run) for run in runs)
    # under the runs: their peer votes, and the own precommit, fsynced
    # in the run that signed it
    peer_votes = sum(r["attrs"]["votes"] for r in runs)
    under = [a for a in appends if a["pid"] in {r["sid"] for r in runs}]
    assert len(under) == peer_votes + 1
    assert [a["pid"] for a in under if a["attrs"]["sync"]] == \
        [runs[0]["sid"]]

    # the prevote the proposal triggers is signed outside a run (a root),
    # the precommit inside the run that brings +2/3 of the prevotes
    signs = _named(spans, "privval.sign")
    assert [(s["attrs"]["type"], s["pid"]) for s in signs] == [
        (PREVOTE_TYPE, 0), (PRECOMMIT_TYPE, runs[0]["sid"])]

    # native checks are roots, found inside the runs by time
    checks = _named(spans, "vote.verify")
    assert all(c["pid"] == 0 for c in checks)
    assert sum(1 for c in checks if any(_inside(c, r) for r in runs)) >= \
        sum(r["attrs"]["native_lanes"] for r in runs)


def test_children_fit_their_run_and_cpu_time_fits_the_wall(spans):
    spans, _wal = spans
    for run in _named(spans, "consensus.intake"):
        wall = run["t1"] - run["t0"]
        kids = [s for s in spans if s["pid"] == run["sid"]]
        assert kids and all(_inside(k, run) for k in kids)
        assert sum(k["t1"] - k["t0"] for k in kids) <= wall
        assert 0 <= run["attrs"]["cpu_ns"] <= wall


def test_tracing_off_records_nothing_and_reads_no_thread_clock(
        chain, stub, monkeypatch):
    program_trace.disable()

    def refused():
        raise AssertionError("a clock read with tracing off")
    monkeypatch.setattr(timesource, "thread_time_ns", refused)
    cs, wal = _drain(chain)
    assert cs.rs.height == 2 and wal.records
    assert program_trace.shared_recorder().snapshot() == []
    assert program_trace.shared_tracer().start("consensus.wal",
                                               height=1) is NOOP_SPAN


def test_thread_time_follows_a_virtual_clock():
    ticks = iter(range(10, 100, 10))
    timesource.install(lambda: next(ticks))
    try:
        assert [timesource.thread_time_ns() for _ in range(3)] == \
            [10, 20, 30]
    finally:
        timesource.reset()
    a = timesource.thread_time_ns()
    assert timesource.thread_time_ns() >= a >= 0
