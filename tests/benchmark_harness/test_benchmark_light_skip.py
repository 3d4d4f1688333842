"""The skipping light client's cell (`light-skip-100.relayer-update`): its
plain reference on the source's chain, the run at its tiny sizes,
`correct` false with the timed path broken underneath, and its readers
on hand-built spans and counters."""

import time
import types

import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest
from benchmark.reference import light_bisect

CELL = "light-skip-100.relayer-update"
SEED = 2**31 + 42
SOURCE = {"validators": 100, "voting_power": 2, "rotation_per_header": 1,
          "header_interval_s": 60, "trust_level": [1, 3]}


def run(root, seed=SEED, trace=False, plant=""):
    return runner.run_cell(root, CELL, seed, 2.0, trace,
                           time.perf_counter(), look_for_chip=False,
                           in_process_traffic=True, plant=plant)


def over(out):
    return {n for n, row in out["checks"].items()
            if row["value"] > row["limit"]}


# --- the reference -----------------------------------------------------------------


@pytest.mark.parametrize("update", [0, 9])
def test_the_sources_update_is_sixteen_hops_of_a_sixteenth(update):
    """100 validators of power 2, one rotated a header, 1,000 headers an
    update: the trusted set vouches across 66 heights at most (34 members
    of 100 left, 68 > 66), so the walk halves 1,000 four times and takes
    16 hops of 62-63 heights: 15 pivots, 34 + 67 lanes a hop."""
    from benchmark.generators.light_skip_chain import _Sets
    sets = _Sets("source", SOURCE)
    root = 1 + 1000 * update
    sched = light_bisect.schedule(root, root + 1000, sets.plan_header)
    assert sched["refused"] is None and len(sched["pivots"]) == 15
    hops = [s["height"] - s["from"] for s in sched["steps"]]
    assert len(hops) == 16 and set(hops) == {62, 63}
    assert all(len(s["trusting"]) == 34 and len(s["own"]) == 67
               for s in sched["steps"])
    distinct = sum(len(set(s["trusting"]) | set(s["own"]))
                   for s in sched["steps"])
    assert 1024 < distinct < 1536           # three 512-lane chunks


def test_the_tally_skips_members_outside_the_trusted_set():
    members = [(bytes([i]) * 32, 1) for i in range(6)]
    lanes = [(1700, i, b"sig") for i in range(6)]
    trusted = {bytes([i]) * 32: 5 for i in (1, 3, 4)}   # 15: pass 5
    assert light_bisect.taken(members, lanes, 5, trusted) == [1, 3]
    assert light_bisect.taken(members, lanes[:3] + [None, None] + lanes[5:],
                              5, trusted) is None
    assert light_bisect.taken(members, lanes, 4) == [0, 1, 2, 3, 4]


def test_the_benchmarks_header_hash_is_the_programs():
    """The block hash every precommit signs comes from the benchmark's
    own header encoder, and the program's `Header.hash` gives the same;
    a header that differs in one hash field hashes as the program hashes
    that header, and not as the first."""
    from dataclasses import replace
    from benchmark.generators.light_skip_chain import _Sets, _make_header
    from benchmark.reference.header_hash import header_hash
    cfg = dict(SOURCE, validators=4)
    header, commit = _make_header("bench-hash", 5, _Sets("hash", cfg),
                                  "hash", cfg)
    assert commit.block_id.hash == header.hash()
    names = ("last_commit_hash", "data_hash", "validators_hash",
             "next_validators_hash", "consensus_hash", "app_hash",
             "last_results_hash", "evidence_hash")
    last = header.last_block_id
    for name in names:
        hashes = {n: getattr(header, n) for n in names}
        hashes[name] = b"\x01" * 32
        ref = header_hash("bench-hash", 5, (header.time.seconds, 0),
                          (last.hash, last.parts.total, last.parts.hash),
                          hashes, header.proposer_address)
        assert ref != header.hash()
        assert ref == replace(header, **{name: b"\x01" * 32}).hash()


# --- the run ------------------------------------------------------------------------


def test_the_run_is_correct_and_reads_its_counters(tiny_root, fresh_sigcache):
    out = run(tiny_root, trace=True)
    assert out["correct"] and over(out) == set()
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # on a CPU the flush's batch verifier verifies natively, but it is
    # still the one flush an update
    assert m["bisect_device_lane_share.light"] == 100.0
    assert {"bisect_plan_ms_per_update.light",
            "bisect_verify_ms_per_update.light",
            "bisect_save_ms_per_update.light"} <= set(m)


@pytest.mark.parametrize("plant, must_fail", [
    ("accept_all", {"probe_trusting_lane_not_refused",
                    "probe_own_lane_not_refused"}),
    # the upper half of a flush is the target's lanes
    ("half_lanes", {"probe_own_lane_not_refused"}),
])
def test_the_plants_trust_the_altered_lane(tiny_root, fresh_sigcache, plant,
                                           must_fail):
    out = run(tiny_root, plant=plant)
    assert not out["correct"] and must_fail <= over(out)


def _skip_a_lane(monkeypatch):
    """Skipping a lane is faster and wrong: every own check one short."""
    from cometbft_tpu.light import planner, verifier
    real = planner.plan_commit_light

    def short(*a, **kw):
        planned = real(*a, **kw)
        planned.lanes.pop()
        return planned
    monkeypatch.setattr(verifier.planner, "plan_commit_light", short)


def _trust_before_verifying(monkeypatch):
    """A client that stores an update's steps whatever their verdicts."""
    from cometbft_tpu.light import client
    real = client._verify_lanes

    def all_true(vals, lanes):
        oks, flushed = real(vals, lanes)
        return [True] * len(oks), flushed
    monkeypatch.setattr(client, "_verify_lanes", all_true)


def _pivot_one_height_on(monkeypatch):
    """The bisection's mid moved: other headers fetched."""
    from cometbft_tpu.light import client, planner
    real = planner.plan_update

    def moved(chain_id, trusted, target, fetch, *a, **kw):
        return real(chain_id, trusted, target,
                    lambda h: fetch(h + 1 if h + 1 < target.height else h),
                    *a, **kw)
    monkeypatch.setattr(client.planner, "plan_update", moved)


@pytest.mark.parametrize("fault, must_fail", [
    (_skip_a_lane, "lanes_gap"),
    (_trust_before_verifying, "probe_own_lane_stored_diff"),
])
def test_a_fault_under_the_timed_path_is_not_correct(
        tiny_root, fresh_sigcache, monkeypatch, fault, must_fail):
    fault(monkeypatch)
    out = run(tiny_root)
    assert not out["correct"] and must_fail in over(out)


def test_a_pivot_off_the_references_fails_the_run(tiny_root, fresh_sigcache,
                                                  monkeypatch):
    """The provider holds only the heights the reference fetches: the
    warm-up update, through the same entry, raises."""
    from cometbft_tpu.light.provider import ErrLightBlockNotFound
    _pivot_one_height_on(monkeypatch)
    with pytest.raises(ErrLightBlockNotFound):
        run(tiny_root)


def test_a_second_run_of_one_seed_hits_the_sigcache(tiny_root,
                                                    fresh_sigcache):
    assert run(tiny_root)["correct"]
    again = run(tiny_root)
    assert not again["correct"] and "sigcache_hits" in over(again)


# --- the readers --------------------------------------------------------------------


def _ctx(spans=(), counters=None):
    return types.SimpleNamespace(spans=list(spans),
                                 result={"counters": counters or {},
                                         "facts": {}})


def _span(name, ms):
    return {"name": name, "t0": 0, "t1": int(ms * 1e6), "attrs": {}}


def _reader(name):
    return Manifest(REPO).layer_reader(name).read


def test_span_readers_on_hand_built_spans():
    spans = [_span("light.bisect.plan", 30.0), _span("light.bisect.plan",
                                                     31.0),
             _span("light.bisect.plan", 20.0),
             _span("light.bisect.verify", 13.0),
             _span("light.bisect.verify", 12.0),
             _span("light.bisect.verify", 9.0),
             _span("light.bisect.save", 8.0), _span("light.bisect", 55.0),
             _span("light.plan", 99.0)]
    ctx = _ctx(spans)
    assert _reader("bisect_plan_ms_per_update.light")(ctx) == 30.0
    assert _reader("bisect_verify_ms_per_update.light")(ctx) == 12.0
    assert _reader("bisect_save_ms_per_update.light")(ctx) == 8.0
    # a program without the spans (the parent of the planned walk)
    for name in ("bisect_plan_ms_per_update.light",
                 "bisect_verify_ms_per_update.light",
                 "bisect_save_ms_per_update.light"):
        assert _reader(name)(_ctx([_span("light.plan", 1.0)])) is None


def test_the_lane_share_reader_on_hand_built_counters():
    read = _reader("bisect_device_lane_share.light")
    assert read(_ctx(counters={"bisect_device_lanes": 3624,
                               "bisect_native_lanes": 0})) == 100.0
    assert read(_ctx(counters={"bisect_device_lanes": 0,
                               "bisect_native_lanes": 42})) == 0.0
    assert read(_ctx()) is None
    assert read(_ctx(counters={"bisect_device_lanes": 0,
                               "bisect_native_lanes": 0})) is None


def test_the_skip_entries_name_their_layer_and_the_cell(doc):
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name, source in (("bisect_plan_ms_per_update.light", "program_span"),
                         ("bisect_verify_ms_per_update.light",
                          "program_span"),
                         ("bisect_save_ms_per_update.light", "program_span"),
                         ("bisect_device_lane_share.light",
                          "program_counter")):
        m = by_name[name]
        assert (m["layer"], m["source"], m["moves"]) == (
            "light client", source, "commit_verify_p50_ms")
        assert CELL in m["workloads"]
    for name in ("prewarm_s", "pallas_dispatch_share.commit",
                 "rlc_kernel_us_per_sig.commit", "rlc_kernel_roofline.commit",
                 "device_idle_share.commit", "commit_device_ms.commit",
                 "prepare_ms_per_chunk.commit"):
        assert CELL in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    for name in ("commit_verify_p50_ms", "commit_verify_p95_ms"):
        assert CELL in e2e[name]["workloads"]
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "light-skip-100", "relayer-update", 1)
    cfg = next(c for c in doc["configs"] if c["name"] == "light-skip-100")
    assert cfg["reduced"] == []
