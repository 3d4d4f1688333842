"""The cell `catchup-200-churn.bad-peer` with its timed path broken, its
traffic moved and its peer made stubborn, on the CPU backend at 8
validators and 4-block tiles: `correct` comes out false where a guarantee
is broken and true wherever the chain's set changes; and the driver's
judge on the PIPELINED path (the one the chip runs), which a CPU run of
the cell never takes."""

import json
import os
import time

import pytest

from conftest import REPO, make_tiny_root
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest

CELL = "catchup-200-churn.bad-peer"
SEED = 2**31 + 29
# the per-layer metrics PR 29 entered for this cell alone, by name
CHURN_METRICS = {
    "respeculate_ms_per_block.churn", "respeculated_share.churn",
    "barrier_ms_per_change.churn", "ban_refetch_ms.churn",
    "attribution_kernel_us_per_sig.churn",
    "attribution_kernel_roofline.churn"}


def run(root, seed=SEED, trace=False, plant=""):
    return runner.run_cell(root, CELL, seed, 2.0, trace,
                           time.perf_counter(), look_for_chip=False,
                           in_process_traffic=True, plant=plant)


def over(out):
    return {n for n, row in out["checks"].items()
            if row["value"] > row["limit"]}


def _resize(root, kind, name, **sizes):
    path = os.path.join(root, "benchmark", kind, name + ".json")
    with open(path) as f:
        doc = json.load(f)
    doc.update(sizes)
    with open(path, "w") as f:
        json.dump(doc, f)


@pytest.mark.parametrize("plant", [
    # every lane taken for good
    "accept_all",
    # the altered lane sits in the upper half of its tile's lanes, the
    # half that is left out: the same outcome by another road
    "half_lanes",
])
def test_a_planted_fault_is_not_correct(tiny_root, fresh_sigcache, plant):
    """The altered commit is applied and stored as the seen commit of its
    height. (The peer is banned all the same, one height late: the block
    that carried the lie has another part-set hash than the one the next
    commit signed, which no verifier has to look at a signature to see.)"""
    out = run(tiny_root, plant=plant)
    assert not out["correct"]
    assert {"altered_stored", "honest_missing", "ban_height_off"} <= over(out)
    # the program reached the tip all the same: nothing but the judge
    # tells this run from a sound one
    assert out["failed"] == 0


def test_a_peer_that_never_serves_the_honest_block_is_refused(
        tiny_root, fresh_sigcache, monkeypatch):
    driver = Manifest(tiny_root).load_module("drivers", "blocksync_churn")
    from benchmark.drivers import blocksync_sync
    monkeypatch.setattr(driver.LyingOncePeer, "fetch",
                        blocksync_sync.TamperingPeer.fetch)
    out = run(tiny_root)
    bad = Manifest(tiny_root).cell(CELL).traffic["bad_height"]
    assert not out["correct"]
    # the store stops one below the bad height, and nothing altered is in it
    assert out["failed"] == out["attempted"] - (bad - 1)
    assert out["checks"]["store_short"]["value"] == out["failed"]
    assert out["checks"]["altered_stored"]["value"] == 0
    assert out["checks"]["bans_off"]["value"] >= 1     # banned at every retry
    assert out["checks"]["ban_height_off"]["value"] == 0


@pytest.mark.parametrize("first_update_block, where", [
    (3, "the first height of a tile"),      # in force at 5, 13, 21, 29
    (2, "the last height of a tile"),       # in force at 4, 12, 20, 28
    (5, "the height after the bad one's tile starts"),   # 7, 15, 23, 31
])
def test_a_set_change_anywhere_in_a_tile_is_correct(
        tmp_path, fresh_sigcache, first_update_block, where):
    root = make_tiny_root(str(tmp_path / "checkout"))
    _resize(root, "configs", "catchup-200-churn",
            first_update_block=first_update_block)
    out = run(root, seed=SEED + first_update_block)
    assert out["correct"] and over(out) == set(), where
    assert out["checks"]["altered_stored"]["value"] == 0


def test_the_bad_height_in_a_broken_tile_is_correct(tmp_path,
                                                    fresh_sigcache):
    """The altered commit seals a height of a tile that a set change
    broke, so the synchronous route, not a tile, has to refuse it."""
    root = make_tiny_root(str(tmp_path / "checkout"))
    _resize(root, "traffic", "bad-peer", bad_height=15)   # change at 14
    out = run(root, seed=SEED + 100)
    # refused by `verify_commit`, which had cached the lanes it found
    # good before the altered one: those answer the second serving
    assert out["correct"] and over(out) == set()
    assert out["checks"]["sigcache_no_hits"]["value"] == 0


def test_a_bad_height_outside_the_chain_is_refused(tmp_path):
    root = make_tiny_root(str(tmp_path / "checkout"))
    _resize(root, "traffic", "bad-peer", bad_height=33)
    with pytest.raises(ValueError, match="outside the chain"):
        run(root)


def test_every_seed_gives_the_same_sizes(fresh_sigcache):
    make = Manifest(REPO).load_module("generators", "churn_chain").make
    cfg = {"validators": 8, "voting_power": 10, "txs_per_block": 2,
           "tile_size": 4, "update_period": 8, "first_update_block": 4}
    mix = {"blocks_per_window_second": 16, "warmup_blocks": 8,
           "bad_height": 19, "bad_index": 5}
    shapes = set()
    for seed in (1, 2**31 + 5):
        chain = make({"seed": seed, "seconds": 2.0, "config": cfg,
                      "traffic": mix})["main"]
        sets = {tuple(cs.validator_address for cs in
                      b.last_commit.signatures) for b in chain["blocks"][1:]}
        shapes.add((chain["n_blocks"], tuple(chain["update_blocks"]),
                    tuple(len(b.last_commit.signatures)
                          for b in chain["blocks"][1:]), len(sets)))
    # 32 blocks, updates in 4, 12, 20, 28, 8 lanes a commit, and five
    # orders of signers: the genesis set and one after every change
    assert shapes == {(32, (4, 12, 20, 28), (8,) * 32, 5)}
    from cometbft_tpu.pipeline.cache import shared_cache
    assert len(shared_cache()) == 0


def test_the_judge_on_the_pipelined_path(tiny_root, fresh_sigcache):
    """What the chip runs: depth 4 under the watchdog. A bucket makes
    the driver build that reactor; tiles of 32 lanes stay under the
    program's batch threshold, so every lane is verified natively and no
    kernel is compiled. Only the two checks that need a device path are
    over; the spans are the ones the new readers read."""
    from cometbft_tpu import trace as program_trace
    manifest = Manifest(tiny_root)
    cell = manifest.cell(CELL)
    driver = manifest.load_module("drivers", cell.config["driver"])
    params = {"seed": SEED + 200, "seconds": 2.0, "config": cell.config,
              "traffic": cell.traffic}
    payload = manifest.load_module("generators", "churn_chain").make(params)
    session = driver.build(cell.config, cell.traffic, payload,
                           {"batch": 512, "prewarm_s": 0.0}, params["seed"])
    assert session.main["reactor"].pipeline_depth > 1
    program_trace.enable(seed=0, ring=1 << 12)
    try:
        result = driver.window(session, 2.0)
        spans = program_trace.shared_recorder().snapshot()
    finally:
        program_trace.disable()
    checks = driver.judge(session, result, 0)
    assert {n for n, v, lim in checks if v > lim} == {
        "device_lanes_off", "attribution_runs_off"}
    c = result["counters"]
    assert c["bans"] == 1 and c["sigcache_hits"] > 0
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp)
    # every change once, and once more where the ban cut a barrier short
    # (the tile that saw the change at 22 was in flight behind the bad one)
    changes = [h + 2 for h in payload["main"]["update_blocks"]]
    assert [sp["attrs"]["change_height"]
            for sp in by_name["pipeline.barrier"]
            if "outcome" not in sp["attrs"]] == changes
    assert [sp["attrs"]["change_height"]
            for sp in by_name["pipeline.barrier"]
            if sp["attrs"].get("outcome") == "cut-short"] == [22]
    assert len(by_name["pipeline.respeculate"]) == c["respeculations"]
    assert sum(sp["attrs"]["lanes"] for sp in
               by_name["pipeline.respeculate"]) == c["respeculated_sigs"]
    (ban,) = by_name["pipeline.ban"]
    assert ban["attrs"]["height"] == cell.traffic["bad_height"]
    assert ban["attrs"]["refetched"] == cell.traffic["bad_height"]

    class Ctx:
        pass
    ctx = Ctx()
    ctx.spans, ctx.result, ctx.trace = spans, result, None
    for metric, reads in (("respeculate_ms_per_block", True),
                          ("barrier_ms_per_change", True),
                          ("ban_refetch_ms", True),
                          ("respeculated_share", True),
                          # no device trace on a CPU: nothing to read
                          ("attribution_kernel_us_per_sig", False),
                          ("attribution_kernel_roofline", False)):
        value = manifest.layer_reader(metric + ".churn").read(ctx)
        assert (value is not None and value > 0) == reads, metric
    share = manifest.layer_reader("respeculated_share.churn").read(ctx)
    assert share == 100.0 * c["respeculated_sigs"] / result["facts"]["lanes"]


def test_the_steady_cell_opens_none_of_the_new_spans(doc, tiny_root,
                                                     fresh_sigcache):
    out = runner.run_cell(tiny_root, "catchup-200.steady", SEED + 300, 2.0,
                          True, time.perf_counter(), look_for_chip=False,
                          in_process_traffic=True)
    assert out["correct"]
    assert out["checks"]["respeculations"] == {"value": 0, "limit": 0}
    assert CHURN_METRICS <= {m["name"] for m in doc["per_layer"]}
    assert not set(out["metrics"]) & CHURN_METRICS


def test_attribution_readers_on_a_recorded_trace():
    """The two device-trace readers on a summary built by hand: the
    per-lane program's seconds over the attributed lanes, the RLC
    program's left alone."""
    from benchmark.harness.xplane import TraceSummary
    manifest = Manifest(REPO)

    class Ctx:
        spans = []
        device = {"kind": "TPU v5 lite"}

        def peaks(self):
            return manifest.peaks(self.device["kind"])

        def roofline(self, kernel):
            return manifest.load_module("rooflines", kernel)
    ctx = Ctx()
    ctx.trace = TraceSummary(
        window_s=5.0, busy_s=1.0, n_device_planes=1,
        programs={"jit_verify_core": [0.25, 1],
                  "jit_verify_rlc_core_pallas": [0.9, 300]})
    ctx.result = {"facts": {"attributed_lanes": 512,
                            "attributed_hash_blocks": 1024},
                  "counters": {}}
    us = manifest.layer_reader("attribution_kernel_us_per_sig.churn")
    roof = manifest.layer_reader("attribution_kernel_roofline.churn")
    assert us.read(ctx) == pytest.approx(0.25e6 / 512)
    least = manifest.load_module("rooflines", "ed25519_verify") \
        .least_seconds(512, 1024, ctx.peaks())[0]
    assert roof.read(ctx) == pytest.approx(100.0 * least / 0.25)
    assert 0 < roof.read(ctx) < 100
    # no attributed lanes, or the program never ran: nothing to read
    ctx.result["facts"]["attributed_lanes"] = 0
    assert us.read(ctx) is None and roof.read(ctx) is None
    ctx.result["facts"]["attributed_lanes"] = 512
    ctx.trace.programs.pop("jit_verify_core")
    assert us.read(ctx) is None and roof.read(ctx) is None
