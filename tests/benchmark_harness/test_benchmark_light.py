"""The light client's cell (`light-seq-150.tip-catch-up`, PR 36): its
plain reference against the program's planner, the run at its tiny sizes
with the tiles the chip would cut forced on the CPU, `correct` false
with the timed path broken underneath, and its readers on hand-built
spans and counters."""

import random
import time
import types

import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest
from benchmark.reference import light_rule, valset_replay

CELL = "light-seq-150.tip-catch-up"
SEED = 2**31 + 36


def run(root, seed=SEED, trace=False, plant=""):
    return runner.run_cell(root, CELL, seed, 2.0, trace,
                           time.perf_counter(), look_for_chip=False,
                           in_process_traffic=True, plant=plant)


def over(out):
    return {n for n, row in out["checks"].items()
            if row["value"] > row["limit"]}


@pytest.fixture
def tiles(monkeypatch):
    """The tiles a chip would cut, on a CPU: 8 chunks of a lane bucket
    of 8 (a tile of 64 lanes is 16 of the tiny set's headers), the batch
    threshold at
    two headers' lanes, the flush through `Ed25519BatchVerifier`, which
    off a TPU verifies natively."""
    from cometbft_tpu.light import client as light_client
    from cometbft_tpu.types import validation
    monkeypatch.setattr(light_client, "kernel_width", lambda: 8)
    monkeypatch.setattr(light_client, "TILE_CHUNKS", 8)
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 8)


# --- the reference ----------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_the_rule_agrees_with_the_programs_planner(seed):
    """Seeded sets of 3-40 members with skewed or equal power and
    seed-drawn absences: the plain rule takes the lanes the program
    plans, and refuses for want of power where it refuses."""
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.engine.chain_gen import sign_commit
    from cometbft_tpu.light.planner import plan_commit_light
    from cometbft_tpu.pipeline.cache import SigCache
    from cometbft_tpu.types.block import (BLOCK_ID_FLAG_ABSENT, BlockID,
                                          Commit, CommitSig, PartSetHeader)
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.validation import ErrNotEnoughVotingPowerSigned
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    rng = random.Random(seed)
    n = rng.randrange(3, 41)
    keys = [Ed25519PrivKey(bytes(rng.randrange(256) for _ in range(32)))
            for _ in range(n)]
    power = [int(10**6 * (i + 1) ** -0.8) if seed % 2 else 10
             for i in range(n)]
    vals = ValidatorSet([Validator(k.pub_key(), p)
                         for k, p in zip(keys, power)])
    members = valset_replay.ordered(
        {k.pub_key().bytes_(): p for k, p in zip(keys, power)})
    assert [v.pub_key.bytes_() for v in vals.validators] == \
        [pub for pub, _p in members]
    assert vals.hash() == light_rule.validators_hash(members)
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    signed = sign_commit("rule-chain", 5, 0, bid, vals,
                         {k.pub_key().address(): k for k in keys})
    for absent_share in (0.0, 0.2, 0.5):
        gone = {i for i in range(n) if rng.random() < absent_share}
        commit = Commit(5, 0, bid, [
            CommitSig(BLOCK_ID_FLAG_ABSENT, b"", Timestamp(), b"")
            if i in gone else cs for i, cs in enumerate(signed.signatures)])
        lanes = [None if i in gone else
                 (cs.timestamp.seconds, cs.timestamp.nanos, cs.signature)
                 for i, cs in enumerate(signed.signatures)]
        want = light_rule.taken(members, lanes)
        try:
            plan = plan_commit_light("rule-chain", vals, bid, 5, commit,
                                     SigCache(0), path="light")
            assert [lane.sig_index for lane in plan.lanes] == want
            for lane in plan.lanes:
                assert lane.msg == light_rule.sign_bytes(
                    "rule-chain", 5, (bid.hash, 1, bid.parts.hash),
                    lanes[lane.sig_index])
        except ErrNotEnoughVotingPowerSigned:
            assert want is None


def test_the_rule_stops_when_the_tally_passes_two_thirds():
    members = [(bytes([i]) * 32, p) for i, p in
               enumerate((50, 40, 30, 20, 10, 5))]
    lanes = [(1700, i, b"sig") for i in range(6)]
    # 155 in all, to pass 103: the first three lanes (120)
    assert light_rule.taken(members, lanes) == [0, 1, 2]
    # an absent lane adds nothing and the walk goes on behind it
    assert light_rule.taken(members, [None] + lanes[1:]) == [1, 2, 3, 4, 5]
    assert light_rule.taken(members, [None, None] + lanes[2:]) is None


# --- the run, with the chip's tiles forced ------------------------------------------

def test_the_tiled_run_is_correct_and_reads_its_counters(
        tiny_root, fresh_sigcache, tiles):
    out = run(tiny_root, trace=True)
    assert out["correct"] and over(out) == set()
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # 39 headers of 4 lanes: tiles of 16, 16 and 7 headers, each a flush
    assert m["lanes_per_flush.light"] == 52.0
    assert m["device_lane_share.light"] == 100.0
    assert {"plan_ms_per_tile.light", "verify_ms_per_tile.light",
            "save_ms_per_tile.light"} <= set(m)


@pytest.mark.parametrize("plant", ["accept_all", "half_lanes"])
def test_the_plants_trust_the_altered_lane(tiny_root, fresh_sigcache, tiles,
                                           plant):
    """As the control runs on the chip use them: the probe's altered
    signature sits in the upper half of its tile's lanes."""
    out = run(tiny_root, plant=plant)
    assert not out["correct"]
    assert {"probe_altered_not_refused", "probe_altered_latest_gap"} <= \
        over(out)


def _accept_everything(monkeypatch):
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    monkeypatch.setattr(Ed25519PubKey, "verify_signature",
                        lambda self, msg, sig: True)


def _skip_the_last_lane(monkeypatch):
    """Skipping a lane is faster and wrong: every plan one lane short."""
    from cometbft_tpu.light import planner, verifier
    real = planner.plan_commit_light

    def short(*a, **kw):
        planned = real(*a, **kw)
        planned.lanes.pop()
        return planned
    monkeypatch.setattr(verifier.planner, "plan_commit_light", short)


def _one_lane_more(monkeypatch):
    """The rule's stop moved one lane on."""
    from cometbft_tpu.light import planner, verifier
    real = planner.plan_commit_light

    def more(chain_id, vals, block_id, height, commit, cache, path):
        planned = real(chain_id, vals, block_id, height, commit, cache, path)
        idx = planned.lanes[-1].sig_index + 1
        planner._add_lane(planned, chain_id, commit, idx,
                          vals.get_by_index(idx), commit.signatures[idx],
                          cache, path)
        return planned
    monkeypatch.setattr(verifier.planner, "plan_commit_light", more)


def _trust_before_verifying(monkeypatch):
    """A client that stores a tile's headers whatever their verdicts."""
    from cometbft_tpu.light import client
    real = client._verify_lanes

    def all_true(vals, lanes):
        oks, flushed = real(vals, lanes)
        return [True] * len(oks), flushed
    monkeypatch.setattr(client, "_verify_lanes", all_true)


@pytest.mark.parametrize("fault, must_fail", [
    (_accept_everything, "probe_altered_not_refused"),
    (_skip_the_last_lane, "lanes_gap"),
    (_one_lane_more, "lanes_gap"),
    (_trust_before_verifying, "probe_altered_latest_gap"),
])
def test_a_fault_under_the_timed_path_is_not_correct(
        tiny_root, fresh_sigcache, tiles, monkeypatch, fault, must_fail):
    fault(monkeypatch)
    out = run(tiny_root)
    assert not out["correct"] and must_fail in over(out)


def test_a_second_catch_up_of_one_seed_hits_the_sigcache(tiny_root,
                                                         fresh_sigcache):
    assert run(tiny_root)["correct"]
    again = run(tiny_root)
    assert not again["correct"] and "sigcache_hits" in over(again)


# --- the readers --------------------------------------------------------------------

def _ctx(spans=(), counters=None):
    return types.SimpleNamespace(spans=list(spans),
                                 result={"counters": counters or {},
                                         "facts": {}})


def _span(name, ms, **attrs):
    return {"name": name, "t0": 0, "t1": int(ms * 1e6), "attrs": attrs}


def _reader(name):
    return Manifest(REPO).layer_reader(name).read


def test_span_readers_on_hand_built_spans():
    spans = [_span("light.plan", 300.0), _span("light.plan", 310.0),
             _span("light.plan", 90.0), _span("light.verify", 31.0),
             _span("light.verify", 29.0), _span("light.verify", 9.0),
             _span("light.save", 100.0), _span("light.save", 101.0),
             _span("light.save", 30.0), _span("light.tile", 440.0)]
    ctx = _ctx(spans)
    assert _reader("plan_ms_per_tile.light")(ctx) == 300.0
    assert _reader("verify_ms_per_tile.light")(ctx) == 29.0
    assert _reader("save_ms_per_tile.light")(ctx) == 100.0
    # a program without the spans (the parent of PR 36)
    for name in ("plan_ms_per_tile.light", "verify_ms_per_tile.light",
                 "save_ms_per_tile.light"):
        assert _reader(name)(_ctx([_span("pipeline.apply", 1.0)])) is None


def test_counter_readers_on_hand_built_counters():
    c = {"light_device_lanes": 4074 * 3, "light_native_lanes": 42,
         "light_flushes": 3}
    ctx = _ctx(counters=c)
    assert _reader("lanes_per_flush.light")(ctx) == 4074.0
    assert _reader("device_lane_share.light")(ctx) == pytest.approx(
        100 * 12222 / 12264)
    cpu = _ctx(counters=dict(c, light_device_lanes=0, light_flushes=0))
    assert _reader("device_lane_share.light")(cpu) == 0.0
    assert _reader("lanes_per_flush.light")(cpu) is None
    for name in ("lanes_per_flush.light", "device_lane_share.light"):
        assert _reader(name)(_ctx()) is None


def test_the_light_entries_name_their_layer_and_the_cell(doc):
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name, source in (("plan_ms_per_tile.light", "program_span"),
                         ("verify_ms_per_tile.light", "program_span"),
                         ("save_ms_per_tile.light", "program_span"),
                         ("lanes_per_flush.light", "program_counter"),
                         ("device_lane_share.light", "program_counter")):
        m = by_name[name]
        assert (m["layer"], m["source"], m["moves"]) == (
            "light client", source, "catchup_sigs_per_s")
        assert CELL in m["workloads"]
    for name in ("prewarm_s", "pallas_dispatch_share.catchup",
                 "rlc_kernel_us_per_sig.catchup",
                 "rlc_kernel_roofline.catchup", "device_idle_share.catchup",
                 "prepare_ms_per_chunk.catchup",
                 "chunks_per_readback.catchup"):
        assert CELL in by_name[name]["workloads"]
    # the readers of `pipeline.*` spans find nothing in a light client
    for name in ("marshal_ms_per_tile.catchup", "settle_ms_per_tile.catchup",
                 "apply_ms_per_tile.catchup"):
        assert CELL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert CELL in e2e["catchup_sigs_per_s"]["workloads"]
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "light-seq-150", "tip-catch-up", 1)
    cfg = next(c for c in doc["configs"] if c["name"] == "light-seq-150")
    assert cfg["reduced"] == ["chain_headers"]
