"""The `pipeline settle` layer's `sigcache_keyed_insert_share`: on spans
written out here, on nothing to read (a program whose settle and save
spans carry no insert counts), and on the spans of each of its three
cells at a tiny size: a pipelined catch-up through each catch-up cell's
own driver, where the real chunking loop runs under a stand-in for the
kernel (CPU: nothing is jitted), and a traced run of the light cell."""

import pickle
import time

import numpy as np
import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest
from benchmark.harness.runner import LayerContext

METRIC = "sigcache_keyed_insert_share.catchup"
CATCHUP = ["catchup-200.steady", "catchup-200-churn.bad-peer"]
LIGHT = "light-seq-150.tip-catch-up"
MS = 1_000_000


def _span(name, t0_ms, **attrs):
    span = {"name": name, "sid": 1, "tid": 1, "pid": 0,
            "t0": int(t0_ms * MS), "t1": int((t0_ms + 5) * MS)}
    if attrs:
        span["attrs"] = attrs
    return span


def _inserts(name, t0_ms, inserted, keyed):
    return _span(name, t0_ms, sigcache_inserted=inserted,
                 sigcache_inserted_keyed=keyed)


def _read(spans):
    ctx = LayerContext(cell=None, device={}, boot={},
                       result={"facts": {}, "counters": {}}, spans=spans)
    return Manifest(REPO).layer_reader(METRIC).read(ctx)


@pytest.mark.parametrize("spans, want", [
    ([_inserts("pipeline.settle", t, 3200, 3200) for t in (0, 10, 20)],
     100.0),
    ([_inserts("light.save", 0, 8190, 8190),
      _inserts("light.save", 10, 8190, 8190)], 100.0),
    # lanes hashed again by `add` beside keyed ones
    ([_inserts("pipeline.settle", 0, 3200, 3200),
      _inserts("pipeline.settle", 10, 800, 0)], 80.0),
    # a settle that inserted nothing (every lane a hit) weighs nothing;
    # spans of other names are not read
    ([_inserts("pipeline.settle", 0, 0, 0),
      _inserts("pipeline.settle", 10, 100, 50),
      _inserts("pipeline.apply", 20, 900, 0)], 50.0),
], ids=["catch-up", "light", "mixed", "empty-settle"])
def test_reader_on_written_spans(spans, want):
    assert _read(spans) == pytest.approx(want)


@pytest.mark.parametrize("spans", [
    [],
    # a program whose settle and save spans carry no insert counts
    [_span("pipeline.settle", 0), _span("pipeline.settle.wait", 0),
     _span("light.save", 10, sig_encodings=600, sig_ts_prefix_reused=595)],
    # counts that say nothing was inserted
    [_inserts("light.save", 0, 0, 0)],
], ids=["no-spans", "parent-program", "nothing-inserted"])
def test_nothing_to_read_is_none_and_does_not_raise(spans):
    assert _read(spans) is None


def test_the_entry_names_its_layer_and_cells(doc):
    """By membership: its three cells, whatever a later PR lists since,
    all of them cells that report the rate it moves; its layer is the
    settle stage's."""
    entries = {m["name"]: m for m in doc["per_layer"]}
    rate = next(m for m in doc["end_to_end"]
                if m["name"] == "catchup_sigs_per_s")
    m = entries[METRIC]
    assert (m["unit"], m["better"], m["source"], m["moves"]) == \
        ("%", "higher", "program_counter", "catchup_sigs_per_s")
    assert m["layer"] == entries["settle_ms_per_tile.catchup"]["layer"]
    assert set(CATCHUP + [LIGHT]) <= set(m["workloads"]) \
        <= set(rate["workloads"])


@pytest.mark.parametrize("cell_name", CATCHUP)
def test_a_pipelined_catch_up_inserts_every_lane_keyed(
        cell_name, tiny_root, fresh_sigcache, monkeypatch):
    from cometbft_tpu import trace
    from cometbft_tpu.crypto.keys import verify_native
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.types import validation

    def accept(pub_a, sig_a, hb, hn, z):
        return True, np.ones(pub_a.shape[0], dtype=bool)

    def verify_batch(pubs, msgs, sigs, batch_size=None):
        shaped = e5._verify_batch_loop(pubs, msgs, sigs, 8, accept, None)
        return shaped & verify_native(pubs, msgs, sigs)

    monkeypatch.setattr(e5, "verify_batch", verify_batch)
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 4)
    manifest = Manifest(tiny_root)
    cell = manifest.cell(cell_name)
    params = {"seed": 2**31 + 41, "seconds": 3.0, "config": cell.config,
              "traffic": cell.traffic}
    payload = pickle.loads(pickle.dumps(
        manifest.load_module("generators", cell.traffic["generator"]).make(
            params), protocol=pickle.HIGHEST_PROTOCOL))
    driver = manifest.load_module("drivers", cell.config["driver"])
    # a bucket over 0 builds the reactor pipelined, as on the chip
    session = driver.build(cell.config, cell.traffic, payload,
                           {"batch": 64, "prewarm_s": 0.0}, params["seed"])
    trace.enable(seed=0, ring=1 << 14)
    try:
        result = driver.window(session, params["seconds"])
        spans = trace.shared_recorder().snapshot()
    finally:
        trace.disable()
    assert result["failed"] == 0
    settles = [s["attrs"] for s in spans if s["name"] == "pipeline.settle"]
    assert settles and all(a["sigcache_inserted_keyed"]
                           == a["sigcache_inserted"] for a in settles)
    ctx = LayerContext(cell=cell, device={}, boot={}, result=result,
                       spans=spans, manifest=manifest)
    assert manifest.layer_reader(METRIC).read(ctx) == 100.0


def test_a_traced_light_run_reports_it(tiny_root, fresh_sigcache):
    out = runner.run_cell(tiny_root, LIGHT, 2**31 + 43, 2.0, True,
                          time.perf_counter(), look_for_chip=False,
                          in_process_traffic=True)
    assert out["correct"]
    assert out["metrics"][METRIC]["value"] == 100.0
