"""The engine layer's third reader (`valset_encode_reuse_share`, PR 32):
on span lists written out here, on nothing to read (the parent's program
sets no such attribute), and on the spans of a pipelined catch-up through
each catch-up cell's own driver at a tiny size (CPU: 32 lanes a tile take
the native route)."""

import pickle
import time

import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest, validate
from benchmark.harness.runner import LayerContext

METRIC = "valset_encode_reuse_share.catchup"
ENTRY = {"name": METRIC, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "engine",
         "moves": "catchup_sigs_per_s",
         "workloads": ["catchup-200.steady", "catchup-200-churn.bad-peer"]}
MS = 1_000_000


def _span(name, t0_ms, ms, **attrs):
    span = {"name": name, "sid": 1, "tid": 1, "pid": 0,
            "t0": t0_ms * MS, "t1": (t0_ms + ms) * MS}
    if attrs:
        span["attrs"] = attrs
    return span


def _enc(computed, reused):
    return {"valset_enc_computed": computed, "valset_enc_reused": reused}


def _read(spans, blocks=0):
    ctx = LayerContext(cell=None, device={}, boot={},
                       result={"facts": {"blocks": blocks}, "counters": {}},
                       spans=spans)
    return Manifest(REPO).layer_reader(METRIC).read(ctx)


STEADY = [
    _span("pipeline.fetch", 0, 30, **_enc(0, 0)),
    _span("pipeline.apply", 130, 100, **_enc(16, 48)),
    _span("pipeline.apply", 300, 120, **_enc(16, 48)),
    # a set change inside the tile: two more first encodings
    _span("pipeline.apply", 420, 104, **_enc(18, 46)),
    # another stage's attributes of the same names are not apply's
    _span("pipeline.marshal", 600, 60, **_enc(5, 5)),
]


@pytest.mark.parametrize("spans, want", [
    (STEADY[:3], 75.0),
    (STEADY, 100.0 * 142 / 192),
    # a memo that never answers: every encoding computed
    ([_span("pipeline.apply", 0, 100, **_enc(64, 0))], 0.0),
], ids=["unchanged-set", "a-set-change", "dead-memo"])
def test_reader_on_written_spans(spans, want):
    assert _read(spans) == pytest.approx(want)


@pytest.mark.parametrize("spans", [
    [],
    # the parent's program: the stage spans with the CommitSig counters
    # alone
    [_span("pipeline.fetch", 0, 30, sig_enc_computed=3200, sig_enc_reused=0),
     _span("pipeline.apply", 130, 100, sig_enc_computed=0,
           sig_enc_reused=9600)],
    # the synchronous loop's spans (a CPU run of the cell)
    [_span("blocksync.fetch", 0, 30), _span("blocksync.apply", 30, 100)],
    # apply spans that saved no state
    [_span("pipeline.apply", 0, 100, **_enc(0, 0))],
], ids=["no-spans", "parent-program", "synchronous-loop", "nothing-asked"])
def test_nothing_to_read_is_none_and_does_not_raise(spans):
    assert _read(spans) is None


def test_the_share_prints_both_sums_beside_the_blocks(capsys):
    _read(STEADY, blocks=48)
    assert "computed 50 reused 142 (blocks 48)" in capsys.readouterr().out


def test_the_metrics_entry_validates_and_a_traced_run_takes_it(
        doc, tiny_root, fresh_sigcache):
    """`BENCHMARK.json` lists the metric since PR 33, as PR 32 wrote
    ENTRY, beside the engine layer's other share and for the cells that
    one had then (a later PR may have listed either for more). A traced
    run of the cell asks the reader: on a CPU, where the sync is the
    synchronous loop, it has nothing to read, leaves the metric out and
    does not raise."""
    entries = {m["name"]: m for m in doc["per_layer"]}
    listed = entries[METRIC]
    beside = entries["commit_encode_reuse_share.catchup"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert ENTRY[key] == listed[key] == beside[key], key
    for m in (listed, beside):
        assert set(ENTRY["workloads"]) <= set(m["workloads"])
    cell = ENTRY["workloads"][0]
    tiny = Manifest(tiny_root)
    assert validate(tiny.doc) == []
    assert METRIC in {m["name"] for m in tiny.per_layer_for(cell)}
    out = runner.run_cell(tiny_root, cell, 2**31 + 33, 2.0, True,
                          time.perf_counter(), look_for_chip=False,
                          in_process_traffic=True)
    assert out["correct"] and METRIC not in out["metrics"]


# the cells PR 32 wrote the reader for, by name: a catch-up cell that a
# later PR adds need not apply blocks alike
@pytest.mark.parametrize("cell_name", ENTRY["workloads"])
def test_reader_on_a_pipelined_sync_through_the_cells_driver(
        cell_name, tiny_root, fresh_sigcache):
    """Four encodings asked for a block applied, whatever the route the
    block took, and one of them computed, whether the set changed or
    not: a change touches next_validators alone, whose priorities
    rotate, and whose encoding is new, at every height anyway."""
    from cometbft_tpu import trace
    manifest = Manifest(tiny_root)
    cell = manifest.cell(cell_name)
    params = {"seed": 2**31 + 32, "seconds": 4.0, "config": cell.config,
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
    applies = [s["attrs"] for s in spans if s["name"] == "pipeline.apply"]
    computed = sum(a["valset_enc_computed"] for a in applies)
    reused = sum(a["valset_enc_reused"] for a in applies)
    blocks = result["facts"]["blocks"]
    assert computed + reused == 4 * blocks
    # the first save of a sync has only `validators` to reuse
    assert computed == blocks + 2
    # (and the cell whose set changes did change it)
    assert ("churn" in cell_name) == (
        result["facts"].get("set_changes", 0) > 0)
    ctx = LayerContext(cell=cell, device={}, boot={}, result=result,
                       spans=spans, manifest=manifest)
    share = manifest.layer_reader(METRIC).read(ctx)
    assert share == pytest.approx(75.0 - 50.0 / blocks)
