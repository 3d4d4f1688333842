"""The engine layer's two readers (`apply_ms_per_tile`,
`commit_encode_reuse_share`): on span lists written out here, on nothing
to read, and on the spans of a pipelined catch-up through the cell's own
driver at a tiny size (CPU: 32 lanes a tile take the native route)."""

import time

import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest
from benchmark.harness.runner import LayerContext

CATCHUP = "catchup-200.steady"
MS = 1_000_000


def _span(name, t0_ms, ms, **attrs):
    span = {"name": name, "sid": 1, "tid": 1, "pid": 0,
            "t0": t0_ms * MS, "t1": (t0_ms + ms) * MS}
    if attrs:
        span["attrs"] = attrs
    return span


def _enc(computed, reused):
    return {"sig_enc_computed": computed, "sig_enc_reused": reused}


def _read(metric, spans, lanes=0):
    ctx = LayerContext(cell=None, device={}, boot={},
                       result={"facts": {"lanes": lanes}, "counters": {}},
                       spans=spans)
    return Manifest(REPO).layer_reader(metric).read(ctx)


STEADY = [
    _span("pipeline.tile", 0, 90),
    _span("pipeline.fetch", 0, 30, **_enc(3200, 0)),
    _span("pipeline.marshal", 30, 60),
    _span("pipeline.settle", 90, 40),
    _span("pipeline.apply", 130, 100, **_enc(0, 9600)),
    _span("pipeline.fetch", 230, 31, **_enc(3000, 200)),
    _span("pipeline.apply", 300, 120, **_enc(200, 9000)),
    _span("pipeline.apply", 420, 104, **_enc(0, 600)),
    # another stage's attributes of the same names are not the engine's
    _span("pipeline.marshal", 600, 60, **_enc(5, 5)),
]


@pytest.mark.parametrize("metric, spans, want", [
    ("apply_ms_per_tile.catchup", STEADY, 104.0),
    ("apply_ms_per_tile.catchup", STEADY[:5], 100.0),
    ("commit_encode_reuse_share.catchup", STEADY,
     100.0 * 19400 / (6400 + 19400)),
    ("commit_encode_reuse_share.catchup", STEADY[:5], 75.0),
    # the first pass of a chain: everything computed, nothing reused yet
    ("commit_encode_reuse_share.catchup",
     [_span("pipeline.fetch", 0, 30, **_enc(3200, 0))], 0.0),
])
def test_readers_on_written_spans(metric, spans, want):
    assert _read(metric, spans, lanes=6400) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["apply_ms_per_tile.catchup",
                                    "commit_encode_reuse_share.catchup"])
@pytest.mark.parametrize("spans", [
    [],
    # a program from before PR 27: the pipeline's spans without an apply
    # span and without the counters
    [_span("pipeline.tile", 0, 90), _span("pipeline.fetch", 0, 30),
     _span("pipeline.marshal", 30, 60), _span("pipeline.settle", 90, 40)],
    # the synchronous loop's spans (a CPU run of the cell)
    [_span("blocksync.fetch", 0, 30), _span("blocksync.apply", 30, 100)],
], ids=["no-spans", "parent-program", "synchronous-loop"])
def test_nothing_to_read_is_none_and_does_not_raise(metric, spans):
    assert _read(metric, spans) is None


def test_the_reuse_share_prints_both_sums_beside_the_lanes(capsys):
    _read("commit_encode_reuse_share.catchup", STEADY, lanes=6400)
    assert ("computed 6400 reused 19400 (lanes 6400)"
            in capsys.readouterr().out)


def test_a_traced_cpu_run_of_the_cell_leaves_both_out(tiny_root,
                                                      fresh_sigcache):
    out = runner.run_cell(tiny_root, CATCHUP, 2**31 + 27, 2.0, True,
                          time.perf_counter(), look_for_chip=False,
                          in_process_traffic=True)
    # the node's bucket is 0 on a CPU, so the sync is the synchronous loop
    assert out["correct"] and not set(out["metrics"]) & {
        "apply_ms_per_tile.catchup", "commit_encode_reuse_share.catchup"}


def test_readers_on_a_pipelined_sync_through_the_cells_driver(
        tiny_root, fresh_sigcache):
    """The node pays every first encoding: the chain comes through a
    pickle, as from the generator's child, and `computed` over the
    window is the signatures the window served."""
    import pickle
    from cometbft_tpu import trace
    manifest = Manifest(tiny_root)
    cell = manifest.cell(CATCHUP)
    params = {"seed": 2**31 + 28, "seconds": 4.0, "config": cell.config,
              "traffic": cell.traffic}
    payload = pickle.loads(pickle.dumps(
        manifest.load_module("generators", "fresh_chain").make(params),
        protocol=pickle.HIGHEST_PROTOCOL))
    driver = manifest.load_module("drivers", cell.config["driver"])
    # a bucket over 0 builds the reactor pipelined, as on the chip
    session = driver.build(cell.config, cell.traffic, payload,
                           {"batch": 64, "prewarm_s": 0.0}, params["seed"])
    trace.enable(seed=0)
    try:
        result = driver.window(session, params["seconds"])
        spans = trace.shared_recorder().snapshot()
    finally:
        trace.disable()
    assert result["failed"] == 0
    lanes, tiles = result["facts"]["lanes"], result["facts"]["tiles"]
    validators = cell.config["validators"]
    assert lanes == result["facts"]["blocks"] * validators and tiles >= 4
    ctx = LayerContext(cell=cell, device={}, boot={}, result=result,
                       spans=spans, manifest=manifest)
    apply_ms = manifest.layer_reader("apply_ms_per_tile.catchup").read(ctx)
    assert apply_ms is not None and apply_ms > 0
    assert len([s for s in spans if s["name"] == "pipeline.apply"]) == tiles
    share = manifest.layer_reader(
        "commit_encode_reuse_share.catchup").read(ctx)
    reused = 3 * (lanes - validators)       # the tip's seal is only stored
    assert share == pytest.approx(100.0 * reused / (lanes + reused))
    assert 70.0 < share < 75.0
