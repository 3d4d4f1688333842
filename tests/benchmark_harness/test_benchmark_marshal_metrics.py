"""The host marshal's two readers added by PR 30 (`prepare_ms_per_chunk`,
`sign_bytes_template_share`): on span lists written out here, on nothing
to read, and on the spans of a pipelined catch-up through the cell's own
driver at a tiny size (CPU: 32 lanes a tile take the native route, so
there is a template share to read and no chunk)."""

import pickle

import pytest

from conftest import REPO
from benchmark.harness.manifest import Manifest
from benchmark.harness.runner import LayerContext

CATCHUP = "catchup-200.steady"
MS = 1_000_000


def _span(name, t0_ms, ms, **attrs):
    span = {"name": name, "sid": 1, "tid": 1, "pid": 0,
            "t0": int(t0_ms * MS), "t1": int((t0_ms + ms) * MS)}
    if attrs:
        span["attrs"] = attrs
    return span


def _tpl(built, served):
    return {"sign_bytes_templates": built, "sign_bytes_templated": served}


def _read(metric, spans, lanes=0):
    ctx = LayerContext(cell=None, device={}, boot={},
                       result={"facts": {"lanes": lanes}, "counters": {}},
                       spans=spans)
    return Manifest(REPO).layer_reader(metric).read(ctx)


TILES = [
    _span("pipeline.marshal", 0, 30, **_tpl(16, 3184)),
    _span("ed25519.prepare", 31, 0.5, lanes=512, batch_size=512),
    _span("ed25519.prepare", 40, 0.25, lanes=512, batch_size=512),
    _span("ed25519.prepare", 50, 0.75, lanes=128, batch_size=512),
    _span("pipeline.marshal", 100, 28, **_tpl(16, 3184)),
    # a tile cut short by a set change, and one the sigcache answered
    _span("pipeline.marshal", 200, 9, **_tpl(8, 1592)),
    _span("pipeline.marshal", 300, 2, **_tpl(0, 0)),
    # another stage's attributes of the same names are not the marshal's
    _span("pipeline.apply", 400, 80, **_tpl(7, 7)),
]


@pytest.mark.parametrize("metric, spans, want", [
    ("prepare_ms_per_chunk.catchup", TILES, 0.5),
    # nearest rank: of two, the lower, a value that was measured
    ("prepare_ms_per_chunk.catchup", TILES[:3], 0.25),
    ("prepare_ms_per_chunk.commit",
     [_span("ed25519.prepare", 0, 0.125, lanes=150, batch_size=512)], 0.125),
    ("sign_bytes_template_share.catchup", TILES, 100.0 * 7960 / 8000),
    ("sign_bytes_template_share.catchup", TILES[:1], 99.5),
    # commits of one lane: every call builds, none is served
    ("sign_bytes_template_share.catchup",
     [_span("pipeline.marshal", 0, 1, **_tpl(16, 0))], 0.0),
])
def test_readers_on_written_spans(metric, spans, want):
    assert _read(metric, spans, lanes=8000) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["prepare_ms_per_chunk.catchup",
                                    "prepare_ms_per_chunk.commit",
                                    "sign_bytes_template_share.catchup"])
@pytest.mark.parametrize("spans", [
    [],
    # a program from before PR 30: no prepare span, a bare marshal span
    [_span("pipeline.tile", 0, 90), _span("pipeline.fetch", 0, 30),
     _span("pipeline.marshal", 30, 60), _span("pipeline.settle", 90, 40),
     _span("pipeline.apply", 130, 80)],
    # the synchronous loop's spans (a CPU run of the cell)
    [_span("blocksync.fetch", 0, 30), _span("blocksync.apply", 30, 100)],
    # a tile whose every lane the sigcache answered before its sign-bytes
    # were asked for cannot happen, but a marshal span of nothing can
    [_span("pipeline.marshal", 0, 1, **_tpl(0, 0))],
], ids=["no-spans", "parent-program", "synchronous-loop", "empty-tile"])
def test_nothing_to_read_is_none_and_does_not_raise(metric, spans):
    assert _read(metric, spans) is None


def test_the_template_share_prints_both_sums_beside_the_lanes(capsys):
    _read("sign_bytes_template_share.catchup", TILES, lanes=8000)
    assert ("built 40 lanes served from one 7960 (lanes 8000)"
            in capsys.readouterr().out)


def test_the_new_entries_name_their_layer_and_cells(doc):
    """Each entry by its name, each cell by membership: the cells PR 30
    listed an entry for, whatever a later PR has listed it for since,
    and wherever in the list the entry stands."""
    entries = {m["name"]: m for m in doc["per_layer"]}
    catchup = {"catchup-200.steady", "catchup-200-churn.bad-peer"}
    for name, source, moves, cells in [
            ("prepare_ms_per_chunk.catchup", "program_span",
             "catchup_sigs_per_s", catchup),
            ("prepare_ms_per_chunk.commit", "program_span",
             "commit_verify_p50_ms", {"hub-live-150.cold-commit"}),
            ("sign_bytes_template_share.catchup", "program_counter",
             "catchup_sigs_per_s", catchup)]:
        m = entries[name]
        assert m["layer"] == "pipeline + host marshal"
        assert (m["source"], m["moves"]) == (source, moves)
        assert cells <= set(m["workloads"])


def test_readers_on_a_pipelined_sync_through_the_cells_driver(
        tiny_root, fresh_sigcache):
    """A commit's first lane builds its template and the others are
    served from it: over the window, one template a commit marshalled."""
    from cometbft_tpu import trace
    manifest = Manifest(tiny_root)
    cell = manifest.cell(CATCHUP)
    params = {"seed": 2**31 + 30, "seconds": 4.0, "config": cell.config,
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
    lanes, blocks = result["facts"]["lanes"], result["facts"]["blocks"]
    ctx = LayerContext(cell=cell, device={}, boot={}, result=result,
                       spans=spans, manifest=manifest)
    share = manifest.layer_reader(
        "sign_bytes_template_share.catchup").read(ctx)
    assert share == pytest.approx(100.0 * (lanes - blocks) / lanes)
    assert sum(s["attrs"]["sign_bytes_templates"] for s in spans
               if s["name"] == "pipeline.marshal") == blocks
    # 32 lanes a tile take the native route: no chunk, nothing to read
    assert manifest.layer_reader(
        "prepare_ms_per_chunk.catchup").read(ctx) is None
