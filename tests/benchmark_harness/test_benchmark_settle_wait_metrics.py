"""The two readers PR 35 added for the hand-over of a tile between the
dispatch thread and the main thread (`settle_wait_ms_per_tile`, layer
`pipeline settle`; `chunks_per_readback`, layer `dispatch`): on span
lists written out here, on nothing to read (the parent's program opens
neither span), and on the spans of a pipelined catch-up through each
catch-up cell's own driver at a tiny size, where the real chunking loop
runs under a stand-in for the kernel (CPU: nothing is jitted)."""

import pickle

import numpy as np
import pytest

from conftest import REPO
from benchmark.harness.manifest import Manifest
from benchmark.harness.runner import LayerContext

WAIT, CHUNKS = "settle_wait_ms_per_tile.catchup", "chunks_per_readback.catchup"
CATCHUP = ["catchup-200.steady", "catchup-200-churn.bad-peer"]
MS = 1_000_000


def _span(name, t0_ms, ms, **attrs):
    span = {"name": name, "sid": 1, "tid": 1, "pid": 0,
            "t0": int(t0_ms * MS), "t1": int((t0_ms + ms) * MS)}
    if attrs:
        span["attrs"] = attrs
    return span


def _rb(t0_ms, chunks, lanes, attributed=0):
    return _span("ed25519.readback", t0_ms, 3, chunks=chunks, lanes=lanes,
                 attributed_chunks=attributed)


def _read(metric, spans):
    ctx = LayerContext(cell=None, device={}, boot={},
                       result={"facts": {}, "counters": {}}, spans=spans)
    return Manifest(REPO).layer_reader(metric).read(ctx)


TILES = [
    _span("pipeline.settle", 100, 14), _span("pipeline.settle.wait", 100, 2),
    _rb(90, 7, 3200),
    _span("pipeline.settle", 250, 12), _span("pipeline.settle.wait", 250, .5),
    _rb(240, 7, 3200),
    _span("pipeline.settle", 400, 40), _span("pipeline.settle.wait", 400, 28),
    # the churn cell's: a tile cut short, a commit on the synchronous
    # route (one chunk a call), a strict-mode call that read nothing back
    _rb(390, 3, 1400, attributed=1), _rb(410, 1, 200), _rb(420, 0, 64),
    # a tile the sigcache answered settles without a wait
    _span("pipeline.settle", 500, 1),
]


@pytest.mark.parametrize("metric, spans, want", [
    (WAIT, TILES, 2.0),
    # nearest rank: of two, the lower, a value that was measured
    (WAIT, TILES[:5], 0.5),
    (CHUNKS, TILES[:6], 7.0),
    (CHUNKS, TILES, 18 / 4),
    # a loop that reads each chunk back before it prepares the next
    (CHUNKS, [_rb(t, 1, 512) for t in range(7)], 1.0),
])
def test_readers_on_written_spans(metric, spans, want):
    assert _read(metric, spans) == pytest.approx(want)


@pytest.mark.parametrize("metric", [WAIT, CHUNKS])
@pytest.mark.parametrize("spans", [
    [],
    # the parent's program: settle is one span, the loop has `prepare`
    [_span("pipeline.tile", 0, 90), _span("pipeline.marshal", 30, 60),
     _span("ed25519.prepare", 91, 0.5, lanes=512, batch_size=512),
     _span("pipeline.settle", 90, 48), _span("pipeline.apply", 140, 48)],
    # the synchronous loop's spans (a CPU run of the cell)
    [_span("blocksync.fetch", 0, 30), _span("blocksync.apply", 30, 100)],
    # strict mode alone, and tiles that the sigcache answered
    [_rb(0, 0, 64), _span("pipeline.settle", 10, 1)],
], ids=["no-spans", "parent-program", "synchronous-loop", "nothing-waited"])
def test_nothing_to_read_is_none_and_does_not_raise(metric, spans):
    assert _read(metric, spans) is None


def test_the_chunk_count_is_printed_beside_its_read_backs(capsys):
    _read(CHUNKS, TILES)
    assert "18 chunks in 4 read-backs" in capsys.readouterr().out


def test_the_handover_entries_name_their_layer_and_cells(doc):
    """Each entry by its name, the cells by membership: the two catch-up
    cells, whatever a later PR has listed an entry for since, and no
    cell that reports no `catchup_sigs_per_s` (one chunk a call, no
    pipeline: the hub cells have neither span's meaning)."""
    entries = {m["name"]: m for m in doc["per_layer"]}
    rate = next(m for m in doc["end_to_end"]
                if m["name"] == "catchup_sigs_per_s")
    for name, unit, better, source, layer in [
            (WAIT, "ms", "lower", "program_span", "pipeline settle"),
            (CHUNKS, "chunks", "higher", "program_counter", "dispatch")]:
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, better, source, layer,
                                "catchup_sigs_per_s")
        assert set(CATCHUP) <= set(m["workloads"]) <= set(rate["workloads"])
    assert entries[WAIT]["layer"] \
        == entries["settle_ms_per_tile.catchup"]["layer"]
    assert entries[CHUNKS]["layer"] \
        == entries["pallas_dispatch_share.catchup"]["layer"]


@pytest.mark.parametrize("cell_name", CATCHUP)
def test_readers_on_a_pipelined_sync_through_the_cells_driver(
        cell_name, tiny_root, fresh_sigcache, monkeypatch):
    """The program's own spans, read by the readers: one wait a tile
    that was dispatched, and for every call of the chunking loop its
    chunks read back at once (8-lane chunks here, under a kernel
    stand-in that accepts what the native check accepts)."""
    from cometbft_tpu import trace
    from cometbft_tpu.crypto.keys import verify_native
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.types import validation
    calls = []

    def accept(pub_a, sig_a, hb, hn, z):
        return True, np.ones(pub_a.shape[0], dtype=bool)

    def verify_batch(pubs, msgs, sigs, batch_size=None):
        calls.append(len(pubs))
        shaped = e5._verify_batch_loop(pubs, msgs, sigs, 8, accept, None)
        return shaped & verify_native(pubs, msgs, sigs)

    monkeypatch.setattr(e5, "verify_batch", verify_batch)
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 4)
    manifest = Manifest(tiny_root)
    cell = manifest.cell(cell_name)
    params = {"seed": 2**31 + 35, "seconds": 4.0, "config": cell.config,
              "traffic": cell.traffic}
    payload = pickle.loads(pickle.dumps(
        manifest.load_module("generators", cell.traffic["generator"]).make(
            params), protocol=pickle.HIGHEST_PROTOCOL))
    driver = manifest.load_module("drivers", cell.config["driver"])
    # a bucket over 0 builds the reactor pipelined, as on the chip
    session = driver.build(cell.config, cell.traffic, payload,
                           {"batch": 64, "prewarm_s": 0.0}, params["seed"])
    del calls[:]                    # the driver's warm-up pass
    before = e5.batch_stats()
    trace.enable(seed=0, ring=1 << 14)
    try:
        result = driver.window(session, params["seconds"])
        spans = trace.shared_recorder().snapshot()
    finally:
        trace.disable()
    assert result["failed"] == 0 and calls
    ctx = LayerContext(cell=cell, device={}, boot={}, result=result,
                       spans=spans, manifest=manifest)
    chunks = e5.batch_stats()["chunks"] - before["chunks"]
    assert chunks == sum(-(-n // 8) for n in calls) > len(calls)
    assert manifest.layer_reader(CHUNKS).read(ctx) \
        == pytest.approx(chunks / len(calls))
    waits = [s for s in spans if s["name"] == "pipeline.settle.wait"]
    settles = {s["sid"]: s for s in spans if s["name"] == "pipeline.settle"}
    # a wait is inside the settle that caused it, one a dispatched tile
    assert 0 < len(waits) <= len(settles)
    for w in waits:
        parent = settles[w["pid"]]
        assert parent["t0"] <= w["t0"] and w["t1"] <= parent["t1"]
    wait = manifest.layer_reader(WAIT).read(ctx)
    assert 0 <= wait <= manifest.layer_reader(
        "settle_ms_per_tile.catchup").read(ctx)
