"""The light client layer's `set_hash_reuse_share` (PR 37): on counters
written out here, on nothing to read (the parent's program keeps no such
counter), and through the light cell's own run at its tiny sizes, where
every header but the target takes the hash of the set before it."""

import time
import types

import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest, validate

METRIC = "set_hash_reuse_share.light"
CELL = "light-seq-150.tip-catch-up"
ENTRY = {"name": METRIC, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "light client",
         "moves": "catchup_sigs_per_s", "workloads": [CELL]}


def _read(counters):
    ctx = types.SimpleNamespace(spans=[], result={"counters": counters,
                                                  "facts": {}})
    return Manifest(REPO).layer_reader(METRIC).read(ctx)


@pytest.mark.parametrize("counters, want", [
    # the cell's chain: every header but the target
    ({"light_set_hashes_reused": 4094, "light_headers": 4095},
     100.0 * 4094 / 4095),
    # a set that changes at every header
    ({"light_set_hashes_reused": 0, "light_headers": 195}, 0.0),
    ({"light_set_hashes_reused": 195, "light_headers": 195}, 100.0),
], ids=["constant-set", "changes-every-header", "every-header"])
def test_reader_on_written_counters(counters, want):
    assert _read(counters) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {},
    # the parent's program: the walk's other counters alone
    {"light_headers": 4095, "light_tiles": 21, "light_flushes": 21},
    # no header trusted
    {"light_set_hashes_reused": 0, "light_headers": 0},
], ids=["no-counters", "parent-program", "no-headers"])
def test_nothing_to_read_is_none_and_does_not_raise(counters):
    assert _read(counters) is None


def test_the_entry_validates_and_a_traced_run_reports_it(
        doc, tiny_root, fresh_sigcache):
    """Listed as ENTRY has it, beside the layer's other counter share; a
    traced run of the tiny cell (a tile a header on the CPU) reads it:
    of 39 headers trusted, all but the target reused a hash."""
    entries = {m["name"]: m for m in doc["per_layer"]}
    listed, beside = entries[METRIC], entries["device_lane_share.light"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert ENTRY[key] == listed[key] == beside[key], key
    assert CELL in listed["workloads"]
    tiny = Manifest(tiny_root)
    assert validate(tiny.doc) == []
    assert METRIC in {m["name"] for m in tiny.per_layer_for(CELL)}
    out = runner.run_cell(tiny_root, CELL, 2**31 + 37, 2.0, True,
                          time.perf_counter(), look_for_chip=False,
                          in_process_traffic=True)
    assert out["correct"]
    assert out["metrics"][METRIC]["value"] == pytest.approx(100.0 * 38 / 39)
