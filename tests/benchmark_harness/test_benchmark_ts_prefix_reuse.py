"""The light client layer's `ts_prefix_reuse_share`: on counters written
out here, on nothing to read (a program that keeps no such counter), and
through the light cell's own run, where every commit's precommits share
their second: at the tiny size (8 validators) and with the published
150 validators over the tiny chain."""

import json
import os
import time
import types

import pytest

from conftest import REPO
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest, validate

METRIC = "ts_prefix_reuse_share.light"
CELL = "light-seq-150.tip-catch-up"
ENTRY = {"name": METRIC, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "light client",
         "moves": "catchup_sigs_per_s", "workloads": [CELL]}


def _read(counters):
    ctx = types.SimpleNamespace(spans=[], result={"counters": counters,
                                                  "facts": {}})
    return Manifest(REPO).layer_reader(METRIC).read(ctx)


@pytest.mark.parametrize("counters, want", [
    # the cell's chain: 150 lanes a commit, one second a commit
    ({"light_sig_ts_prefix_reused": 149 * 4095,
      "light_sig_encodings": 150 * 4095}, 100.0 * 149 / 150),
    # precommits that straddle a second in every commit
    ({"light_sig_ts_prefix_reused": 148 * 195,
      "light_sig_encodings": 150 * 195}, 100.0 * 148 / 150),
    # one lane a commit
    ({"light_sig_ts_prefix_reused": 0, "light_sig_encodings": 195}, 0.0),
], ids=["one-second-a-commit", "two-seconds-a-commit", "one-lane"])
def test_reader_on_written_counters(counters, want):
    assert _read(counters) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {},
    # a program that keeps no such counter: the walk's other counters
    {"light_headers": 4095, "light_tiles": 21,
     "light_set_hashes_reused": 4094},
    # nothing encoded
    {"light_sig_ts_prefix_reused": 0, "light_sig_encodings": 0},
], ids=["no-counters", "parent-program", "nothing-encoded"])
def test_nothing_to_read_is_none_and_does_not_raise(counters):
    assert _read(counters) is None


def test_the_entry_validates_beside_the_layers_others(doc):
    entries = {m["name"]: m for m in doc["per_layer"]}
    listed, beside = entries[METRIC], entries["set_hash_reuse_share.light"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert ENTRY[key] == listed[key] == beside[key], key
    assert CELL in listed["workloads"]


def _published_validators(root: str) -> None:
    """The tiny checkout's light cell with the configuration's 150
    validators and the published probes' lanes; the tiny chain lengths
    stay."""
    for rel, sizes in (
            (("configs", "light-seq-150.json"), {"validators": 150}),
            (("traffic", "tip-catch-up.json"),
             {"probe_bad_lane": 17, "probe_beyond_lane": 100,
              "probe_absent_heaviest": 12})):
        path = os.path.join(root, "benchmark", *rel)
        with open(path) as f:
            doc = json.load(f)
        doc.update(sizes)
        with open(path, "w") as f:
            json.dump(doc, f)


@pytest.mark.parametrize("validators", [8, 150])
def test_a_traced_run_reports_it(tiny_root, fresh_sigcache, validators):
    """Of every commit's lanes, all but the first reuse its seconds
    field: 7 of 8 at the tiny size, above 99 % with 150."""
    if validators == 150:
        _published_validators(tiny_root)
    tiny = Manifest(tiny_root)
    assert validate(tiny.doc) == []
    assert METRIC in {m["name"] for m in tiny.per_layer_for(CELL)}
    out = runner.run_cell(tiny_root, CELL, 2**31 + 41, 2.0, True,
                          time.perf_counter(), look_for_chip=False,
                          in_process_traffic=True)
    assert out["correct"]
    share = out["metrics"][METRIC]["value"]
    assert share == pytest.approx(100.0 * (validators - 1) / validators)
    assert validators == 8 or share > 99.0
