"""BENCHMARK.json is valid, every file it names loads by name, and every
cell it lists has the tiny sizes that the tests run it at. (`tree` and
`doc`: conftest.py; `test_benchmark_additions.py` runs these once more
on a tree with a later PR's additions.)"""

import json
import os

import pytest

from conftest import REPO, missing_tiny
from benchmark.harness.manifest import (NAME_RE, UNIT_RE, Manifest,
                                        ManifestError, validate)


def test_manifest_is_valid(tree, doc):
    assert validate(doc) == []
    assert 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(tree, "BENCHMARK.json")) < 64 * 1024


def test_names_and_units_keep_to_the_allowed_characters(doc):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in doc[group]:
            assert NAME_RE.match(row["name"]), row["name"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT_RE.match(m["unit"]) and len(m["unit"]) <= 16


def test_every_per_layer_cell_reports_what_the_metric_moves(doc):
    reports = {w["name"]: set() for w in doc["workloads"]}
    for m in doc["end_to_end"]:
        for c in m.get("workloads", list(reports)):
            reports[c].add(m["name"])
    for m in doc["per_layer"]:
        for c in m.get("workloads", list(reports)):
            assert m["moves"] in reports[c], (m["name"], c)


@pytest.mark.parametrize("breakage, complaint", [
    (lambda d: d["end_to_end"].pop(), "no setup_s"),
    (lambda d: d["per_layer"][1].update(moves="commit_verify_p50_ms"),
     "does not report"),
    (lambda d: d["end_to_end"][0].update(unit="sigs per second"),
     "bad unit"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d["end_to_end"][0].update(bound=0.5), "bound"),
])
def test_validate_names_the_fault(doc, breakage, complaint):
    broken = json.loads(json.dumps(doc))
    breakage(broken)
    assert any(complaint in line for line in validate(broken))


def test_every_named_file_loads(tree, doc):
    manifest = Manifest(tree)
    for w in doc["workloads"]:
        cell = manifest.cell(w["name"])
        driver = manifest.load_module("drivers", cell.config["driver"])
        for fn in ("warm", "build", "window", "judge"):
            assert callable(getattr(driver, fn))
        gen = manifest.load_module("generators", cell.traffic["generator"])
        assert callable(gen.make)
        for ref in cell.config["reference"]:
            manifest.load_module("reference", ref)
    for c in doc["configs"]:
        sizes = manifest.load_json(c["file"], from_repo=True)
        assert set(c["reduced"]) <= set(sizes), c["name"]
    for m in doc["per_layer"]:
        assert callable(manifest.layer_reader(m["name"]).read)
    assert manifest.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(ManifestError):
        manifest.peaks("TPU v9 imaginary")
    with pytest.raises(ManifestError):
        manifest.cell("no-such.cell")


def test_every_cell_has_its_tiny_sizes(tree):
    """A cell without them is left out of the tests' tiny checkout
    (conftest.py `make_tiny_root`), so no test would run it: it fails
    here, once, by name."""
    missing = [f"cell {cell}: {os.path.relpath(path, tree)} is missing"
               for cell, path in missing_tiny(tree)]
    assert not missing, (
        "; ".join(missing) + " (its tiny sizes: a JSON dict merged over "
        "the real file of that name, benchmark/README.md says how)")


def test_the_plain_reference_imports_nothing_of_the_program():
    ref_dir = os.path.join(REPO, "benchmark", "reference")
    for name in os.listdir(ref_dir):
        if name.endswith(".py"):
            with open(os.path.join(ref_dir, name)) as f:
                assert "cometbft_tpu" not in f.read(), name
