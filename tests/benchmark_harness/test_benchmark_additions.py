"""The rule of benchmark/README.md "Adding things", held on the real
manifest at every run: a later PR adds a configuration, a cell or a
per-layer metric by new files and appended entries alone, and no test of
this directory is in its way.

`tree` is here the real tree with what such a PR would leave: a second
hub configuration and its cell, listed wherever the hub cell is; one more
catch-up cell that is a traffic file alone, listed wherever the steady
cell is but for one metric; two more `per_layer` entries at the END. Every
test of this directory that takes `tree` or `doc` (conftest.py) is
collected here once more and judges that tree with the same code. A test
written later that holds `BENCHMARK.json` to today's lists (a position, a
length, a list's equality) fails here, in the PR that writes it; one
that reads the real manifest around the fixtures fails the last test."""

import glob
import importlib
import inspect
import os
import re
import time

import pytest

from conftest import TINY_REL, add_to_tree, real_json, tree_copy
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest, validate

HERE = os.path.dirname(os.path.abspath(__file__))
HUB, ADDED_HUB = "hub-live-150.cold-commit", "added-hub.closed-loop-commits"
STEADY, ADDED_MIX = "catchup-200.steady", "catchup-200.added-mix"
NOT_FOR_THE_ADDED_MIX = "prepare_ms_per_chunk.catchup"
ADDED = ["added_count.commit", "added_count.catchup"]


def _files() -> dict:
    """New files only, each a copy of one that is there under a new
    name, and one reader (which reads on a CPU too)."""
    return {
        "benchmark/configs/added-hub.json":
            dict(real_json("benchmark", "configs", "hub-live-150.json"),
                 name="added-hub"),
        os.path.join(TINY_REL, "configs", "added-hub.json"):
            real_json(TINY_REL, "configs", "hub-live-150.json"),
        "benchmark/traffic/added-mix.json":
            dict(real_json("benchmark", "traffic", "steady-fresh-chain.json"),
                 name="added-mix"),
        os.path.join(TINY_REL, "traffic", "added-mix.json"):
            real_json(TINY_REL, "traffic", "steady-fresh-chain.json"),
        "benchmark/layer_metrics/added_count.py":
            "def read(ctx):\n    return ctx.result['attempted']\n",
    }


def _entries(doc: dict) -> None:
    hub = next(c for c in doc["configs"] if c["name"] == "hub-live-150")
    doc["configs"].append(dict(hub, name="added-hub",
                               file="benchmark/configs/added-hub.json"))
    doc["workloads"] += [
        {"name": ADDED_HUB, "config": "added-hub",
         "traffic": "closed-loop-commits", "chips": 1, "why": "a test"},
        {"name": ADDED_MIX, "config": "catchup-200", "traffic": "added-mix",
         "chips": 1, "why": "a test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if HUB in cells:
            cells.append(ADDED_HUB)
        if STEADY in cells and m["name"] != NOT_FOR_THE_ADDED_MIX:
            cells.append(ADDED_MIX)
    common = {"unit": "1", "better": "higher", "source": "program_counter"}
    doc["per_layer"] += [
        dict(common, name=ADDED[0], layer="crypto seam",
             moves="commit_verify_p50_ms", workloads=[HUB, ADDED_HUB]),
        dict(common, name=ADDED[1], layer="engine",
             moves="catchup_sigs_per_s", workloads=[STEADY, ADDED_MIX])]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """Overrides conftest.py's: the real tree, copied, with the
    additions made as a PR makes them."""
    dst = tree_copy(str(tmp_path_factory.mktemp("additions") / "tree"))
    add_to_tree(dst, _files(), _entries)
    return dst


def _collect_the_suites_manifest_tests() -> None:
    """Every test of this directory that takes `tree` or `doc`, under
    its own name in this module: pytest collects it here with this
    module's `tree`."""
    for path in sorted(glob.glob(os.path.join(HERE, "test_*.py"))):
        name = os.path.splitext(os.path.basename(path))[0]
        if name == __name__:
            continue
        for test, fn in vars(importlib.import_module(name)).items():
            if not (test.startswith("test") and inspect.isfunction(fn)
                    and {"tree", "doc"} & set(
                        inspect.signature(fn).parameters)):
                continue
            if test in globals():
                raise RuntimeError(
                    f"two tests of this directory that take the manifest "
                    f"are called {test}: rename the one in {name}.py")
            globals()[test] = fn


_collect_the_suites_manifest_tests()


def test_the_additions_are_new_files_and_appended_entries(tree, doc):
    """What was there is unchanged and in its place; a list of cells has
    grown at its end only; the new per-layer entries stand last."""
    real = real_json("BENCHMARK.json")
    assert validate(doc) == [] and set(doc) == set(real)
    for key, was in real.items():
        if not isinstance(was, list) or not isinstance(was[0], dict):
            assert doc[key] == was, key
            continue
        assert len(doc[key]) >= len(was), key
        for old, new in zip(was, doc[key]):
            cells = old.get("workloads", [])
            assert new.get("workloads", [])[:len(cells)] == cells
            assert dict(new, workloads=cells) == dict(old, workloads=cells)
    assert [m["name"] for m in doc["per_layer"]][-2:] == ADDED
    manifest = Manifest(tree)
    added = {m["name"] for m in manifest.per_layer_for(ADDED_MIX)}
    steady = {m["name"] for m in manifest.per_layer_for(STEADY)}
    assert steady - added == {NOT_FOR_THE_ADDED_MIX}


@pytest.mark.parametrize("cell, like, added", [
    (ADDED_HUB, HUB, ADDED[0]), (ADDED_MIX, STEADY, ADDED[1])])
def test_an_added_cell_runs_as_the_cell_it_copies(tiny_root, fresh_sigcache,
                                                  cell, like, added):
    manifest = Manifest(tiny_root)

    def run(name, seed, trace):
        out = runner.run_cell(tiny_root, name, 2**31 + seed, 2.0, trace,
                              time.perf_counter(), look_for_chip=False,
                              in_process_traffic=True)
        assert out["correct"] and out["failed"] == 0
        return out
    plain, beside = run(cell, 331, False), run(like, 332, False)
    assert set(plain["metrics"]) == set(beside["metrics"]) == {
        m["name"] for m in manifest.end_to_end_for(cell)}
    assert plain["attempted"] == beside["attempted"] > 0
    traced = run(cell, 333, True)
    assert traced["metrics"][added]["value"] == plain["attempted"]
    assert set(traced["metrics"]) <= {
        m["name"] for m in manifest.per_layer_for(cell)}


READS_AROUND_THE_FIXTURES = re.compile(
    r"Manifest\(\s*REPO\s*\)\s*\.\s*(doc|cell|end_to_end_for|per_layer_for)"
    r"|(load_json|open)\(\s*os\.path\.join\(\s*REPO,\s*\"BENCHMARK\.json\""
    r"|real_json\(\s*\"BENCHMARK\.json\"")


def test_no_test_reads_the_real_manifest_around_the_fixtures():
    """Such a test would judge the real BENCHMARK.json alone, and could
    pin its lists unseen: it takes `tree` or `doc` instead. (Loading a
    reader, a generator or the peaks through `Manifest(REPO)` asserts
    nothing of the manifest, and this file reads the real one to compare
    its own tree with.)"""
    found = []
    for path in sorted(glob.glob(os.path.join(HERE, "test_*.py"))):
        if os.path.abspath(path) == os.path.abspath(__file__):
            continue
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if READS_AROUND_THE_FIXTURES.search(line):
                    found.append(f"{os.path.basename(path)}:{n}")
    assert not found, found
