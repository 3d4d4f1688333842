"""Fixtures for the benchmark's own tests: a copy of the benchmark at a
tiny size (8 validators, 4-block tiles) that runs in seconds on the CPU
backend, where no kernel is traced or jitted (the node's bucket is 0
there and every signature takes the native route).

The tiny sizes are data, found by name: `tiny/configs/<config>.json` and
`tiny/traffic/<mix>.json` beside this file, each a dict merged over the
real file. A PR that lists a cell in `BENCHMARK.json` adds the two files
for it (where they are not there yet) and edits nothing here; the tests
that walk the cells (`CELLS`) then run it.

A test that asserts anything of `BENCHMARK.json` takes it from the
fixtures `tree` (the checkout whose manifest is judged) or `doc` (that
manifest), never from `REPO` itself: `test_benchmark_additions.py` runs
every such test once more on a tree to which a later PR's entries have
been appended, so a test that pins today's lists fails in the PR that
writes it."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import validate  # noqa: E402

TINY_REL = os.path.join("tests", "benchmark_harness", "tiny")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def real_json(*rel) -> dict:
    return load_json(os.path.join(REPO, *rel))


def _tiny_file(src: str, kind: str, name: str) -> str:
    return os.path.join(src, TINY_REL, kind, name + ".json")


def _survey(src: str) -> tuple:
    """`src`'s BENCHMARK.json, and `(cell, file)` for every file of tiny
    sizes that one of its cells needs and that is not there."""
    doc = load_json(os.path.join(src, "BENCHMARK.json"))
    return doc, [(w["name"], path) for w in doc["workloads"]
                 for path in (_tiny_file(src, "configs", w["config"]),
                              _tiny_file(src, "traffic", w["traffic"]))
                 if not os.path.isfile(path)]


def missing_tiny(src: str = REPO) -> list:
    return _survey(src)[1]


def tiny_cells(src: str = REPO) -> list:
    """The cells of `src`'s BENCHMARK.json that a tiny checkout holds:
    those whose tiny sizes are there."""
    doc, missing = _survey(src)
    without = {cell for cell, _path in missing}
    return [w["name"] for w in doc["workloads"] if w["name"] not in without]


def _without_cells(doc: dict, gone: set) -> dict:
    """`doc` with the cells `gone` taken out, and with them whatever
    only they used, so that what is left still validates."""
    doc = dict(doc, workloads=[w for w in doc["workloads"]
                               if w["name"] not in gone])
    used = {w["config"] for w in doc["workloads"]}
    doc["configs"] = [c for c in doc["configs"] if c["name"] in used]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in doc[group]:
            if "workloads" in m:
                m = dict(m, workloads=[c for c in m["workloads"]
                                       if c not in gone])
                if not m["workloads"]:
                    continue
            kept.append(m)
        doc[group] = kept
    return doc


def make_tiny_root(dst: str, src: str = REPO) -> str:
    """`dst` becomes a checkout that holds only BENCHMARK.json and the
    benchmark's files as `src` has them, with the sizes cut down. A cell
    whose tiny sizes are missing is left out, entries and files, never
    run at its published size (`missing_tiny` names it, and one test
    fails on it)."""
    doc, missing = _survey(src)
    doc = _without_cells(doc, {cell for cell, _path in missing})
    bench = doc["paths"][0]
    shutil.copytree(os.path.join(src, bench), os.path.join(dst, bench),
                    ignore=shutil.ignore_patterns("__pycache__", "configs",
                                                  "traffic"))
    tiny = {c["file"]: _tiny_file(src, "configs", c["name"])
            for c in doc["configs"]}
    for w in doc["workloads"]:
        tiny[os.path.join(bench, "traffic", w["traffic"] + ".json")] = \
            _tiny_file(src, "traffic", w["traffic"])
    for rel, override in tiny.items():
        sizes = load_json(os.path.join(src, rel))
        sizes.update(load_json(override))
        os.makedirs(os.path.dirname(os.path.join(dst, rel)), exist_ok=True)
        with open(os.path.join(dst, rel), "w") as f:
            json.dump(sizes, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return dst


def tree_copy(dst: str) -> str:
    """What the benchmark's tests read of the real tree, copied to
    `dst`, for a test to add to as a PR would: files and BENCHMARK.json
    entries."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, TINY_REL),
                    os.path.join(dst, TINY_REL))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    return dst


def add_to_tree(tree: str, files: dict, entries) -> None:
    """New files (`files`: path -> text or JSON value; a path that is
    there is refused) and the new entries that `entries(doc)` makes."""
    for rel, content in files.items():
        path = os.path.join(tree, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "x") as f:
            f.write(content if isinstance(content, str)
                    else json.dumps(content))
    path = os.path.join(tree, "BENCHMARK.json")
    doc = load_json(path)
    entries(doc)
    assert validate(doc) == []
    with open(path, "w") as f:
        json.dump(doc, f)


CELLS = tiny_cells()


@pytest.fixture
def tree():
    """The checkout whose BENCHMARK.json a test judges: the real one
    here, the one with a later PR's additions where
    `test_benchmark_additions.py` runs the same test."""
    return REPO


@pytest.fixture
def doc(tree):
    return load_json(os.path.join(tree, "BENCHMARK.json"))


@pytest.fixture
def tiny_root(tmp_path, tree):
    return make_tiny_root(str(tmp_path / "checkout"), tree)


@pytest.fixture
def fresh_sigcache():
    """The process-wide verified-signature cache is shared by every test
    of this worker: a run must start with none of its signatures seen."""
    from cometbft_tpu.pipeline.cache import reset_shared_cache
    reset_shared_cache()
    yield
    reset_shared_cache()
