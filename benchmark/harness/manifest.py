"""`BENCHMARK.json` and the files it names, found by name.

Layout under the benchmark's root directory (the manifest's first
`paths` entry):

    configs/<config>.json        sizes, guarantees, `driver`, `reference`
    traffic/<traffic>.json       `generator` + its parameters
    generators/<generator>.py    make(params) -> payload (runs in a CPU child)
    drivers/<driver>.py          warm / build / window / judge
    layer_metrics/<family>.py    read(ctx) -> number or None
    rooflines/<kernel>.py        work counts, pure functions of the traffic
    reference/<name>.py          the plain reference, imports nothing of
                                 the program
    peaks.json                   published peaks by device_kind

A per-layer metric `family.suffix` is read by `layer_metrics/family.py`;
the suffix is data (which cells, which end-to-end metric)."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
_modules: dict = {}


class ManifestError(ValueError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict


class Manifest:
    def __init__(self, repo_root: str):
        self.repo_root = os.path.abspath(repo_root)
        path = os.path.join(self.repo_root, "BENCHMARK.json")
        with open(path) as f:
            self.doc = json.load(f)
        self.bench_root = os.path.join(self.repo_root, self.doc["paths"][0])

    # --- cells ---------------------------------------------------------------

    def cell(self, name: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                break
        else:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (have "
                f"{[w['name'] for w in self.doc['workloads']]})")
        cfg = next((c for c in self.doc["configs"]
                    if c["name"] == w["config"]), None)
        if cfg is None:
            raise ManifestError(f"workload {name!r}: no config "
                                f"{w['config']!r}")
        return Cell(name=name, chips=int(w["chips"]), why=w["why"],
                    config_name=w["config"], traffic_name=w["traffic"],
                    config=self.load_json(cfg["file"], from_repo=True),
                    traffic=self.load_json(
                        os.path.join("traffic", w["traffic"] + ".json")))

    def end_to_end_for(self, cell: str) -> list:
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer_for(self, cell: str) -> list:
        return [m for m in self.doc["per_layer"]
                if cell in m.get("workloads", [cell])]

    # --- files by name -------------------------------------------------------

    def load_json(self, rel: str, from_repo: bool = False) -> dict:
        base = self.repo_root if from_repo else self.bench_root
        with open(os.path.join(base, rel)) as f:
            return json.load(f)

    def load_module(self, kind: str, name: str):
        """The module `<bench_root>/<kind>/<name>.py`, loaded once per
        path. `kind` is a directory of the layout above."""
        if not NAME_RE.match(name):
            raise ManifestError(f"bad {kind} name {name!r}")
        path = os.path.join(self.bench_root, kind, name + ".py")
        if path in _modules:
            return _modules[path]
        if not os.path.isfile(path):
            raise ManifestError(f"no {kind} {name!r}: {path} is missing")
        modname = f"_bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}" \
                  f"_{len(_modules)}"
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        _modules[path] = mod
        return mod

    def layer_reader(self, metric_name: str):
        return self.load_module("layer_metrics", metric_name.split(".")[0])

    def peaks(self, device_kind: str) -> dict:
        table = self.load_json("peaks.json")
        if device_kind not in table["devices"]:
            raise ManifestError(
                f"device kind {device_kind!r} is not in peaks.json: add it "
                "with its published source, never a default")
        return table["devices"][device_kind]


def validate(doc: dict) -> list:
    """Problems with a BENCHMARK.json document, as strings; the rules of
    the builder's contract that a file can be checked against alone."""
    bad = []
    names = lambda rows: [r.get("name", "") for r in rows]  # noqa: E731
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in doc:
            bad.append(f"missing key {key}")
    if bad:
        return bad
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = names(doc[group])
        for n in ns:
            if not NAME_RE.match(n):
                bad.append(f"{group}: bad name {n!r}")
        if len(set(ns)) != len(ns):
            bad.append(f"{group}: duplicate names")
    if len(set(names(doc["end_to_end"]) + names(doc["per_layer"]))) != \
            len(doc["end_to_end"]) + len(doc["per_layer"]):
        bad.append("a metric name is used twice")
    cfgs = set(names(doc["configs"]))
    cells = {}
    for w in doc["workloads"]:
        if w["config"] not in cfgs:
            bad.append(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not (1 <= len(w["why"]) <= 200) or "\n" in w["why"]:
            bad.append(f"workload {w['name']}: why length")
        for k in ("config", "traffic"):
            if not NAME_RE.match(w[k]):
                bad.append(f"workload {w['name']}: bad {k}")
        cells[w["name"]] = set()
    for c in doc["configs"]:
        if c["name"] not in {w["config"] for w in doc["workloads"]}:
            bad.append(f"config {c['name']} is used by no cell")
        if not any(c["file"].startswith(p + "/") for p in doc["paths"]):
            bad.append(f"config {c['name']}: file outside paths")
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in doc["end_to_end"] + doc["per_layer"]:
        if not UNIT_RE.match(m.get("unit", "")):
            bad.append(f"metric {m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: source")
        for c in m.get("workloads", []):
            if c not in cells:
                bad.append(f"metric {m['name']}: unknown cell {c}")
    for m in doc["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']}: source {m['source']}")
        if not (0 < m.get("bound", 0) <= 0.25):
            bad.append(f"end-to-end {m['name']}: bound")
        for c in m.get("workloads", list(cells)):
            cells[c].add(m["name"])
    for c, reported in cells.items():
        if reported == {"setup_s"} or "setup_s" not in reported:
            bad.append(f"cell {c}: needs setup_s and one more end-to-end "
                       "metric")
    layered = set()
    for m in doc["per_layer"]:
        if m.get("moves") not in e2e:
            bad.append(f"per-layer {m['name']}: moves {m.get('moves')!r}")
            continue
        if not (1 <= len(m.get("layer", "")) <= 200):
            bad.append(f"per-layer {m['name']}: layer")
        for c in m.get("workloads", list(cells)):
            layered.add(c)
            if m["moves"] not in cells.get(c, ()):
                bad.append(f"per-layer {m['name']}: cell {c} does not "
                           f"report {m['moves']}")
    for c in cells:
        if c not in layered:
            bad.append(f"cell {c}: no per-layer metric")
    return bad
