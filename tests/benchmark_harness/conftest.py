"""Fixtures for the benchmark's own tests: a copy of the benchmark at a
tiny size (8 validators, 4-block tiles) that runs in seconds on the CPU
backend, where no kernel is traced or jitted (the node's bucket is 0
there and every signature takes the native route)."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

TINY = {
    "validators": 8,
    "steady-fresh-chain": dict(blocks_per_window_second=4, warmup_blocks=4,
                               probe_blocks=12, probe_bad_height=7,
                               probe_bad_index=3),
    "closed-loop-commits": dict(commits_per_window_second=20,
                                warmup_commits=2, probe_commits=4),
}


def make_tiny_root(dst: str) -> str:
    """`dst` becomes a checkout that holds only BENCHMARK.json and the
    benchmark's files, with the sizes cut down."""
    for d in ("drivers", "generators", "layer_metrics", "rooflines"):
        shutil.copytree(os.path.join(REPO, "benchmark", d),
                        os.path.join(dst, "benchmark", d))
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"),
                os.path.join(dst, "benchmark", "peaks.json"))
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(dst, "benchmark", d))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for c in doc["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg["validators"] = TINY["validators"]
        if "tile_size" in cfg:
            cfg["tile_size"] = 4
        with open(os.path.join(dst, c["file"]), "w") as f:
            json.dump(cfg, f)
    for w in doc["workloads"]:
        rel = os.path.join("benchmark", "traffic", w["traffic"] + ".json")
        with open(os.path.join(REPO, rel)) as f:
            mix = json.load(f)
        mix.update(TINY[w["traffic"]])
        with open(os.path.join(dst, rel), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path / "checkout"))


@pytest.fixture
def fresh_sigcache():
    """The process-wide verified-signature cache is shared by every test
    of this worker: a run must start with none of its signatures seen."""
    from cometbft_tpu.pipeline.cache import reset_shared_cache
    reset_shared_cache()
    yield
    reset_shared_cache()
