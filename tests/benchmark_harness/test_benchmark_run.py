"""The run itself, driven past the look for a chip on the CPU backend at
a tiny size: `correct` comes out true on sound code, in every cell that
BENCHMARK.json lists, and false with the timed path broken underneath,
adding a cell takes only new files and entries, tests included, and the
command refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from conftest import (CELLS, REPO, TINY_REL, add_to_tree, make_tiny_root,
                      missing_tiny, real_json, tiny_cells, tree_copy)
from benchmark.harness import runner
from benchmark.harness.manifest import Manifest, ManifestError, validate

CATCHUP, COMMIT = "catchup-200.steady", "hub-live-150.cold-commit"
SEED = 2**31 + 77


def run(root, cell, trace=False, seed=SEED, in_process=True, plant=""):
    return runner.run_cell(root, cell, seed, 2.0, trace,
                           time.perf_counter(), look_for_chip=False,
                           in_process_traffic=in_process, plant=plant)


def over(out):
    return {n for n, row in out["checks"].items()
            if row["value"] > row["limit"]}


def names(metrics):
    return {m["name"] for m in metrics}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, fresh_sigcache, cell, capfd):
    out = run(tiny_root, cell)
    assert out["correct"] and over(out) == set()
    assert set(out["metrics"]) == names(
        Manifest(tiny_root).end_to_end_for(cell))
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    runner.print_result(out)
    stdout, stderr = capfd.readouterr()
    assert json.loads(stdout.strip().splitlines()[-1])["correct"] is True
    assert stderr.strip().splitlines()[-1].startswith("[check] ")


def test_same_seed_twice_in_one_process_hits_the_sigcache(tiny_root,
                                                          fresh_sigcache):
    assert run(tiny_root, CATCHUP)["correct"]
    again = run(tiny_root, CATCHUP)
    assert not again["correct"] and "sigcache_hits" in over(again)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_only_the_cells_per_layer_metrics(
        tiny_root, fresh_sigcache, cell):
    out = run(tiny_root, cell, trace=True)
    assert out["correct"] and over(out) == set()
    per_layer = Manifest(tiny_root).per_layer_for(cell)
    assert set(out["metrics"]) <= names(per_layer)
    # a CPU gives no device trace: nothing read from one, no breakdown
    assert not set(out["metrics"]) & names(
        m for m in per_layer if m["source"] == "device_trace")
    assert "breakdown" not in out and "busy_s" not in out["device"]


def test_traced_run_reports_per_layer_metrics_only(tiny_root,
                                                   fresh_sigcache):
    out = run(tiny_root, COMMIT, trace=True)
    assert out["correct"] and "breakdown" not in out
    manifest = Manifest(tiny_root)
    assert not set(out["metrics"]) & names(manifest.end_to_end_for(COMMIT))
    # nothing to read on a CPU for the readers this cell had when the
    # test was written (no device trace, no dispatches, no prewarm); a
    # reader that a later PR lists for the cell may well read here
    assert not set(out["metrics"]) & {
        "prewarm_s", "pallas_dispatch_share.commit",
        "rlc_kernel_us_per_sig.commit", "rlc_kernel_roofline.commit",
        "device_idle_share.commit", "commit_device_ms.commit",
        "prepare_ms_per_chunk.commit"}


# --- the timed path broken underneath ------------------------------------------

def _break_apply(monkeypatch):
    """A step that returns its state unchanged: at one height the
    application commits the state it was given. (This test makes its
    traffic in the child process, which the patch does not reach.)"""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    real = KVStoreApplication.finalize_block

    def finalize(self, req):
        resp = real(self, req)
        if req.height == 3:
            self.staged = dict(self.state)
        return resp
    monkeypatch.setattr(KVStoreApplication, "finalize_block", finalize)


def _break_one_verdict(monkeypatch):
    """An answer altered where it is produced: one valid lane of every
    tile comes back false."""
    from cometbft_tpu.engine import blocksync
    real = blocksync.verify_lanes

    def flipped(pubs, msgs, sigs, batch_size):
        out = real(pubs, msgs, sigs, batch_size).copy()
        if len(out) > 5:
            out[5] = False
        return out
    monkeypatch.setattr(blocksync, "verify_lanes", flipped)


@pytest.mark.parametrize("fault, must_fail", [
    (_break_apply, "height_short"),
    # the driver's own plants, as the control runs on the chip use them:
    # half of every tile's lanes left out; every lane taken for good
    ("half_lanes", "tamper_applied"),
    ("accept_all", "tamper_applied"),
    # the refused tile is fetched again and passes on the signatures the
    # first pass cached: the window no longer verified everything afresh
    (_break_one_verdict, "sigcache_hits"),
])
def test_catchup_with_a_fault_is_not_correct(tiny_root, fresh_sigcache,
                                             monkeypatch, fault, must_fail):
    if isinstance(fault, str):
        out = run(tiny_root, CATCHUP, plant=fault)
    else:
        fault(monkeypatch)
        out = run(tiny_root, CATCHUP, in_process=fault is not _break_apply)
    assert not out["correct"] and must_fail in over(out)


def _commit_half(monkeypatch):
    """Half of the batch left out: signatures of the upper half of the
    validator set are taken for good."""
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    real = Ed25519PubKey.verify_signature
    seen = []

    def verify(self, msg, sig):
        seen.append(1)
        if (len(seen) - 1) % 8 >= 4:
            return True
        return real(self, msg, sig)
    monkeypatch.setattr(Ed25519PubKey, "verify_signature", verify)


def _commit_flip(monkeypatch):
    """An answer altered where it is produced: every 50th verdict comes
    back false."""
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    real = Ed25519PubKey.verify_signature
    seen = []

    def verify(self, msg, sig):
        seen.append(1)
        return real(self, msg, sig) and len(seen) % 50 != 0
    monkeypatch.setattr(Ed25519PubKey, "verify_signature", verify)


def _commit_accept_everything(monkeypatch):
    """The control: every signature is taken for good."""
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    monkeypatch.setattr(Ed25519PubKey, "verify_signature",
                        lambda self, msg, sig: True)


@pytest.mark.parametrize("fault, must_fail", [
    (_commit_half, "tamper_accepted"),
    (_commit_flip, "calls_failed"),
    (_commit_accept_everything, "tamper_accepted"),
])
def test_commit_with_a_fault_is_not_correct(tiny_root, fresh_sigcache,
                                            monkeypatch, fault, must_fail):
    fault(monkeypatch)
    # seed chosen so that a probe's altered signature sits in the upper
    # half of the validator set
    out = run(tiny_root, COMMIT, seed=5)
    assert not out["correct"] and must_fail in over(out)


# --- a new cell is new files and new entries -----------------------------------

AGAIN = "catchup-again.other-fresh-chain"


def _missing(src: str) -> list:
    return [(cell, os.path.relpath(path, src))
            for cell, path in missing_tiny(src)]


def _add_a_second_catchup_cell(tree: str, tiny_files: bool) -> dict:
    """The catch-up configuration and its traffic once more under new
    names, listed under the cell's end-to-end metric and one per-layer
    metric. Returns the tiny files' paths by kind."""
    files = {
        "benchmark/configs/catchup-again.json":
            dict(real_json("benchmark", "configs", "catchup-200.json"),
                 name="catchup-again"),
        "benchmark/traffic/other-fresh-chain.json":
            dict(real_json("benchmark", "traffic", "steady-fresh-chain.json"),
                 name="other-fresh-chain"),
    }
    tiny = {
        "config": os.path.join(TINY_REL, "configs", "catchup-again.json"),
        "traffic": os.path.join(TINY_REL, "traffic",
                                "other-fresh-chain.json"),
    }
    if tiny_files:
        files[tiny["config"]] = real_json(TINY_REL, "configs",
                                      "catchup-200.json")
        # 6 blocks a second, where the cell that is there has 4: a 2 s
        # window is 12 blocks in this cell and 8 in that one
        files[tiny["traffic"]] = dict(
            real_json(TINY_REL, "traffic", "steady-fresh-chain.json"),
            blocks_per_window_second=6)

    def entries(doc):
        first = next(c for c in doc["configs"] if c["name"] == "catchup-200")
        doc["configs"].append(dict(
            first, name="catchup-again",
            file="benchmark/configs/catchup-again.json"))
        doc["workloads"].append({
            "name": AGAIN, "config": "catchup-again",
            "traffic": "other-fresh-chain", "chips": 1, "why": "a test"})
        for group, metric in (("end_to_end", "catchup_sigs_per_s"),
                              ("per_layer", "marshal_ms_per_tile.catchup")):
            next(m for m in doc[group]
                 if m["name"] == metric)["workloads"].append(AGAIN)
    add_to_tree(tree, files, entries)
    return tiny


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path,
                                                     fresh_sigcache):
    """What a PR that brings a cell does, in that order: the files and
    the entries first, the tiny checkout built from them afterwards."""
    tree = tree_copy(str(tmp_path / "tree"))
    _add_a_second_catchup_cell(tree, tiny_files=True)
    assert _missing(tree) == _missing(REPO)
    assert tiny_cells(tree) == CELLS + [AGAIN]
    root = make_tiny_root(str(tmp_path / "checkout"), tree)
    manifest = Manifest(root)
    assert validate(manifest.doc) == []
    plain = run(root, AGAIN)
    assert plain["correct"] and plain["attempted"] == 12
    assert set(plain["metrics"]) == {"catchup_sigs_per_s", "setup_s"} \
        == names(manifest.end_to_end_for(AGAIN))
    # (another seed: a seed's signatures are in the sigcache by now)
    traced = run(root, AGAIN, trace=True, seed=SEED + 1)
    assert traced["correct"]
    assert set(traced["metrics"]) <= names(manifest.per_layer_for(AGAIN))
    # and the cells that were there still run, at their own sizes
    there = run(root, CATCHUP, seed=SEED + 2)
    assert there["correct"] and there["attempted"] == 8
    assert run(root, COMMIT)["correct"]


def test_a_cell_without_tiny_sizes_is_left_out_and_named(tmp_path,
                                                         fresh_sigcache):
    tree = tree_copy(str(tmp_path / "tree"))
    tiny = _add_a_second_catchup_cell(tree, tiny_files=False)
    assert _missing(tree) == _missing(REPO) + [(AGAIN, tiny["config"]),
                                               (AGAIN, tiny["traffic"])]
    assert tiny_cells(tree) == CELLS
    root = make_tiny_root(str(tmp_path / "checkout"), tree)
    # the tiny checkout is the one of the tree without that cell: nothing
    # of it is left to run at its published size
    before = make_tiny_root(str(tmp_path / "as-before"))
    assert Manifest(root).doc == Manifest(before).doc
    with pytest.raises(ManifestError):
        Manifest(root).cell(AGAIN)
    for kind in ("configs", "traffic"):
        assert sorted(os.listdir(os.path.join(root, "benchmark", kind))) \
            == sorted(os.listdir(os.path.join(before, "benchmark", kind)))
    # one of the two files is not enough
    shutil.copy(os.path.join(REPO, TINY_REL, "configs", "catchup-200.json"),
                os.path.join(tree, tiny["config"]))
    assert _missing(tree) == _missing(REPO) + [(AGAIN, tiny["traffic"])]
    assert run(root, COMMIT)["correct"]


DUMMY_DRIVER = '''
import time
def warm():
    return {"batch": 0, "prewarm_s": 0.0}
def build(config, traffic, payload, boot, seed):
    return {"payload": payload}
def window(session, seconds):
    t = time.perf_counter()
    total = sum(session["payload"]["numbers"])
    return {"end_to_end": {"dummy_per_s": total / (time.perf_counter() - t + 1)},
            "attempted": 1, "failed": 0, "counters": {"sum": total},
            "facts": {"lanes": 0, "hash_blocks": 0, "calls": 1}}
def judge(session, result, compiles):
    return [("sum_diff", abs(result["counters"]["sum"] - 6), 0)]
'''


def test_a_new_kind_of_cell_brings_its_driver_generator_and_reader(
        tmp_path, fresh_sigcache):
    tree = tree_copy(str(tmp_path / "tree"))
    files = {
        "benchmark/drivers/dummy_driver.py": DUMMY_DRIVER,
        "benchmark/generators/dummy_gen.py":
            "def make(params):\n    return {'numbers': params['traffic']"
            "['numbers']}\n",
        "benchmark/layer_metrics/dummy_sum.py":
            "def read(ctx):\n    return ctx.result['counters']['sum']\n",
        "benchmark/configs/dummy-cfg.json": {"driver": "dummy_driver"},
        "benchmark/traffic/dummy-mix.json": {"generator": "dummy_gen",
                                             "numbers": [100, 200, 300]},
        os.path.join(TINY_REL, "configs", "dummy-cfg.json"): {},
        os.path.join(TINY_REL, "traffic", "dummy-mix.json"):
            {"numbers": [1, 2, 3]},
    }

    def entries(doc):
        doc["configs"].append({"name": "dummy-cfg", "source": "a test",
                               "file": "benchmark/configs/dummy-cfg.json",
                               "reduced": [], "why": "a test"})
        doc["workloads"].append({"name": "dummy-cfg.mix",
                                 "config": "dummy-cfg",
                                 "traffic": "dummy-mix", "chips": 1,
                                 "why": "a test"})
        doc["end_to_end"].append({"name": "dummy_per_s", "unit": "1/s",
                                  "better": "higher", "bound": 0.05,
                                  "source": "host_clock",
                                  "workloads": ["dummy-cfg.mix"]})
        doc["per_layer"].append({"name": "dummy_sum.mix", "unit": "1",
                                 "better": "higher",
                                 "source": "program_counter",
                                 "layer": "dummy", "moves": "dummy_per_s",
                                 "workloads": ["dummy-cfg.mix"]})
    add_to_tree(tree, files, entries)
    root = make_tiny_root(str(tmp_path / "checkout"), tree)
    plain = run(root, "dummy-cfg.mix")
    assert plain["correct"] and set(plain["metrics"]) == {"dummy_per_s",
                                                          "setup_s"}
    traced = run(root, "dummy-cfg.mix", trace=True)
    assert traced["metrics"] == {"dummy_sum.mix": {"value": 6, "unit": "1"}}
    # and the cells that were there still run
    assert run(root, COMMIT)["correct"]


# --- the command without a chip -------------------------------------------------

def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", COMMIT, "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_non_zero_with_no_result_line():
    done = _command(REPO)
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout


def test_benchmark_alone_exits_non_zero(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: the program is not beside them."""
    root = str(tmp_path / "alone")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    done = _command(root)
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
