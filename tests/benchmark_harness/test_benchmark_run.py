"""The run itself, driven past the look for a chip on the CPU backend at
a tiny size: `correct` comes out true on sound code and false with the
timed path broken underneath, adding a cell takes only new files, and
the command refuses to run without a TPU."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import REPO, make_tiny_root
from benchmark.harness import runner

CATCHUP, COMMIT = "catchup-200.steady", "hub-live-150.cold-commit"
SEED = 2**31 + 77


def run(root, cell, trace=False, seed=SEED, in_process=True, plant=""):
    return runner.run_cell(root, cell, seed, 2.0, trace,
                           time.perf_counter(), look_for_chip=False,
                           in_process_traffic=in_process, plant=plant)


def over(out):
    return {n for n, row in out["checks"].items()
            if row["value"] > row["limit"]}


@pytest.mark.parametrize("cell, metrics", [
    (CATCHUP, {"catchup_sigs_per_s", "setup_s"}),
    (COMMIT, {"commit_verify_p50_ms", "commit_verify_p95_ms", "setup_s"}),
])
def test_sound_run_is_correct(tiny_root, fresh_sigcache, cell, metrics,
                              capfd):
    out = run(tiny_root, cell)
    assert out["correct"] and over(out) == set()
    assert set(out["metrics"]) == metrics
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


def test_traced_run_reports_per_layer_metrics_only(tiny_root,
                                                   fresh_sigcache):
    out = run(tiny_root, COMMIT, trace=True)
    assert out["correct"]
    # nothing to read on a CPU: no device trace, no dispatches, no prewarm
    assert out["metrics"] == {} and "breakdown" not in out


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


def test_a_new_cell_needs_only_new_files_and_entries(tiny_root,
                                                     fresh_sigcache):
    b = os.path.join(tiny_root, "benchmark")
    files = {
        "drivers/dummy_driver.py": DUMMY_DRIVER,
        "generators/dummy_gen.py":
            "def make(params):\n    return {'numbers': params['traffic']"
            "['numbers']}\n",
        "layer_metrics/dummy_sum.py":
            "def read(ctx):\n    return ctx.result['counters']['sum']\n",
        "configs/dummy-cfg.json": json.dumps({"driver": "dummy_driver"}),
        "traffic/dummy-mix.json": json.dumps({"generator": "dummy_gen",
                                              "numbers": [1, 2, 3]}),
    }
    for rel, text in files.items():
        with open(os.path.join(b, rel), "w") as f:
            f.write(text)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "dummy-cfg", "source": "a test",
                           "file": "benchmark/configs/dummy-cfg.json",
                           "reduced": [], "why": "a test"})
    doc["workloads"].append({"name": "dummy-cfg.mix", "config": "dummy-cfg",
                             "traffic": "dummy-mix", "chips": 1,
                             "why": "a test"})
    doc["end_to_end"].append({"name": "dummy_per_s", "unit": "1/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["dummy-cfg.mix"]})
    doc["per_layer"].append({"name": "dummy_sum.mix", "unit": "1",
                             "better": "higher",
                             "source": "program_counter", "layer": "dummy",
                             "moves": "dummy_per_s",
                             "workloads": ["dummy-cfg.mix"]})
    with open(path, "w") as f:
        json.dump(doc, f)
    from benchmark.harness.manifest import validate
    assert validate(doc) == []
    plain = run(tiny_root, "dummy-cfg.mix")
    assert plain["correct"] and set(plain["metrics"]) == {"dummy_per_s",
                                                          "setup_s"}
    traced = run(tiny_root, "dummy-cfg.mix", trace=True)
    assert traced["metrics"] == {"dummy_sum.mix": {"value": 6, "unit": "1"}}
    # and the cells that were there still run
    assert run(tiny_root, COMMIT)["correct"]


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
    import shutil
    root = str(tmp_path / "alone")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    done = _command(root)
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
