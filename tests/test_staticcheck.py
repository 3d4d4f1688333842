"""tools/staticcheck — the project-invariant linter.

Two layers: (1) every rule gets at least one positive and one negative
fixture on a scratch tree, plus pragma/exemption/baseline mechanics;
(2) the full pass runs over THIS repository and must be clean — that
is the enforcement that keeps future PRs paying the seams forward.

Stdlib-only imports: this module must stay cheap to collect (tier-1
collects the whole suite up front).
"""

import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.staticcheck import (Finding, default_baseline_path,  # noqa: E402
                               load_baseline, run_checks, write_baseline)
from tools.staticcheck import rules as R  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(tmp_path, files, tree_rules=False, rules=None):
    """Write `files` ({relpath: source}) under tmp_path and lint it.
    Returns the Result. Baseline defaults to empty (no file)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return run_checks(str(tmp_path), tree_rules=tree_rules, rules=rules)


def names(result):
    return [(f.rule, f.path) for f in result.findings]


# --- rule: wallclock ------------------------------------------------------

def test_wallclock_positive(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/consensus/x.py":
            "import time\nt = time.monotonic()\n"})
    assert names(res) == [("wallclock", "cometbft_tpu/consensus/x.py")]


def test_wallclock_alias_and_from_import(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/a.py": "import time as _t\nx = _t.time()\n",
        "cometbft_tpu/b.py": "from time import monotonic\nx = monotonic()\n",
        "cometbft_tpu/c.py":
            "from datetime import datetime\nx = datetime.now()\n"})
    assert sorted(names(res)) == [
        ("wallclock", "cometbft_tpu/a.py"),
        ("wallclock", "cometbft_tpu/b.py"),
        ("wallclock", "cometbft_tpu/c.py")]


def test_wallclock_negative(tmp_path):
    res = lint(tmp_path, {
        # the seam itself is exempt; timesource reads are the fix
        "cometbft_tpu/libs/timesource.py":
            "import time\n\ndef monotonic():\n    return time.monotonic()\n",
        "cometbft_tpu/consensus/x.py":
            "from ..libs import timesource\nt = timesource.monotonic()\n",
        # time.sleep is NOT a clock read (reactor-sleep's domain, and
        # this file is outside that rule's roots)
        "cometbft_tpu/rpc/y.py": "import time\ntime.sleep(0.1)\n"})
    assert res.findings == []


# --- rule: global-rng -----------------------------------------------------

def test_global_rng_positive(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/p2p/x.py":
            "import random\nrandom.shuffle([1, 2])\n"
            "j = random.random()\n"})
    assert names(res) == [("global-rng", "cometbft_tpu/p2p/x.py")] * 2


def test_global_rng_boolop_fallback_positive(tmp_path):
    # `(rng or random).choice(...)` still reaches the global RNG
    res = lint(tmp_path, {
        "cometbft_tpu/p2p/x.py":
            "import random\n\ndef f(rng=None):\n"
            "    return (rng or random).choice([1])\n"})
    assert names(res) == [("global-rng", "cometbft_tpu/p2p/x.py")]


def test_global_rng_unseeded_instance_positive(tmp_path):
    # an unseeded Random() is OS entropy — deterministic for nobody
    res = lint(tmp_path, {
        "cometbft_tpu/p2p/x.py": "import random\nr = random.Random()\n",
        "cometbft_tpu/p2p/y.py":
            "from random import Random\nr = Random()\n"})
    assert sorted(names(res)) == [
        ("global-rng", "cometbft_tpu/p2p/x.py"),
        ("global-rng", "cometbft_tpu/p2p/y.py")]


def test_global_rng_negative(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/p2p/x.py":
            "import random\n_rng = random.Random(42)\n"
            "_rng.shuffle([1, 2])\nx = _rng.random()\n"})
    assert res.findings == []


# --- rule: raw-env --------------------------------------------------------

def test_raw_env_positive(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/p2p/x.py":
            "import os\nT = float(os.environ.get('K', '10'))\n",
        "cometbft_tpu/ops/y.py":
            "import os as _os\nN = int(_os.environ.get('K', '512'))\n",
        # os.getenv is the same footgun in different spelling
        "cometbft_tpu/ops/z.py":
            "import os\nN = int(os.getenv('K', '512'))\n"})
    assert sorted(names(res)) == [
        ("raw-env", "cometbft_tpu/ops/y.py"),
        ("raw-env", "cometbft_tpu/ops/z.py"),
        ("raw-env", "cometbft_tpu/p2p/x.py")]


def test_raw_env_negative(tmp_path):
    res = lint(tmp_path, {
        # env.py itself is the exempt implementation site
        "cometbft_tpu/libs/env.py":
            "import os\n\ndef env_float(n, d):\n"
            "    return float(os.environ.get(n, d))\n",
        # plain string reads (no cast) are allowed
        "cometbft_tpu/p2p/x.py":
            "import os\nA = os.environ.get('ADDR', '')\n"
            "B = os.environ.get('FLAG') == '1'\n"})
    assert res.findings == []


# --- rule: reactor-sleep --------------------------------------------------

def test_reactor_sleep_positive(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/pipeline/x.py": "import time\ntime.sleep(1)\n",
        "cometbft_tpu/consensus/y.py":
            "from time import sleep\nsleep(0.1)\n"})
    assert sorted(names(res)) == [
        ("reactor-sleep", "cometbft_tpu/consensus/y.py"),
        ("reactor-sleep", "cometbft_tpu/pipeline/x.py")]


def test_reactor_sleep_negative_outside_scope(tmp_path):
    # rpc/ is outside the rule's roots; Event.wait is the blessed form
    res = lint(tmp_path, {
        "cometbft_tpu/rpc/x.py": "import time\ntime.sleep(1)\n",
        "cometbft_tpu/consensus/y.py":
            "import threading\nev = threading.Event()\nev.wait(1.0)\n"})
    assert res.findings == []


# --- rule: guarded-by -----------------------------------------------------

_GUARDED_POS = """\
import threading

class C:
    # guarded-by: _lock: _peers, _count
    def __init__(self):
        self._lock = threading.Lock()
        self._peers = {}
        self._count = 0

    def bad(self):
        return len(self._peers)

    def bad_closure(self):
        with self._lock:
            return lambda: self._count
"""

_GUARDED_NEG = """\
import threading

class C:
    # guarded-by: _lock: _peers
    def __init__(self):
        self._lock = threading.Lock()
        self._peers = {}

    def good(self):
        with self._lock:
            return len(self._peers)

    def also_good(self):
        with self._lock:
            if True:
                self._peers.clear()
"""


def test_guarded_by_positive(tmp_path):
    res = lint(tmp_path, {"cometbft_tpu/p2p/x.py": _GUARDED_POS})
    assert names(res) == [("guarded-by", "cometbft_tpu/p2p/x.py")] * 2
    # __init__ writes were NOT flagged
    assert all(f.line > 8 for f in res.findings)


def test_guarded_by_negative(tmp_path):
    res = lint(tmp_path, {"cometbft_tpu/p2p/x.py": _GUARDED_NEG})
    assert res.findings == []


def test_guarded_by_undeclared_class_ignored(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/p2p/x.py":
            "class C:\n    def f(self):\n        return self._peers\n"})
    assert res.findings == []


# --- rule: failpoint ------------------------------------------------------

def _fp_tree(doc_labels, **extra):
    files = {
        "cometbft_tpu/a.py":
            "from .libs.fail import fail_point\nfail_point('x:one')\n",
        "docs/SIMNET.md":
            "# registry\n" + "\n".join(f"`{l}`" for l in doc_labels),
    }
    files.update(extra)
    return files


def test_failpoint_negative(tmp_path):
    res = lint(tmp_path, _fp_tree(["x:one"]), tree_rules=True,
               rules=[R.FailPointRule])
    assert res.findings == []


def test_failpoint_unregistered_label(tmp_path):
    res = lint(tmp_path, _fp_tree([]), tree_rules=True,
               rules=[R.FailPointRule])
    assert names(res) == [("failpoint", "cometbft_tpu/a.py")]
    assert "missing from" in res.findings[0].message


def test_failpoint_duplicate_label(tmp_path):
    res = lint(tmp_path, _fp_tree(
        ["x:one"],
        **{"cometbft_tpu/b.py":
           "from .libs.fail import fail_point\nfail_point('x:one')\n"}),
        tree_rules=True, rules=[R.FailPointRule])
    assert names(res) == [("failpoint", "cometbft_tpu/b.py")]
    assert "duplicate" in res.findings[0].message


def test_failpoint_prefix_of_documented_label_still_fails(tmp_path):
    # "x:one" is documented; "x:on" is a substring of it AND of prose —
    # only the exact backtick-delimited form may satisfy the registry
    res = lint(tmp_path, _fp_tree(
        ["x:one"],
        **{"cometbft_tpu/b.py":
           "from .libs.fail import fail_point\nfail_point('x:on')\n"}),
        tree_rules=True, rules=[R.FailPointRule])
    assert names(res) == [("failpoint", "cometbft_tpu/b.py")]


def test_failpoint_non_literal_label(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/a.py":
            "from .libs.fail import fail_point\nlbl = 'x'\n"
            "fail_point(lbl)\n",
        "docs/SIMNET.md": "# registry\n"},
        tree_rules=True, rules=[R.FailPointRule])
    assert names(res) == [("failpoint", "cometbft_tpu/a.py")]
    assert "string literal" in res.findings[0].message


# --- rule: bare-except ----------------------------------------------------

def test_bare_except_positive(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/device/x.py":
            "try:\n    f()\nexcept:\n    pass\n"})
    assert names(res) == [("bare-except", "cometbft_tpu/device/x.py")]


def test_bare_except_negative(tmp_path):
    res = lint(tmp_path, {
        # named exceptions in scope; bare except OUTSIDE the hot paths
        "cometbft_tpu/device/x.py":
            "try:\n    f()\nexcept OSError:\n    pass\n",
        "cometbft_tpu/rpc/y.py":
            "try:\n    f()\nexcept:\n    pass\n"})
    assert res.findings == []


# --- rule: raw-file-io ----------------------------------------------------

def test_raw_file_io_positive(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/db/x.py":
            "f = open('log', 'ab')\n",
        "cometbft_tpu/consensus/y.py":
            "import os\n\ndef sync(f):\n    os.fsync(f.fileno())\n",
        "cometbft_tpu/privval/z.py":
            "import os\nfd = os.open('s', 0)\n"})
    assert sorted(names(res)) == [
        ("raw-file-io", "cometbft_tpu/consensus/y.py"),
        ("raw-file-io", "cometbft_tpu/db/x.py"),
        ("raw-file-io", "cometbft_tpu/privval/z.py")]


def test_raw_file_io_negative(tmp_path):
    res = lint(tmp_path, {
        # the seam is the fix — and it lives OUTSIDE the rule's roots
        "cometbft_tpu/libs/faultio.py":
            "def open_file(p, m, label=''):\n    return open(p, m)\n",
        "cometbft_tpu/store/x.py":
            "from ..libs import faultio\n"
            "f = faultio.open_file('log', 'ab', label='db:log')\n"
            "faultio.fsync(f)\n",
        # raw open outside the crash-consistent trees is fine
        "cometbft_tpu/rpc/y.py": "f = open('dump', 'wb')\n",
        # os.path.* / os.remove are not file-handle I/O
        "cometbft_tpu/db/z.py":
            "import os\nos.remove('stale')\nos.path.exists('p')\n"})
    assert res.findings == []


# --- rule: metrics-drift --------------------------------------------------

def _metrics_tree(tmp_path):
    for rel in ("tools/metricsgen.py", "cometbft_tpu/__init__.py",
                "cometbft_tpu/libs/__init__.py",
                "cometbft_tpu/libs/metrics_defs.py",
                "cometbft_tpu/libs/metrics_gen.py"):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(os.path.join(REPO, rel), dst)


def test_metrics_drift_negative(tmp_path):
    _metrics_tree(tmp_path)
    res = run_checks(str(tmp_path), tree_rules=True,
                     rules=[R.MetricsDriftRule])
    assert res.findings == []


def test_metrics_drift_positive(tmp_path):
    _metrics_tree(tmp_path)
    gen = tmp_path / "cometbft_tpu/libs/metrics_gen.py"
    gen.write_text(gen.read_text() + "\n# hand edit\n")
    res = run_checks(str(tmp_path), tree_rules=True,
                     rules=[R.MetricsDriftRule])
    assert names(res) == [
        ("metrics-drift", "cometbft_tpu/libs/metrics_gen.py")]


# --- pragmas --------------------------------------------------------------

def test_pragma_same_line_suppresses(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/x.py":
            "import time\n"
            "t = time.monotonic()  # staticcheck: allow(wallclock)\n"})
    assert res.findings == [] and res.suppressed == 1


def test_pragma_line_above_suppresses(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/x.py":
            "import time\n"
            "# staticcheck: allow(wallclock) — justification here\n"
            "t = time.monotonic()\n"})
    assert res.findings == [] and res.suppressed == 1


def test_pragma_on_code_line_does_not_cover_next_line(tmp_path):
    # a same-line pragma must not silently disable the rule for the
    # statement below it
    res = lint(tmp_path, {
        "cometbft_tpu/x.py":
            "import time\n"
            "a = time.monotonic()  # staticcheck: allow(wallclock)\n"
            "b = time.time()\n"})
    assert names(res) == [("wallclock", "cometbft_tpu/x.py")]
    assert res.findings[0].line == 3 and res.suppressed == 1


def test_pragma_wrong_rule_does_not_suppress(tmp_path):
    # the wallclock finding stands, AND the raw-env pragma is itself a
    # stale-pragma finding (it suppresses nothing on that line)
    res = lint(tmp_path, {
        "cometbft_tpu/x.py":
            "import time\n"
            "t = time.monotonic()  # staticcheck: allow(raw-env)\n"})
    assert sorted(names(res)) == [
        ("stale-pragma", "cometbft_tpu/x.py"),
        ("wallclock", "cometbft_tpu/x.py")]


def test_pragma_has_no_wildcard(tmp_path):
    # rules must be named explicitly; allow(all) is not a thing — the
    # finding stands and the unknown rule name is flagged
    res = lint(tmp_path, {
        "cometbft_tpu/x.py":
            "import time\n"
            "t = time.monotonic()  # staticcheck: allow(all)\n"})
    assert sorted(names(res)) == [
        ("stale-pragma", "cometbft_tpu/x.py"),
        ("wallclock", "cometbft_tpu/x.py")]
    assert any("unknown rule" in f.message for f in res.findings)


# --- baseline mechanics ---------------------------------------------------

def test_baseline_matches_by_fingerprint_not_line(tmp_path):
    src = "import time\nt = time.monotonic()\n"
    (tmp_path / "cometbft_tpu").mkdir(parents=True)
    (tmp_path / "cometbft_tpu/x.py").write_text(src)
    res = run_checks(str(tmp_path))
    bl = tmp_path / "baseline.txt"
    write_baseline(str(bl), res.findings)
    # code motion ABOVE the finding must not churn the baseline
    (tmp_path / "cometbft_tpu/x.py").write_text(
        "import time\n\n\n# moved down\nt = time.monotonic()\n")
    res2 = run_checks(str(tmp_path), baseline_path=str(bl))
    assert res2.ok and len(res2.baselined) == 1


def test_baseline_entry_absorbs_at_most_one_finding(tmp_path):
    # a NEW violation whose normalized line duplicates a grandfathered
    # one must fail, not ride the old entry
    (tmp_path / "cometbft_tpu").mkdir(parents=True)
    (tmp_path / "cometbft_tpu/x.py").write_text(
        "import time\nt = time.monotonic()\n")
    res = run_checks(str(tmp_path))
    bl = tmp_path / "baseline.txt"
    write_baseline(str(bl), res.findings)
    (tmp_path / "cometbft_tpu/x.py").write_text(
        "import time\nt = time.monotonic()\n\n\nt = time.monotonic()\n")
    res2 = run_checks(str(tmp_path), baseline_path=str(bl))
    assert len(res2.baselined) == 1
    assert [f.line for f in res2.findings] == [5]


def test_baseline_stale_entry_fails(tmp_path):
    (tmp_path / "cometbft_tpu").mkdir(parents=True)
    (tmp_path / "cometbft_tpu/x.py").write_text("x = 1\n")
    bl = tmp_path / "baseline.txt"
    bl.write_text("wallclock|cometbft_tpu/x.py|t = time.monotonic()"
                  "  ## fixed long ago\n")
    res = run_checks(str(tmp_path), baseline_path=str(bl))
    # shrink-only: the entry's finding is gone, so the run FAILS until
    # the line is deleted
    assert not res.ok and len(res.stale_baseline) == 1


def test_baseline_comment_preserved_on_rewrite(tmp_path):
    bl = tmp_path / "baseline.txt"
    f = Finding("wallclock", "cometbft_tpu/x.py", 2, "m",
                "t = time.monotonic()")
    write_baseline(str(bl), [f], {f.fingerprint(): "keep: reason"})
    assert load_baseline(str(bl)) == {f.fingerprint(): "keep: reason"}


# --- syntax errors surface, not crash ------------------------------------

def test_unparseable_file_is_a_finding(tmp_path):
    res = lint(tmp_path, {"cometbft_tpu/x.py": "def broken(:\n"})
    assert [f.rule for f in res.findings] == ["parse"]


# --- the real tree --------------------------------------------------------

def test_full_tree_is_clean():
    """THE enforcement test: the repository lints clean against its
    checked-in baseline — no new findings, no stale entries. A failure
    here names the file/line and rule; see docs/STATICCHECK.md for
    fix/pragma/baseline options."""
    res = run_checks(REPO)
    assert res.findings == [], "\n" + "\n".join(
        f.render() for f in res.findings)
    assert res.stale_baseline == [], (
        "stale baseline entries (delete the lines): "
        f"{res.stale_baseline}")


def test_checked_in_baseline_entries_are_justified():
    """Every baseline entry (if any ever appear) carries a non-TODO
    justification comment."""
    entries = load_baseline(default_baseline_path(REPO))
    for fp, comment in entries.items():
        assert comment and not comment.lower().startswith("todo"), (
            f"baseline entry needs a real justification: {fp}")


def test_cli_clean_on_tree():
    """`python -m tools.staticcheck` (the run_suite.sh wiring) exits 0
    and says so. Every rule over the tree is test_full_tree_is_clean's
    half-minute, in-process; the CLI's own part — arguments, the
    full-tree branch with the checked-in baseline, the verdict line and
    the exit code — is proven with one cheap rule over the same tree."""
    import subprocess
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--rule", "wallclock"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_subset_accepts_directories(tmp_path):
    """A directory argument scopes to the files under it — it must not
    silently filter every finding away and report clean."""
    import subprocess
    pkg = tmp_path / "cometbft_tpu" / "p2p"
    pkg.mkdir(parents=True)
    (pkg / "x.py").write_text("import time\nt = time.monotonic()\n")
    (tmp_path / "cometbft_tpu" / "clean.py").write_text("x = 1\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    # cwd is NOT the root: relative args must resolve against --root,
    # so running from anywhere gives the same verdict
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--root",
         str(tmp_path), "cometbft_tpu/p2p"],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "cometbft_tpu/p2p/x.py" in proc.stdout
    # a path that matches nothing is a usage error, never a false clean
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--root",
         str(tmp_path), "cometbft_tpu/nope.py"],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    # non-normalized spellings (./x, a/../a/x) must not scan zero
    # files and report a vacuous clean
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--root",
         str(tmp_path), "./cometbft_tpu/p2p/x.py"],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "cometbft_tpu/p2p/x.py" in proc.stdout
