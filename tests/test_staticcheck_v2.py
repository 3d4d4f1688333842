"""staticcheck v2 — the whole-program engine (graph / lock-order /
verdict-taint / kernel-discipline) plus the runner satellites (per-rule
timing, stale-pragma audit, --format json, --rule filter).

Every new rule family gets at least one positive and one negative
fixture on a scratch tree, the call-graph/symbol-table builder is
pinned on cross-module + method-resolution + cycle + dynamic-dispatch
shapes, and the acceptance goldens live here: a seeded lock-order
cycle is detected, an un-canaried device->apply path is flagged while
the real canaried shape is not.

Stdlib-only imports: this module must stay cheap to collect (tier-1
collects the whole suite up front).
"""

import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.staticcheck import FileCtx, run_checks  # noqa: E402
from tools.staticcheck import rules as R  # noqa: E402
from tools.staticcheck.graph import build_project, module_name  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_tree(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)


def lint(tmp_path, files, rules=None):
    """Full-pipeline lint (tree rules ON — the v2 families need the
    project graph). Baseline defaults to empty."""
    write_tree(tmp_path, files)
    return run_checks(str(tmp_path), tree_rules=True, rules=rules)


def names(result):
    return [(f.rule, f.path) for f in result.findings]


def project_of(tmp_path, files):
    write_tree(tmp_path, files)
    ctxs = {}
    for rel in files:
        if rel.endswith(".py"):
            ctxs[rel] = FileCtx(str(tmp_path), rel)
    return build_project(str(tmp_path), ctxs)


# --- the graph: symbol table + call resolution ----------------------------

_GRAPH_TREE = {
    "cometbft_tpu/libs/util.py":
        "def helper():\n    return 1\n",
    "cometbft_tpu/svc/core.py": (
        "from ..libs.util import helper\n"
        "from ..libs import util\n"
        "\n"
        "\n"
        "class Base:\n"
        "    def shared(self):\n"
        "        return helper()\n"
        "\n"
        "\n"
        "class Svc(Base):\n"
        "    def __init__(self, n: int):\n"
        "        self.n = n\n"
        "\n"
        "    def __len__(self):\n"
        "        return self.n\n"
        "\n"
        "    def run(self):\n"
        "        self.shared()\n"
        "        util.helper()\n"
        "        return len(self)\n"
        "\n"
        "\n"
        "def make() -> Svc:\n"
        "    return Svc(3)\n"
        "\n"
        "\n"
        "def drive():\n"
        "    s = make()\n"
        "    s.run()\n"
    ),
}


def _resolved(project, func_qual):
    f = project.functions[func_qual]
    out = []
    for c in project.iter_calls(f):
        from tools.staticcheck.lock_rules import _local_env
        out.extend(project.resolve_call(f, c, _local_env(project, f)))
    return out


def test_graph_cross_module_and_relative_imports(tmp_path):
    p = project_of(tmp_path, _GRAPH_TREE)
    assert "cometbft_tpu.libs.util.helper" in p.functions
    assert "cometbft_tpu.svc.core.Svc.run" in p.functions
    got = _resolved(p, "cometbft_tpu.svc.core.Base.shared")
    assert got == ["cometbft_tpu.libs.util.helper"]  # from-import
    got = _resolved(p, "cometbft_tpu.svc.core.Svc.run")
    # self.shared -> base-class method; util.helper -> module attr;
    # len(self) -> __len__
    assert "cometbft_tpu.svc.core.Base.shared" in got
    assert "cometbft_tpu.libs.util.helper" in got
    assert "cometbft_tpu.svc.core.Svc.__len__" in got


def test_graph_return_annotation_types_local_vars(tmp_path):
    p = project_of(tmp_path, _GRAPH_TREE)
    # drive(): s = make() -> Svc via make's return annotation, so
    # s.run() resolves to the method
    got = _resolved(p, "cometbft_tpu.svc.core.drive")
    assert "cometbft_tpu.svc.core.Svc.run" in got


def test_graph_call_cycle_does_not_hang(tmp_path):
    p = project_of(tmp_path, {
        "cometbft_tpu/a.py":
            "def f():\n    return g()\n\n\ndef g():\n    return f()\n"})
    assert _resolved(p, "cometbft_tpu.a.f") == ["cometbft_tpu.a.g"]
    assert _resolved(p, "cometbft_tpu.a.g") == ["cometbft_tpu.a.f"]


def test_graph_dynamic_dispatch_conservative_fallback(tmp_path):
    p = project_of(tmp_path, {
        "cometbft_tpu/a.py":
            "class A:\n    def poke(self):\n        pass\n",
        "cometbft_tpu/b.py":
            "class B:\n    def poke(self):\n        pass\n",
        "cometbft_tpu/c.py":
            "def drive(obj):\n    obj.poke()\n"})
    f = p.functions["cometbft_tpu.c.drive"]
    call = next(p.iter_calls(f))
    # untyped receiver: nothing without the opt-in...
    assert p.resolve_call(f, call) == []
    # ...every same-named method with it
    got = p.resolve_call(f, call, dynamic=True)
    assert got == ["cometbft_tpu.a.A.poke", "cometbft_tpu.b.B.poke"]


def test_graph_attr_callable_plugin_seam(tmp_path):
    p = project_of(tmp_path, {
        "cometbft_tpu/a.py": (
            "def default_backend(x):\n    return x\n"
            "\n"
            "\n"
            "class C:\n"
            "    def __init__(self, backend=None):\n"
            "        self._backend = backend or default_backend\n"
            "\n"
            "    def run(self, x):\n"
            "        return self._backend(x)\n")})
    got = _resolved(p, "cometbft_tpu.a.C.run")
    assert got == ["cometbft_tpu.a.default_backend"]


def test_module_name_mapping():
    assert module_name("cometbft_tpu/farm/batcher.py") \
        == "cometbft_tpu.farm.batcher"
    assert module_name("cometbft_tpu/farm/__init__.py") \
        == "cometbft_tpu.farm"


# --- rule: lock-order -----------------------------------------------------

_CYCLE_TREE = {
    "cometbft_tpu/a.py": (
        "import threading\n"
        "\n"
        "\n"
        "class A:\n"
        "    def __init__(self, b: 'B'):\n"
        "        self._alock = threading.Lock()\n"
        "        self.b = b\n"
        "\n"
        "    def go(self):\n"
        "        with self._alock:\n"
        "            self.b.poke()\n"
        "\n"
        "\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._block = threading.Lock()\n"
        "\n"
        "    def poke(self):\n"
        "        with self._block:\n"
        "            pass\n"
        "\n"
        "    def reverse(self, a: 'A'):\n"
        "        with self._block:\n"
        "            a.go()\n"),
}


def test_lock_order_cycle_positive(tmp_path):
    res = lint(tmp_path, _CYCLE_TREE, rules=[R.LockOrderRule])
    assert any("lock-order cycle" in f.message for f in res.findings)
    assert all(f.rule == "lock-order" for f in res.findings)


def test_lock_order_consistent_order_negative(tmp_path):
    # both paths acquire alock THEN block: an order, not a cycle
    files = dict(_CYCLE_TREE)
    files["cometbft_tpu/a.py"] = files["cometbft_tpu/a.py"].replace(
        "    def reverse(self, a: 'A'):\n"
        "        with self._block:\n"
        "            a.go()\n",
        "    def reverse(self, a: 'A'):\n"
        "        a.go()\n")
    res = lint(tmp_path, files, rules=[R.LockOrderRule])
    assert res.findings == []


def test_lock_order_self_reacquire_positive(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/a.py": (
            "import threading\n"
            "\n"
            "\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "\n"
            "    def outer(self):\n"
            "        with self._lock:\n"
            "            self.inner()\n"
            "\n"
            "    def inner(self):\n"
            "        with self._lock:\n"
            "            pass\n")},
        rules=[R.LockOrderRule])
    assert len(res.findings) == 1
    assert "re-acquired" in res.findings[0].message


def test_lock_order_rlock_reentry_negative(tmp_path):
    # the same shape on an RLock is by design (db/kv.MemDB.write_batch)
    res = lint(tmp_path, {
        "cometbft_tpu/a.py": (
            "import threading\n"
            "\n"
            "\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "\n"
            "    def outer(self):\n"
            "        with self._lock:\n"
            "            self.inner()\n"
            "\n"
            "    def inner(self):\n"
            "        with self._lock:\n"
            "            pass\n")},
        rules=[R.LockOrderRule])
    assert res.findings == []


def test_lock_order_closure_acquisition_not_charged_to_definer(tmp_path):
    # registering a callback that takes a lock, while holding another
    # lock, must NOT fabricate an edge: the closure runs later, on the
    # caller's thread, without the registrar's lock
    res = lint(tmp_path, {
        "cometbft_tpu/a.py": (
            "import threading\n"
            "\n"
            "\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._alock = threading.Lock()\n"
            "        self._block = threading.Lock()\n"
            "        self._cbs = []\n"
            "\n"
            "    def register(self):\n"
            "        def cb():\n"
            "            with self._block:\n"
            "                pass\n"
            "        self._cbs.append(cb)\n"
            "\n"
            "    def arm(self):\n"
            "        with self._alock:\n"
            "            self.register()\n"
            "\n"
            "    def other(self):\n"
            "        with self._block:\n"
            "            self.take_a()\n"
            "\n"
            "    def take_a(self):\n"
            "        with self._alock:\n"
            "            pass\n")},
        rules=[R.LockOrderRule])
    assert res.findings == [], "\n".join(
        f.render() for f in res.findings)


# --- rule: guarded-by (flow-aware) ----------------------------------------

_FLOW_TREE = {
    "cometbft_tpu/a.py": (
        "import threading\n"
        "\n"
        "\n"
        "class C:\n"
        "    # guarded-by: _lock: _n\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self._helper()\n"
        "\n"
        "    def _helper(self):\n"
        "        self._n += 1\n"),
}


def test_guarded_by_helper_under_lock_promoted(tmp_path):
    # _helper is private, never escapes, and its only call site holds
    # the lock: flow-aware v2 accepts the access WITHOUT a pragma (the
    # lexical PR-4 rule would have flagged it)
    res = lint(tmp_path, _FLOW_TREE, rules=[R.GuardedByRule])
    assert res.findings == []


def test_guarded_by_skippable_path_is_a_finding(tmp_path):
    # add one unlocked call site: the helper's entry set intersects to
    # empty and the access is flagged again
    files = dict(_FLOW_TREE)
    files["cometbft_tpu/a.py"] += (
        "\n"
        "    def sometimes(self):\n"
        "        self._helper()\n")
    res = lint(tmp_path, files, rules=[R.GuardedByRule])
    assert [f.rule for f in res.findings] == ["guarded-by"]


def test_guarded_by_escaped_method_not_promoted(tmp_path):
    # a method whose reference escapes (Thread target, callback) can
    # run without the lock no matter what its call sites look like
    files = dict(_FLOW_TREE)
    files["cometbft_tpu/a.py"] += (
        "\n"
        "    def start(self):\n"
        "        import threading as t\n"
        "        t.Thread(target=self._helper).start()\n")
    res = lint(tmp_path, files, rules=[R.GuardedByRule])
    assert [f.rule for f in res.findings] == ["guarded-by"]


def test_guarded_by_public_method_not_promoted(tmp_path):
    files = {
        "cometbft_tpu/a.py": _FLOW_TREE["cometbft_tpu/a.py"].replace(
            "_helper", "helper")}
    res = lint(tmp_path, files, rules=[R.GuardedByRule])
    assert [f.rule for f in res.findings] == ["guarded-by"]


def test_guarded_by_external_class_call_site_not_promoted(tmp_path):
    # another class resolves a call to the "private" method: its entry
    # set must drop to empty
    files = dict(_FLOW_TREE)
    files["cometbft_tpu/b.py"] = (
        "from .a import C\n"
        "\n"
        "\n"
        "def drive(c: C):\n"
        "    c._helper()\n")
    res = lint(tmp_path, files, rules=[R.GuardedByRule])
    assert [(f.rule, f.path) for f in res.findings] == [
        ("guarded-by", "cometbft_tpu/a.py")]


# --- rule: verdict-taint --------------------------------------------------

_DEVICE_STUBS = {
    "cometbft_tpu/device/__init__.py": "",
    "cometbft_tpu/device/client.py": (
        "from typing import List, Optional, Tuple\n"
        "\n"
        "\n"
        "class DeviceFuture:\n"
        "    def result(self, timeout=None) -> Tuple[bool, List[bool]]:\n"
        "        return True, []\n"
        "\n"
        "\n"
        "class DeviceClient:\n"
        "    def submit(self, pubs, msgs, sigs) -> DeviceFuture:\n"
        "        return DeviceFuture()\n"
        "\n"
        "    def verify(self, pubs, msgs, sigs):\n"
        "        return self.submit(pubs, msgs, sigs).result()\n"
        "\n"
        "\n"
        "def shared_client() -> Optional[DeviceClient]:\n"
        "    return DeviceClient()\n"),
    "cometbft_tpu/device/health.py": (
        "def check_canaries(out, n_lanes=None):\n"
        "    return True, list(out)[:-2]\n"),
    "cometbft_tpu/pipeline/__init__.py": "",
    "cometbft_tpu/pipeline/cache.py": (
        "class SigCache:\n"
        "    def add(self, pub, sign_bytes, sig):\n"
        "        pass\n"),
}


def _taint_tree(body):
    files = dict(_DEVICE_STUBS)
    files["cometbft_tpu/flow.py"] = (
        "from .device.client import shared_client\n"
        "from .device import health\n"
        "from .pipeline.cache import SigCache\n"
        "\n"
        "\n" + body)
    return files


def test_taint_uncanaried_sigcache_insert_positive(tmp_path):
    res = lint(tmp_path, _taint_tree(
        "def bad(lanes, cache: SigCache):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.submit([], [], []).result()\n"
        "    for lane, ok in zip(lanes, oks):\n"
        "        if ok:\n"
        "            cache.add(lane.pub, lane.msg, lane.sig)\n"),
        rules=[R.VerdictTaintRule])
    assert any(f.rule == "verdict-taint" for f in res.findings)


def test_taint_canaried_path_negative(tmp_path):
    # the REAL shape: same dispatch, verdicts pass check_canaries first
    res = lint(tmp_path, _taint_tree(
        "def good(lanes, cache: SigCache):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.submit([], [], []).result()\n"
        "    ok, oks = health.check_canaries(oks, len(lanes))\n"
        "    if not ok:\n"
        "        return\n"
        "    for lane, k in zip(lanes, oks):\n"
        "        if k:\n"
        "            cache.add(lane.pub, lane.msg, lane.sig)\n"),
        rules=[R.VerdictTaintRule])
    assert res.findings == []


@pytest.mark.parametrize("gated", [False, True],
                         ids=["uncanaried", "canaried"])
def test_taint_sigcache_keyed_insert(tmp_path, gated):
    """A batch insert of the lanes a device called true is a sink as
    `add` is: the verdicts it filters on must be canary-gated first."""
    files = _taint_tree(
        "def insert(keys, cache: SigCache):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.submit([], [], []).result()\n"
        + ("    ok, oks = health.check_canaries(oks, len(keys))\n"
           "    if not ok:\n"
           "        return\n" if gated else "")
        + "    cache.insert([k for k, ok in zip(keys, oks) if ok])\n")
    files["cometbft_tpu/pipeline/cache.py"] += (
        "\n"
        "    def insert(self, keys):\n"
        "        pass\n")
    res = lint(tmp_path, files, rules=[R.VerdictTaintRule])
    assert bool(res.findings) != gated
    assert all(f.rule == "verdict-taint" for f in res.findings)


def test_taint_mempool_check_tx_guard_positive(tmp_path):
    # a raw device verdict deciding admission — the exact invariant
    # ingest/ pins by test, caught statically
    res = lint(tmp_path, _taint_tree(
        "def admit(mempool, tx):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.verify([], [], [])\n"
        "    if oks[0]:\n"
        "        mempool.check_tx(tx)\n"),
        rules=[R.VerdictTaintRule])
    assert any("check_tx" in f.message for f in res.findings)


def test_taint_interprocedural_critical_param(tmp_path):
    # the verdict crosses a function boundary before gating the sink:
    # apply()'s sig_ok is sink-critical, so passing a raw verdict in
    # is a finding AT THE CALLER
    res = lint(tmp_path, _taint_tree(
        "def apply_verdict(mempool, tx, sig_ok):\n"
        "    if not sig_ok:\n"
        "        return 1\n"
        "    return mempool.check_tx(tx)\n"
        "\n"
        "\n"
        "def flow(mempool, tx):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.verify([], [], [])\n"
        "    apply_verdict(mempool, tx, oks[0])\n"),
        rules=[R.VerdictTaintRule])
    assert any(f.rule == "verdict-taint"
               and "cometbft_tpu/flow.py" == f.path
               for f in res.findings)


def test_taint_pragma_on_return_clears_summary_and_counts_used(tmp_path):
    # the canary-opt-out shape: the pragma'd return keeps downstream
    # sinks clean AND the stale-pragma audit counts the pragma as used
    res = lint(tmp_path, _taint_tree(
        "def backend():\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.submit([], [], []).result()\n"
        "    # staticcheck: allow(verdict-taint)\n"
        "    return oks\n"
        "\n"
        "\n"
        "def consume(mempool, tx):\n"
        "    oks = backend()\n"
        "    if oks[0]:\n"
        "        mempool.check_tx(tx)\n"),
        rules=[R.VerdictTaintRule])
    assert res.findings == []


def test_taint_unpragmad_tainted_return_propagates(tmp_path):
    res = lint(tmp_path, _taint_tree(
        "def backend():\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.submit([], [], []).result()\n"
        "    return oks\n"
        "\n"
        "\n"
        "def consume(mempool, tx):\n"
        "    oks = backend()\n"
        "    if oks[0]:\n"
        "        mempool.check_tx(tx)\n"),
        rules=[R.VerdictTaintRule])
    assert any("check_tx" in f.message for f in res.findings)


def test_taint_apply_one_sink_pair(tmp_path):
    # positive: a raw verdict gates the block apply
    res = lint(tmp_path, _taint_tree(
        "def sync(reactor, state, h, block):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.verify([], [], [])\n"
        "    if oks[0]:\n"
        "        return reactor._apply_one(state, h, block)\n"
        "    return state\n"),
        rules=[R.VerdictTaintRule])
    assert any("_apply_one" in f.message for f in res.findings)
    # negative: the canaried shape of the same flow
    res = lint(tmp_path, _taint_tree(
        "def sync(reactor, state, h, block, n):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.verify([], [], [])\n"
        "    ok, oks = health.check_canaries(oks, n)\n"
        "    if ok and oks[0]:\n"
        "        return reactor._apply_one(state, h, block)\n"
        "    return state\n"),
        rules=[R.VerdictTaintRule])
    assert res.findings == []


def test_taint_farm_commit_sink_pair(tmp_path):
    # positive: a raw verdict decides a farm session commit
    res = lint(tmp_path, _taint_tree(
        "def commit(session, lb):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.verify([], [], [])\n"
        "    if all(oks):\n"
        "        session.store.save_light_block(lb)\n"),
        rules=[R.VerdictTaintRule])
    assert any("save_light_block" in f.message for f in res.findings)
    # negative: gated through check_canaries first
    res = lint(tmp_path, _taint_tree(
        "def commit(session, lb, n):\n"
        "    client = shared_client()\n"
        "    _ok, oks = client.verify([], [], [])\n"
        "    ok, oks = health.check_canaries(oks, n)\n"
        "    if ok and all(oks):\n"
        "        session.store.save_light_block(lb)\n"),
        rules=[R.VerdictTaintRule])
    assert res.findings == []


# --- rule: kernel-discipline ----------------------------------------------

_KERNEL_TREE = {
    "cometbft_tpu/ops/__init__.py": "",
    "cometbft_tpu/ops/k.py": (
        "import numpy as np\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "\n"
        "\n"
        "def helper(x, flag):\n"
        "    if flag:\n"
        "        return x + 1\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return x - 1\n"
        "\n"
        "\n"
        "def widen(x):\n"
        "    return x.astype(jnp.int64)\n"
        "\n"
        "\n"
        "def core(a, b):\n"
        "    n = a.shape[0]\n"
        "    if n > 4:\n"
        "        a = a[:4]\n"
        "    v = helper(a, True)\n"
        "    v = widen(v)\n"
        "    c = np.asarray([1, 2, 3])\n"
        "    k = int(b)\n"
        "    return v + k + jnp.asarray(c)\n"
        "\n"
        "\n"
        "kernel = jax.jit(core)\n"
        "\n"
        "\n"
        "def scan_user(x):\n"
        "    def step(c, _):\n"
        "        if c.sum() > 0:\n"
        "            return c, None\n"
        "        return c + 1, None\n"
        "    out, _ = lax.scan(step, x, None, length=3)\n"
        "    return out\n"
        "\n"
        "\n"
        "def host_only(x):\n"
        "    big = np.asarray(x)\n"
        "    if big.sum() > 0:\n"
        "        return np.int64(1)\n"
        "    return 0\n"),
}


def test_kernel_discipline_positives(tmp_path):
    res = lint(tmp_path, _KERNEL_TREE, rules=[R.KernelDisciplineRule])
    msgs = [f.message for f in res.findings]
    assert any("data-dependent python `if`" in m for m in msgs)
    assert any("int64" in m for m in msgs)
    assert any("without dtype=" in m for m in msgs)
    assert any("int() concretizes" in m for m in msgs)
    # the scan body's traced branch is caught too
    assert any(f.line == 35 for f in res.findings), \
        [(f.line, f.message) for f in res.findings]


def test_kernel_discipline_static_negatives(tmp_path):
    res = lint(tmp_path, _KERNEL_TREE, rules=[R.KernelDisciplineRule])
    lines = {f.line for f in res.findings}
    # `if flag:` (call-site literal -> static) and `if n > 4:`
    # (shape-derived) must NOT be flagged
    assert 8 not in lines and 22 not in lines
    # host_only is unreachable from any entry: none of its sins count
    assert not any(f.line >= 41 for f in res.findings)


def test_kernel_discipline_static_argnames(tmp_path):
    files = {
        "cometbft_tpu/ops/__init__.py": "",
        "cometbft_tpu/ops/s.py": (
            "import jax\n"
            "\n"
            "\n"
            "def core(x, strict):\n"
            "    if strict:\n"
            "        return x\n"
            "    return x + 1\n"
            "\n"
            "\n"
            "kernel = jax.jit(core, static_argnames=('strict',))\n"),
    }
    res = lint(tmp_path, files, rules=[R.KernelDisciplineRule])
    assert res.findings == []
    # ...and without the static marker the same branch is a finding
    files["cometbft_tpu/ops/s.py"] = files[
        "cometbft_tpu/ops/s.py"].replace(", static_argnames=('strict',)",
                                         "")
    res = lint(tmp_path, {k: v for k, v in files.items()},
               rules=[R.KernelDisciplineRule])
    assert [f.rule for f in res.findings] == ["kernel-discipline"]


# --- stale-pragma audit + inventory ---------------------------------------

def test_stale_pragma_flagged_and_used_pragma_kept(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/x.py": (
            "import time\n"
            "t = time.monotonic()  # staticcheck: allow(wallclock)\n"
            "y = 1  # staticcheck: allow(wallclock)\n")})
    assert names(res) == [("stale-pragma", "cometbft_tpu/x.py")]
    assert res.findings[0].line == 3
    assert res.suppressed == 1
    assert ("cometbft_tpu/x.py", 2, "wallclock") in [
        (p, l, r) for (p, l, r) in res.pragma_inventory]


def test_pragma_inventory_lists_all(tmp_path):
    res = lint(tmp_path, {
        "cometbft_tpu/x.py": (
            "import time\n"
            "t = time.monotonic()  # staticcheck: allow(wallclock)\n")})
    assert res.pragma_inventory == [("cometbft_tpu/x.py", 2, "wallclock")]


# --- per-rule timing + CLI surfaces ---------------------------------------

def test_rule_seconds_populated(tmp_path):
    res = lint(tmp_path, {"cometbft_tpu/x.py": "x = 1\n"})
    assert "wallclock" in res.rule_seconds
    assert "(project-graph)" in res.rule_seconds
    assert all(v >= 0 for v in res.rule_seconds.values())


def test_cli_format_json_and_rule_filter(tmp_path):
    pkg = tmp_path / "cometbft_tpu"
    pkg.mkdir(parents=True)
    (pkg / "x.py").write_text("import time\nt = time.monotonic()\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--root",
         str(tmp_path), "--format", "json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    import json
    doc = json.loads(proc.stdout)
    assert doc["findings"][0]["rule"] == "wallclock"
    assert "rule_seconds" in doc and "wallclock" in doc["rule_seconds"]
    # --rule filter: only the named rule runs; a finding for another
    # rule's domain does not appear
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--root",
         str(tmp_path), "--rule", "global-rng"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--root",
         str(tmp_path), "--rule", "nope"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2


def test_cli_list_pragmas(tmp_path):
    pkg = tmp_path / "cometbft_tpu"
    pkg.mkdir(parents=True)
    (pkg / "x.py").write_text(
        "import time\n"
        "t = time.monotonic()  # staticcheck: allow(wallclock)\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--root",
         str(tmp_path), "--list-pragmas"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "cometbft_tpu/x.py:2: allow(wallclock)" in proc.stdout


# --- the real tree (v2 families) ------------------------------------------

@pytest.fixture(scope="module")
def real_tree():
    """The four v2 families over the real tree, linted ONCE for this
    file (every run_checks(REPO) parses the tree and builds the project
    graph again); a finding of one family fails the others' tests too,
    and names its rule."""
    return run_checks(REPO, rules=[R.GuardedByRule, R.LockOrderRule,
                                   R.VerdictTaintRule,
                                   R.KernelDisciplineRule])


def test_real_tree_has_flow_promoted_helpers(real_tree):
    """The flow-aware engine accepts the tree's caller-holds-the-lock
    helpers (ingest _shed_locked, farm _run_batch, supervisor
    _set_state) with NO pragma — if this starts failing, either a new
    unlocked call site appeared (a real bug) or the promotion
    regressed."""
    res = real_tree
    assert [f for f in res.findings if f.rule == "guarded-by"] == [], \
        "\n".join(f.render() for f in res.findings)


def test_real_tree_lock_graph_acyclic(real_tree):
    res = real_tree
    assert res.findings == [], "\n".join(
        f.render() for f in res.findings)


def test_real_tree_verdict_taint_clean_with_optout_pragmas(real_tree):
    """The canaried paths (farm/ingest/aggsig/RemoteBatchVerifier) are
    clean; the two deliberate canary-opt-out returns are pragma'd with
    a why and must stay both pragma'd AND exercised (the stale audit
    fails if taint stops reaching them)."""
    res = real_tree
    assert res.findings == [], "\n".join(
        f.render() for f in res.findings)


def test_real_tree_kernel_discipline_clean(real_tree):
    res = real_tree
    assert res.findings == [], "\n".join(
        f.render() for f in res.findings)
