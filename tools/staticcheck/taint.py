"""verdict-taint — device-produced verdicts must pass a canary gate (or
a CPU re-verify) before anything acts on them.

This pins the PR-3/PR-7 invariant ("device results are never trusted
un-canaried") STATICALLY instead of only by test: a device can answer
wrong without failing, so the only trustworthy paths from a device
answer to a state-changing decision run through `check_canaries`, a
canary-gated checker, or a CPU recomputation.

Model (interprocedural, over the shared Project graph):

SOURCES — expressions whose value is a raw device verdict:
  * `DeviceFuture.result()` / `DeviceClient.verify()` calls, resolved
    through the light type facts (a receiver is device-typed when it
    came from `shared_client()`, a `DeviceClient(...)` constructor, or
    a parameter/attribute annotated `DeviceClient`; `.submit()` on a
    device client returns a `DeviceFuture` via its return annotation);
  * `ops.bls12.final_exp_is_one_batch(...)` and
    `ops.bls12.miller_finalexp_is_one_batch(...)` (the FinalExpChecker
    and PairingChecker kernel feeds).

SANITIZERS / GATES — what clears taint:
  * assignment from `device.health.check_canaries(...)` (the verdicts
    come back stripped and length-checked);
  * calls into GATE functions whose *internal* canary discipline is
    pinned by tests (`FinalExpChecker.check`/`_kernel_check`,
    `PairingChecker.check`/`_kernel_check`,
    `PipelinedBlocksync._canary_check`): their returns are clean;
  * re-binding a name from any clean expression (a CPU re-verify).

SINKS — where a tainted verdict becomes consensus/cache state:
  * `SigCache.add` and `SigCache.insert` (type-resolved receiver),
  * attribute calls named `check_tx`, `_apply_one`, or
    `save_light_block` (mempool admission, block apply, farm decision
    commit) — name-matched, because the mempool/reactor seams pass
    these objects untyped.

A finding fires when a tainted value (1) is an argument to a sink or
to a resolved callee's SINK-CRITICAL parameter (a parameter that
itself flows into a sink, computed to fixpoint), or (2) guards —
directly or via an early-return — a call that reaches a sink.

Escape hatch: a `# staticcheck: allow(verdict-taint)` pragma on a
RETURN that deliberately forwards an un-gated verdict (the
canary-opt-out configuration) marks the function's summary clean, and
the runner's stale-pragma audit keeps that pragma honest — if the
return stops being tainted, the pragma must go. Unresolved calls are
treated as CLEAN (the conservative direction here would flood every
`.verify()` in the tree); the dynamic-dispatch seams this misses are
exactly the ones the canary/quarantine tests pin at runtime — see
docs/STATICCHECK.md for the soundness tradeoff.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from . import FileCtx, Finding

# label "T" = a real device verdict; "P<i>" = the value of parameter i
Labels = FrozenSet[str]
EMPTY: Labels = frozenset()
T: Labels = frozenset({"T"})

_PKG = "cometbft_tpu"

SOURCE_METHODS = {
    f"{_PKG}.device.client.DeviceClient.verify",
    f"{_PKG}.device.client.DeviceFuture.result",
}
SOURCE_FUNCS = {
    f"{_PKG}.ops.bls12.final_exp_is_one_batch",
    f"{_PKG}.ops.bls12.miller_finalexp_is_one_batch",
}
SANITIZERS = {
    f"{_PKG}.device.health.check_canaries",
}
# canary gates whose internal discipline is pinned by tests
# (test_aggsig: wrong canary -> quarantine + CPU re-verify;
# test_pipeline/test_device_health: tile canary mismatch -> quarantine
# + CPU re-verify): their RETURNS are trusted clean.
GATES = {
    f"{_PKG}.aggsig.verify.FinalExpChecker.check",
    f"{_PKG}.aggsig.verify.FinalExpChecker._kernel_check",
    f"{_PKG}.aggsig.verify.PairingChecker.check",
    f"{_PKG}.aggsig.verify.PairingChecker._kernel_check",
    f"{_PKG}.pipeline.scheduler.PipelinedBlocksync._canary_check",
}
SINK_QUALS = {
    f"{_PKG}.pipeline.cache.SigCache.add",
    f"{_PKG}.pipeline.cache.SigCache.insert",
}
SINK_NAMES = {"check_tx", "_apply_one", "save_light_block",
              "install_adopted"}


class _Summary:
    __slots__ = ("returns", "critical", "reaches_sink")

    def __init__(self):
        self.returns: Labels = EMPTY       # labels a call may return
        self.critical: Set[int] = set()    # param indices flowing to a sink
        self.reaches_sink = False


class VerdictTaintRule:
    name = "verdict-taint"
    doc = ("un-canaried device verdict reaches mempool.check_tx / "
           "_apply_one / SigCache.add / a farm decision commit — gate "
           "it through check_canaries, a canary-gated checker, or a "
           "CPU re-verify (docs/STATICCHECK.md)")
    roots: Tuple[str, ...] = ("cometbft_tpu",)
    exempt: frozenset = frozenset()
    tree_rule = True
    needs_project = True

    def __init__(self):
        self.used_pragmas: Set[Tuple[str, int, str]] = set()

    def applies_to(self, path: str) -> bool:
        if path in self.exempt:
            return False
        return any(path == top or path.startswith(top + "/")
                   for top in self.roots)

    def check(self, ctx: FileCtx):
        return ()

    # --- driver -----------------------------------------------------------

    def finalize(self, root: str, project=None) -> Iterator[Finding]:
        if project is None:
            return
        from .lock_rules import _local_env
        funcs = [f for f in project.functions.values()
                 if self.applies_to(f.path)]
        envs = {f.qualname: _local_env(project, f) for f in funcs}
        # (env + call resolution are memoized on the project and
        # shared with lock-order/guarded-by — see lock_rules)
        summaries: Dict[str, _Summary] = {f.qualname: _Summary()
                                          for f in funcs}
        # fixpoint over summaries (returns / critical params / reaches)
        for _ in range(len(funcs)):
            changed = False
            for f in funcs:
                s = summaries[f.qualname]
                before = (s.returns, frozenset(s.critical),
                          s.reaches_sink)
                _Interp(self, project, f, envs[f.qualname], summaries,
                        emit=None).run()
                if (s.returns, frozenset(s.critical),
                        s.reaches_sink) != before:
                    changed = True
            if not changed:
                break
        findings: List[Finding] = []
        for f in funcs:
            ctx = project.ctxs.get(f.path)
            _Interp(self, project, f, envs[f.qualname], summaries,
                    emit=findings.append, ctx=ctx).run()
        seen = set()
        for fnd in sorted(findings, key=lambda x: (x.path, x.line,
                                                   x.message)):
            key = (fnd.path, fnd.line, fnd.message)
            if key not in seen:
                seen.add(key)
                yield fnd

    def record_pragma(self, ctx: FileCtx, line: int) -> bool:
        """True (and records the use for the stale-pragma audit) when
        an allow(verdict-taint) covers `line`."""
        if ctx is None:
            return False
        if ctx.has_pragma(self.name, line):
            at = line if self.name in ctx.pragmas.get(line, set()) \
                else line - 1
            self.used_pragmas.add((ctx.path, at, self.name))
            return True
        return False


class _Interp:
    """One pass of the labels-based abstract interpreter over a
    function body. With emit=None it only updates the function's
    summary; with an emit callback it reports sink findings."""

    def __init__(self, rule: VerdictTaintRule, project, func, env,
                 summaries: Dict[str, _Summary], emit, ctx=None):
        self.rule = rule
        self.project = project
        self.func = func
        self.env = env
        self.summaries = summaries
        self.emit = emit
        self.ctx = ctx if ctx is not None else project.ctxs.get(func.path)
        self.summary = summaries[func.qualname]
        from .lock_rules import _call_targets
        self._targets = _call_targets(project, func)
        self.params: List[str] = []
        args = getattr(func.node, "args", None)
        if args is not None:
            self.params = [a.arg for a in
                           args.posonlyargs + args.args]

    # --- entry ------------------------------------------------------------

    def run(self) -> None:
        state: Dict[str, Labels] = {}
        for i, p in enumerate(self.params):
            if p == "self":
                continue
            state[p] = frozenset({f"P{i}"})
        self.exec_block(self.func.node.body, state, EMPTY)

    # --- expression labels ------------------------------------------------

    def labels(self, node: ast.AST, state: Dict[str, Labels]) -> Labels:
        if isinstance(node, ast.Name):
            return state.get(node.id, EMPTY)
        if isinstance(node, ast.Call):
            return self.call_labels(node, state)
        if isinstance(node, ast.Lambda):
            return EMPTY
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return EMPTY
        out: Labels = EMPTY
        for child in ast.iter_child_nodes(node):
            out |= self.labels(child, state)
        return out

    def _resolve(self, call: ast.Call) -> List[str]:
        return self._targets.get(id(call), [])

    def call_labels(self, node: ast.Call,
                    state: Dict[str, Labels]) -> Labels:
        targets = self._resolve(node)
        arg_labels: Labels = EMPTY
        for a in list(node.args) + [kw.value for kw in node.keywords]:
            arg_labels |= self.labels(a, state)
        self._check_sink(node, state, arg_labels, targets)
        if any(t in SANITIZERS or t in GATES for t in targets):
            return EMPTY
        out: Labels = EMPTY
        if any(t in SOURCE_FUNCS or t in SOURCE_METHODS
               for t in targets):
            out |= T
        fn = node.func
        resolved_fn = [t for t in targets
                       if t in self.project.functions]
        if resolved_fn:
            for t in resolved_fn:
                s = self.summaries.get(t)
                if s is not None:
                    out |= (s.returns & T)
            # resolved callees still pass their inputs through
            # (identity/transform helpers): assume arg labels survive
            out |= arg_labels
        else:
            # unresolved / builtin: pass-through of argument labels,
            # plus the receiver's labels for method calls
            out |= arg_labels
            if isinstance(fn, ast.Attribute):
                out |= self.labels(fn.value, state)
        return out

    # --- sinks ------------------------------------------------------------

    def _is_sink(self, node: ast.Call, targets: List[str]) -> bool:
        if any(t in SINK_QUALS for t in targets):
            return True
        fn = node.func
        return isinstance(fn, ast.Attribute) and fn.attr in SINK_NAMES

    def _check_sink(self, node: ast.Call, state: Dict[str, Labels],
                    arg_labels: Labels, targets: List[str]) -> None:
        sink = self._is_sink(node, targets)
        reaches = sink or any(
            self.summaries[t].reaches_sink
            for t in targets if t in self.summaries)
        if reaches:
            self.summary.reaches_sink = True
        # tainted ARGUMENT into a sink / a callee's sink-critical param
        crit_hit: Labels = EMPTY
        if sink:
            crit_hit |= arg_labels
        for t in targets:
            s = self.summaries.get(t)
            if s is None or not s.critical:
                continue
            callee = self.project.functions.get(t)
            offset = 1 if (callee is not None and callee.is_method
                           and not isinstance(node.func, ast.Name)) \
                else 0
            for j, a in enumerate(node.args):
                if j + offset in s.critical:
                    crit_hit |= self.labels(a, state)
            if callee is not None:
                names = [a.arg for a in
                         callee.node.args.posonlyargs
                         + callee.node.args.args]
                for kw in node.keywords:
                    if kw.arg in names and \
                            names.index(kw.arg) in s.critical:
                        crit_hit |= self.labels(kw.value, state)
        self._hit(node, crit_hit,
                  "flows into" if sink else "flows into a call that "
                  "reaches")
        # sink (or sink-reaching call) under a tainted guard
        if reaches and self.guard:
            self._hit(node, self.guard, "gates")

    def _hit(self, node: ast.Call, labels: Labels, how: str) -> None:
        for lbl in labels:
            if lbl == "T":
                if self.emit is not None:
                    name = ast.unparse(node.func) if hasattr(
                        ast, "unparse") else "<sink>"
                    self.emit(Finding(
                        self.rule.name, self.func.path, node.lineno,
                        f"un-canaried device verdict {how} "
                        f"`{name}(...)` — gate it through "
                        f"check_canaries / a canary-gated checker / a "
                        f"CPU re-verify first",
                        self.ctx.line_text(node.lineno)
                        if self.ctx else ""))
            elif lbl.startswith("P"):
                self.summary.critical.add(int(lbl[1:]))

    # --- statements -------------------------------------------------------

    def exec_block(self, body: List[ast.stmt], state: Dict[str, Labels],
                   guard: Labels) -> bool:
        """Returns True when the block terminates (return/raise/...).
        `guard` = labels controlling whether this block runs at all."""
        self.guard = guard
        for i, stmt in enumerate(body):
            self.guard = guard
            if self.exec_stmt(stmt, state, guard):
                return True
            # an early-terminating tainted If extends its guard over
            # the REST of the block (implicit control dependence)
            if isinstance(stmt, ast.If):
                test_labels = self.labels(stmt.test, state)
                if test_labels and (
                        _terminates(stmt.body)
                        or (stmt.orelse and _terminates(stmt.orelse))):
                    guard = guard | test_labels
        return False

    def exec_stmt(self, stmt: ast.stmt, state: Dict[str, Labels],
                  guard: Labels) -> bool:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return False   # nested defs: analyzed conservatively never
        if isinstance(stmt, ast.Return):
            if stmt.value is not None:
                lbls = self.labels(stmt.value, state)
                if "T" in lbls and self.rule.record_pragma(
                        self.ctx, stmt.lineno):
                    lbls = lbls - T
                self.summary.returns |= lbls | (guard & T)
            return True
        if isinstance(stmt, (ast.Raise, ast.Break, ast.Continue)):
            return True
        if isinstance(stmt, ast.Assign):
            lbls = self.labels(stmt.value, state)
            sanitized = self._is_sanitizer_call(stmt.value)
            for t in stmt.targets:
                self._bind(t, EMPTY if sanitized else lbls, state)
            return False
        if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._bind(stmt.target, self.labels(stmt.value, state),
                       state)
            return False
        if isinstance(stmt, ast.AugAssign):
            lbls = self.labels(stmt.value, state)
            if isinstance(stmt.target, ast.Name):
                state[stmt.target.id] = \
                    state.get(stmt.target.id, EMPTY) | lbls
            else:
                self._bind(stmt.target, lbls, state)
            return False
        if isinstance(stmt, ast.If):
            test = self.labels(stmt.test, state)
            inner_guard = guard | (test & T)
            s1 = dict(state)
            t1 = self.exec_block(stmt.body, s1, inner_guard)
            s2 = dict(state)
            t2 = self.exec_block(stmt.orelse, s2, inner_guard)
            _merge(state, s1 if not t1 else None, s2 if not t2 else None)
            return t1 and t2 and bool(stmt.orelse)
        if isinstance(stmt, (ast.While,)):
            test = self.labels(stmt.test, state)
            inner_guard = guard | (test & T)
            for _ in range(2):          # quasi-fixpoint: labels grow
                s1 = dict(state)
                self.exec_block(stmt.body, s1, inner_guard)
                _merge(state, s1, None)
            self.exec_block(stmt.orelse, state, guard)
            return False
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            it = self.labels(stmt.iter, state)
            self._bind(stmt.target, it, state)
            for _ in range(2):
                s1 = dict(state)
                self.exec_block(stmt.body, s1, guard)
                _merge(state, s1, None)
            self.exec_block(stmt.orelse, state, guard)
            return False
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                lbls = self.labels(item.context_expr, state)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars, lbls, state)
            return self.exec_block(stmt.body, state, guard)
        if isinstance(stmt, ast.Try):
            s1 = dict(state)
            self.exec_block(stmt.body, s1, guard)
            _merge(state, s1, None)
            for h in stmt.handlers:
                s2 = dict(state)
                self.exec_block(h.body, s2, guard)
                _merge(state, s2, None)
            self.exec_block(stmt.orelse, state, guard)
            self.exec_block(stmt.finalbody, state, guard)
            return False
        if isinstance(stmt, ast.Expr):
            self.labels(stmt.value, state)
            return False
        # default: evaluate embedded expressions for sink detection
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self.labels(child, state)
        return False

    def _is_sanitizer_call(self, node: ast.AST) -> bool:
        return isinstance(node, ast.Call) and any(
            t in SANITIZERS for t in self._resolve(node))

    def _bind(self, target: ast.AST, lbls: Labels,
              state: Dict[str, Labels]) -> None:
        if isinstance(target, ast.Name):
            state[target.id] = lbls
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, lbls, state)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, lbls, state)
        elif isinstance(target, (ast.Attribute, ast.Subscript)):
            # write through an object: taint sticks to the base name
            base = target.value
            while isinstance(base, (ast.Attribute, ast.Subscript)):
                base = base.value
            if isinstance(base, ast.Name) and lbls:
                state[base.id] = state.get(base.id, EMPTY) | lbls


def _terminates(body: List[ast.stmt]) -> bool:
    return bool(body) and isinstance(
        body[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))


def _merge(state: Dict[str, Labels], a: Optional[Dict[str, Labels]],
           b: Optional[Dict[str, Labels]]) -> None:
    branches = [s for s in (a, b) if s is not None]
    if not branches:
        return   # both paths terminated; fall-through state unchanged
    keys = set(state)
    for src in branches:
        keys |= set(src)
    for k in keys:
        vals: Labels = EMPTY
        for s in branches:
            vals |= s.get(k, state.get(k, EMPTY))
        state[k] = vals
