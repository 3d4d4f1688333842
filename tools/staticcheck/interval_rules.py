"""kernel-interval — interval-domain abstract interpretation proving
the int32 no-overflow contract over every ops/ kernel path.

PR 9's kernel-discipline rule pattern-matches the int32 Montgomery
discipline (no int64 mentions, no >= 2**31 literals); it cannot prove
that a limb product plus carry accumulator actually stays below 2**31
on every reachable path — the silent-wraparound class that corrupts a
verdict without tripping a canary. This rule interprets the kernel
sources abstractly, mirroring jax tracing: concrete python host values
execute concretely (unrolled range loops, shape arithmetic, module
constants), traced arrays carry integer intervals per dtype.

Domain
  - `IV(lo, hi)`: integer interval (python ints, saturating sentinels).
  - `Arr(dtype, shape, rows, iv)`: abstract array. `rows` tracks one
    interval per leading-axis index when the leading dim is concrete —
    load-bearing for CIOS fixpoint convergence (mont_mul's per-limb
    accumulator rows converge where a single hull would not).
  - Symbolic batch dims are `SymDim`s bounded [1, 2**40] by default;
    `assert` statements refine them (sc_dot_mod_l's
    `assert la + lb <= 30 and n <= (1 << 15)` is what makes its
    batch-sum provably int32-safe, exactly as its docstring claims).

Policy
  - int32-typed results escaping [-2**31, 2**31) are findings carrying
    the computed bounds and the interpretation call path.
  - uint32 arithmetic wraps mod 2**32 BY DESIGN (sha512's two-word
    adds); the transfer keeps the exact interval when it fits and
    silently widens to [0, 2**32) otherwise. uint32→int32 astype is
    still checked for fit.
  - `# staticcheck: assume(x, lo, hi[, shape=][, dtype=])` pragmas are
    checked, not trusted: computed ⊆ assumed proves the pragma;
    disjoint is a contradiction finding; overlap refines the value AND
    registers a runtime obligation that tools/interval_fuzz.py
    re-checks on concrete shadow executions. On entry params (pragma
    lines between `def` and the first body statement) they are the
    preconditions the fuzzer samples inside.
  - lax.scan / fori_loop / while_loop and python `while` on symbolic
    conditions run join-to-fixpoint (cap, then widening to the dtype
    range); small concrete fori/scan bodies unroll for precision.

Entries are every jax.jit target in ops/ (decorators, module-level
jit() assignments, and jit() closures inside lru_cached factories,
whose params seed from assume() pragmas or the unique module constant
every call site passes). See docs/STATICCHECK.md §v3.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field as dc_field
from typing import (Any, Callable, Dict, Iterable, Iterator, List,
                    Optional, Set, Tuple)

from . import Assume, FileCtx, Finding

INF = 1 << 140          # saturating "unbounded" sentinel
I32_LO, I32_HI = -(1 << 31), (1 << 31) - 1
DTYPE_RANGE: Dict[str, Tuple[int, int]] = {
    "int32": (I32_LO, I32_HI),
    "uint32": (0, (1 << 32) - 1),
    "int64": (-(1 << 63), (1 << 63) - 1),
    "uint64": (0, (1 << 64) - 1),
    "uint8": (0, 255),
    "int8": (-128, 127),
    "int16": (-(1 << 15), (1 << 15) - 1),
    "uint16": (0, (1 << 16) - 1),
    "bool": (0, 1),
}
# dtypes whose arithmetic wraps silently by design (modular packing);
# int32 is the CONTRACT dtype: escapes are findings, never wraps.
_WRAP_DTYPES = {"uint32", "uint8", "uint16", "uint64", "int8", "int16"}
DEFAULT_DIM_HI = 1 << 40    # unrefined symbolic batch dim upper bound
ROWS_MAX = 1024             # leading-axis row tracking cap
UNROLL_MAX = 128            # concrete fori/scan unroll cap
JOIN_CAP = 64               # plain fixpoint joins before widening
WIDEN_EXTRA = 8             # widened iterations before giving up
CONCRETE_WHILE_CAP = 8192   # concrete python-loop runaway guard


def _clamp(v: int) -> int:
    return -INF if v < -INF else (INF if v > INF else v)


class IV:
    """Closed integer interval [lo, hi], saturating at +-INF."""
    __slots__ = ("lo", "hi")

    def __init__(self, lo: int, hi: int):
        self.lo, self.hi = _clamp(lo), _clamp(hi)

    def __repr__(self):
        def s(v):
            return "-inf" if v <= -INF else ("+inf" if v >= INF else str(v))
        return f"[{s(self.lo)}, {s(self.hi)}]"

    def __eq__(self, other):
        return isinstance(other, IV) and self.lo == other.lo \
            and self.hi == other.hi

    def __hash__(self):
        return hash((self.lo, self.hi))

    @property
    def exact(self) -> Optional[int]:
        return self.lo if self.lo == self.hi else None

    def join(self, o: "IV") -> "IV":
        return IV(min(self.lo, o.lo), max(self.hi, o.hi))

    def meet(self, o: "IV") -> Optional["IV"]:
        lo, hi = max(self.lo, o.lo), min(self.hi, o.hi)
        return IV(lo, hi) if lo <= hi else None

    def inside(self, lo: int, hi: int) -> bool:
        return lo <= self.lo and self.hi <= hi

    def widen(self, new: "IV", dtype: Optional[str]) -> "IV":
        dlo, dhi = DTYPE_RANGE.get(dtype or "", (-INF, INF))
        lo = self.lo if new.lo >= self.lo else min(dlo, new.lo)
        hi = self.hi if new.hi <= self.hi else max(dhi, new.hi)
        return IV(lo, hi)


def iv_of(v: Any) -> IV:
    if isinstance(v, IV):
        return v
    if isinstance(v, bool):
        return IV(int(v), int(v))
    if isinstance(v, int):
        return IV(v, v)
    if isinstance(v, SymDim):
        return v.bound
    if isinstance(v, Arr):
        return v.iv
    raise TypeError(f"no interval for {type(v).__name__}")


def _minmax(*vals: int) -> IV:
    return IV(min(vals), max(vals))


def iv_add(a: IV, b: IV) -> IV:
    return IV(a.lo + b.lo, a.hi + b.hi)


def iv_sub(a: IV, b: IV) -> IV:
    return IV(a.lo - b.hi, a.hi - b.lo)


def iv_mul(a: IV, b: IV) -> IV:
    return _minmax(a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi)


def iv_floordiv(a: IV, b: IV) -> Optional[IV]:
    # split the divisor range around zero; empty nonzero part -> None
    cands: List[int] = []
    for blo, bhi in ((max(b.lo, 1), b.hi), (b.lo, min(b.hi, -1))):
        if blo > bhi:
            continue
        cands += [a.lo // blo, a.lo // bhi, a.hi // blo, a.hi // bhi]
    return _minmax(*cands) if cands else None


def iv_mod(a: IV, b: IV) -> Optional[IV]:
    # python semantics: sign follows the divisor
    if b.lo >= 1:
        if a.lo >= 0 and a.hi < b.lo and b.exact is not None:
            return IV(a.lo, a.hi)      # already reduced
        return IV(0, b.hi - 1)
    if b.hi <= -1:
        return IV(b.lo + 1, 0)
    return None


def iv_lshift(a: IV, b: IV) -> Optional[IV]:
    if b.lo < 0 or b.hi >= 512:
        return None
    return _minmax(a.lo << b.lo, a.lo << b.hi,
                   a.hi << b.lo, a.hi << b.hi)


def iv_rshift(a: IV, b: IV) -> Optional[IV]:
    if b.lo < 0:
        return None
    bhi = min(b.hi, 512)
    return _minmax(a.lo >> b.lo, a.lo >> bhi,
                   a.hi >> b.lo, a.hi >> bhi)


def iv_and(a: IV, b: IV) -> IV:
    if a.exact is not None and b.exact is not None:
        v = a.exact & b.exact
        return IV(v, v)
    # a non-negative mask bounds the result in [0, mask] regardless of
    # the other side's sign (two's complement)
    if b.lo >= 0:
        return IV(0, b.hi if a.lo < 0 else min(a.hi, b.hi))
    if a.lo >= 0:
        return IV(0, a.hi if b.lo < 0 else min(a.hi, b.hi))
    return IV(min(a.lo, b.lo), max(a.hi, b.hi))


def _pow2_ceil(v: int) -> int:
    return (1 << v.bit_length()) - 1 if v > 0 else 0


def iv_or(a: IV, b: IV) -> IV:
    if a.exact is not None and b.exact is not None:
        v = a.exact | b.exact
        return IV(v, v)
    if a.lo >= 0 and b.lo >= 0:
        return IV(max(a.lo, b.lo), _pow2_ceil(max(a.hi, b.hi)))
    return IV(min(a.lo, b.lo), max(a.hi, b.hi, -1))


def iv_xor(a: IV, b: IV) -> IV:
    if a.exact is not None and b.exact is not None:
        v = a.exact ^ b.exact
        return IV(v, v)
    if a.lo >= 0 and b.lo >= 0:
        return IV(0, _pow2_ceil(max(a.hi, b.hi)))
    m = max(abs(a.lo), abs(a.hi), abs(b.lo), abs(b.hi))
    bound = _pow2_ceil(m) + 1
    return IV(-bound, bound)


_IV_BINOPS: Dict[type, Callable[[IV, IV], Optional[IV]]] = {
    ast.Add: iv_add, ast.Sub: iv_sub, ast.Mult: iv_mul,
    ast.FloorDiv: iv_floordiv, ast.Mod: iv_mod,
    ast.LShift: iv_lshift, ast.RShift: iv_rshift,
    ast.BitAnd: iv_and, ast.BitOr: iv_or, ast.BitXor: iv_xor,
}


class SymDim:
    """A symbolic array dimension with a refinable bound. Identity is
    object identity: the same assume() shape symbol within one entry
    names the same dim. `assert` comparisons tighten `bound` — sound
    because a trace-time assert guards every concrete execution."""
    __slots__ = ("name", "bound")

    def __init__(self, name: str, bound: Optional[IV] = None):
        self.name = name
        self.bound = bound or IV(1, DEFAULT_DIM_HI)

    def __repr__(self):
        return f"<{self.name}{self.bound}>"


Dim = Any   # int | SymDim | IV


def dim_iv(d: Dim) -> IV:
    return iv_of(d)


def dim_eq(a: Dim, b: Dim) -> bool:
    if isinstance(a, int) and isinstance(b, int):
        return a == b
    return a is b


def unify_dim(a: Dim, b: Dim) -> Optional[Dim]:
    """Broadcast-unify two dims (1 broadcasts; equal survives; a
    concrete int refines a symbolic dim — jax would have raised on a
    real mismatch, so taking the concrete side is sound)."""
    if isinstance(a, int):
        if a == 1:
            return b
        if isinstance(b, int):
            return a if (a == b or b == 1) else None
        return a
    if isinstance(b, int):
        return unify_dim(b, a)
    return a    # two symbolic dims: assume equal (trace would check)


def broadcast_shapes(*shapes: Tuple[Dim, ...]) -> Optional[Tuple[Dim, ...]]:
    rank = max((len(s) for s in shapes), default=0)
    out: List[Dim] = []
    for i in range(rank):
        d: Dim = 1
        for s in shapes:
            j = i - (rank - len(s))
            if j < 0:
                continue
            u = unify_dim(d, s[j])
            if u is None:
                return None
            d = u
        out.append(d)
    return tuple(out)


def shape_numel(shape: Tuple[Dim, ...]) -> Optional[int]:
    n = 1
    for d in shape:
        if not isinstance(d, int):
            return None
        n *= d
    return n


class Arr:
    """Abstract jax array: dtype tag, shape, optional per-leading-axis
    row intervals, and the hull interval. Immutable — every transfer
    returns a new Arr."""
    __slots__ = ("dtype", "shape", "rows", "iv")

    def __init__(self, dtype: str, shape: Tuple[Dim, ...],
                 rows: Optional[List[IV]], iv: IV):
        self.dtype = dtype
        self.shape = tuple(shape)
        if rows is not None and (not self.shape
                                 or not isinstance(self.shape[0], int)
                                 or len(rows) != self.shape[0]
                                 or len(rows) > ROWS_MAX):
            rows = None
        self.rows = rows
        if rows:
            iv = rows[0]
            for r in rows[1:]:
                iv = iv.join(r)
        self.iv = iv

    def __repr__(self):
        return f"Arr({self.dtype}, {self.shape}, {self.iv})"

    def row_list(self) -> Optional[List[IV]]:
        """Rows, materializing a uniform list when the leading dim is
        concrete and small — lets strided slices stay exact even after
        a row-discarding op."""
        if self.rows is not None:
            return list(self.rows)
        if self.shape and isinstance(self.shape[0], int) \
                and self.shape[0] <= ROWS_MAX:
            return [self.iv] * self.shape[0]
        return None

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def sig(self):
        return ("a", self.dtype, shape_sig(self.shape),
                tuple((r.lo, r.hi) for r in self.rows)
                if self.rows is not None else None,
                (self.iv.lo, self.iv.hi))


def shape_sig(shape: Tuple[Dim, ...]):
    return tuple(d if isinstance(d, int)
                 else ("s", id(d)) if isinstance(d, SymDim)
                 else ("v", d.lo, d.hi) for d in shape)


class Opaque:
    """Analysis hole. Creating one inside an entry interpretation is a
    reportable gap in the proof (the creator calls Interp.unknown)."""
    __slots__ = ("reason",)

    def __init__(self, reason: str):
        self.reason = reason

    def __repr__(self):
        return f"Opaque({self.reason})"


class Unknown:
    """Three-valued truth for static flags (zip215/interpret) and
    undecidable comparisons: `if` joins both branches."""
    __slots__ = ("why",)

    def __init__(self, why: str = ""):
        self.why = why

    def __repr__(self):
        return f"Unknown({self.why})"


class ModuleVal:
    """Reference to an accelerator-API module namespace (jnp/lax/...)."""
    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name


class DtypeVal:
    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name


class Clo:
    """A function value: AST + captured scopes + home module."""
    __slots__ = ("node", "scopes", "mod", "qual", "path")

    def __init__(self, node, scopes, mod, qual, path):
        self.node = node          # FunctionDef | Lambda
        self.scopes = scopes      # captured enclosing scopes (inner first)
        self.mod = mod            # ModScope
        self.qual = qual
        self.path = path


class RealFn:
    """Host function executed for real when every argument is concrete
    (numpy/math/libs helpers and ops host helpers)."""
    __slots__ = ("fn", "name")

    def __init__(self, fn, name):
        self.fn, self.name = fn, name


class Bound:
    """Bound method / intrinsic attribute awaiting its call."""
    __slots__ = ("kind", "recv", "name")

    def __init__(self, kind: str, recv: Any, name: str):
        self.kind, self.recv, self.name = kind, recv, name


class Partial:
    __slots__ = ("fn", "args", "kwargs")

    def __init__(self, fn, args, kwargs):
        self.fn, self.args, self.kwargs = fn, args, kwargs


class Jitted:
    """jax.jit(f) result; calling it calls f. The rule also treats its
    creation as an analysis entry."""
    __slots__ = ("clo", "static")

    def __init__(self, clo: Clo, static: Tuple[str, ...]):
        self.clo, self.static = clo, static


class SDS:
    """jax.ShapeDtypeStruct."""
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape, self.dtype = tuple(shape), dtype


class BlockSpec:
    __slots__ = ("block_shape", "index_map")

    def __init__(self, block_shape=None, index_map=None):
        self.block_shape = tuple(block_shape) if block_shape else None
        self.index_map = index_map


class VMEM:
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape, self.dtype = tuple(shape), dtype


_BOTTOM = IV(INF, -INF)     # "never written" ref-row sentinel


class Ref:
    """Mutable pallas ref cell: per-row content with strong updates on
    concrete leading-axis indices, weak (join) updates otherwise."""
    __slots__ = ("dtype", "shape", "rows", "hull", "written")

    def __init__(self, dtype: str, shape: Tuple[Dim, ...],
                 init: Optional[Arr] = None):
        self.dtype = dtype
        self.shape = tuple(shape)
        n = shape[0] if shape and isinstance(shape[0], int) \
            and shape[0] <= ROWS_MAX else None
        if init is not None:
            self.rows = init.row_list() if n else None
            self.hull: Optional[IV] = init.iv
            self.written = True
        else:
            self.rows = [_BOTTOM] * n if n else None
            self.hull = None
            self.written = False

    def value(self) -> Optional[Arr]:
        if not self.written:
            return None
        rows = None
        if self.rows is not None:
            live = [r for r in self.rows if r is not _BOTTOM]
            if not live:
                return None
            hull = live[0]
            for r in live[1:]:
                hull = hull.join(r)
            rows = [hull if r is _BOTTOM else r for r in self.rows]
            return Arr(self.dtype, self.shape, rows, hull)
        return Arr(self.dtype, self.shape, None, self.hull or _BOTTOM)


# --- value plumbing -------------------------------------------------------

def vjoin(a: Any, b: Any) -> Any:
    """Structural join of two abstract values."""
    if a is None and b is None:
        return None
    if isinstance(a, Opaque):
        return a
    if isinstance(b, Opaque):
        return b
    if a is b:
        return a
    if isinstance(a, Arr) and isinstance(b, Arr):
        shape = broadcast_shapes(a.shape, b.shape)
        if shape is None or a.dtype != b.dtype:
            return Arr(a.dtype, a.shape, None, a.iv.join(b.iv))
        ra, rb = a.rows, b.rows
        rows = None
        if ra is not None and rb is not None and len(ra) == len(rb):
            rows = [x.join(y) for x, y in zip(ra, rb)]
        return Arr(a.dtype, shape, rows, a.iv.join(b.iv))
    if isinstance(a, (int, bool, IV, SymDim)) \
            and isinstance(b, (int, bool, IV, SymDim)):
        ia, ib = iv_of(a), iv_of(b)
        if isinstance(a, int) and isinstance(b, int) and a == b:
            return a
        return ia.join(ib)
    if isinstance(a, tuple) and isinstance(b, tuple) and len(a) == len(b):
        return tuple(vjoin(x, y) for x, y in zip(a, b))
    if isinstance(a, list) and isinstance(b, list) and len(a) == len(b):
        return [vjoin(x, y) for x, y in zip(a, b)]
    if isinstance(a, dict) and isinstance(b, dict) \
            and set(a.keys()) == set(b.keys()):
        return {k: vjoin(a[k], b[k]) for k in a}
    if isinstance(a, str) and a == b:
        return a
    if isinstance(a, Unknown) or isinstance(b, Unknown):
        return Unknown("join")
    return Opaque(f"join of {type(a).__name__}/{type(b).__name__}")


def veq(a: Any, b: Any) -> bool:
    if a is b:
        return True
    if isinstance(a, Arr) and isinstance(b, Arr):
        return a.dtype == b.dtype and a.iv == b.iv \
            and shape_sig(a.shape) == shape_sig(b.shape) \
            and a.rows == b.rows
    if isinstance(a, IV) and isinstance(b, IV):
        return a == b
    if type(a) is not type(b):
        return isinstance(a, (int, bool)) and isinstance(b, (int, bool)) \
            and a == b
    if isinstance(a, (int, bool, str)) or a is None:
        return a == b
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(veq(x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return set(a) == set(b) and all(veq(a[k], b[k]) for k in a)
    return False


def vwiden(old: Any, new: Any) -> Any:
    """Widen `old` toward `new` (dtype range for arrays)."""
    j = vjoin(old, new)
    if isinstance(j, Arr) and isinstance(old, Arr) and not veq(old, j):
        return Arr(j.dtype, j.shape, None, old.iv.widen(j.iv, j.dtype))
    if isinstance(j, IV) and isinstance(old, IV) and j != old:
        return old.widen(j, None)
    return j


def sig_of(v: Any):
    """Hashable memo signature; raises TypeError on unmemoizable
    values (Refs and friends)."""
    if isinstance(v, Arr):
        return v.sig()
    if isinstance(v, bool):
        return ("b", v)
    if isinstance(v, int):
        return ("i", v)
    if isinstance(v, IV):
        return ("v", v.lo, v.hi)
    if isinstance(v, SymDim):
        return ("d", id(v))
    if isinstance(v, (tuple, list)):
        return ("t", tuple(sig_of(x) for x in v))
    if isinstance(v, dict):
        return ("m", tuple(sorted((k, sig_of(x)) for k, x in v.items())))
    if isinstance(v, str):
        return ("s", v)
    if v is None:
        return ("n",)
    if isinstance(v, Clo):
        return ("c", id(v.node))
    if isinstance(v, DtypeVal):
        return ("dt", v.name)
    if isinstance(v, Unknown):
        return ("u",)
    if isinstance(v, slice):
        return ("sl", sig_of(v.start), sig_of(v.stop), sig_of(v.step))
    raise TypeError(f"unmemoizable {type(v).__name__}")


# --- module scopes --------------------------------------------------------

_JAX_MODULES = {
    "jax": "jax", "jax.numpy": "jax.numpy", "jax.lax": "jax.lax",
    "jax.experimental.pallas": "pallas",
    "jax.experimental.pallas.tpu": "pallas.tpu",
    "jax.tree_util": "jax.tree_util",
    "jax.experimental": "jax.experimental",
}
# modules safe to import for real inside the linter process (no jax)
_REAL_IMPORT_OK = ("numpy", "math", "functools", "cometbft_tpu.libs.",
                   "cometbft_tpu.crypto.")


def _posix_module(path: str) -> str:
    return path[:-3].replace("/", ".") if path.endswith(".py") else path


def _load_of(node: ast.expr) -> ast.expr:
    """Store-context target rewritten as a load expression (AugAssign)."""
    import copy
    n2 = copy.deepcopy(node)
    for sub in ast.walk(n2):
        if hasattr(sub, "ctx"):
            sub.ctx = ast.Load()
    return n2


def _decide(a: IV, op: ast.cmpop, b: IV) -> Any:
    if isinstance(op, ast.Lt):
        if a.hi < b.lo:
            return True
        if a.lo >= b.hi:
            return False
        return Unknown("cmp")
    if isinstance(op, ast.LtE):
        if a.hi <= b.lo:
            return True
        if a.lo > b.hi:
            return False
        return Unknown("cmp")
    if isinstance(op, ast.Gt):
        return _decide(b, ast.Lt(), a)
    if isinstance(op, ast.GtE):
        return _decide(b, ast.LtE(), a)
    if isinstance(op, ast.Eq):
        if a.exact is not None and a.exact == b.exact:
            return True
        if a.hi < b.lo or a.lo > b.hi:
            return False
        return Unknown("cmp")
    if isinstance(op, ast.NotEq):
        r = _decide(a, ast.Eq(), b)
        return (not r) if isinstance(r, bool) else r
    return Unknown("cmp")


_DT_ORDER = {"bool": 0, "uint8": 1, "int8": 1, "uint16": 2, "int16": 2,
             "int32": 3, "uint32": 3, "int64": 4, "uint64": 4}


def promote(da: Optional[str], db: Optional[str]) -> str:
    """Result dtype of a two-array op. Mixed int32/uint32 does not
    occur in the kernels (uint32 work is explicitly astype-bracketed);
    resolve it to int32 so the stricter contract applies."""
    if da is None:
        return db or "int32"
    if db is None or da == db:
        return da
    if {"int32", "uint32"} == {da, db}:
        return "int32"
    return da if _DT_ORDER.get(da, 3) >= _DT_ORDER.get(db, 3) else db


def DT_IV(dtype: str) -> IV:
    lo, hi = DTYPE_RANGE.get(dtype, (-INF, INF))
    return IV(lo, hi)


class ModScope:
    """Lazy namespace of one ops module: AST defs become Clo values,
    module-level constant assignments are evaluated by the interpreter
    itself (host python executes concretely — limbs_from_int and
    friends return exact values without importing jax)."""

    def __init__(self, analysis: "Analysis", ctx: FileCtx):
        self.analysis = analysis
        self.ctx = ctx
        self.path = ctx.path
        self.modname = _posix_module(ctx.path)
        self.names: Dict[str, Any] = {}
        self.assigns: Dict[str, ast.stmt] = {}
        self.defs: Dict[str, ast.FunctionDef] = {}
        self.imports: Dict[str, Any] = {}       # name -> resolver thunk
        self._evaluating: Set[str] = set()
        for node in ctx.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs[node.name] = node
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            self.assigns[n.id] = node
            elif isinstance(node, ast.AnnAssign) \
                    and isinstance(node.target, ast.Name) and node.value:
                self.assigns[node.target.id] = node
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                self._register_import(node)

    def _register_import(self, node) -> None:
        if isinstance(node, ast.Import):
            for a in node.names:
                local = a.asname or a.name.split(".")[0]
                target = a.name if a.asname else a.name.split(".")[0]
                self.imports[local] = ("module", target)
            return
        mod = node.module or ""
        if node.level:
            base = self.modname.rsplit(".", node.level)[0]
            mod = f"{base}.{mod}" if mod else base
        for a in node.names:
            self.imports[a.asname or a.name] = ("from", mod, a.name)

    def resolve_module(self, dotted: str) -> Any:
        a = self.analysis
        if dotted in _JAX_MODULES:
            return ModuleVal(_JAX_MODULES[dotted])
        peer = a.modscopes.get(dotted)
        if peer is not None:
            return peer
        if dotted.startswith(_REAL_IMPORT_OK) or dotted in (
                "numpy", "math", "functools"):
            try:
                import importlib
                return importlib.import_module(dotted)
            except Exception as e:        # noqa: BLE001 — any import
                return Opaque(f"import {dotted}: {e}")
        return Opaque(f"unmodeled module {dotted}")

    def get(self, name: str) -> Any:
        if name in self.names:
            return self.names[name]
        val: Any
        if name in self.defs:
            val = Clo(self.defs[name], [], self, name, self.path)
        elif name in self.imports:
            spec = self.imports[name]
            if spec[0] == "module":
                val = self.resolve_module(spec[1])
            else:
                _, mod, attr = spec
                dotted = f"{mod}.{attr}"
                if dotted in _JAX_MODULES \
                        or dotted in self.analysis.modscopes:
                    # `from . import edwards as ed` — the imported
                    # name is itself a module (peer or jax namespace)
                    val = self.resolve_module(dotted)
                else:
                    holder = self.resolve_module(mod)
                    val = self.analysis.interp.attr_of(holder, attr)
                    if isinstance(val, Opaque) \
                            and dotted.startswith(_REAL_IMPORT_OK):
                        val = self.resolve_module(dotted)
        elif name in self.assigns:
            if name in self._evaluating:
                return Opaque(f"circular module constant {name}")
            self._evaluating.add(name)
            try:
                val = self.analysis.interp.eval_module_assign(
                    self, self.assigns[name], name)
            finally:
                self._evaluating.discard(name)
        else:
            return Opaque(f"{self.modname} has no {name}")
        self.names[name] = val
        return val


# --- interpreter ----------------------------------------------------------

class Frame:
    __slots__ = ("scopes", "mod", "ctx", "qual", "ret", "dims")

    def __init__(self, scopes, mod: ModScope, qual: str,
                 dims: Optional[Dict[str, SymDim]] = None):
        self.scopes = scopes          # [locals, *captured]
        self.mod = mod
        self.ctx = mod.ctx
        self.qual = qual
        self.ret: Any = _NO_RET
        self.dims = dims if dims is not None else {}


class _NoRet:
    def __repr__(self):
        return "<no-return>"


_NO_RET = _NoRet()


class AnalysisError(Exception):
    """Internal interpreter bail-out; surfaces as a finding."""


_PY_BUILTINS = ("len", "range", "min", "max", "abs", "int", "bool",
                "sum", "tuple", "list", "dict", "zip", "enumerate",
                "reversed", "sorted", "bin", "pow", "divmod", "all",
                "any", "isinstance", "float", "str", "set", "round")


class Interp:
    """The abstract evaluator. One instance per Analysis run."""

    def __init__(self, analysis: "Analysis"):
        self.a = analysis
        self.stack: List[str] = []
        self.memo: Dict[Any, Tuple[Any, list]] = {}
        self.call_depth = 0
        self._host_fns: Dict[int, Any] = {}

    # -- reporting --------------------------------------------------------

    def report(self, node: Optional[ast.AST], kind: str, msg: str,
               ctx: Optional[FileCtx] = None) -> None:
        frame_ctx = ctx or (self.a.cur_ctx() if self.a else None)
        if frame_ctx is None:
            return
        line = getattr(node, "lineno", 1) if node is not None else 1
        path = frame_ctx.path
        chain = " > ".join(self.stack[-4:]) or "<module>"
        self.a.add_finding(path, line, kind, f"{msg} [via {chain}]",
                           frame_ctx)

    def unknown(self, node: Optional[ast.AST], reason: str) -> Opaque:
        if self.a.in_entry:
            self.report(node, "interval-unknown",
                        f"cannot bound this value ({reason}) — the "
                        f"int32 proof has a hole here")
        return Opaque(reason)

    # -- entry points ------------------------------------------------------

    def eval_module_assign(self, mod: ModScope, stmt: ast.stmt,
                           name: str) -> Any:
        frame = Frame([{}], mod, f"{mod.modname}:<module>")
        self.a.push_ctx(mod.ctx)
        was = self.a.in_entry
        self.a.in_entry = False     # module constants never hole the proof
        try:
            val = self.eval(stmt.value, frame)
        except AnalysisError as e:
            val = Opaque(str(e))
        except RecursionError:
            val = Opaque("recursion evaluating module constant")
        finally:
            self.a.in_entry = was
            self.a.pop_ctx()
        tgt = stmt.targets[0] if isinstance(stmt, ast.Assign) \
            else stmt.target
        if isinstance(tgt, ast.Name):
            return val
        # tuple-target module assign: bind all, then answer for `name`
        tmp = Frame([{}], mod, frame.qual)
        try:
            self.assign(tgt, val, tmp)
        except AnalysisError as e:
            return Opaque(str(e))
        return tmp.scopes[0].get(name, Opaque(f"unbound {name}"))

    def _host_fn_for(self, clo: Clo) -> Any:
        """Compile a PURE-HOST helper (touches only builtins/math/np —
        no jax, no module globals) to a real python function. Abstract
        interpretation of e.g. the cube-root fixup loop in sha512's
        round-constant derivation would need ~57k concrete iterations;
        native execution is exact and instant."""
        key = id(clo.node)
        if key in self._host_fns:
            return self._host_fns[key]
        fn = None
        fnode = clo.node
        if isinstance(fnode, ast.FunctionDef) \
                and not fnode.decorator_list \
                and not any(isinstance(n, (ast.Yield, ast.YieldFrom,
                                           ast.Await, ast.Global,
                                           ast.Nonlocal))
                            for n in ast.walk(fnode)):
            bound = {a.arg for a in (fnode.args.posonlyargs
                                     + fnode.args.args
                                     + fnode.args.kwonlyargs)}
            for n in ast.walk(fnode):
                if isinstance(n, ast.Name) \
                        and isinstance(n.ctx, (ast.Store, ast.Del)):
                    bound.add(n.id)
                elif isinstance(n, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)) \
                        and n is not fnode:
                    bound.add(n.name)
            used = {n.id for n in ast.walk(fnode)
                    if isinstance(n, ast.Name)}
            allowed = set(_PY_BUILTINS) | {"math", "np", "numpy",
                                           "Tuple", "List", "Optional"}
            if used - bound <= allowed:
                import math as _math
                ns: Dict[str, Any] = {"math": _math, "Tuple": tuple,
                                      "List": list, "Optional": None}
                try:
                    import numpy as _np
                    ns["np"] = ns["numpy"] = _np
                except ImportError:
                    pass
                mod = ast.Module(body=[fnode], type_ignores=[])
                ast.fix_missing_locations(mod)
                try:
                    exec(compile(mod, clo.path, "exec"), ns)  # noqa: S102
                    fn = ns.get(fnode.name)
                except Exception:       # noqa: BLE001
                    fn = None
        self._host_fns[key] = fn
        return fn

    def call_clo(self, clo: Clo, args: List[Any],
                 kwargs: Dict[str, Any], node: Optional[ast.AST]) -> Any:
        self.a.covered.add(f"{clo.path}::{clo.qual}")
        host = self._host_fn_for(clo)
        if host is not None:
            try:
                cargs = [self.to_concrete(a) for a in args]
                ckw = {k: self.to_concrete(v)
                       for k, v in kwargs.items()}
            except TypeError:
                host = None
            if host is not None:
                try:
                    return self.to_abstract(host(*cargs, **ckw))
                except AnalysisError:
                    raise
                except Exception as e:  # noqa: BLE001
                    raise AnalysisError(
                        f"host helper {clo.qual} raised: {e}")
        key = None
        try:
            # scope-dict identity distinguishes closures of the same
            # def captured from different factory invocations
            key = (id(clo.node),
                   tuple(id(s) for s in clo.scopes),
                   tuple(sig_of(a) for a in args),
                   tuple(sorted((k, sig_of(v)) for k, v in kwargs.items())))
        except TypeError:
            pass
        if key is not None and key in self.memo:
            ret, recorded = self.memo[key][:2]
            for rec in recorded:
                self.a.replay(rec)
            return ret
        if self.call_depth > 60:
            raise AnalysisError(f"call depth exceeded at {clo.qual}")
        frame = Frame([{}] + list(clo.scopes), clo.mod, clo.qual)
        self.bind_params(clo, args, kwargs, frame, node)
        self.stack.append(clo.qual)
        self.call_depth += 1
        self.a.push_ctx(clo.mod.ctx)
        cap = self.a.push_capture()
        try:
            if isinstance(clo.node, ast.Lambda):
                ret = self.eval(clo.node.body, frame)
            else:
                flow = self.exec_block(clo.node.body, frame)
                ret = frame.ret if frame.ret is not _NO_RET else None
                if flow == "fall" and frame.ret is not _NO_RET:
                    ret = vjoin(frame.ret, None) \
                        if self._may_fall_off(clo.node) else frame.ret
        except AnalysisError as e:
            if not getattr(e, "stack", None):
                e.stack = list(self.stack)
            raise
        finally:
            recorded = self.a.pop_capture(cap)
            self.a.pop_ctx()
            self.call_depth -= 1
            self.stack.pop()
        if key is not None:
            # pin every object whose id() appears in the key (scope dicts,
            # SymDims/Clos inside args) — otherwise GC can recycle an address
            # and a later closure aliases a dead frame's memo entry
            self.memo[key] = (ret, recorded, (clo.scopes, args, kwargs))
        return ret

    @staticmethod
    def _may_fall_off(node) -> bool:
        last = node.body[-1] if node.body else None
        return not isinstance(last, ast.Return)

    def bind_params(self, clo: Clo, args: List[Any],
                    kwargs: Dict[str, Any], frame: Frame,
                    node: Optional[ast.AST]) -> None:
        a = clo.node.args
        names = [p.arg for p in a.posonlyargs + a.args]
        local = frame.scopes[0]
        if len(args) > len(names) and a.vararg is None:
            raise AnalysisError(
                f"too many args for {clo.qual}: {len(args)}")
        for i, name in enumerate(names):
            if i < len(args):
                local[name] = args[i]
            elif name in kwargs:
                local[name] = kwargs.pop(name)
        if a.vararg is not None:
            local[a.vararg.arg] = tuple(args[len(names):])
        # defaults for the tail
        defaults = a.defaults
        for i, d in enumerate(defaults):
            name = names[len(names) - len(defaults) + i]
            if name not in local:
                local[name] = self.eval(d, frame)
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg in kwargs:
                local[p.arg] = kwargs.pop(p.arg)
            elif d is not None:
                local[p.arg] = self.eval(d, frame)
            else:
                raise AnalysisError(
                    f"missing kwonly {p.arg} for {clo.qual}")
        if a.kwarg is not None:
            local[a.kwarg.arg] = dict(kwargs)
            kwargs.clear()
        if kwargs:
            raise AnalysisError(
                f"unexpected kwargs {sorted(kwargs)} for {clo.qual}")
        missing = [n for n in names if n not in local]
        if missing:
            raise AnalysisError(
                f"missing args {missing} for {clo.qual}")

    # -- statements --------------------------------------------------------

    def exec_block(self, stmts: List[ast.stmt], frame: Frame) -> str:
        for stmt in stmts:
            flow = self.exec_stmt(stmt, frame)
            if flow != "fall":
                return flow
        return "fall"

    def exec_stmt(self, stmt: ast.stmt, frame: Frame) -> str:
        if isinstance(stmt, ast.Assign):
            val = self.eval(stmt.value, frame)
            val = self.apply_assumes(stmt, val, frame)
            for t in stmt.targets:
                self.assign(t, val, frame)
            return "fall"
        if isinstance(stmt, ast.AugAssign):
            cur = self.eval(_load_of(stmt.target), frame)
            rhs = self.eval(stmt.value, frame)
            val = self.binop(cur, stmt.op, rhs, stmt)
            val = self.apply_assumes(stmt, val, frame)
            self.assign(stmt.target, val, frame)
            return "fall"
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                val = self.eval(stmt.value, frame)
                val = self.apply_assumes(stmt, val, frame)
                self.assign(stmt.target, val, frame)
            return "fall"
        if isinstance(stmt, ast.Expr):
            if not isinstance(stmt.value, ast.Constant):
                self.eval(stmt.value, frame)
            return "fall"
        if isinstance(stmt, ast.Return):
            val = self.eval(stmt.value, frame) \
                if stmt.value is not None else None
            val = self.apply_assumes(stmt, val, frame, returning=True)
            frame.ret = val if frame.ret is _NO_RET \
                else vjoin(frame.ret, val)
            return "return"
        if isinstance(stmt, ast.If):
            return self.exec_if(stmt, frame)
        if isinstance(stmt, ast.For):
            return self.exec_for(stmt, frame)
        if isinstance(stmt, ast.While):
            return self.exec_while(stmt, frame)
        if isinstance(stmt, ast.Assert):
            self.exec_assert(stmt.test, frame)
            return "fall"
        if isinstance(stmt, ast.FunctionDef):
            frame.scopes[0][stmt.name] = Clo(
                stmt, frame.scopes, frame.mod,
                f"{frame.qual}.{stmt.name}", frame.mod.path)
            return "fall"
        if isinstance(stmt, (ast.Pass, ast.Import, ast.ImportFrom)):
            if isinstance(stmt, (ast.Import, ast.ImportFrom)):
                self.exec_import(stmt, frame)
            return "fall"
        if isinstance(stmt, ast.Break):
            return "break"
        if isinstance(stmt, ast.Continue):
            return "continue"
        if isinstance(stmt, ast.Raise):
            return "return"     # abandon the path; no value joins
        if isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    frame.scopes[0].pop(t.id, None)
            return "fall"
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                v = self.eval(item.context_expr, frame)
                if item.optional_vars is not None:
                    self.assign(item.optional_vars, v, frame)
            return self.exec_block(stmt.body, frame)
        if isinstance(stmt, ast.Try):
            flow = self.exec_block(stmt.body, frame)
            if flow == "fall":
                flow = self.exec_block(stmt.orelse, frame)
            f2 = self.exec_block(stmt.finalbody, frame)
            return f2 if f2 != "fall" else flow
        raise AnalysisError(
            f"unhandled statement {type(stmt).__name__} at "
            f"{frame.ctx.path}:{stmt.lineno}")

    def exec_import(self, stmt, frame: Frame) -> None:
        """Function-local import: resolve through the module machinery
        (ed25519's local `from .pallas_verify import ...`)."""
        tmp = ModScope.__new__(ModScope)
        tmp.analysis = self.a
        tmp.modname = frame.mod.modname
        tmp.imports = {}
        ModScope._register_import(tmp, stmt)
        for local, spec in tmp.imports.items():
            if spec[0] == "module":
                frame.scopes[0][local] = frame.mod.resolve_module(spec[1])
            else:
                _, mod, attr = spec
                holder = frame.mod.resolve_module(mod)
                frame.scopes[0][local] = self.attr_of(holder, attr)

    def apply_assumes(self, stmt: ast.stmt, val: Any, frame: Frame,
                      returning: bool = False) -> Any:
        """Check (never trust) assume() pragmas on this statement:
        computed ⊆ assumed proves it; disjoint is a contradiction;
        overlap refines + registers a runtime obligation for
        tools/interval_fuzz.py."""
        specs = frame.ctx.assumes_at(stmt.lineno)
        if not specs:
            return val
        names: Set[str] = set()
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)) \
                and isinstance(stmt.target, ast.Name):
            names.add(stmt.target.id)
        for spec in specs:
            if not returning and spec.var not in names:
                continue
            self.a.used_assumes.add((frame.ctx.path, spec.line))
            try:
                got = iv_of(val)
            except TypeError:
                self.report(stmt, "assume-unverifiable",
                            f"assume({spec.var}, ...) on a value with "
                            f"no interval ({type(val).__name__})")
                continue
            want = IV(spec.lo, spec.hi)
            if got.inside(spec.lo, spec.hi):
                continue    # statically proven; nothing to refine
            met = got.meet(want)
            if met is None:
                self.report(stmt, "assume-contradiction",
                            f"assume({spec.var}, {spec.lo}, {spec.hi}) "
                            f"contradicts computed bounds {got}")
                continue
            self.a.add_obligation(frame, spec, stmt, got)
            if isinstance(val, Arr):
                rows = None if val.rows is None else \
                    [r.meet(want) or IV(spec.lo, spec.lo)
                     for r in val.rows]
                val = Arr(val.dtype, val.shape, rows, met)
            elif isinstance(val, (int, IV)):
                val = met
        return val

    def assign(self, target: ast.expr, val: Any, frame: Frame) -> None:
        if isinstance(target, ast.Name):
            frame.scopes[0][target.id] = val
            return
        if isinstance(target, (ast.Tuple, ast.List)):
            items = self.unpack(val, len(target.elts), target)
            star = [i for i, e in enumerate(target.elts)
                    if isinstance(e, ast.Starred)]
            if star:
                raise AnalysisError("starred unpack unsupported")
            for el, item in zip(target.elts, items):
                self.assign(el, item, frame)
            return
        if isinstance(target, ast.Subscript):
            recv = self.eval(target.value, frame)
            idx = self.eval_index(target.slice, frame)
            self.store_item(recv, idx, val, target)
            return
        raise AnalysisError(
            f"unhandled assign target {type(target).__name__}")

    def unpack(self, val: Any, n: int, node) -> List[Any]:
        if isinstance(val, (tuple, list)):
            if len(val) != n:
                raise AnalysisError(
                    f"unpack arity {len(val)} != {n}")
            return list(val)
        if isinstance(val, Arr) and val.shape \
                and isinstance(val.shape[0], int) and val.shape[0] == n:
            return [self.index_axis0(val, i, node) for i in range(n)]
        if isinstance(val, Opaque):
            return [val] * n
        raise AnalysisError(f"cannot unpack {type(val).__name__}")

    def store_item(self, recv: Any, idx: Any, val: Any, node) -> None:
        if isinstance(recv, Ref):
            self.ref_store(recv, idx, val, node)
            return
        if isinstance(recv, list):
            if isinstance(idx, bool) or not isinstance(idx, int):
                raise AnalysisError("abstract list index store")
            recv[idx] = val
            return
        if isinstance(recv, dict):
            try:
                hash(idx)
            except TypeError:
                raise AnalysisError("unhashable dict key")
            recv[idx] = val
            return
        if isinstance(recv, Opaque):
            return
        if isinstance(recv, Arr):
            # host-numpy arrays alias their buffer, so an in-place store is
            # the faithful model; only concrete int/slice leading-axis
            # indices are handled — anything else stays a hard error
            rows = recv.row_list()
            if rows is not None:
                if isinstance(idx, slice):
                    try:
                        rng = range(*idx.indices(len(rows)))
                    except TypeError:
                        rng = None
                    if rng is not None:
                        if isinstance(val, Arr) and val.ndim == recv.ndim:
                            vrows = val.row_list()
                            if vrows is None or len(vrows) != len(rng):
                                vrows = [val.iv] * len(rng)
                        else:
                            vrows = [iv_of(val)] * len(rng)
                        for k, i in enumerate(rng):
                            rows[i] = vrows[k]
                        self._rewrite_rows(recv, rows)
                        return
                elif isinstance(idx, int) and not isinstance(idx, bool):
                    n = len(rows)
                    if -n <= idx < n:
                        rows[idx] = val.iv if isinstance(val, Arr) \
                            else iv_of(val)
                        self._rewrite_rows(recv, rows)
                        return
                    raise AnalysisError(
                        f"store index {idx} out of range for ({n}, ...)")
        raise AnalysisError(
            f"cannot store into {type(recv).__name__} at line "
            f"{getattr(node, 'lineno', '?')}")

    @staticmethod
    def _rewrite_rows(recv: "Arr", rows: List[IV]) -> None:
        recv.rows = rows
        iv = rows[0]
        for r in rows[1:]:
            iv = iv.join(r)
        recv.iv = iv

    # -- control flow ------------------------------------------------------

    def snapshot(self, frame: Frame) -> Dict[str, Any]:
        out = {}
        for k, v in frame.scopes[0].items():
            if isinstance(v, list):
                v = list(v)
            elif isinstance(v, dict):
                v = dict(v)
            out[k] = v
        return out

    def restore(self, frame: Frame, snap: Dict[str, Any]) -> None:
        frame.scopes[0] = {
            k: (list(v) if isinstance(v, list)
                else dict(v) if isinstance(v, dict) else v)
            for k, v in snap.items()}

    @staticmethod
    def join_env(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
        out = {}
        for k in set(a) | set(b):
            if k in a and k in b:
                out[k] = vjoin(a[k], b[k])
            # a name bound on only one path stays unbound in the join
        return out

    @staticmethod
    def env_eq(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
        return set(a) == set(b) and all(veq(a[k], b[k]) for k in a)

    @staticmethod
    def widen_env(old: Dict[str, Any], new: Dict[str, Any]) \
            -> Dict[str, Any]:
        out = {}
        for k in set(old) & set(new):
            out[k] = vwiden(old[k], new[k])
        return out

    def truth(self, v: Any) -> Optional[bool]:
        if isinstance(v, bool):
            return v
        if isinstance(v, int):
            return v != 0
        if v is None:
            return False
        if isinstance(v, (str, tuple, list, dict)):
            return bool(v)
        if isinstance(v, (IV, SymDim)):
            iv = iv_of(v)
            if iv.lo > 0 or iv.hi < 0:
                return True
            if iv.lo == iv.hi == 0:
                return False
            return None
        if isinstance(v, (Unknown, Opaque, Arr)):
            return None
        return None

    def exec_if(self, stmt: ast.If, frame: Frame) -> str:
        t = self.truth(self.eval(stmt.test, frame))
        if t is True:
            return self.exec_block(stmt.body, frame)
        if t is False:
            return self.exec_block(stmt.orelse, frame)
        base = self.snapshot(frame)
        flow1 = self.exec_block(stmt.body, frame)
        env1 = self.snapshot(frame)
        self.restore(frame, base)
        flow2 = self.exec_block(stmt.orelse, frame)
        env2 = self.snapshot(frame)
        if flow1 == "fall" and flow2 == "fall":
            self.restore(frame, self.join_env(env1, env2))
            return "fall"
        if flow1 == "fall":
            self.restore(frame, env1)
            return "fall"
        if flow2 == "fall":
            self.restore(frame, env2)
            return "fall"
        if flow1 == flow2:
            return flow1
        # mixed return/break/continue across an unknown branch: treat
        # as falling through with the join — over-approximate but sound
        self.restore(frame, self.join_env(env1, env2))
        return "fall"

    def exec_for(self, stmt: ast.For, frame: Frame) -> str:
        it = self.eval(stmt.iter, frame)
        items = self.concrete_iter(it)
        if items is not None:
            if len(items) > CONCRETE_WHILE_CAP:
                raise AnalysisError("concrete for-loop too long")
            for item in items:
                self.assign(stmt.target, item, frame)
                flow = self.exec_block(stmt.body, frame)
                if flow == "break":
                    return "fall"
                if flow == "return":
                    return "return"
            return self.exec_block(stmt.orelse, frame)
        # symbolic iterable: fixpoint with the target bound to a hull
        hull = self.iter_hull(it, stmt)

        def body_once() -> str:
            self.assign(stmt.target, hull, frame)
            return self.exec_block(stmt.body, frame)

        self.fix_loop(body_once, frame)
        return "fall"

    def exec_while(self, stmt: ast.While, frame: Frame) -> str:
        for _ in range(CONCRETE_WHILE_CAP):
            t = self.truth(self.eval(stmt.test, frame))
            if t is None:
                break
            if t is False:
                return self.exec_block(stmt.orelse, frame)
            flow = self.exec_block(stmt.body, frame)
            if flow == "break":
                return "fall"
            if flow == "return":
                return "return"
        else:
            raise AnalysisError("concrete while-loop did not terminate")

        def body_once() -> str:
            self.eval(stmt.test, frame)
            return self.exec_block(stmt.body, frame)

        self.fix_loop(body_once, frame)
        return "fall"

    def fix_loop(self, body_once: Callable[[], str],
                 frame: Frame) -> None:
        """Join-to-fixpoint on the innermost scope; findings recorded
        along the way overwrite earlier, smaller-bound duplicates (the
        findings store dedups by site), so the stabilized iteration's
        report is the one that survives."""
        inv = self.snapshot(frame)
        for it in range(JOIN_CAP + WIDEN_EXTRA):
            self.restore(frame, inv)
            flow = body_once()
            if flow == "return":
                # a symbolic-loop return joins into frame.ret already
                pass
            after = self.snapshot(frame)
            new = self.join_env(inv, after)
            if self.env_eq(new, inv):
                break
            inv = self.widen_env(inv, new) if it >= JOIN_CAP else new
        else:
            raise AnalysisError("loop fixpoint did not converge")
        self.restore(frame, inv)

    def concrete_iter(self, it: Any) -> Optional[List[Any]]:
        if isinstance(it, (list, tuple)):
            return list(it)
        if isinstance(it, str):
            return list(it)
        if isinstance(it, dict):
            return list(it.keys())
        if isinstance(it, range):
            return list(it)
        return None

    def iter_hull(self, it: Any, node) -> Any:
        if isinstance(it, Arr):
            return self.index_axis0(it, None, node)
        if isinstance(it, Opaque):
            return it
        raise AnalysisError(
            f"cannot iterate {type(it).__name__}")

    def exec_assert(self, test: ast.expr, frame: Frame) -> None:
        if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
            for clause in test.values:
                self.exec_assert(clause, frame)
            return
        t = self.truth(self.eval(test, frame))
        if t is False:
            self.report(test, "assert-false",
                        "assert provably fails under computed bounds")
        if t is not None:
            return
        # refinement: `n <= C`, `n < C`, `C >= n`, `n == C` on a local
        # whose value is a SymDim or IV tightens the bound — a trace-
        # time assert guards every concrete execution, so leaning on it
        # is sound (sc_dot_mod_l's batch-sum proof needs exactly this).
        if not isinstance(test, ast.Compare) or len(test.ops) != 1:
            return
        left, op, right = test.left, test.ops[0], test.comparators[0]
        if not isinstance(left, ast.Name):
            if isinstance(right, ast.Name):
                left, right = right, left
                flip = {ast.Lt: ast.Gt, ast.Gt: ast.Lt,
                        ast.LtE: ast.GtE, ast.GtE: ast.LtE,
                        ast.Eq: ast.Eq, ast.NotEq: ast.NotEq}
                if type(op) not in flip:
                    return
                op = flip[type(op)]()
            else:
                return
        try:
            bound = iv_of(self.eval(right, frame))
        except (TypeError, AnalysisError):
            return
        cur = frame.scopes[0].get(left.id)
        if cur is None:
            return
        if isinstance(op, ast.LtE):
            ref = IV(-INF, bound.hi)
        elif isinstance(op, ast.Lt):
            ref = IV(-INF, bound.hi - 1)
        elif isinstance(op, ast.GtE):
            ref = IV(bound.lo, INF)
        elif isinstance(op, ast.Gt):
            ref = IV(bound.lo + 1, INF)
        elif isinstance(op, ast.Eq):
            ref = bound
        else:
            return
        if isinstance(cur, SymDim):
            met = cur.bound.meet(ref)
            if met is not None:
                cur.bound = met
        elif isinstance(cur, IV):
            met = cur.meet(ref)
            if met is not None:
                frame.scopes[0][left.id] = met

    # -- expressions -------------------------------------------------------

    def eval(self, node: ast.expr, frame: Frame) -> Any:
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            return self.lookup(node, frame)
        if isinstance(node, ast.Tuple):
            return tuple(self.eval_splice(node.elts, frame))
        if isinstance(node, ast.List):
            return list(self.eval_splice(node.elts, frame))
        if isinstance(node, ast.Dict):
            out = {}
            for k, v in zip(node.keys, node.values):
                if k is None:
                    raise AnalysisError("dict ** splat unsupported")
                out[self.eval(k, frame)] = self.eval(v, frame)
            return out
        if isinstance(node, ast.Set):
            return set(self.eval_splice(node.elts, frame))
        if isinstance(node, ast.BinOp):
            return self.binop(self.eval(node.left, frame), node.op,
                              self.eval(node.right, frame), node)
        if isinstance(node, ast.UnaryOp):
            return self.unaryop(node, frame)
        if isinstance(node, ast.BoolOp):
            return self.boolop(node, frame)
        if isinstance(node, ast.Compare):
            return self.compare(node, frame)
        if isinstance(node, ast.IfExp):
            t = self.truth(self.eval(node.test, frame))
            if t is True:
                return self.eval(node.body, frame)
            if t is False:
                return self.eval(node.orelse, frame)
            return vjoin(self.eval(node.body, frame),
                         self.eval(node.orelse, frame))
        if isinstance(node, ast.Call):
            return self.call(node, frame)
        if isinstance(node, ast.Attribute):
            return self.attr_of(self.eval(node.value, frame),
                                node.attr, node)
        if isinstance(node, ast.Subscript):
            recv = self.eval(node.value, frame)
            idx = self.eval_index(node.slice, frame)
            return self.load_item(recv, idx, node)
        if isinstance(node, ast.Lambda):
            return Clo(node, frame.scopes, frame.mod,
                       f"{frame.qual}.<lambda>@{node.lineno}",
                       frame.mod.path)
        if isinstance(node, (ast.ListComp, ast.GeneratorExp, ast.SetComp)):
            vals = self.comprehension(node, frame)
            return set(vals) if isinstance(node, ast.SetComp) else \
                (list(vals) if isinstance(node, ast.ListComp)
                 else tuple(vals))
        if isinstance(node, ast.DictComp):
            out = {}
            for env in self.comp_envs(node.generators, frame):
                out[self.eval(node.key, env)] = \
                    self.eval(node.value, env)
            return out
        if isinstance(node, ast.NamedExpr):
            val = self.eval(node.value, frame)
            self.assign(node.target, val, frame)
            return val
        if isinstance(node, ast.Starred):
            raise AnalysisError("bare starred expression")
        if isinstance(node, ast.JoinedStr):
            return "<fstring>"
        raise AnalysisError(
            f"unhandled expression {type(node).__name__} at "
            f"{frame.ctx.path}:{getattr(node, 'lineno', '?')}")

    def eval_splice(self, elts, frame: Frame) -> List[Any]:
        out: List[Any] = []
        for el in elts:
            if isinstance(el, ast.Starred):
                seq = self.eval(el.value, frame)
                if not isinstance(seq, (tuple, list)):
                    raise AnalysisError("starred non-sequence")
                out.extend(seq)
            else:
                out.append(self.eval(el, frame))
        return out

    def comprehension(self, node, frame: Frame) -> List[Any]:
        return [self.eval(node.elt, env)
                for env in self.comp_envs(node.generators, frame)]

    def comp_envs(self, gens, frame: Frame,
                  i: int = 0) -> Iterator[Frame]:
        if i == len(gens):
            yield frame
            return
        g = gens[i]
        items = self.concrete_iter(self.eval(g.iter, frame))
        if items is None:
            raise AnalysisError("comprehension over symbolic iterable")
        for item in items:
            self.assign(g.target, item, frame)
            if all(self.truth(self.eval(cond, frame)) is True
                   for cond in g.ifs):
                yield from self.comp_envs(gens, frame, i + 1)

    def lookup(self, node: ast.Name, frame: Frame) -> Any:
        for scope in frame.scopes:
            if node.id in scope:
                return scope[node.id]
        if node.id in frame.dims:
            return frame.dims[node.id]
        mod_val = frame.mod.get(node.id)
        if not isinstance(mod_val, Opaque):
            return mod_val
        if node.id in _PY_BUILTINS:
            return Bound("builtin", None, node.id)
        if node.id in ("True", "False", "None"):
            return {"True": True, "False": False, "None": None}[node.id]
        return self.unknown(node, f"unresolved name {node.id!r}")

    # -- operators ---------------------------------------------------------

    def binop(self, a: Any, op: ast.operator, b: Any, node) -> Any:
        if isinstance(a, Opaque) or isinstance(b, Opaque):
            return a if isinstance(a, Opaque) else b
        # pure host python: lists/tuples/strings concatenate, repeat
        if isinstance(op, ast.Add) and isinstance(a, (list, tuple, str)) \
                and isinstance(b, (list, tuple, str)):
            return a + b
        if isinstance(op, ast.Mult) and (
                isinstance(a, (list, tuple, str)) and isinstance(b, int)):
            return a * b
        if isinstance(op, ast.Mult) and (
                isinstance(b, (list, tuple, str)) and isinstance(a, int)):
            return b * a
        if isinstance(a, (int, bool)) and isinstance(b, (int, bool)):
            return self.concrete_binop(a, op, b, node)
        if isinstance(a, (int, bool, float)) \
                and isinstance(b, (int, bool, float)):
            if self.a.in_entry:
                # floats never enter the int32 contract; host module
                # constants (frac(cbrt(p)) seeds etc.) compute freely
                raise AnalysisError("float arithmetic in kernel path")
            return self.concrete_binop(a, op, b, node)
        if isinstance(a, float) or isinstance(b, float):
            raise AnalysisError("float arithmetic in kernel path")
        if isinstance(a, Arr) or isinstance(b, Arr):
            return self.arr_binop(a, op, b, node)
        # scalar abstract (IV / SymDim mixed with int)
        try:
            ia, ib = iv_of(a), iv_of(b)
        except TypeError:
            raise AnalysisError(
                f"binop on {type(a).__name__}/{type(b).__name__}")
        fn = _IV_BINOPS.get(type(op))
        if fn is None:
            raise AnalysisError(
                f"unhandled operator {type(op).__name__}")
        out = fn(ia, ib)
        if out is None:
            return self.unknown(node, "unbounded scalar op")
        return out.exact if out.exact is not None else out

    def concrete_binop(self, a, op, b, node) -> Any:
        try:
            return {
                ast.Add: lambda: a + b, ast.Sub: lambda: a - b,
                ast.Mult: lambda: a * b, ast.FloorDiv: lambda: a // b,
                ast.Mod: lambda: a % b, ast.Pow: lambda: a ** b,
                ast.LShift: lambda: a << b, ast.RShift: lambda: a >> b,
                ast.BitAnd: lambda: a & b, ast.BitOr: lambda: a | b,
                ast.BitXor: lambda: a ^ b,
                ast.Div: lambda: a / b,
            }[type(op)]()
        except KeyError:
            raise AnalysisError(
                f"unhandled operator {type(op).__name__}")
        except ZeroDivisionError:
            raise AnalysisError("host division by zero")

    def arr_binop(self, a: Any, op: ast.operator, b: Any, node) -> Any:
        fn = _IV_BINOPS.get(type(op))
        if fn is None:
            if isinstance(op, ast.Pow):
                fn = lambda x, y: iv_mul(x, x) if y.exact == 2 else None
            else:
                raise AnalysisError(
                    f"unhandled array operator {type(op).__name__}")
        arr_a = a if isinstance(a, Arr) else None
        arr_b = b if isinstance(b, Arr) else None
        dtype = promote(arr_a.dtype if arr_a else None,
                        arr_b.dtype if arr_b else None)
        shape = broadcast_shapes(arr_a.shape if arr_a else (),
                                 arr_b.shape if arr_b else ())
        if shape is None:
            raise AnalysisError(
                "unbroadcastable shapes "
                f"{arr_a and arr_a.shape} vs {arr_b and arr_b.shape} "
                f"at line {getattr(node, 'lineno', '?')}")
        try:
            ia, ib = iv_of(a), iv_of(b)
        except TypeError:
            raise AnalysisError("array op with non-interval operand")
        rows = self.zip_rows(arr_a, arr_b, a, b, shape,
                             lambda x, y: fn(x, y))
        hull = fn(ia, ib)
        if hull is None or (rows is not None and any(
                r is None for r in rows)):
            return self.finish(Arr(dtype, shape, None,
                                   DT_IV(dtype)), node, wrapped=True)
        return self.finish(Arr(dtype, shape, rows, hull), node)

    def zip_rows(self, arr_a: Optional[Arr], arr_b: Optional[Arr],
                 a: Any, b: Any, shape: Tuple[Dim, ...],
                 fn: Callable[[IV, IV], Optional[IV]]) \
            -> Optional[List[Optional[IV]]]:
        """Per-leading-axis transfer when row alignment is sound: both
        operands span the result's axis 0 (equal concrete length or
        broadcast from rank-deficient / length-1)."""
        if not shape or not isinstance(shape[0], int) \
                or shape[0] > ROWS_MAX:
            return None
        n = shape[0]

        def rows_for(arr: Optional[Arr], other: Any) -> Optional[List[IV]]:
            if arr is None:
                iv = iv_of(other)
                return [iv] * n
            if arr.ndim < len(shape) or (
                    arr.shape and arr.shape[0] == 1 and n != 1):
                return [arr.iv] * n
            rl = arr.row_list()
            if rl is None or len(rl) != n:
                return None
            return rl
        ra = rows_for(arr_a, a)
        rb = rows_for(arr_b, b)
        if ra is None or rb is None:
            return None
        return [fn(x, y) for x, y in zip(ra, rb)]

    def finish(self, arr: Arr, node, wrapped: bool = False) -> Arr:
        """Dtype-lattice clamp: int32 escapes are findings; wrap
        dtypes silently reduce to their range (by-design modular
        packing); bool clamps."""
        lo, hi = DTYPE_RANGE.get(arr.dtype, (-INF, INF))
        if arr.iv.inside(lo, hi):
            return arr
        if arr.dtype == "int32":
            self.report(node, "int32-escape",
                        f"int32 value may reach {arr.iv}, escaping "
                        f"[-2**31, 2**31)")
            return Arr(arr.dtype, arr.shape, None, IV(lo, hi))
        if arr.dtype in _WRAP_DTYPES or arr.dtype == "bool":
            rows = None
            if arr.rows is not None:
                rows = [r if r.inside(lo, hi) else IV(lo, hi)
                        for r in arr.rows]
            return Arr(arr.dtype, arr.shape, rows, IV(lo, hi))
        self.report(node, "int32-escape",
                    f"{arr.dtype} value may reach {arr.iv}")
        return Arr(arr.dtype, arr.shape, None, IV(lo, hi))

    def unaryop(self, node: ast.UnaryOp, frame: Frame) -> Any:
        v = self.eval(node.operand, frame)
        if isinstance(node.op, ast.Not):
            t = self.truth(v)
            return Unknown("not") if t is None else (not t)
        if isinstance(v, Opaque):
            return v
        if isinstance(v, (int, bool)):
            return {ast.USub: lambda: -v, ast.UAdd: lambda: v,
                    ast.Invert: lambda: ~v}[type(node.op)]()
        if isinstance(v, (IV, SymDim)):
            iv = iv_of(v)
            if isinstance(node.op, ast.USub):
                return IV(-iv.hi, -iv.lo)
            if isinstance(node.op, ast.Invert):
                return IV(-iv.hi - 1, -iv.lo - 1)
            return iv
        if isinstance(v, Arr):
            iv = v.iv
            if isinstance(node.op, ast.USub):
                out, rows = IV(-iv.hi, -iv.lo), None
                if v.rows is not None:
                    rows = [IV(-r.hi, -r.lo) for r in v.rows]
            elif isinstance(node.op, ast.Invert):
                out, rows = IV(-iv.hi - 1, -iv.lo - 1), None
                if v.rows is not None:
                    rows = [IV(-r.hi - 1, -r.lo - 1) for r in v.rows]
            else:
                return v
            return self.finish(Arr(v.dtype, v.shape, rows, out), node)
        raise AnalysisError(f"unary on {type(v).__name__}")

    def boolop(self, node: ast.BoolOp, frame: Frame) -> Any:
        is_and = isinstance(node.op, ast.And)
        last: Any = None
        saw_unknown = False
        for clause in node.values:
            v = self.eval(clause, frame)
            t = self.truth(v)
            if t is None:
                saw_unknown = True
                last = v
                continue
            if is_and and t is False:
                return v
            if not is_and and t is True:
                return v
            last = v
        return Unknown("boolop") if saw_unknown else last

    def compare(self, node: ast.Compare, frame: Frame) -> Any:
        left = self.eval(node.left, frame)
        result: Any = True
        for op, rnode in zip(node.ops, node.comparators):
            right = self.eval(rnode, frame)
            r = self.compare_one(left, op, right, node)
            if r is False:
                return False
            if not isinstance(r, bool):
                result = r
            left = right
        return result

    def compare_one(self, a: Any, op: ast.cmpop, b: Any, node) -> Any:
        if isinstance(op, (ast.In, ast.NotIn)):
            if isinstance(b, (dict, list, tuple, set, str)):
                try:
                    hit = a in b
                except TypeError:
                    return Unknown("in")
                return (not hit) if isinstance(op, ast.NotIn) else hit
            return Unknown("in")
        if isinstance(op, (ast.Is, ast.IsNot)):
            if a is None or b is None:
                hit = a is b
                return (not hit) if isinstance(op, ast.IsNot) else hit
            return Unknown("is")
        if isinstance(a, Opaque) or isinstance(b, Opaque):
            return Unknown("opaque compare")
        if isinstance(a, Arr) or isinstance(b, Arr):
            return self.arr_compare(a, op, b, node)
        if isinstance(a, str) and isinstance(b, str):
            return {ast.Eq: a == b, ast.NotEq: a != b}.get(
                type(op), Unknown("str compare"))
        if isinstance(a, (tuple, list)) or isinstance(b, (tuple, list)):
            if isinstance(op, (ast.Eq, ast.NotEq)) \
                    and type(a) is type(b):
                hit = veq(a, b)
                return (not hit) if isinstance(op, ast.NotEq) else hit
            return Unknown("sequence compare")
        if isinstance(a, (int, bool)) and isinstance(b, (int, bool)):
            # concrete host ints compare EXACTLY — routing them through
            # IV would saturate crypto-sized constants at +-INF and
            # "prove" a true comparison false
            return {ast.Eq: a == b, ast.NotEq: a != b, ast.Lt: a < b,
                    ast.LtE: a <= b, ast.Gt: a > b,
                    ast.GtE: a >= b}[type(op)]
        try:
            ia, ib = iv_of(a), iv_of(b)
        except TypeError:
            return Unknown(f"compare {type(a).__name__}/"
                           f"{type(b).__name__}")
        return _decide(ia, op, ib)

    def arr_compare(self, a: Any, op: ast.cmpop, b: Any, node) -> Arr:
        arr_a = a if isinstance(a, Arr) else None
        arr_b = b if isinstance(b, Arr) else None
        shape = broadcast_shapes(arr_a.shape if arr_a else (),
                                 arr_b.shape if arr_b else ()) or ()

        def cmp_iv(x: IV, y: IV) -> IV:
            d = _decide(x, op, y)
            if d is True:
                return IV(1, 1)
            if d is False:
                return IV(0, 0)
            return IV(0, 1)
        rows = self.zip_rows(arr_a, arr_b, a, b, shape, cmp_iv)
        try:
            hull = cmp_iv(iv_of(a), iv_of(b))
        except TypeError:
            hull = IV(0, 1)
        if rows is not None and any(r is None for r in rows):
            rows = None
        return Arr("bool", shape, rows, hull)

    # -- attributes --------------------------------------------------------

    _DTYPE_ATTRS = {"int32": "int32", "uint32": "uint32",
                    "uint8": "uint8", "int8": "int8", "bool_": "bool",
                    "int16": "int16", "uint16": "uint16",
                    "int64": "int64", "uint64": "uint64",
                    "float32": "float32"}

    def attr_of(self, recv: Any, name: str, node=None) -> Any:
        if isinstance(recv, Opaque):
            return recv
        if isinstance(recv, Bound) and recv.kind in ("atview",
                                                     "refatview"):
            if name in ("set", "add", "max", "min"):
                return Bound(recv.kind + "op", recv.recv, name)
            raise AnalysisError(f"unmodeled .at[].{name}")
        if isinstance(recv, ModScope):
            return recv.get(name)
        if isinstance(recv, ModuleVal):
            return self.module_attr(recv, name, node)
        if isinstance(recv, Arr):
            if name == "shape":
                return tuple(recv.shape)
            if name == "ndim":
                return recv.ndim
            if name == "dtype":
                return DtypeVal(recv.dtype)
            if name == "at":
                return Bound("at", recv, "at")
            if name in ("astype", "reshape", "sum", "min", "max",
                        "transpose", "squeeze", "ravel", "view"):
                return Bound("arrmethod", recv, name)
            if name == "T":
                return self.intrinsic_transpose(recv, None, node)
            raise AnalysisError(f"unknown array attribute .{name}")
        if isinstance(recv, Ref):
            if name == "shape":
                return tuple(recv.shape)
            if name == "dtype":
                return DtypeVal(recv.dtype)
            if name == "at":
                return Bound("refat", recv, "at")
            raise AnalysisError(f"unknown ref attribute .{name}")
        if isinstance(recv, DtypeVal):
            return recv
        if isinstance(recv, dict) and name in ("get", "items", "keys",
                                               "values", "setdefault",
                                               "pop"):
            return Bound("dictmethod", recv, name)
        if isinstance(recv, list) and name in ("append", "extend",
                                               "insert", "pop"):
            return Bound("listmethod", recv, name)
        if isinstance(recv, str):
            return Bound("strmethod", recv, name)
        if isinstance(recv, SDS):
            if name == "shape":
                return tuple(recv.shape)
            if name == "dtype":
                return recv.dtype
        if hasattr(recv, name) and not isinstance(
                recv, (Arr, Ref, Clo, IV, SymDim)):
            # real host object (imported module, numpy array, ...)
            try:
                return self.to_abstract(getattr(recv, name))
            except Exception as e:      # noqa: BLE001
                return self.unknown(node, f"host attr .{name}: {e}")
        raise AnalysisError(
            f"attribute .{name} on {type(recv).__name__}")

    def module_attr(self, mod: ModuleVal, name: str, node) -> Any:
        if mod.name == "jax":
            if name == "jit":
                return Bound("jit", None, "jit")
            if name == "numpy":
                return ModuleVal("jax.numpy")
            if name == "lax":
                return ModuleVal("jax.lax")
            if name == "tree_util":
                return ModuleVal("jax.tree_util")
            if name == "experimental":
                return ModuleVal("jax.experimental")
            if name == "ShapeDtypeStruct":
                return Bound("intrinsic", "jax", "ShapeDtypeStruct")
            if name in ("Array", "config"):
                return Opaque(f"jax.{name}")
        if mod.name == "jax.experimental":
            if name == "pallas":
                return ModuleVal("pallas")
        if mod.name == "jax.numpy":
            if name in self._DTYPE_ATTRS:
                return DtypeVal(self._DTYPE_ATTRS[name])
            return Bound("jnp", None, name)
        if mod.name == "jax.lax":
            return Bound("lax", None, name)
        if mod.name == "jax.tree_util":
            return Bound("intrinsic", "tree", name)
        if mod.name == "pallas":
            if name == "BlockSpec":
                return Bound("intrinsic", "pl", "BlockSpec")
            if name == "pallas_call":
                return Bound("intrinsic", "pl", "pallas_call")
            if name == "program_id":
                return Bound("intrinsic", "pl", "program_id")
            if name == "tpu":
                return ModuleVal("pallas.tpu")
            if name in ("ANY", "MemorySpace"):
                return Opaque(f"pl.{name}")
        if mod.name == "pallas.tpu":
            if name == "VMEM":
                return Bound("intrinsic", "pltpu", "VMEM")
            if name == "roll":
                return Bound("intrinsic", "pltpu", "roll")
            return Opaque(f"pltpu.{name}")
        if mod.name == "functools":
            if name == "partial":
                return Bound("intrinsic", "functools", "partial")
            if name in ("lru_cache", "cache", "wraps"):
                return Bound("intrinsic", "functools", "lru_cache")
        raise AnalysisError(f"unmodeled {mod.name}.{name}")

    # -- indexing ----------------------------------------------------------

    def eval_index(self, node: ast.expr, frame: Frame) -> Any:
        if isinstance(node, ast.Slice):
            return slice(
                self.eval(node.lower, frame) if node.lower else None,
                self.eval(node.upper, frame) if node.upper else None,
                self.eval(node.step, frame) if node.step else None)
        if isinstance(node, ast.Tuple):
            return tuple(self.eval_index(e, frame) for e in node.elts)
        return self.eval(node, frame)

    def load_item(self, recv: Any, idx: Any, node) -> Any:
        if isinstance(recv, Opaque):
            return recv
        if isinstance(recv, (list, tuple)):
            if isinstance(idx, slice):
                return recv[self._host_slice(idx, len(recv))]
            if isinstance(idx, bool) or not isinstance(idx, int):
                if isinstance(idx, (IV, SymDim)):
                    iv = iv_of(idx)
                    lo = max(iv.lo, -len(recv))
                    hi = min(iv.hi, len(recv) - 1)
                    if lo > hi:
                        raise AnalysisError("index out of range")
                    out = recv[lo]
                    for i in range(lo + 1, hi + 1):
                        out = vjoin(out, recv[i])
                    return out
                raise AnalysisError(
                    f"abstract sequence index {type(idx).__name__}")
            return recv[idx]
        if isinstance(recv, dict):
            try:
                return recv[idx]
            except (KeyError, TypeError):
                raise AnalysisError(f"missing dict key {idx!r}")
        if isinstance(recv, str):
            if isinstance(idx, int):
                return recv[idx]
            if isinstance(idx, slice):
                return recv[self._host_slice(idx, len(recv))]
            raise AnalysisError("abstract string index")
        if isinstance(recv, Arr):
            return self.arr_getitem(recv, idx, node)
        if isinstance(recv, Ref):
            val = recv.value()
            if val is None:
                return self.unknown(node, "read of unwritten ref")
            return self.arr_getitem(val, idx, node)
        if isinstance(recv, range):
            if isinstance(idx, int):
                return recv[idx]
            raise AnalysisError("abstract range index")
        if isinstance(recv, Bound) and recv.name == "at":
            # x.at[idx] / ref.at[idx] -> view awaiting .set/.add
            kind = "atview" if recv.kind == "at" else "refatview"
            return Bound(kind, (recv.recv, idx), "view")
        raise AnalysisError(
            f"cannot index {type(recv).__name__}")

    @staticmethod
    def _host_slice(s: slice, n: int) -> slice:
        def ok(v):
            return v is None or isinstance(v, int)
        if not (ok(s.start) and ok(s.stop) and ok(s.step)):
            raise AnalysisError("abstract host slice")
        return s

    def index_axis0(self, arr: Arr, i: Optional[Any], node) -> Any:
        """arr[i] on the leading axis; i=None or abstract -> row hull."""
        if not arr.shape:
            raise AnalysisError("indexing a rank-0 array")
        rows = arr.row_list()
        shape = arr.shape[1:]
        if isinstance(i, bool):
            i = int(i)
        if isinstance(i, int) and rows is not None:
            if not -len(rows) <= i < len(rows):
                raise AnalysisError(f"row index {i} out of range")
            return Arr(arr.dtype, shape, None, rows[i])
        if i is None or isinstance(i, (IV, SymDim, Arr)):
            if rows is not None and i is not None \
                    and isinstance(i, (IV, SymDim)):
                iv = iv_of(i)
                lo = max(iv.lo, 0)
                hi = min(iv.hi, len(rows) - 1)
                if lo <= hi:
                    hull = rows[lo]
                    for r in rows[lo + 1:hi + 1]:
                        hull = hull.join(r)
                    return Arr(arr.dtype, shape, None, hull)
            return Arr(arr.dtype, shape, None, arr.iv)
        if isinstance(i, int):
            return Arr(arr.dtype, shape, None, arr.iv)
        raise AnalysisError(
            f"unhandled axis-0 index {type(i).__name__}")

    def arr_getitem(self, arr: Arr, idx: Any, node) -> Arr:
        if not isinstance(idx, tuple):
            idx = (idx,)
        # expand Ellipsis to full slices
        n_spec = sum(1 for i in idx if i is not None
                     and not isinstance(i, type(Ellipsis)))
        n_real = sum(1 for i in idx
                     if i is not None and i is not Ellipsis)
        if any(i is Ellipsis for i in idx):
            fill = arr.ndim - n_real
            out: List[Any] = []
            for i in idx:
                if i is Ellipsis:
                    out.extend([slice(None)] * fill)
                else:
                    out.append(i)
            idx = tuple(out)
        _ = n_spec
        # leading-axis handling drives row precision; everything past
        # axis 0 only reshapes within rows (row hulls stay sound)
        shape: List[Dim] = []
        rows = arr.row_list()
        axis = 0
        first_real = True
        out_rows: Optional[List[IV]] = rows
        leading_new_axes = 0
        iv = arr.iv
        for item in idx:
            if item is None:
                shape.append(1)
                if first_real:
                    leading_new_axes += 1
                continue
            if axis >= arr.ndim:
                raise AnalysisError("too many indices")
            dim = arr.shape[axis]
            if isinstance(item, slice):
                start, stop, step = item.start, item.stop, item.step
                if axis == 0 and first_real and rows is not None \
                        and all(x is None or isinstance(x, int)
                                for x in (start, stop, step)):
                    sel = rows[slice(start, stop, step)]
                    out_rows = sel
                    shape.append(len(sel))
                else:
                    shape.append(self._slice_dim(dim, item))
                    if axis == 0:
                        out_rows = None
                first_real = False
            elif isinstance(item, (int, bool)):
                if axis == 0 and first_real:
                    sub = self.index_axis0(arr, int(item), node)
                    rest = idx[idx.index(item) + 1:]
                    if rest:
                        return self.arr_getitem(sub, tuple(rest), node)
                    return sub
                # dropping a non-leading axis keeps rows sound
                first_real = False
            elif isinstance(item, (IV, SymDim, Arr, Opaque)):
                if axis == 0 and first_real:
                    sub = self.index_axis0(
                        arr, item if not isinstance(item, Opaque)
                        else None, node)
                    if isinstance(item, Arr):
                        # gather: indexed result keeps the index shape
                        sub = Arr(arr.dtype,
                                  tuple(item.shape) + tuple(sub.shape),
                                  None, sub.iv)
                    rest = idx[idx.index(item) + 1:]
                    if rest:
                        return self.arr_getitem(sub, tuple(rest), node)
                    return sub
                if isinstance(item, Arr):
                    shape.extend(item.shape)
                first_real = False
            else:
                raise AnalysisError(
                    f"unhandled index {type(item).__name__}")
            axis += 1
        shape.extend(arr.shape[axis:])
        if leading_new_axes:
            # x[None] / x[None, :]: old hull becomes the single row
            out_rows = [arr.iv] if shape and shape[0] == 1 else None
        if out_rows is not None and (not shape
                                     or not isinstance(shape[0], int)
                                     or len(out_rows) != shape[0]):
            out_rows = None
        return Arr(arr.dtype, tuple(shape), out_rows, iv)

    @staticmethod
    def _slice_dim(dim: Dim, s: slice) -> Dim:
        if isinstance(dim, int) and all(
                x is None or isinstance(x, int)
                for x in (s.start, s.stop, s.step)):
            return len(range(dim)[s])
        if s.start is None and s.stop is None and s.step is None:
            return dim
        # symbolic dim sliced with concrete bounds: length unknown
        if isinstance(s.stop, int) and (s.start is None
                                        or isinstance(s.start, int)) \
                and s.stop >= 0 and s.step is None:
            return s.stop - (s.start or 0)
        return IV(0, dim_iv(dim).hi)

    # -- ref updates -------------------------------------------------------

    def ref_store(self, ref: Ref, idx: Any, val: Any, node) -> None:
        try:
            viv = iv_of(val)
        except TypeError:
            if isinstance(val, Opaque):
                viv = DT_IV(ref.dtype)
            else:
                raise AnalysisError(
                    f"storing {type(val).__name__} into ref")
        if isinstance(val, Arr):
            self.finish(Arr(ref.dtype, val.shape, val.rows, val.iv),
                        node)
        ref.written = True
        idx_t = idx if isinstance(idx, tuple) else (idx,)
        first = idx_t[0] if idx_t else slice(None)
        full0 = isinstance(first, slice) and first.start is None \
            and first.stop is None and first.step is None
        rest_full = all(isinstance(i, slice) and i.start is None
                        and i.stop is None and i.step is None
                        or i is Ellipsis
                        for i in idx_t[1:])
        if ref.rows is None:
            ref.hull = viv if ref.hull is None else ref.hull.join(viv)
            return
        if full0 and rest_full:
            # o_ref[:] = v — strong whole-block update
            if isinstance(val, Arr) and val.rows is not None \
                    and len(val.rows) == len(ref.rows):
                ref.rows = list(val.rows)
            else:
                ref.rows = [viv] * len(ref.rows)
            return
        if isinstance(first, bool):
            first = int(first)
        if isinstance(first, int) and -len(ref.rows) <= first \
                < len(ref.rows):
            if rest_full:
                # strong single-row update (tab_ref[j] = acc, j concrete)
                row = viv
                if isinstance(val, Arr) and val.rows is not None \
                        and len(idx_t) == 1 and False:
                    pass
                ref.rows[first] = row
            else:
                old = ref.rows[first]
                ref.rows[first] = viv if old is _BOTTOM \
                    else old.join(viv)
            return
        if isinstance(first, slice):
            try:
                sel = range(len(ref.rows))[self._host_slice(
                    first, len(ref.rows))]
            except AnalysisError:
                sel = range(len(ref.rows))
            for i in sel:
                if rest_full:
                    ref.rows[i] = viv
                else:
                    old = ref.rows[i]
                    ref.rows[i] = viv if old is _BOTTOM \
                        else old.join(viv)
            return
        # abstract leading index: weak update on every row
        ref.rows = [viv if r is _BOTTOM else r.join(viv)
                    for r in ref.rows]

    # -- calls -------------------------------------------------------------

    def call(self, node: ast.Call, frame: Frame) -> Any:
        fn = self.eval(node.func, frame)
        args = []
        for a in node.args:
            if isinstance(a, ast.Starred):
                star = self.eval(a.value, frame)
                if not isinstance(star, (list, tuple)):
                    raise AnalysisError("abstract *args splat")
                args.extend(star)
            else:
                args.append(self.eval(a, frame))
        kwargs: Dict[str, Any] = {}
        for kw in node.keywords:
            if kw.arg is None:
                d = self.eval(kw.value, frame)
                if not isinstance(d, dict):
                    raise AnalysisError("abstract **kwargs splat")
                kwargs.update(d)
            else:
                kwargs[kw.arg] = self.eval(kw.value, frame)
        try:
            return self.apply(fn, args, kwargs, node, frame)
        except (TypeError, ValueError, AttributeError, IndexError,
                KeyError, ZeroDivisionError, OverflowError) as e:
            # abstract value reached a construct the model can't take
            # it through — surface as an analysis hole, not a crash
            raise AnalysisError(
                f"{type(e).__name__} at line {node.lineno}: {e}")

    def apply(self, fn: Any, args: list, kwargs: dict,
              node, frame: Frame) -> Any:
        if isinstance(fn, Opaque):
            return self.unknown(node, f"call of opaque {fn.reason}")
        if isinstance(fn, Clo):
            return self.call_clo(fn, args, kwargs, node)
        if isinstance(fn, Jitted):
            return self.call_clo(fn.clo, args, kwargs, node)
        if isinstance(fn, Partial):
            return self.apply(fn.fn, list(fn.args) + args,
                              {**fn.kwargs, **kwargs}, node, frame)
        if isinstance(fn, RealFn):
            return self.call_real(fn, args, kwargs, node, frame)
        if isinstance(fn, Bound):
            return self.call_bound(fn, args, kwargs, node, frame)
        if isinstance(fn, str) and fn in _PY_BUILTINS:
            return self.call_builtin(fn, args, kwargs, node, frame)
        if isinstance(fn, DtypeVal):
            # jnp.uint32(x) style cast
            return self.cast(args[0], fn.name, node)
        raise AnalysisError(f"call of {type(fn).__name__}")

    def call_real(self, fn: RealFn, args: list, kwargs: dict,
                  node, frame: Optional[Frame] = None) -> Any:
        try:
            cargs = [self.to_concrete(a) for a in args]
            ckw = {k: self.to_concrete(v) for k, v in kwargs.items()}
        except TypeError:
            # numpy structural fns with abstract (Arr) operands fall
            # back to the jnp transfer functions — np.stack over limb
            # constants mixed with traced rows is idiomatic host code
            if fn.name in ("stack", "concatenate", "asarray", "array",
                           "broadcast_to", "where", "minimum",
                           "maximum") and frame is not None:
                return self.jnp_call(fn.name, args, kwargs, node,
                                     frame)
            return self.unknown(
                node, f"abstract arg to host fn {fn.name}")
        try:
            out = fn.fn(*cargs, **ckw)
        except Exception as e:          # noqa: BLE001
            raise AnalysisError(f"host fn {fn.name} raised: {e}")
        return self.to_abstract(out)

    def to_concrete(self, v: Any) -> Any:
        if isinstance(v, (bool, int, str, bytes, float)) or v is None:
            return v
        if isinstance(v, tuple):
            return tuple(self.to_concrete(x) for x in v)
        if isinstance(v, list):
            return [self.to_concrete(x) for x in v]
        if isinstance(v, dict):
            return {k: self.to_concrete(x) for k, x in v.items()}
        if isinstance(v, IV) and v.exact:
            return v.lo
        if isinstance(v, SymDim) and v.bound is not None \
                and v.bound.exact:
            return v.bound.lo
        if isinstance(v, RealFn):
            return v.fn
        if isinstance(v, (Arr, IV, SymDim, Opaque, Unknown, Clo,
                          Bound, Partial, Jitted, ModuleVal, DtypeVal,
                          SDS, BlockSpec, VMEM, Ref, ModScope)):
            raise TypeError("abstract")
        # anything else is already a real host object (numpy dtype,
        # ndarray, imported module) — hand it through untouched
        return v

    def to_abstract(self, v: Any) -> Any:
        if isinstance(v, bool) or v is None:
            return v
        if isinstance(v, int):
            return v
        if isinstance(v, (str, bytes, float)):
            return v
        if isinstance(v, tuple):
            return tuple(self.to_abstract(x) for x in v)
        if isinstance(v, list):
            return [self.to_abstract(x) for x in v]
        if isinstance(v, dict):
            return {k: self.to_abstract(x) for k, x in v.items()}
        try:
            import numpy as _np
            if isinstance(v, _np.ndarray):
                if v.dtype.kind in "iub":
                    dt = str(v.dtype) if str(v.dtype) in DTYPE_RANGE \
                        else "int64"
                    flat = v.reshape(v.shape[0], -1) if v.ndim > 1 \
                        else v.reshape(-1, 1)
                    rows = None
                    if v.ndim >= 1 and v.shape[0] <= ROWS_MAX:
                        rows = [IV(int(r.min()), int(r.max()))
                                for r in flat]
                    iv = IV(int(v.min()), int(v.max())) if v.size \
                        else IV(0, 0)
                    return Arr(dt, tuple(int(d) for d in v.shape),
                               rows, iv)
                raise TypeError("non-integer ndarray")
            if isinstance(v, _np.integer):
                return int(v)
        except ImportError:
            pass
        if callable(v):
            return RealFn(v, getattr(v, "__name__", "<fn>"))
        raise TypeError(f"unconvertible host value {type(v).__name__}")

    def cast(self, v: Any, dtype: str, node) -> Any:
        if isinstance(v, Opaque):
            return Arr(dtype, (), None, DT_IV(dtype))
        if isinstance(v, bool):
            v = int(v)
        if isinstance(v, int):
            return self.finish(Arr(dtype, (), None, IV(v, v)), node)
        if isinstance(v, (IV, SymDim)):
            return self.finish(Arr(dtype, (), None, iv_of(v)), node)
        if isinstance(v, Arr):
            return self.finish(
                Arr(dtype, v.shape, v.rows, v.iv), node)
        if isinstance(v, (list, tuple)):
            arr = self.from_nested(v, dtype, node)
            return self.finish(arr, node)
        raise AnalysisError(f"cast of {type(v).__name__}")

    def from_nested(self, v: Any, dtype: str, node) -> Arr:
        """Build an exact Arr from a (nested) python list/tuple."""
        def scan(x, depth):
            if isinstance(x, (list, tuple)):
                if not x:
                    raise AnalysisError("empty array literal")
                subs = [scan(e, depth + 1) for e in x]
                sh = subs[0][0]
                for s, _ in subs[1:]:
                    if s != sh:
                        raise AnalysisError("ragged array literal")
                iv = subs[0][1]
                for _, i2 in subs[1:]:
                    iv = iv.join(i2)
                return (len(x),) + sh, iv
            return (), iv_of(x)
        shape, iv = scan(v, 0)
        rows = None
        if shape and isinstance(v, (list, tuple)) \
                and len(v) <= ROWS_MAX:
            rows = [scan(e, 1)[1] for e in v]
        return Arr(dtype, shape, rows, iv)

    # -- python builtins ---------------------------------------------------

    def call_builtin(self, name: str, args: list, kwargs: dict,
                     node, frame: Frame) -> Any:
        a = args
        if name == "round":
            if all(isinstance(v, (int, bool, float)) for v in a):
                return round(*a)
            raise AnalysisError("round of abstract value")
        if name == "len":
            v = a[0]
            if isinstance(v, (list, tuple, str, dict, range, set)):
                return len(v)
            if isinstance(v, Arr):
                return v.shape[0] if v.shape else \
                    self._die("len of rank-0")
            if isinstance(v, Ref):
                return v.shape[0]
            raise AnalysisError(f"len of {type(v).__name__}")
        if name == "range":
            ints = []
            for v in a:
                if isinstance(v, bool):
                    v = int(v)
                if not isinstance(v, int):
                    raise AnalysisError("abstract range bound")
                ints.append(v)
            return range(*ints)
        if name in ("min", "max"):
            pick = min if name == "min" else max
            vals = list(a[0]) if len(a) == 1 \
                and isinstance(a[0], (list, tuple, range)) else a
            if all(isinstance(v, (int, bool)) for v in vals):
                return pick(vals)
            ivs = [iv_of(v) for v in vals]
            if name == "min":
                return IV(pick(i.lo for i in ivs),
                          pick(i.hi for i in ivs))
            return IV(pick(i.lo for i in ivs),
                      pick(i.hi for i in ivs))
        if name == "abs":
            v = a[0]
            if isinstance(v, (int, bool)):
                return abs(int(v))
            iv = iv_of(v)
            lo = 0 if iv.lo <= 0 <= iv.hi else min(abs(iv.lo),
                                                   abs(iv.hi))
            return IV(lo, max(abs(iv.lo), abs(iv.hi)))
        if name == "int":
            v = a[0]
            if isinstance(v, (int, bool)):
                return int(v)
            if isinstance(v, str):
                return int(v, *a[1:])
            if isinstance(v, IV):
                return v
            if isinstance(v, Arr) and not v.shape:
                return v.iv
            raise AnalysisError("abstract int()")
        if name == "bool":
            t = self.truth(a[0])
            return t if t is not None else Unknown("bool()")
        if name == "float":
            raise AnalysisError("float() in kernel path")
        if name == "sum":
            v = a[0]
            start = a[1] if len(a) > 1 else kwargs.get("start", 0)
            if isinstance(v, (list, tuple)):
                out = start
                for x in v:
                    out = self.binop_vals(out, ast.Add(), x, node)
                return out
            if isinstance(v, range):
                return sum(v) + (start if isinstance(start, int)
                                 else 0)
            raise AnalysisError("sum of abstract iterable")
        if name == "tuple":
            if not a:
                return ()
            v = a[0]
            if isinstance(v, (list, tuple, range, str)):
                return tuple(v)
            raise AnalysisError("tuple() of abstract value")
        if name == "list":
            if not a:
                return []
            v = a[0]
            if isinstance(v, (list, tuple, range, str, set)):
                return list(v)
            raise AnalysisError("list() of abstract value")
        if name == "dict":
            d = dict(kwargs)
            if a and isinstance(a[0], dict):
                d = {**a[0], **d}
            return d
        if name == "set":
            if not a:
                return set()
            if isinstance(a[0], (list, tuple, range, str)):
                return set(a[0])
            raise AnalysisError("set() of abstract value")
        if name == "zip":
            seqs = []
            for v in a:
                if not isinstance(v, (list, tuple, range, str)):
                    raise AnalysisError("zip of abstract iterable")
                seqs.append(list(v))
            return [tuple(t) for t in zip(*seqs)]
        if name == "enumerate":
            v = a[0]
            start = a[1] if len(a) > 1 else kwargs.get("start", 0)
            if not isinstance(v, (list, tuple, range, str)):
                raise AnalysisError("enumerate of abstract iterable")
            if not isinstance(start, int):
                raise AnalysisError("abstract enumerate start")
            return [(start + i, x) for i, x in enumerate(v)]
        if name == "reversed":
            v = a[0]
            if isinstance(v, (list, tuple, range, str)):
                return list(reversed(v))
            raise AnalysisError("reversed of abstract iterable")
        if name == "sorted":
            v = a[0]
            if isinstance(v, (list, tuple, range)) and all(
                    isinstance(x, (int, bool, str)) for x in v):
                return sorted(v, **{k: self.to_concrete(x)
                                    for k, x in kwargs.items()})
            raise AnalysisError("sorted of abstract iterable")
        if name == "bin":
            v = a[0]
            if isinstance(v, (int, bool)):
                return bin(v)
            raise AnalysisError("bin of abstract value")
        if name == "pow":
            if all(isinstance(v, (int, bool)) for v in a):
                return pow(*[int(v) for v in a])
            raise AnalysisError("abstract pow()")
        if name == "divmod":
            x, y = a
            q = self.binop_vals(x, ast.FloorDiv(), y, node)
            r = self.binop_vals(x, ast.Mod(), y, node)
            return (q, r)
        if name in ("all", "any"):
            v = a[0]
            if isinstance(v, (list, tuple)):
                acc: Any = (name == "all")
                for x in v:
                    t = self.truth(x)
                    if t is None:
                        acc = Unknown(name)
                    elif name == "all" and not t:
                        return False
                    elif name == "any" and t:
                        return True
                return acc
            raise AnalysisError(f"{name} of abstract iterable")
        if name == "isinstance":
            return Unknown("isinstance")
        if name == "str":
            v = a[0]
            if isinstance(v, (int, bool, str)):
                return str(v)
            return "<abstract>"
        raise AnalysisError(f"unmodeled builtin {name}")

    @staticmethod
    def _die(msg: str):
        raise AnalysisError(msg)

    def binop_vals(self, a: Any, op: ast.operator, b: Any,
                   node) -> Any:
        """binop on already-evaluated values (helper for builtins)."""
        return self.binop(a, op, b, node)

    # -- bound methods -----------------------------------------------------

    def call_bound(self, b: Bound, args: list, kwargs: dict,
                   node, frame: Frame) -> Any:
        k = b.kind
        if k == "builtin":
            return self.call_builtin(b.name, args, kwargs, node, frame)
        if k == "jit":
            return self.make_jit(args, kwargs, node)
        if k == "jnp":
            return self.jnp_call(b.name, args, kwargs, node, frame)
        if k == "lax":
            return self.lax_call(b.name, args, kwargs, node, frame)
        if k == "intrinsic":
            return self.intrinsic_call(b, args, kwargs, node, frame)
        if k == "pallascall":
            return self.call_pallas(b.recv, args, node, frame)
        if k == "arrmethod":
            return self.arr_method(b.recv, b.name, args, kwargs, node)
        if k in ("atviewop", "refatviewop"):
            recv, idx = b.recv
            if k == "refatviewop":
                if b.name == "set":
                    self.ref_store(recv, idx, args[0], node)
                    return None
                cur = self.load_item(recv, idx, node)
                if b.name == "add":
                    upd = self.binop(cur, ast.Add(), args[0], node)
                else:
                    upd = vjoin(cur, args[0])
                self.ref_store(recv, idx, upd, node)
                return None
            return self.at_set(recv, idx, args[0], b.name, node)
        if k == "dictmethod":
            return self.dict_method(b.recv, b.name, args, kwargs, node)
        if k == "listmethod":
            m = b.name
            if m == "append":
                b.recv.append(args[0])
                return None
            if m == "extend":
                v = args[0]
                if not isinstance(v, (list, tuple, range)):
                    raise AnalysisError("extend with abstract iterable")
                b.recv.extend(v)
                return None
            if m == "insert":
                if not isinstance(args[0], int):
                    raise AnalysisError("abstract insert position")
                b.recv.insert(args[0], args[1])
                return None
            if m == "pop":
                i = args[0] if args else -1
                if not isinstance(i, int):
                    raise AnalysisError("abstract pop position")
                return b.recv.pop(i)
        if k == "strmethod":
            try:
                cargs = [self.to_concrete(x) for x in args]
                return self.to_abstract(
                    getattr(b.recv, b.name)(*cargs))
            except (TypeError, AttributeError) as e:
                raise AnalysisError(f"str.{b.name}: {e}")
        raise AnalysisError(f"unmodeled bound {k}.{b.name}")

    def dict_method(self, d: dict, m: str, args: list, kwargs: dict,
                    node) -> Any:
        if m == "get":
            try:
                return d.get(args[0],
                             args[1] if len(args) > 1 else None)
            except TypeError:
                raise AnalysisError("abstract dict key")
        if m == "items":
            return [(k, v) for k, v in d.items()]
        if m == "keys":
            return list(d.keys())
        if m == "values":
            return list(d.values())
        if m == "setdefault":
            try:
                return d.setdefault(args[0],
                                    args[1] if len(args) > 1 else None)
            except TypeError:
                raise AnalysisError("abstract dict key")
        if m == "pop":
            try:
                return d.pop(*args)
            except (TypeError, KeyError) as e:
                raise AnalysisError(f"dict.pop: {e}")
        raise AnalysisError(f"unmodeled dict.{m}")

    def make_jit(self, args: list, kwargs: dict, node) -> Any:
        fn = args[0]
        static = kwargs.get("static_argnames", ())
        if isinstance(static, str):
            static = (static,)
        elif isinstance(static, (list, tuple)):
            static = tuple(str(s) for s in static)
        else:
            static = ()
        if isinstance(fn, Jitted):
            fn = fn.clo
        if isinstance(fn, Clo):
            j = Jitted(fn, static)
            self.a.register_entry(j, node)
            return j
        if isinstance(fn, Partial) and isinstance(fn.fn, Clo):
            # jit(partial(f, const)): entry sees the bound prefix
            j = Jitted(fn.fn, static)
            self.a.register_entry(j, node, prefix=tuple(fn.args),
                                  prekw=dict(fn.kwargs))
            return Partial(j, fn.args, fn.kwargs)
        raise AnalysisError("jit of non-closure")

    def arr_method(self, arr: Any, m: str, args: list, kwargs: dict,
                   node) -> Any:
        if isinstance(arr, Ref):
            v = arr.value()
            if v is None:
                raise AnalysisError(f".{m} on unwritten ref")
            arr = v
        if m == "astype":
            dt = args[0]
            if isinstance(dt, DtypeVal):
                dt = dt.name
            elif isinstance(dt, Bound) and dt.kind == "builtin" \
                    and dt.name == "bool":
                dt = "bool"                    # .astype(bool)
            elif isinstance(dt, RealFn):
                try:
                    import numpy as _np
                    dt = str(_np.dtype(dt.fn))
                except Exception:              # noqa: BLE001
                    pass
            if not isinstance(dt, str):
                raise AnalysisError("abstract astype dtype")
            return self.cast(arr, dt, node)
        if m == "reshape":
            shape = args[0] if len(args) == 1 and isinstance(
                args[0], (tuple, list)) else tuple(args)
            return self.intrinsic_reshape(arr, tuple(shape), node)
        if m == "sum":
            return self.intrinsic_sum(
                arr, args[0] if args else kwargs.get("axis"), node)
        if m in ("min", "max"):
            return Arr(arr.dtype, (), None, arr.iv)
        if m == "transpose":
            return self.intrinsic_transpose(
                arr, tuple(args) if args else None, node)
        if m == "squeeze":
            shape = tuple(d for d in arr.shape
                          if not (isinstance(d, int) and d == 1))
            rows = arr.rows if arr.shape and dim_eq(
                arr.shape[0], (shape[0] if shape else 1)) else None
            return Arr(arr.dtype, shape, rows, arr.iv)
        if m == "ravel":
            n = shape_numel(arr.shape)
            return Arr(arr.dtype,
                       (n if n is not None else IV(0, INF),),
                       None, arr.iv)
        if m == "view":
            raise AnalysisError(".view() reinterprets bits")
        raise AnalysisError(f"unmodeled array method .{m}")

    def at_set(self, arr: Arr, idx: Any, val: Any, opname: str,
               node) -> Arr:
        idx_t = idx if isinstance(idx, tuple) else (idx,)
        if opname in ("add", "max", "min"):
            cur = self.arr_getitem(arr, idx, node)
            if opname == "add":
                val = self.binop(cur, ast.Add(), val, node)
            else:
                val = vjoin(cur, val)
        try:
            viv = iv_of(val)
        except TypeError:
            viv = DT_IV(arr.dtype)
        rows = arr.row_list()
        first = idx_t[0] if idx_t else slice(None)
        rest_full = all(
            (isinstance(i, slice) and i.start is None
             and i.stop is None and i.step is None) or i is Ellipsis
            for i in idx_t[1:])
        if isinstance(first, bool):
            first = int(first)
        if rows is not None and isinstance(first, int) \
                and rest_full and -len(rows) <= first < len(rows):
            rows = list(rows)
            rows[first] = viv
            out = Arr(arr.dtype, arr.shape, rows, viv)
        elif rows is not None and isinstance(first, slice) \
                and rest_full:
            try:
                sel = range(len(rows))[self._host_slice(
                    first, len(rows))]
                rows = list(rows)
                for i in sel:
                    rows[i] = viv
                out = Arr(arr.dtype, arr.shape, rows, viv)
            except AnalysisError:
                out = Arr(arr.dtype, arr.shape, None,
                          arr.iv.join(viv))
        else:
            out = Arr(arr.dtype, arr.shape, None, arr.iv.join(viv))
        return self.finish(out, node)

    # -- jnp intrinsics ----------------------------------------------------

    _JNP_BINOP = {"add": ast.Add, "subtract": ast.Sub,
                  "multiply": ast.Mult, "floor_divide": ast.FloorDiv,
                  "mod": ast.Mod, "remainder": ast.Mod,
                  "left_shift": ast.LShift, "right_shift": ast.RShift,
                  "bitwise_and": ast.BitAnd, "bitwise_or": ast.BitOr,
                  "bitwise_xor": ast.BitXor, "power": ast.Pow}
    _JNP_CMP = {"equal": ast.Eq, "not_equal": ast.NotEq,
                "less": ast.Lt, "less_equal": ast.LtE,
                "greater": ast.Gt, "greater_equal": ast.GtE}

    def jnp_call(self, name: str, args: list, kwargs: dict,
                 node, frame: Frame) -> Any:
        if name in self._JNP_BINOP:
            return self.binop(args[0], self._JNP_BINOP[name](),
                              args[1], node)
        if name in self._JNP_CMP:
            return self.compare_one(args[0], self._JNP_CMP[name](),
                                    args[1], node)
        if name == "broadcast_shapes":
            out: Tuple[Any, ...] = ()
            for s in args:
                if not isinstance(s, tuple):
                    raise AnalysisError("abstract broadcast_shapes arg")
                b = broadcast_shapes(out, s)
                if b is None:
                    raise AnalysisError("incompatible broadcast_shapes")
                out = b
            return out
        if name in ("asarray", "array"):
            v = args[0]
            dt = kwargs.get("dtype",
                            args[1] if len(args) > 1 else None)
            dt = dt.name if isinstance(dt, DtypeVal) else dt
            if isinstance(v, Arr):
                return self.cast(v, dt or v.dtype, node)
            if isinstance(v, (int, bool, IV, SymDim)):
                return self.cast(v, dt or "int32", node)
            if isinstance(v, (list, tuple)):
                return self.finish(
                    self.from_nested(v, dt or "int32", node), node)
            if isinstance(v, Opaque):
                d = dt or "int32"
                return Arr(d, (), None, DT_IV(d))
            raise AnalysisError(f"asarray of {type(v).__name__}")
        if name == "stack":
            return self.intrinsic_stack(
                args[0], kwargs.get("axis",
                                    args[1] if len(args) > 1 else 0),
                node)
        if name == "concatenate":
            return self.intrinsic_concat(
                args[0], kwargs.get("axis",
                                    args[1] if len(args) > 1 else 0),
                node)
        if name in ("zeros", "ones", "full"):
            shape = args[0]
            if isinstance(shape, (int, IV, SymDim)):
                shape = (shape,)
            fill: Any = 0 if name == "zeros" else 1
            if name == "full":
                fill = args[1]
            dt = kwargs.get("dtype",
                            args[2] if len(args) > 2 else None)
            dt = dt.name if isinstance(dt, DtypeVal) else (dt
                                                           or "int32")
            iv = iv_of(fill)
            rows = None
            if shape and isinstance(shape[0], int) \
                    and shape[0] <= ROWS_MAX:
                rows = [iv] * shape[0]
            return self.finish(Arr(dt, tuple(shape), rows, iv), node)
        if name in ("zeros_like", "ones_like", "full_like"):
            a = args[0]
            if isinstance(a, Ref):
                a = Arr(a.dtype, a.shape, None, IV(0, 0))
            if not isinstance(a, Arr):
                a = Arr("int32", (), None, IV(0, 0))
            fill = 0 if name == "zeros_like" else 1
            if name == "full_like":
                fill = args[1]
            dt = kwargs.get("dtype")
            dt = dt.name if isinstance(dt, DtypeVal) else (dt
                                                           or a.dtype)
            iv = iv_of(fill)
            rows = None
            if a.shape and isinstance(a.shape[0], int) \
                    and a.shape[0] <= ROWS_MAX:
                rows = [iv] * a.shape[0]
            return Arr(dt, a.shape, rows, iv)
        if name in ("where", "select"):
            cond, x, y = args[0], args[1], args[2]
            return self.intrinsic_where(cond, x, y, node)
        if name == "sum":
            return self.intrinsic_sum(
                args[0],
                kwargs.get("axis", args[1] if len(args) > 1 else None),
                node)
        if name in ("all", "any"):
            a = args[0]
            sh = ()
            ax = kwargs.get("axis", args[1] if len(args) > 1 else None)
            if isinstance(a, Arr) and ax is not None:
                sh = tuple(d for i, d in enumerate(a.shape)
                           if i != (ax if ax >= 0 else len(a.shape)
                                    + ax))
            return Arr("bool", sh, None, IV(0, 1))
        if name in ("minimum", "maximum"):
            return self.intrinsic_minmax(args[0], args[1],
                                         name == "minimum", node)
        if name == "abs":
            a = args[0]
            if isinstance(a, (int, bool)):
                return abs(int(a))
            iv = iv_of(a)
            lo = 0 if iv.lo <= 0 <= iv.hi else min(abs(iv.lo),
                                                   abs(iv.hi))
            out = IV(lo, max(abs(iv.lo), abs(iv.hi)))
            if isinstance(a, Arr):
                rows = a.row_list()
                if rows is not None:
                    rows = [IV(0 if r.lo <= 0 <= r.hi
                               else min(abs(r.lo), abs(r.hi)),
                               max(abs(r.lo), abs(r.hi)))
                            for r in rows]
                return self.finish(Arr(a.dtype, a.shape, rows, out),
                                   node)
            return out
        if name == "clip":
            a = args[0]
            lo = iv_of(args[1]) if len(args) > 1 and args[1] is not None \
                else None
            hi = iv_of(args[2]) if len(args) > 2 and args[2] is not None \
                else None
            iv = iv_of(a)
            clo = max(iv.lo, lo.lo) if lo else iv.lo
            chi = min(iv.hi, hi.hi) if hi else iv.hi
            if clo > chi:
                clo, chi = chi, clo
            if isinstance(a, Arr):
                return Arr(a.dtype, a.shape, None, IV(clo, chi))
            return IV(clo, chi)
        if name == "take":
            a, i = args[0], args[1]
            ax = kwargs.get("axis", args[2] if len(args) > 2 else None)
            if not isinstance(a, Arr):
                raise AnalysisError("take of non-array")
            if ax in (0, None) and not isinstance(i, Arr):
                return self.index_axis0(
                    a, i if isinstance(i, (int, IV, SymDim)) else None,
                    node)
            ish = i.shape if isinstance(i, Arr) else ()
            if ax is None:
                return Arr(a.dtype, tuple(ish), None, a.iv)
            if not isinstance(ax, int):
                raise AnalysisError("abstract take axis")
            ax %= a.ndim
            sh = a.shape[:ax] + tuple(ish) + a.shape[ax + 1:]
            return Arr(a.dtype, sh, None, a.iv)
        if name == "broadcast_arrays":
            bsh: Tuple[Any, ...] = ()
            for a in args:
                s = a.shape if isinstance(a, Arr) else ()
                b = broadcast_shapes(bsh, s)
                if b is None:
                    raise AnalysisError("incompatible broadcast_arrays")
                bsh = b
            out = []
            for a in args:
                if isinstance(a, Arr):
                    keep = shape_sig(a.shape) == shape_sig(bsh)
                    out.append(Arr(a.dtype, bsh,
                                   a.rows if keep else None, a.iv))
                else:
                    out.append(Arr("int32", bsh, None, iv_of(a)))
            return out
        if name == "arange":
            if args and isinstance(args[0], (SymDim, IV)) \
                    and all(isinstance(x, DtypeVal) for x in args[1:]):
                # arange over a symbolic length: shape keeps the
                # symbol, values span [0, n-1]
                d = args[0]
                dtv = kwargs.get("dtype")
                for x in args[1:]:
                    dtv = x
                dtn = dtv.name if isinstance(dtv, DtypeVal) \
                    else "int32"
                hi = dim_iv(d).hi
                dim = d if isinstance(d, SymDim) else SymDim("_n", d)
                return self.finish(
                    Arr(dtn, (dim,), None, IV(0, max(0, hi - 1))),
                    node)
            ints = []
            for v in args:
                if isinstance(v, DtypeVal):
                    break
                if isinstance(v, bool):
                    v = int(v)
                if isinstance(v, IV) and v.exact is not None:
                    v = v.exact
                if not isinstance(v, int):
                    raise AnalysisError("abstract arange bound")
                ints.append(v)
            dt = kwargs.get("dtype")
            for v in args:
                if isinstance(v, DtypeVal):
                    dt = v
            dt = dt.name if isinstance(dt, DtypeVal) else (dt
                                                           or "int32")
            r = list(range(*ints))
            rows = [IV(v, v) for v in r] if len(r) <= ROWS_MAX else None
            iv = IV(min(r), max(r)) if r else IV(0, 0)
            return self.finish(Arr(dt, (len(r),), rows, iv), node)
        if name == "reshape":
            shape = args[1]
            if isinstance(shape, (int, IV, SymDim)):
                shape = (shape,)
            return self.intrinsic_reshape(args[0], tuple(shape), node)
        if name == "broadcast_to":
            a, shape = args[0], tuple(args[1])
            iv = iv_of(a)
            dt = a.dtype if isinstance(a, Arr) else "int32"
            rows = None
            if isinstance(a, Arr):
                arows = a.row_list()
                if arows is not None and shape \
                        and dim_eq(a.shape[0] if a.shape else 1,
                                   shape[0]) \
                        and len(a.shape) == len(shape):
                    rows = arows
                elif shape and isinstance(shape[0], int) \
                        and shape[0] <= ROWS_MAX \
                        and (a.ndim < len(shape)
                             or (a.shape and a.shape[0] == 1)):
                    rows = [iv] * shape[0]
            return Arr(dt, shape, rows, iv)
        if name in ("expand_dims",):
            a = args[0]
            ax = args[1] if len(args) > 1 else kwargs.get("axis", 0)
            if not isinstance(a, Arr):
                a = self.cast(a, "int32", node)
            if not isinstance(ax, int):
                raise AnalysisError("abstract expand_dims axis")
            if ax < 0:
                ax = a.ndim + 1 + ax
            sh = a.shape[:ax] + (1,) + a.shape[ax:]
            rows = [a.iv] if ax == 0 else a.rows
            return Arr(a.dtype, sh, rows, a.iv)
        if name in ("moveaxis", "swapaxes"):
            a, src, dst = args[0], args[1], args[2]
            if not isinstance(a, Arr) or not isinstance(src, int) \
                    or not isinstance(dst, int):
                raise AnalysisError("abstract moveaxis")
            nd = a.ndim
            src %= nd
            dst %= nd
            order = [i for i in range(nd) if i != src]
            order.insert(dst, src)
            if name == "swapaxes":
                order = list(range(nd))
                order[src], order[dst] = order[dst], order[src]
            sh = tuple(a.shape[i] for i in order)
            rows = a.rows if order and order[0] == 0 else None
            return Arr(a.dtype, sh, rows, a.iv)
        if name == "transpose":
            return self.intrinsic_transpose(
                args[0], tuple(args[1]) if len(args) > 1 else None,
                node)
        if name == "squeeze":
            return self.arr_method(args[0], "squeeze", [], {}, node)
        if name in ("logical_and", "logical_or", "logical_xor"):
            sh = broadcast_shapes(
                *(a.shape for a in args if isinstance(a, Arr))) or ()
            return Arr("bool", sh, None, IV(0, 1))
        if name == "logical_not":
            a = args[0]
            sh = a.shape if isinstance(a, Arr) else ()
            return Arr("bool", sh, None, IV(0, 1))
        if name == "invert":
            return self.unary_invert(args[0], node)
        if name == "dot":
            return self.intrinsic_dot(args[0], args[1], node)
        if name == "cumsum":
            a = args[0]
            if not isinstance(a, Arr):
                raise AnalysisError("cumsum of non-array")
            n = dim_iv(a.shape[0] if a.shape else 1)
            iv = iv_mul(a.iv, IV(min(1, n.hi), max(1, n.hi)))
            return self.finish(Arr(a.dtype, a.shape, None, iv), node)
        raise AnalysisError(f"unmodeled jnp.{name}")

    def unary_invert(self, a: Any, node) -> Any:
        iv = iv_of(a)
        out = IV(-iv.hi - 1, -iv.lo - 1)
        if isinstance(a, Arr):
            rows = a.row_list()
            if rows is not None:
                rows = [IV(-r.hi - 1, -r.lo - 1) for r in rows]
            return self.finish(Arr(a.dtype, a.shape, rows, out), node)
        return out

    def intrinsic_stack(self, seq: Any, axis: Any, node) -> Arr:
        if not isinstance(seq, (list, tuple)):
            raise AnalysisError("stack of abstract sequence")
        if not seq:
            raise AnalysisError("stack of empty sequence")
        elems = [e if isinstance(e, Arr)
                 else Arr("int32", (), None, iv_of(e)) for e in seq]
        dt = None
        for e in elems:
            dt = promote(dt, e.dtype)
        sh = elems[0].shape
        for e in elems[1:]:
            u = []
            if len(e.shape) != len(sh):
                raise AnalysisError(
                    f"ragged stack {shape_sig(sh)} vs "
                    f"{shape_sig(e.shape)} at line "
                    f"{getattr(node, 'lineno', '?')}")
            for d1, d2 in zip(sh, e.shape):
                ud = unify_dim(d1, d2)
                if ud is None:
                    raise AnalysisError("ragged stack dims")
                u.append(ud)
            sh = tuple(u)
        iv = elems[0].iv
        for e in elems[1:]:
            iv = iv.join(e.iv)
        if not isinstance(axis, int):
            raise AnalysisError("abstract stack axis")
        nd = len(sh) + 1
        if axis < 0:
            axis += nd
        if not 0 <= axis < nd:
            raise AnalysisError(f"stack axis={axis}")
        out_sh = sh[:axis] + (len(elems),) + sh[axis:]
        rows = None
        if axis == 0 and len(elems) <= ROWS_MAX:
            rows = [e.iv for e in elems]
        elif axis > 0 and elems[0].rows is not None \
                and all(e.rows is not None
                        and len(e.rows) == len(elems[0].rows)
                        for e in elems):
            # stacking along a later axis keeps the leading axis —
            # per-row bounds survive as the joins across elements
            rows = [elems[0].rows[i]
                    for i in range(len(elems[0].rows))]
            for e in elems[1:]:
                rows = [r.join(er) for r, er in zip(rows, e.rows)]
        return self.finish(
            Arr(dt or "int32", out_sh, rows, iv), node)

    def intrinsic_concat(self, seq: Any, axis: Any, node) -> Arr:
        if not isinstance(seq, (list, tuple)) or not seq:
            raise AnalysisError("concatenate of abstract sequence")
        elems = [e for e in seq if isinstance(e, Arr)]
        if len(elems) != len(seq):
            raise AnalysisError("concatenate of non-arrays")
        dt = None
        for e in elems:
            dt = promote(dt, e.dtype)
        nd = elems[0].ndim
        if axis is None:
            axis = 0
        if axis < 0:
            axis += nd
        iv = elems[0].iv
        for e in elems[1:]:
            iv = iv.join(e.iv)
        if axis == 0:
            rows: Optional[List[IV]] = []
            total: Any = 0
            for e in elems:
                er = e.row_list()
                d0 = e.shape[0]
                if rows is not None and er is not None:
                    rows.extend(er)
                else:
                    rows = None
                if isinstance(total, int) and isinstance(d0, int):
                    total += d0
                else:
                    total = iv_add(dim_iv(total) if not isinstance(
                        total, IV) else total, dim_iv(d0))
            if rows is not None and (not isinstance(total, int)
                                     or len(rows) != total
                                     or total > ROWS_MAX):
                rows = None
            sh = (total,) + elems[0].shape[1:]
            return Arr(dt or "int32", sh, rows, iv)
        # non-leading axis: axis-0 length unchanged; join rows
        rows2 = elems[0].row_list()
        for e in elems[1:]:
            er = e.row_list()
            if rows2 is None or er is None or len(er) != len(rows2):
                rows2 = None
                break
            rows2 = [r1.join(r2) for r1, r2 in zip(rows2, er)]
        dim: Any = 0
        for e in elems:
            d = e.shape[axis]
            if isinstance(dim, int) and isinstance(d, int):
                dim += d
            else:
                dim = IV(0, INF)
        sh = elems[0].shape[:axis] + (dim,) + elems[0].shape[axis + 1:]
        return Arr(dt or "int32", sh, rows2, iv)

    def intrinsic_where(self, cond: Any, x: Any, y: Any, node) -> Arr:
        shapes = [v.shape for v in (cond, x, y) if isinstance(v, Arr)]
        sh = broadcast_shapes(*shapes) if shapes else ()
        if sh is None:
            raise AnalysisError("where: unbroadcastable shapes")
        dt = promote(x.dtype if isinstance(x, Arr) else None,
                     y.dtype if isinstance(y, Arr) else None)
        xa = x if isinstance(x, Arr) else Arr(dt, (), None, iv_of(x))
        ya = y if isinstance(y, Arr) else Arr(dt, (), None, iv_of(y))
        rows = self.zip_rows(xa, ya, xa, ya, sh,
                             lambda p, q: p.join(q))
        if rows is not None and any(r is None for r in rows):
            rows = None
        return self.finish(Arr(dt, sh, rows, xa.iv.join(ya.iv)), node)

    def intrinsic_minmax(self, x: Any, y: Any, is_min: bool,
                         node) -> Any:
        def mm(p: IV, q: IV) -> IV:
            if is_min:
                return IV(min(p.lo, q.lo), min(p.hi, q.hi))
            return IV(max(p.lo, q.lo), max(p.hi, q.hi))
        if not isinstance(x, Arr) and not isinstance(y, Arr):
            return mm(iv_of(x), iv_of(y))
        dt = promote(x.dtype if isinstance(x, Arr) else None,
                     y.dtype if isinstance(y, Arr) else None)
        xa = x if isinstance(x, Arr) else Arr(dt, (), None, iv_of(x))
        ya = y if isinstance(y, Arr) else Arr(dt, (), None, iv_of(y))
        sh = broadcast_shapes(xa.shape, ya.shape)
        if sh is None:
            raise AnalysisError("minimum/maximum: bad shapes")
        rows = self.zip_rows(xa, ya, xa, ya, sh, mm)
        if rows is not None and any(r is None for r in rows):
            rows = None
        return self.finish(Arr(dt, sh, rows, mm(xa.iv, ya.iv)), node)

    def intrinsic_sum(self, a: Any, axis: Any, node) -> Any:
        if isinstance(a, (list, tuple)):
            out: Any = 0
            for x in a:
                out = self.binop(out, ast.Add(), x, node)
            return out
        if not isinstance(a, Arr):
            return a
        rows = a.row_list()
        inner = shape_numel(a.shape[1:]) if a.shape else 1
        if axis is None:
            if rows is not None and inner is not None:
                lo = sum(r.lo for r in rows) * inner \
                    if inner >= 0 else 0
                hi = sum(r.hi for r in rows) * inner
                lo, hi = min(lo, hi), max(lo, hi)
                return self.finish(Arr(a.dtype, (), None, IV(lo, hi)),
                                   node)
            n = shape_numel(a.shape)
            niv = IV(n, n) if n is not None else IV(0, DEFAULT_DIM_HI)
            if a.shape and not isinstance(a.shape[0], int):
                niv = dim_iv(a.shape[0])
                for d in a.shape[1:]:
                    niv = iv_mul(niv, dim_iv(d))
            return self.finish(
                Arr(a.dtype, (), None, iv_mul(a.iv, niv)), node)
        if isinstance(axis, int) and axis < 0:
            axis += a.ndim
        if axis == 0:
            sh = a.shape[1:]
            if rows is not None:
                iv = IV(sum(r.lo for r in rows),
                        sum(r.hi for r in rows))
            else:
                iv = iv_mul(a.iv, dim_iv(a.shape[0]))
            return self.finish(Arr(a.dtype, sh, None, iv), node)
        if isinstance(axis, int) and 0 < axis < a.ndim:
            d = dim_iv(a.shape[axis])
            sh = a.shape[:axis] + a.shape[axis + 1:]
            iv = iv_mul(a.iv, d)
            out_rows = rows
            if rows is not None and a.shape[axis:axis + 1] \
                    and isinstance(a.shape[axis], int):
                k = a.shape[axis]
                out_rows = [IV(r.lo * k, r.hi * k) if r.lo >= 0
                            else iv_mul(r, IV(k, k)) for r in rows]
            return self.finish(Arr(a.dtype, sh, out_rows, iv), node)
        raise AnalysisError(f"sum axis={axis!r}")

    def intrinsic_dot(self, a: Any, b: Any, node) -> Arr:
        if not isinstance(a, Arr) or not isinstance(b, Arr):
            raise AnalysisError("dot of non-arrays")
        if a.ndim == 1 and b.ndim == 1:
            k = dim_iv(a.shape[0])
            sh: Tuple[Dim, ...] = ()
        elif a.ndim == 2 and b.ndim == 1:
            k = dim_iv(a.shape[1])
            sh = (a.shape[0],)
        elif a.ndim == 1 and b.ndim == 2:
            k = dim_iv(a.shape[0])
            sh = (b.shape[1],)
        else:
            k = dim_iv(a.shape[-1])
            sh = a.shape[:-1] + b.shape[1:]
        prod = iv_mul(a.iv, b.iv)
        return self.finish(
            Arr(promote(a.dtype, b.dtype), sh, None,
                iv_mul(prod, k)), node)

    def intrinsic_reshape(self, a: Any, shape: Tuple[Any, ...],
                          node) -> Arr:
        if not isinstance(a, Arr):
            a = self.cast(a, "int32", node)
        n = shape_numel(a.shape)
        shape = tuple(shape)
        if -1 in shape:
            known = 1
            ok = True
            for d in shape:
                if d == -1:
                    continue
                if not isinstance(d, int):
                    ok = False
                    break
                known *= d
            if ok and n is not None and known and n % known == 0:
                shape = tuple(n // known if d == -1 else d
                              for d in shape)
            else:
                shape = tuple(IV(0, INF) if d == -1 else d
                              for d in shape)
        rows = a.row_list()
        out_rows: Optional[List[IV]] = None
        if rows is not None and shape:
            n0 = shape[0]
            if isinstance(n0, int) and dim_eq(a.shape[0], n0):
                out_rows = rows
            elif isinstance(n0, int) and n0 and len(rows) % n0 == 0 \
                    and n0 <= ROWS_MAX:
                k = len(rows) // n0
                out_rows = []
                for i in range(n0):
                    h = rows[i * k]
                    for r in rows[i * k + 1:(i + 1) * k]:
                        h = h.join(r)
                    out_rows.append(h)
            elif isinstance(n0, int) and len(rows) and \
                    n0 % len(rows) == 0 and n0 <= ROWS_MAX:
                k = n0 // len(rows)
                out_rows = [r for r in rows for _ in range(k)]
        return Arr(a.dtype, shape, out_rows, a.iv)

    def intrinsic_transpose(self, a: Any, axes: Optional[tuple],
                            node) -> Arr:
        if not isinstance(a, Arr):
            raise AnalysisError("transpose of non-array")
        if axes is None:
            axes = tuple(reversed(range(a.ndim)))
        sh = tuple(a.shape[i] for i in axes)
        rows = a.rows if axes and axes[0] == 0 else None
        return Arr(a.dtype, sh, rows, a.iv)

    # -- lax intrinsics ----------------------------------------------------

    def lax_call(self, name: str, args: list, kwargs: dict,
                 node, frame: Frame) -> Any:
        if name == "scan":
            return self.lax_scan(args, kwargs, node, frame)
        if name == "fori_loop":
            return self.lax_fori(args, kwargs, node, frame)
        if name == "while_loop":
            return self.lax_while(args, kwargs, node, frame)
        if name == "cond":
            return self.lax_cond(args, kwargs, node, frame)
        if name == "select":
            return self.intrinsic_where(args[0], args[1], args[2],
                                        node)
        if name == "dynamic_index_in_dim":
            operand, index = args[0], args[1]
            axis = kwargs.get("axis",
                              args[2] if len(args) > 2 else 0)
            keepdims = kwargs.get(
                "keepdims", args[3] if len(args) > 3 else True)
            if axis != 0 or not isinstance(operand, Arr):
                raise AnalysisError("dynamic_index_in_dim axis != 0")
            sub = self.index_axis0(
                operand,
                index if isinstance(index, (int, IV, SymDim))
                else None, node)
            if keepdims is True:
                return Arr(sub.dtype, (1,) + tuple(sub.shape),
                           [sub.iv], sub.iv)
            return sub
        if name == "dynamic_slice":
            operand, starts, sizes = args[0], args[1], args[2]
            if not isinstance(operand, Arr):
                raise AnalysisError("dynamic_slice of non-array")
            return Arr(operand.dtype, tuple(sizes), None, operand.iv)
        if name == "dynamic_update_slice":
            operand, update = args[0], args[1]
            if not isinstance(operand, Arr):
                raise AnalysisError("dynamic_update_slice target")
            uiv = iv_of(update)
            return self.finish(
                Arr(operand.dtype, operand.shape, None,
                    operand.iv.join(uiv)), node)
        if name in ("bitcast_convert_type",):
            raise AnalysisError("bitcast reinterprets bits")
        raise AnalysisError(f"unmodeled lax.{name}")

    def lax_scan(self, args: list, kwargs: dict, node,
                 frame: Frame) -> Any:
        f = args[0] if args else kwargs.get("f")
        init = args[1] if len(args) > 1 else kwargs.get("init")
        xs = args[2] if len(args) > 2 else kwargs.get("xs")
        length = kwargs.get("length")
        if not isinstance(f, (Clo, Partial, Jitted)):
            raise AnalysisError("scan of non-closure")

        def leaf_elem(v: Any) -> Any:
            if isinstance(v, Arr):
                return self.index_axis0(v, None, node)
            if isinstance(v, (tuple, list)):
                return type(v)(leaf_elem(e) for e in v)
            if v is None:
                return None
            raise AnalysisError(
                f"scan xs of abstract structure ({type(v).__name__}"
                f" {str(v)[:40]})")

        def lead_dim(v: Any) -> Any:
            if isinstance(v, Arr):
                return v.shape[0] if v.shape else 1
            if isinstance(v, (tuple, list)):
                for e in v:
                    d = lead_dim(e)
                    if d is not None:
                        return d
            return None

        x_elem = leaf_elem(xs) if xs is not None else None
        n = length if length is not None else lead_dim(xs)
        if n is None:
            n = IV(0, DEFAULT_DIM_HI)
        carry = init
        y_out: Any = None
        for it in range(JOIN_CAP + WIDEN_EXTRA):
            out = self.apply(f, [carry, x_elem], {}, node, frame)
            if not (isinstance(out, tuple) and len(out) == 2):
                raise AnalysisError("scan body must return (carry, y)")
            new_carry, y = out
            y_out = y if y_out is None else vjoin(y_out, y)
            joined = vjoin(carry, new_carry)
            if veq(joined, carry):
                break
            carry = vwiden(carry, joined) if it >= JOIN_CAP else joined
        else:
            raise AnalysisError("scan carry did not converge")

        def stack_leaf(v: Any) -> Any:
            if isinstance(v, Arr):
                rows = None
                if isinstance(n, int) and n <= ROWS_MAX:
                    rows = [v.iv] * n
                return Arr(v.dtype, (n,) + tuple(v.shape), rows, v.iv)
            if isinstance(v, (tuple, list)):
                return type(v)(stack_leaf(e) for e in v)
            if v is None:
                return None
            if isinstance(v, (int, bool, IV, SymDim)):
                iv = iv_of(v)
                rows = [iv] * n if isinstance(n, int) \
                    and n <= ROWS_MAX else None
                return Arr("int32", (n,), rows, iv)
            raise AnalysisError("scan y of abstract structure")

        return (carry, stack_leaf(y_out))

    def lax_fori(self, args: list, kwargs: dict, node,
                 frame: Frame) -> Any:
        lo, hi, body, init = args[0], args[1], args[2], args[3]
        if not isinstance(body, (Clo, Partial, Jitted)):
            raise AnalysisError("fori_loop of non-closure")
        if isinstance(lo, bool):
            lo = int(lo)
        if isinstance(hi, bool):
            hi = int(hi)
        if isinstance(lo, int) and isinstance(hi, int) \
                and hi - lo <= UNROLL_MAX:
            val = init
            for i in range(lo, hi):
                val = self.apply(body, [i, val], {}, node, frame)
            return val
        ilo = iv_of(lo)
        ihi = iv_of(hi)
        i_iv = IV(ilo.lo, ihi.hi - 1)
        val = init
        for it in range(JOIN_CAP + WIDEN_EXTRA):
            new = self.apply(body, [i_iv, val], {}, node, frame)
            joined = vjoin(val, new)
            if veq(joined, val):
                break
            val = vwiden(val, joined) if it >= JOIN_CAP else joined
        else:
            raise AnalysisError("fori_loop did not converge")
        return val

    def lax_while(self, args: list, kwargs: dict, node,
                  frame: Frame) -> Any:
        cond_fn, body_fn, init = args[0], args[1], args[2]
        val = init
        for it in range(JOIN_CAP + WIDEN_EXTRA):
            t = self.truth(self.apply(cond_fn, [val], {}, node, frame))
            if t is False:
                return val
            new = self.apply(body_fn, [val], {}, node, frame)
            joined = vjoin(val, new)
            if veq(joined, val):
                break
            val = vwiden(val, joined) if it >= JOIN_CAP else joined
        else:
            raise AnalysisError("while_loop did not converge")
        # run cond once more for its own findings, then return the fix
        self.apply(cond_fn, [val], {}, node, frame)
        return val

    def lax_cond(self, args: list, kwargs: dict, node,
                 frame: Frame) -> Any:
        pred, tf, ff = args[0], args[1], args[2]
        operands = args[3:]
        t = self.truth(pred)
        if t is True:
            return self.apply(tf, list(operands), {}, node, frame)
        if t is False:
            return self.apply(ff, list(operands), {}, node, frame)
        a = self.apply(tf, list(operands), {}, node, frame)
        b = self.apply(ff, list(operands), {}, node, frame)
        return vjoin(a, b)

    # -- jax / pallas / functools intrinsics -------------------------------

    def intrinsic_call(self, b: Bound, args: list, kwargs: dict,
                       node, frame: Frame) -> Any:
        ns = b.recv
        if ns == "functools":
            if b.name == "partial":
                return Partial(args[0], tuple(args[1:]), dict(kwargs))
            # lru_cache()/cache/wraps: identity decorator for analysis
            if args and isinstance(args[0], (Clo, Partial, Jitted,
                                             Bound, RealFn)):
                return args[0]
            return Bound("intrinsic", "functools", "lru_cache")
        if ns == "jax" and b.name == "ShapeDtypeStruct":
            shape = args[0] if args else kwargs.get("shape")
            dt = args[1] if len(args) > 1 else kwargs.get("dtype")
            dt = dt.name if isinstance(dt, DtypeVal) else dt
            return SDS(tuple(shape), dt or "int32")
        if ns == "pl":
            if b.name == "BlockSpec":
                block = args[0] if args else kwargs.get("block_shape")
                imap = args[1] if len(args) > 1 \
                    else kwargs.get("index_map")
                return BlockSpec(
                    tuple(block) if block is not None else None, imap)
            if b.name == "program_id":
                ax = args[0] if args else kwargs.get("axis", 0)
                grid = self.a.grid
                if grid is None:
                    raise AnalysisError("program_id outside kernel")
                if not isinstance(ax, int) or ax >= len(grid):
                    raise AnalysisError("bad program_id axis")
                d = dim_iv(grid[ax])
                return IV(0, d.hi - 1)
            if b.name == "pallas_call":
                kern = args[0] if args else kwargs.pop("kernel", None)
                return Bound("pallascall", (kern, dict(kwargs)),
                             "pallas")
        if ns == "pltpu" and b.name == "VMEM":
            shape = args[0] if args else kwargs.get("shape")
            dt = args[1] if len(args) > 1 else kwargs.get("dtype")
            dt = dt.name if isinstance(dt, DtypeVal) else dt
            return VMEM(tuple(shape), dt or "int32")
        if ns == "pltpu" and b.name == "roll":
            # a rotation permutes elements along `axis`: same shape,
            # dtype and value hull; the per-row intervals (axis 0)
            # survive unless axis 0 itself is rotated
            x = args[0]
            axis = args[2] if len(args) > 2 else kwargs.get("axis")
            if not isinstance(x, Arr) or not isinstance(axis, int):
                raise AnalysisError("pltpu.roll of non-array / "
                                    "abstract axis")
            rows = x.row_list() if axis % max(1, len(x.shape)) else None
            return Arr(x.dtype, x.shape, rows, x.iv)
        if ns == "tree":
            if b.name == "tree_map":
                return self.tree_map(args[0], args[1:], node, frame)
            raise AnalysisError(f"unmodeled tree_util.{b.name}")
        raise AnalysisError(f"unmodeled intrinsic {ns}.{b.name}")

    def tree_map(self, f: Any, trees: list, node, frame: Frame) -> Any:
        if not trees:
            raise AnalysisError("tree_map with no trees")

        def rec(parts):
            first = parts[0]
            if isinstance(first, (tuple, list)):
                return type(first)(
                    rec([p[i] for p in parts])
                    for i in range(len(first)))
            if isinstance(first, dict):
                return {k: rec([p[k] for p in parts]) for k in first}
            return self.apply(f, list(parts), {}, node, frame)
        return rec(trees)

    # -- pallas kernels ----------------------------------------------------

    def call_pallas(self, spec: tuple, args: list, node,
                    frame: Frame) -> Any:
        kern, kw = spec
        if not isinstance(kern, (Clo, Partial, Jitted)):
            raise AnalysisError("pallas kernel is not a closure")
        grid = kw.get("grid", ())
        if isinstance(grid, (int, IV, SymDim)):
            grid = (grid,)
        grid = tuple(grid)
        out_shape = kw.get("out_shape")
        in_specs = kw.get("in_specs")
        out_specs = kw.get("out_specs")
        scratch = kw.get("scratch_shapes", ()) or ()

        def block_of(spec_v: Any, full: Tuple[Dim, ...]) \
                -> Tuple[Dim, ...]:
            if isinstance(spec_v, BlockSpec) \
                    and spec_v.block_shape is not None:
                return tuple(d for d in spec_v.block_shape)
            return full

        in_refs = []
        specs_list = list(in_specs) if isinstance(
            in_specs, (list, tuple)) else [None] * len(args)
        if len(specs_list) < len(args):
            specs_list += [None] * (len(args) - len(specs_list))
        for v, sp in zip(args, specs_list):
            if isinstance(v, Arr):
                shape = block_of(sp, v.shape)
                r = Ref(v.dtype, tuple(shape))
                full_block = all(dim_eq(a_d, b_d) for a_d, b_d in
                                 zip(v.shape, shape)) \
                    and len(shape) == len(v.shape)
                rows = v.row_list() if full_block else None
                if rows is not None and shape \
                        and isinstance(shape[0], int) \
                        and len(rows) == shape[0]:
                    r.rows = list(rows)
                else:
                    r.rows = None
                    r.hull = v.iv
                r.written = True
                in_refs.append(r)
            elif isinstance(v, Opaque):
                r = Ref("int32", (IV(1, DEFAULT_DIM_HI),))
                r.rows = None
                r.hull = DT_IV("int32")
                r.written = True
                in_refs.append(r)
            else:
                # scalar-prefetch style arg passes through unchanged
                in_refs.append(v)

        outs = out_shape if isinstance(out_shape, (list, tuple)) \
            else [out_shape]
        osp = out_specs if isinstance(out_specs, (list, tuple)) \
            else [out_specs] * len(outs)
        out_refs = []
        for o, sp in zip(outs, osp):
            if not isinstance(o, SDS):
                raise AnalysisError("pallas out_shape must be SDS")
            out_refs.append(Ref(o.dtype, block_of(sp, o.shape)))
        scratch_refs = []
        for s in scratch:
            if isinstance(s, VMEM):
                scratch_refs.append(Ref(s.dtype, s.shape))
            else:
                raise AnalysisError("unmodeled scratch shape")

        prev = self.a.grid
        self.a.grid = grid
        try:
            self.apply(kern, in_refs + out_refs + scratch_refs, {},
                       node, frame)
        finally:
            self.a.grid = prev

        results = []
        for o, r in zip(outs, out_refs):
            v = r.value()
            iv = v.iv if v is not None else DT_IV(o.dtype)
            rows = None
            if v is not None and v.rows is not None and o.shape \
                    and isinstance(o.shape[0], int) \
                    and len(v.rows) == o.shape[0]:
                rows = v.rows
            results.append(Arr(o.dtype, tuple(o.shape), rows, iv))
        if isinstance(out_shape, (list, tuple)):
            return tuple(results)
        return results[0]

def _dotted_name(ctx: FileCtx, node: ast.AST) -> Optional[str]:
    """Dotted name of a Name/Attribute via the file's import aliases."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    base = ctx.from_imports.get(node.id)
    if base is None:
        mod = ctx.module_aliases.get(node.id)
        base = mod if mod is not None else node.id
    parts.append(base)
    return ".".join(reversed(parts))


def _is_jit_name(dn: Optional[str]) -> bool:
    return dn is not None and (dn == "jit" or dn.endswith(".jit"))


def _static_names_of(call: ast.Call) -> Tuple[str, ...]:
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for e in v.elts:
                    if isinstance(e, ast.Constant) \
                            and isinstance(e.value, str):
                        out.append(e.value)
                return tuple(out)
    return ()


class Analysis:
    """One whole-tree interval analysis: module scopes, the abstract
    interpreter, entry discovery/seeding, findings, obligations."""

    def __init__(self, ctxs: Dict[str, FileCtx]):
        self.ctxs = ctxs
        self.modscopes: Dict[str, ModScope] = {}
        self._ctx_stack: List[FileCtx] = []
        self.findings: Dict[Tuple[str, int, str],
                            Tuple[str, FileCtx]] = {}
        self._captures: List[list] = []
        self.used_assumes: Set[Tuple[str, int]] = set()
        self.obligations: List[Dict[str, Any]] = []
        self.covered: Set[str] = set()
        self.entries: List[str] = []
        self.in_entry = False
        self.grid: Optional[Tuple[Any, ...]] = None
        self.pending: List[Tuple[Jitted, tuple, dict]] = []
        self._entry_keys: Set[Any] = set()
        self._factory_done: Set[Any] = set()
        self.interp = Interp(self)
        for path, ctx in sorted(ctxs.items()):
            self.modscopes[_posix_module(path)] = ModScope(self, ctx)

    # -- context & findings ------------------------------------------------

    def cur_ctx(self) -> Optional[FileCtx]:
        return self._ctx_stack[-1] if self._ctx_stack else None

    def push_ctx(self, ctx: FileCtx) -> None:
        self._ctx_stack.append(ctx)

    def pop_ctx(self) -> None:
        self._ctx_stack.pop()

    def add_finding(self, path: str, line: int, kind: str, msg: str,
                    ctx: FileCtx) -> None:
        # overwrite-dict keyed by site: fixpoint iterations report
        # monotonically growing bounds; the stabilized iteration's
        # message (written last) is the one that survives
        self.findings[(path, line, kind)] = (msg, ctx)
        for cap in self._captures:
            cap.append((path, line, kind, msg, ctx))

    def replay(self, rec) -> None:
        path, line, kind, msg, ctx = rec
        self.add_finding(path, line, kind, msg, ctx)

    def push_capture(self) -> list:
        cap: list = []
        self._captures.append(cap)
        return cap

    def pop_capture(self, cap: list) -> list:
        # pop by IDENTITY — list.remove() matches by equality and two
        # empty capture lists are equal, silently popping the wrong one
        for i in range(len(self._captures) - 1, -1, -1):
            if self._captures[i] is cap:
                del self._captures[i]
                break
        return cap

    def add_obligation(self, frame: Frame, spec: Assume,
                       stmt: ast.stmt, got: IV) -> None:
        self.obligations.append({
            "path": frame.ctx.path,
            "qual": frame.qual,
            "func": frame.qual.split(".")[-1],
            "var": spec.var,
            "lo": spec.lo,
            "hi": spec.hi,
            "line": spec.line,
            "computed": (got.lo, got.hi),
            "on_return": isinstance(stmt, ast.Return),
        })

    # -- entry discovery ---------------------------------------------------

    def register_entry(self, j: Jitted, node,
                       prefix: tuple = (),
                       prekw: Optional[dict] = None) -> None:
        clo = j.clo
        try:
            capsig = tuple(
                sorted((k, sig_of(v))
                       for sc in clo.scopes for k, v in sc.items()))
        except TypeError:
            capsig = None
        key = (clo.path, clo.qual, capsig)
        if key in self._entry_keys:
            return
        self._entry_keys.add(key)
        self.pending.append((j, tuple(prefix), dict(prekw or {})))

    def discover(self) -> None:
        for modname in sorted(self.modscopes):
            mod = self.modscopes[modname]
            ctx = mod.ctx
            for fnode in ctx.tree.body:
                if not isinstance(fnode, ast.FunctionDef):
                    continue
                static = self._decorator_static(ctx, fnode)
                if static is not None:
                    clo = mod.get(fnode.name)
                    if isinstance(clo, Clo):
                        self.register_entry(Jitted(clo, static), fnode)
                elif self._contains_jit_call(ctx, fnode):
                    self._seed_factory(mod, fnode)
            # module-level `verify = jax.jit(core, ...)` /
            # `tile = pl.pallas_call(...)` style assigns: force-evaluate
            # so make_jit/pallas registration fires
            for name, stmt in sorted(mod.assigns.items()):
                if any(isinstance(n, ast.Call)
                       and _is_jit_name(_dotted_name(ctx, n.func))
                       for n in ast.walk(stmt)):
                    mod.get(name)

    @staticmethod
    def _decorator_static(ctx: FileCtx, fnode: ast.FunctionDef) \
            -> Optional[Tuple[str, ...]]:
        """static_argnames if fnode is jit-decorated, else None."""
        for dec in fnode.decorator_list:
            if _is_jit_name(_dotted_name(ctx, dec)):
                return ()
            if isinstance(dec, ast.Call):
                dn = _dotted_name(ctx, dec.func)
                if _is_jit_name(dn):
                    return _static_names_of(dec)
                if dn is not None and dn.endswith("partial") \
                        and dec.args and _is_jit_name(
                            _dotted_name(ctx, dec.args[0])):
                    return _static_names_of(dec)
        return None

    @staticmethod
    def _contains_jit_call(ctx: FileCtx, fnode: ast.FunctionDef) -> bool:
        for n in ast.walk(fnode):
            if isinstance(n, ast.Call) \
                    and _is_jit_name(_dotted_name(ctx, n.func)):
                return True
        return False

    def _seed_factory(self, mod: ModScope, fnode: ast.FunctionDef) -> None:
        """A plain function whose body jits a closure (the lru_cached
        `_compiled(bucket, bits)` pattern): call it with params seeded
        from its def-site assume() pragmas, or from a call site whose
        arguments are module-level constants — interpreting the body
        registers the inner jit closure with its live captured env."""
        clo = mod.get(fnode.name)
        if not isinstance(clo, Clo):
            return
        seeds = self._factory_seed_args(mod, fnode)
        if seeds is None:
            self.add_finding(
                mod.path, fnode.lineno, "entry-precondition",
                f"factory {fnode.name}() jits a kernel but its "
                f"parameters cannot be seeded — add assume() pragmas "
                f"between def and body", mod.ctx)
            return
        try:
            fkey = (mod.path, fnode.name, tuple(
                sig_of(s) for s in seeds))
        except TypeError:
            fkey = (mod.path, fnode.name, None)
        if fkey in self._factory_done:
            return
        self._factory_done.add(fkey)
        try:
            self.interp.call_clo(clo, list(seeds), {}, None)
        except (AnalysisError, RecursionError) as e:
            self.add_finding(
                mod.path, fnode.lineno, "interval-crash",
                f"interval analyzer failed seeding factory "
                f"{fnode.name}: {e}", mod.ctx)

    def _entry_specs(self, ctx: FileCtx,
                     fnode: ast.FunctionDef) -> Dict[str, Assume]:
        body_start = fnode.body[0].lineno if fnode.body \
            else fnode.lineno + 1
        return {sp.var: sp for sp in
                ctx.assumes_between(fnode.lineno, body_start)}

    def _factory_seed_args(self, mod: ModScope,
                           fnode: ast.FunctionDef) -> Optional[list]:
        """Per-parameter seeding: def-site assume() pragma first, else
        the module-level constant the call sites pass (traced through
        intermediate host drivers — pow_is_one_batch hands HARD_BITS
        to _compiled through its own `bits` parameter)."""
        specs = self._entry_specs(mod.ctx, fnode)
        args = []
        for i, p in enumerate(fnode.args.posonlyargs
                              + fnode.args.args):
            sp = specs.get(p.arg)
            if sp is not None:
                self.used_assumes.add((mod.ctx.path, sp.line))
                args.append(IV(sp.lo, sp.hi)
                            if sp.lo != sp.hi else sp.lo)
                continue
            v = self._trace_const_arg(fnode.name, i, set())
            if v is None:
                return None
            args.append(v)
        return args

    @staticmethod
    def _concrete_host(v: Any) -> bool:
        if isinstance(v, (int, bool)):
            return True
        if isinstance(v, tuple):
            return all(isinstance(e, (int, bool)) for e in v)
        return False

    def _trace_const_arg(self, fname: str, argpos: int,
                         seen: Set[Tuple[str, int]]) -> Any:
        """Concrete host value flowing into parameter `argpos` of
        `fname` at some call site, following same-named parameters
        through intermediate functions up to the module constant."""
        if (fname, argpos) in seen or len(seen) > 8:
            return None
        seen.add((fname, argpos))
        for modname in sorted(self.modscopes):
            peer = self.modscopes[modname]
            tree = peer.ctx.tree
            for fdef in tree.body:
                if not isinstance(fdef, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                fparams = [q.arg for q in fdef.args.posonlyargs
                           + fdef.args.args]
                for call in ast.walk(fdef):
                    if not isinstance(call, ast.Call):
                        continue
                    cf = call.func
                    if not ((isinstance(cf, ast.Name)
                             and cf.id == fname)
                            or (isinstance(cf, ast.Attribute)
                                and cf.attr == fname)):
                        continue
                    if argpos >= len(call.args):
                        continue
                    a = call.args[argpos]
                    if isinstance(a, ast.Constant) \
                            and isinstance(a.value, (int, bool)):
                        return a.value
                    if not isinstance(a, ast.Name):
                        continue
                    v = peer.get(a.id)
                    if self._concrete_host(v):
                        return v
                    if a.id in fparams:
                        r = self._trace_const_arg(
                            fdef.name, fparams.index(a.id), seen)
                        if r is not None:
                            return r
        return None

    # -- entry runs --------------------------------------------------------

    def run(self) -> None:
        self.discover()
        while self.pending:
            j, prefix, prekw = self.pending.pop(0)
            self.run_entry(j, prefix, prekw)

    def _spec_value(self, spec: Assume, is_static: bool,
                    dims: Dict[str, SymDim]) -> Any:
        iv = IV(spec.lo, spec.hi)
        if spec.shape is None:
            if is_static:
                return iv if spec.lo != spec.hi else spec.lo
            return Arr(spec.dtype, (), None, iv)
        shape = tuple(
            dims.setdefault(d, SymDim(d)) if isinstance(d, str) else d
            for d in spec.shape)
        rows = None
        if shape and isinstance(shape[0], int) \
                and shape[0] <= ROWS_MAX:
            rows = [iv] * shape[0]
        return Arr(spec.dtype, shape, rows, iv)

    def run_entry(self, j: Jitted, prefix: tuple, prekw: dict) -> None:
        clo = j.clo
        ctx = clo.mod.ctx
        fnode = clo.node
        label = f"{clo.path}::{clo.qual}"
        self.entries.append(label)
        if isinstance(fnode, ast.Lambda):
            self.add_finding(clo.path, fnode.lineno,
                             "entry-precondition",
                             "jit of a lambda cannot carry assume() "
                             "preconditions — name the function",
                             ctx)
            return
        specs = self._entry_specs(ctx, fnode)
        dims: Dict[str, SymDim] = {}
        all_params = fnode.args.posonlyargs + fnode.args.args
        params = [p.arg for p in all_params]
        # an assume() on a name that is NOT a parameter bounds a shape
        # symbol instead: `assume(B, 1, 4096)` caps the block-count
        # axis every (N, B, 128) parameter shares
        for sp in specs.values():
            if sp.var not in params and sp.shape is None:
                dims[sp.var] = SymDim(sp.var, IV(sp.lo, sp.hi))
                self.used_assumes.add((ctx.path, sp.line))
        defaults: Dict[str, ast.expr] = {}
        for p, d in zip(all_params[len(all_params)
                                   - len(fnode.args.defaults):],
                        fnode.args.defaults):
            defaults[p.arg] = d
        args: List[Any] = list(prefix)
        for p in params[len(prefix):]:
            if p in prekw:
                args.append(prekw[p])
                continue
            sp = specs.get(p)
            if sp is None:
                if p in defaults:
                    # host-level default (interpret=False, zip215=True)
                    # is the value every kernel trace actually sees
                    dframe = Frame([{}], clo.mod, f"{clo.qual}:<default>")
                    try:
                        args.append(self.interp.eval(defaults[p],
                                                     dframe))
                    except AnalysisError as e:
                        args.append(Opaque(f"default of {p}: {e}"))
                    continue
                self.add_finding(
                    clo.path, fnode.lineno, "entry-precondition",
                    f"entry {clo.qual}() parameter `{p}` lacks an "
                    f"assume() precondition pragma — the int32 proof "
                    f"cannot start unseeded", ctx)
                args.append(Opaque(f"unseeded entry param {p}"))
                continue
            self.used_assumes.add((ctx.path, sp.line))
            args.append(self._spec_value(sp, p in j.static, dims))
        was = self.in_entry
        self.in_entry = True
        try:
            self.interp.call_clo(clo, args, {}, None)
        except (AnalysisError, RecursionError) as e:
            via = " > ".join(getattr(e, "stack", self.interp.stack)[-6:])
            self.add_finding(
                clo.path, fnode.lineno, "interval-crash",
                f"interval analyzer gave up in entry {clo.qual}: {e}"
                f" [in {via}]", ctx)
        finally:
            self.in_entry = was


def analyze_tree(root: str,
                 prefix: str = "cometbft_tpu/ops") -> Analysis:
    """Standalone API (tests, tools/interval_fuzz.py): analyze every
    module under `prefix` and return the finished Analysis."""
    ctxs: Dict[str, FileCtx] = {}
    base = os.path.join(root, prefix)
    for dirpath, _dirs, files in os.walk(base):
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, f), root)
            rel = rel.replace(os.sep, "/")
            ctxs[rel] = FileCtx(root, rel)
    a = Analysis(ctxs)
    a.run()
    return a


class KernelIntervalRule:
    """Interval abstract interpretation over ops/: prove every
    int32-typed value stays inside [-2**31, 2**31) on every path
    reachable from a jit/scan/pallas entry."""
    name = "kernel-interval"
    doc = ("int32 value whose computed interval escapes "
           "[-2**31, 2**31) on a reachable kernel path — or a hole in "
           "the proof (unbounded value, missing assume() "
           "precondition, analyzer bail-out). docs/STATICCHECK.md §v3")
    roots: Tuple[str, ...] = ("cometbft_tpu/ops",)
    exempt: frozenset = frozenset()
    tree_rule = True
    needs_project = True
    audits_assumes = True

    def __init__(self):
        self.used_assumes: Set[Tuple[str, int]] = set()
        self.obligations: List[Dict[str, Any]] = []
        self.covered: Set[str] = set()
        self.entries: List[str] = []

    def applies_to(self, path: str) -> bool:
        if path in self.exempt:
            return False
        return any(path == top or path.startswith(top + "/")
                   for top in self.roots)

    def check(self, ctx: FileCtx) -> Iterable[Finding]:
        return ()

    def finalize(self, root: str, project=None) -> Iterator[Finding]:
        if project is None:
            return
        ctxs = {p: c for p, c in project.ctxs.items()
                if self.applies_to(p)}
        analysis = Analysis(ctxs)
        analysis.run()
        self.used_assumes = analysis.used_assumes
        self.obligations = analysis.obligations
        self.covered = analysis.covered
        self.entries = analysis.entries
        for (path, line, kind) in sorted(analysis.findings):
            msg, ctx = analysis.findings[(path, line, kind)]
            src = ctx.lines[line - 1] \
                if 0 < line <= len(ctx.lines) else ""
            yield Finding(self.name, path, line, f"{kind}: {msg}", src)









