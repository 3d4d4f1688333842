"""Reduction of a JAX profiler trace (`*.xplane.pb`) to the numbers the
benchmark reports: device busy seconds, the traced window, time per
device program, the device operations that took most time and the
longest idle gaps labelled by the benchmark's own annotations.

What the profiler prints for a TPU (looked at by hand, PR 25): one plane
per chip named `/device:TPU:<n>` with the lines `XLA Modules` (one event
per executed program, named `jit_<function>(<fingerprint>)`) and
`XLA Ops` (one event per operation inside a program; a `while` or a
`conditional` and the operations of its body overlap, which is why busy
time is a union of intervals and never a sum), and one `/host:CPU`
plane with a line per thread that carries the benchmark's
`jax.profiler.TraceAnnotation`s by name. All times are nanoseconds on
one clock.

A 15 s window of small programs holds millions of `XLA Ops` events, and
walking them in Python took longer than the run may last (PR 25, call 2:
168 s for 4.2 M events). So busy time is the union of the `XLA Modules`
spans — the time in which a program was executing on the device; the
operations of a program cover 98.7 % of its span in these traces, the
rest being sub-microsecond gaps between them — and the per-operation
breakdown is read from the first `OPS_SAMPLE` events of the ops line and
scaled to the window by program time."""

from __future__ import annotations

import gc
import glob
import itertools
import os
import re
from dataclasses import dataclass, field

from .stats import gaps, union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OPS_SAMPLE = 200_000
WINDOW = "bench.traced"       # tracing.profiled opens it around the window
ANNOTATION_PREFIX = "bench."


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over the chips used
    n_device_planes: int
    programs: dict = field(default_factory=dict)   # name -> [seconds, count]
    device_ops: list = field(default_factory=list)  # [name, seconds] top 10
    idle_gaps: list = field(default_factory=list)   # [label, seconds] top 10
    annotations: dict = field(default_factory=dict)  # name -> [seconds, count]

    def program_seconds(self, pattern: str):
        """(seconds, executions) of the device programs whose name
        matches `pattern`, or None when none ran."""
        rx = re.compile(pattern)
        hit = [v for k, v in self.programs.items() if rx.search(k)]
        if not hit:
            return None
        return sum(v[0] for v in hit), sum(v[1] for v in hit)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line, only_prefix=None, shorten=False, limit=None):
    """(name, start_ns, end_ns) of a line's events; `only_prefix` keeps
    the events so named, `shorten` cuts an instruction to its name (one
    shared string per name: a window holds millions of op events)."""
    out, names = [], {}
    for e in itertools.islice(line.events, limit):
        n = e.name
        if only_prefix is not None and not n.startswith(only_prefix):
            continue
        if shorten:
            n = names.setdefault(n, short_name(n))
        lo = float(e.start_ns)
        out.append((n, lo, lo + float(e.duration_ns)))
    return out


def self_seconds(events) -> dict:
    """Seconds by name with every instant given to the innermost event
    that covers it: a `while` and the operations of its body overlap on
    the ops line, and summing their durations would count the body
    twice."""
    totals: dict = {}
    stack: list = []              # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own / 1e9

    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        close(lo)
        if stack:
            stack[-1][2] -= min(hi, stack[-1][1]) - lo
        stack.append([name, hi, hi - lo])
    close(float("inf"))
    return totals


def short_name(op: str) -> str:
    """`%fusion.12 = s32[...] fusion(...)` -> `fusion.12`: the profiler
    prints the whole instruction, the ledger wants a name."""
    return op.split(" = ", 1)[0].lstrip("%")[:120]


def _strip(name: str) -> str:
    """`jit_f(123456)` -> `jit_f`: the fingerprint changes with the
    compiler, the name does not."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(planes) -> TraceSummary:
    """`planes`: [(plane name, [(line name, [(event, start_ns, end_ns)])])]
    — the shape `load` builds from a file, and what the tests build by
    hand."""
    host_ann: dict = {}
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            continue
        for _lname, events in lines:
            for name, lo, hi in events:
                if name.startswith(ANNOTATION_PREFIX):
                    host_ann.setdefault(name, []).append((lo, hi))
    if WINDOW not in host_ann:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w_lo = min(a for a, _b in host_ann[WINDOW])
    w_hi = max(b for _a, b in host_ann[WINDOW])
    window_s = (w_hi - w_lo) / 1e9

    def clip(evs):
        return [(n, max(lo, w_lo), min(hi, w_hi)) for n, lo, hi in evs
                if hi > w_lo and lo < w_hi]

    busy, n_dev = [], 0
    programs: dict = {}
    op_totals: dict = {}
    first_dev_intervals = None
    for pname, lines in planes:
        if not DEVICE_PLANE.match(pname):
            continue
        by_name = dict(lines)
        modules = clip(by_name.get(MODULES_LINE, []))
        if not modules:
            continue
        n_dev += 1
        intervals = [(lo, hi) for _n, lo, hi in modules]
        busy.append(union_seconds(intervals) / 1e9)
        if first_dev_intervals is None:
            first_dev_intervals = intervals
        for n, lo, hi in modules:
            slot = programs.setdefault(_strip(n), [0.0, 0])
            slot[0] += (hi - lo) / 1e9
            slot[1] += 1
        # the per-operation breakdown: the sampled operations, scaled by
        # (program time of the window) / (program time the sample spans)
        ops = clip(by_name.get(OPS_LINE, []))
        if ops:
            upto = max(hi for _n, _lo, hi in ops)
            spanned = sum(min(hi, upto) - lo for _n, lo, hi in modules
                          if lo < upto)
            scale = sum(hi - lo for _n, lo, hi in modules) / max(spanned, 1.0)
            for n, own in self_seconds(ops).items():
                n = short_name(n)
                op_totals[n] = op_totals.get(n, 0.0) + own * scale
    busy_s = sum(busy) / len(busy) if busy else 0.0

    # a gap is labelled by the most specific annotation (the one with the
    # least time in all) that covers at least half of it
    inner = sorted(((k, v) for k, v in host_ann.items() if k != WINDOW),
                   key=lambda kv: sum(b - a for a, b in kv[1]))
    labelled: dict = {}
    for g_lo, g_hi in gaps(first_dev_intervals or [], w_lo, w_hi):
        best = "in the window, outside every inner bench annotation"
        for name, spans in inner:
            cover = sum(max(0.0, min(b, g_hi) - max(a, g_lo))
                        for a, b in spans)
            if cover >= 0.5 * (g_hi - g_lo):
                best = name
                break
        slot = labelled.setdefault(best, [0.0, 0.0])
        slot[0] += (g_hi - g_lo) / 1e9
        slot[1] = max(slot[1], (g_hi - g_lo) / 1e9)
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return TraceSummary(
        window_s=window_s, busy_s=busy_s, n_device_planes=n_dev,
        programs=programs, device_ops=top(op_totals),
        idle_gaps=top({f"{k} (longest {v[1] * 1e3:.1f} ms)": v[0]
                       for k, v in labelled.items()}),
        annotations={k: [sum(b - a for a, b in v) / 1e9, len(v)]
                     for k, v in host_ann.items()})


def load(path: str):
    """The planes of an `.xplane.pb` file in `reduce_planes`' shape."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        if DEVICE_PLANE.match(p.name):
            planes.append((p.name, [
                (ln.name, _events(ln, shorten=True, limit=OPS_SAMPLE)
                 if ln.name == OPS_LINE else _events(ln))
                for ln in p.lines if ln.name in (OPS_LINE, MODULES_LINE)]))
        else:
            # host threads hold millions of runtime events; only the
            # benchmark's own annotations are read
            planes.append((p.name, [
                (ln.name, _events(ln, ANNOTATION_PREFIX))
                for ln in p.lines]))
    return planes


def reduce_file(path: str) -> TraceSummary:
    # millions of short-lived tuples: the collector's scans would double
    # the time for nothing
    gc.disable()
    try:
        return reduce_planes(load(path))
    finally:
        gc.enable()


def describe(path: str, limit: int = 12) -> str:
    """A by-hand look at a trace: planes, lines, event counts and the
    first names — what `reduce_planes` was written against."""
    out = []
    from jax.profiler import ProfileData
    everything = [(p.name, [(ln.name, _events(ln)) for ln in p.lines])
                  for p in ProfileData.from_file(path).planes]
    for pname, lines in everything:
        out.append(f"PLANE {pname}")
        for lname, events in lines:
            names: dict = {}
            for n, lo, hi in events:
                s = names.setdefault(_strip(n), [0, 0.0])
                s[0] += 1
                s[1] += (hi - lo) / 1e6
            shown = sorted(names.items(), key=lambda kv: -kv[1][1])[:limit]
            out.append(f"  LINE {lname}: {len(events)} events, "
                       f"{len(names)} names")
            for n, (c, ms) in shown:
                out.append(f"      {n[:90]}  x{c}  {ms:.3f} ms")
    return "\n".join(out)
