"""The device's idle gaps labelled by what the host was doing: the
benchmark's `bench.*` annotations and the program's own spans
(`cometbft_tpu/trace`), on one clock.

The program stamps its spans with `libs/timesource.time_ns()`, nanoseconds
since the Unix epoch; the profiler's events, as `xplane.load` reads them,
count from the start of the profile (looked at on a CPU: `bench.traced`
began 33 µs in). So the spans are moved onto the profiler's clock by an
offset measured at both ends of the window: `time.time_ns()` read on
entering and on leaving the `bench.traced` annotation (`stamped`) against
that annotation's own bounds in the trace. Where either offset exceeds
`OFFSET_LIMIT_NS`, the spans are shifted by their mean; how far the two
disagree is how far a span may be misplaced.

A gap is labelled under `xplane.reduce_planes`' rule: by the candidate
with the least time in all that covers at least half of it. The
program's spans, by name, are candidates beside the annotations; given
none, the labels are `reduce_planes`' own."""

from __future__ import annotations

import bisect
import contextlib
import time
from dataclasses import dataclass, field
from typing import Optional

from . import xplane
from .stats import gaps
from .tracing import profiled

OFFSET_LIMIT_NS = 100_000
NO_LABEL = "in the window, outside every inner bench annotation"


@dataclass
class GapLabels:
    offsets_ns: Optional[tuple]   # (enter, leave): trace minus host stamp
    shift_ns: int                 # added to every span
    idle_s: float
    idle_gaps: list = field(default_factory=list)  # [label, seconds] top 10
    program_share: float = 0.0    # % of idle time a program span labels


@contextlib.contextmanager
def stamped(trace_dir: str, stamps: list):
    """`tracing.profiled`, with `time.time_ns()` appended to `stamps` on
    entering the window's annotation and on leaving it."""
    with profiled(trace_dir):
        stamps.append(time.time_ns())
        try:
            yield
        finally:
            stamps.append(time.time_ns())


class _Candidate:
    """One label's intervals, sorted, for the cover of a gap."""

    def __init__(self, name: str, intervals, program: bool):
        self.name, self.program = name, program
        self.spans = sorted(intervals)
        self.starts = [a for a, _b in self.spans]
        self.longest = max((b - a for a, b in self.spans), default=0.0)
        self.total = sum(b - a for a, b in self.spans)

    def cover(self, lo: float, hi: float) -> float:
        i = bisect.bisect_left(self.starts, lo - self.longest)
        j = bisect.bisect_left(self.starts, hi)
        return sum(max(0.0, min(b, hi) - max(a, lo))
                   for a, b in self.spans[i:j])


def label(planes, spans=(), stamps=None) -> GapLabels:
    """The window's idle gaps (of the first chip, as `reduce_planes`
    finds them) labelled by annotations and `spans` (the recorder's
    dicts, `name`, `t0`, `t1`); `stamps` = (enter, leave) host
    nanoseconds of the window, or None to take the spans as they are."""
    host_ann: dict = {}
    devices = []
    for pname, lines in planes:
        if xplane.DEVICE_PLANE.match(pname):
            devices.append(dict(lines).get(xplane.MODULES_LINE, []))
            continue
        for _lname, events in lines:
            for name, lo, hi in events:
                if name.startswith(xplane.ANNOTATION_PREFIX):
                    host_ann.setdefault(name, []).append((lo, hi))
    if xplane.WINDOW not in host_ann:
        raise ValueError(f"the trace holds no {xplane.WINDOW!r} annotation")
    w_lo = min(a for a, _b in host_ann[xplane.WINDOW])
    w_hi = max(b for _a, b in host_ann[xplane.WINDOW])
    busy = []           # the first chip that ran a program in the window
    for modules in devices:
        busy = [(max(lo, w_lo), min(hi, w_hi)) for _n, lo, hi in modules
                if hi > w_lo and lo < w_hi]
        if busy:
            break

    offsets, shift = None, 0
    if stamps is not None:
        # whole nanoseconds: an epoch in ns is past a double's exact range
        offsets = (int(w_lo) - stamps[0], int(w_hi) - stamps[1])
        if max(abs(o) for o in offsets) > OFFSET_LIMIT_NS:
            shift = (offsets[0] + offsets[1]) // 2
    by_name: dict = {}
    for s in spans:
        if s["t1"] >= s["t0"]:
            by_name.setdefault(s["name"], []).append(
                (s["t0"] + shift, s["t1"] + shift))

    candidates = sorted(
        [_Candidate(k, v, False) for k, v in host_ann.items()
         if k != xplane.WINDOW]
        + [_Candidate(k, v, True) for k, v in by_name.items()],
        key=lambda c: c.total)
    labelled: dict = {}
    idle = by_program = 0.0
    for g_lo, g_hi in gaps(busy, w_lo, w_hi):
        best = next((c for c in candidates
                     if c.cover(g_lo, g_hi) >= 0.5 * (g_hi - g_lo)), None)
        seconds = (g_hi - g_lo) / 1e9
        slot = labelled.setdefault(NO_LABEL if best is None else best.name,
                                   [0.0, 0.0])
        slot[0] += seconds
        slot[1] = max(slot[1], seconds)
        idle += seconds
        if best is not None and best.program:
            by_program += seconds
    top = sorted(labelled.items(), key=lambda kv: -kv[1][0])[:10]
    return GapLabels(
        offsets_ns=offsets, shift_ns=shift, idle_s=idle,
        idle_gaps=[[f"{k} (longest {v[1] * 1e3:.1f} ms)", v[0]]
                   for k, v in top],
        program_share=100.0 * (by_program / idle) if idle else 0.0)
