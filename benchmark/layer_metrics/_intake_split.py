"""The split of the validator's `consensus.intake` spans (consensus/state.py
`_intake`, one a drained run of peer votes) into the spans opened inside
them: what the five `*.validator` readers of the consensus intake share.

The children of a run are the spans of `PARTS` that start inside it:
`consensus.wal` (a WAL append; the end-of-height record's lies inside
`consensus.finalize`), `vote.verify` (a native signature check on a
cache miss; a root the program places by its `height`, found here by
time), `privval.sign` (the node's own vote signed, the signer's state
fsynced), `consensus.intake.flush` (the run's lookups, sign-bytes and one
flush) and `consensus.finalize`. A run and its children are on the
consensus thread, so a child that starts inside a run ends inside it; its
time is clipped to the run all the same. Each part is summed by the
run's `height`, as `intake_ms_per_height` sums the runs; `other` is a
run's time that no child covers (the vote sets, `_precheck`, the
transitions, the interpreter). Per height, Σ parts + other − overlap =
the runs' time, exactly, where overlap is what children cover twice (the
end-of-height WAL record inside finalize)."""

from __future__ import annotations

import bisect

from benchmark.harness import stats

RUN = "consensus.intake"
PARTS = ("consensus.wal", "vote.verify", "privval.sign",
         "consensus.intake.flush", "consensus.finalize")


def _runs(spans):
    return sorted((s for s in spans if s["name"] == RUN and
                   s["t1"] >= s["t0"]), key=lambda s: s["t0"])


def split(spans):
    """{height: {"intake", each name of PARTS, "other", "overlap"}} in ms,
    or None where no run was traced."""
    runs = _runs(spans)
    if not runs:
        return None
    children = sorted((s for s in spans if s["name"] in PARTS and
                       s["t1"] >= s["t0"]), key=lambda s: s["t0"])
    starts = [s["t0"] for s in children]
    out: dict = {}
    for run in runs:
        lo, hi = run["t0"], run["t1"]
        row = out.setdefault(run.get("attrs", {}).get("height"), dict(
            {"intake": 0.0, "other": 0.0, "overlap": 0.0},
            **{name: 0.0 for name in PARTS}))
        inside = []
        for s in children[bisect.bisect_left(starts, lo):
                          bisect.bisect_left(starts, hi)]:
            a, b = s["t0"], min(s["t1"], hi)
            inside.append((a, b))
            row[s["name"]] += (b - a) / 1e6
        covered = stats.union_seconds(inside)
        row["intake"] += (hi - lo) / 1e6
        row["other"] += (hi - lo - covered) / 1e6
        row["overlap"] += (sum(b - a for a, b in inside) - covered) / 1e6
    return out


def outside_ms(spans, name: str) -> float:
    """The time of the spans so named that starts in no run, in ms."""
    runs = [(s["t0"], s["t1"]) for s in _runs(spans)]
    los = [lo for lo, _hi in runs]
    total = 0.0
    for s in spans:
        if s["name"] != name or s["t1"] < s["t0"]:
            continue
        k = bisect.bisect_right(los, s["t0"]) - 1
        if k < 0 or s["t0"] >= runs[k][1]:
            total += (s["t1"] - s["t0"]) / 1e6
    return total


def median_part(spans, name: str):
    """Median over the heights of the part `name` of each height's runs,
    in ms; None where the program opens no such span (one that does not
    split its runs) or traced no run. The `[layer]` line says what lies
    outside the runs."""
    if not any(s["name"] == name for s in spans):
        return None
    by_height = split(spans)
    if by_height is None:
        return None
    heights = len(by_height)
    print(f"[layer] {name}: inside the runs of {heights} heights; "
          f"outside them {outside_ms(spans, name) / heights:.3f} ms a "
          f"height", flush=True)
    return stats.median([row[name] for row in by_height.values()])
