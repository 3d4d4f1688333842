"""`intake_other_ms_per_height.*`: of each `consensus.intake` run, the
time that none of the spans opened inside it covers (`consensus.wal`,
`vote.verify`, `privval.sign`, `consensus.intake.flush`,
`consensus.finalize`: the union of their intervals, so the end-of-height
WAL record inside finalize counts once), summed by the run's `height`,
median over the heights, in ms, host clock: the run's self time, i.e.
the vote sets, `_precheck`, the state transitions and the interpreter
(`_intake_split.py`). The `[layer]` line prints the whole split of the
median height and how far the parts are from the runs at the worst
height. Nothing to read where the program does not split its runs (no
`consensus.intake.flush` span)."""

from benchmark.harness import stats
from benchmark.layer_metrics import _intake_split

SHORT = {"consensus.wal": "wal", "vote.verify": "native verify",
         "privval.sign": "sign", "consensus.intake.flush": "flush",
         "consensus.finalize": "finalize"}


def read(ctx):
    if not any(s["name"] == "consensus.intake.flush" for s in ctx.spans):
        return None
    by_height = _intake_split.split(ctx.spans)
    if by_height is None:
        return None
    rows = list(by_height.values())
    worst = max(abs(sum(r[n] for n in _intake_split.PARTS) + r["other"]
                    - r["intake"]) / r["intake"] for r in rows
                if r["intake"] > 0)
    med = {k: stats.median([r[k] for r in rows])
           for k in ("intake", "other", "overlap", *_intake_split.PARTS)}
    parts = ", ".join(f"{SHORT[n]} {med[n]:.3f}"
                      for n in _intake_split.PARTS)
    print(f"[layer] consensus.intake split, medians over {len(rows)} "
          f"heights, ms: runs {med['intake']:.3f} = {parts}, other "
          f"{med['other']:.3f}, less {med['overlap']:.3f} covered twice; "
          f"the parts and other sum to the runs within "
          f"{100 * worst:.3f} % at every height", flush=True)
    return med["other"]
