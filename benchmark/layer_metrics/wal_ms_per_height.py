"""`wal_ms_per_height.*`: the program's `consensus.wal` spans (every WAL
append of consensus/state.py `ConsensusState`: a peer's message written
and flushed, the node's own vote and the end-of-height record also
fsynced, `sync` 1) inside the height's `consensus.intake` runs, summed by
the run's `height`, median over the heights, in ms, host clock
(`_intake_split.py`). The `[layer]` line adds the fsynced part. Nothing
to read where the program opens no such span."""

from benchmark.harness import stats
from benchmark.layer_metrics import _intake_split

NAME = "consensus.wal"


def read(ctx):
    value = _intake_split.median_part(ctx.spans, NAME)
    if value is not None:
        synced = _intake_split.split([
            s for s in ctx.spans if s["name"] != NAME
            or s.get("attrs", {}).get("sync") == 1])
        print(f"[layer] {NAME}: of which sync=1 "
              f"{stats.median([r[NAME] for r in synced.values()]):.3f} ms "
              f"a height (median)", flush=True)
    return value
