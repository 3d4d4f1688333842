"""`intake_ms_per_height.*`: the program's `consensus.intake` spans
(consensus/state.py `_intake`: one a drained run of peer votes, from the
lookups and the flush through the handling of the run's last vote, the
transitions those votes cause included; `consensus.finalize` is inside
the run that crosses +2/3) summed by their `height`, median over the
heights, in ms, host clock. A height's latency minus this is the
proposal, the block parts and the waits between bursts. Nothing to read
where the program opens no such span (before PR 34)."""

from benchmark.harness import stats


def read(ctx):
    by_height: dict = {}
    for s in ctx.spans:
        if s["name"] == "consensus.intake" and s["t1"] >= s["t0"]:
            h = s.get("attrs", {}).get("height")
            by_height[h] = by_height.get(h, 0.0) + (s["t1"] - s["t0"]) / 1e6
    if not by_height:
        return None
    print(f"[layer] consensus.intake: spans of {len(by_height)} heights",
          flush=True)
    return stats.median(list(by_height.values()))
