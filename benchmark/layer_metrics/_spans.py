"""Median duration of the program's spans of one name, in ms."""

from benchmark.harness import stats


def median_ms(spans, name: str):
    durations = [(s["t1"] - s["t0"]) / 1e6 for s in spans
                 if s["name"] == name and s["t1"] >= s["t0"]]
    if not durations:
        return None
    print(f"[layer] {name}: {len(durations)} spans", flush=True)
    return stats.median(durations)
