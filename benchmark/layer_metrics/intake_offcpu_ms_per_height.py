"""`intake_offcpu_ms_per_height.*`: Σ (wall time − `cpu_ns`) of the
program's `consensus.intake` spans, summed by their `height`, median over
the heights, in ms. `cpu_ns` is the consensus thread's CPU time over the
run (`libs/timesource.thread_time_ns`), so this is the time the thread
held a run but did not run: interpreter-lock hand-overs to other threads
(the driver's poller among them), fsyncs and the flush's system calls.
Nothing to read where the program sets no `cpu_ns`."""

from benchmark.harness import stats


def read(ctx):
    by_height: dict = {}
    for s in ctx.spans:
        attrs = s.get("attrs", {})
        if s["name"] == "consensus.intake" and "cpu_ns" in attrs:
            h = attrs.get("height")
            by_height[h] = by_height.get(h, 0.0) + (
                s["t1"] - s["t0"] - attrs["cpu_ns"]) / 1e6
    if not by_height:
        return None
    print(f"[layer] consensus.intake off the CPU: runs of "
          f"{len(by_height)} heights", flush=True)
    return stats.median(list(by_height.values()))
