"""One traced run of a cell, made as `run.py --trace 1` makes it, whose
device idle gaps are labelled by the program's spans as well as by the
benchmark's annotations (`harness/gap_labels.py`).

    python3 benchmark/tools/trace_gaps.py --workload <cell> --seed <n>

It runs `runner.run_cell` with three things kept on the way that the run
itself throws away: `time.time_ns()` on entering and on leaving the
window's annotation, the program's spans with the recorder's count of
spans dropped, and the planes of the trace. Prints the run's lines, then
a `[gaps]` line (the two clock offsets, the shift applied, the share of
the window's idle time that a program span labels, `trace_spans_dropped`)
and the gaps' labels, then the result object as the last line. Not part
of a benchmark run. Needs the TPU, like the command: on a CPU no device
is traced."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)


def traced_run(root: str, workload: str, seed: int, seconds: float,
               t_start: float, **run_kw):
    """`runner.run_cell(..., trace=True)`, and the `GapLabels` of its
    trace (None where no device was traced), the spans dropped."""
    from benchmark.harness import gap_labels, runner, xplane
    from cometbft_tpu import trace as program_trace
    stamps, held = [], {"spans": [], "dropped": None, "labels": None}
    recorder = program_trace.shared_recorder()
    snapshot = recorder.snapshot

    def keep():
        held["spans"] = snapshot()
        held["dropped"] = recorder.stats()["evicted"]
        return held["spans"]

    class Reduction:
        find_xplane = staticmethod(xplane.find_xplane)

        @staticmethod
        def reduce_file(path):
            gc.disable()
            try:
                planes = xplane.load(path)
                held["labels"] = gap_labels.label(planes, held["spans"],
                                                  stamps)
                return xplane.reduce_planes(planes)
            finally:
                gc.enable()

    saved = runner.profiled, runner.xplane
    runner.profiled = lambda trace_dir: gap_labels.stamped(trace_dir, stamps)
    runner.xplane = Reduction
    recorder.snapshot = keep
    try:
        out = runner.run_cell(root, workload, seed, seconds, True, t_start,
                              **run_kw)
    finally:
        runner.profiled, runner.xplane = saved
        del recorder.snapshot
    return out, held["labels"], held["dropped"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    from benchmark.harness import runner
    out, labels, dropped = traced_run(REPO_ROOT, args.workload, args.seed,
                                      args.seconds, T_START)
    if labels is not None:
        enter, leave = labels.offsets_ns
        print(f"[gaps] clock offsets_ns enter {enter} leave {leave} (they "
              f"differ by {(leave - enter) / 1e3:.1f} us) shift_ns "
              f"{labels.shift_ns} idle {labels.idle_s:.4f}s program-"
              f"labelled {labels.program_share:.2f}% trace_spans_dropped "
              f"{dropped}", flush=True)
        print(f"[gaps] {json.dumps(labels.idle_gaps)}", flush=True)
    runner.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
