"""Several seeds of one cell in ONE process, so that the minutes of
kernel tracing are paid once: the program as it is (`--plant none`), or
with one of the driver's `PLANTS` put under the timed path, which has to
come out as not correct.

    python3 benchmark/tools/control_runs.py --workload <cell> \\
        --seeds 11,12,13 --seconds 5 --plant accept_all

Not part of a benchmark run: the readings it gives are the ones `PERF.md`
sets the comparison's limits from. Needs the TPU, like the command."""

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--plant", default="none")
    args = ap.parse_args()
    from benchmark.harness import runner
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = runner.run_cell(
            REPO_ROOT, args.workload, seed, args.seconds, False,
            time.perf_counter(),
            plant="" if args.plant == "none" else args.plant)
        over = {n: row["value"] for n, row in out["checks"].items()
                if row["value"] > row["limit"]}
        rows.append({"seed": seed, "correct": out["correct"], "over": over,
                     "metrics": {k: v["value"]
                                 for k, v in out["metrics"].items()}})
        print(f"[control] plant={args.plant} {json.dumps(rows[-1])}",
              flush=True)
    print(json.dumps({"workload": args.workload, "plant": args.plant,
                      "runs": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
