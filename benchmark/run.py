"""The benchmark's command: one cell, once, in one process, on the
machine it is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero and prints no result line where JAX finds no TPU (or
fewer chips than the cell asks for), or where the program is not beside
it. The last line of standard output is the result object."""

import time

T_START = time.perf_counter()      # set-up counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from benchmark.harness import runner
    from benchmark.harness.device import NoAccelerator
    try:
        out = runner.run_cell(REPO_ROOT, args.workload, args.seed,
                              args.seconds, bool(args.trace), T_START)
    except NoAccelerator as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 3
    runner.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
