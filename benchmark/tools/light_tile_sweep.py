"""The sweep behind `light.client.TILE_CHUNKS`: one sequential light
client's catch-up at several tile sizes, in ONE process (the kernels are
warmed once), each run on objects of its own and an empty sigcache.

    python3 benchmark/tools/light_tile_sweep.py --ks 1,2,4,8,16 \\
        --headers 1536 --reps 2 --seed 2147483647

The chain is the cell's (`light-seq-150`'s set, `light_chain`'s headers)
at `--headers`; every run unpickles it anew, so no memo of an earlier
run (header hashes, sign-bytes templates, set hashes) rides into the
next, and the order of the tile sizes is turned from one repetition to
the next. Prints a line a run and the medians a tile size. Not part of a
benchmark run: the constant in the program is written from its output,
with the sweep beside it. Needs the TPU, like the command."""

import argparse
import json
import os
import pickle
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ks", default="1,2,4,8,16")
    ap.add_argument("--headers", type=int, default=1536)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=2**31 - 1)
    ap.add_argument("--cpu-width", type=int, default=0,
                    help="rehearsal on a CPU: force this lane width")
    args = ap.parse_args()
    from benchmark.harness import device as devmod
    from benchmark.harness import stats
    from benchmark.harness.manifest import Manifest
    manifest = Manifest(REPO_ROOT)
    cell = manifest.cell("light-seq-150.tip-catch-up")
    if not args.cpu_width:
        print(f"[device] {devmod.require_tpu(cell.chips)}", flush=True)
    driver = manifest.load_module("drivers", cell.config["driver"])
    boot = driver.warm()
    from cometbft_tpu.light import client as light_client
    from cometbft_tpu.pipeline.cache import reset_shared_cache
    if args.cpu_width:
        light_client.kernel_width = lambda: args.cpu_width
    gen = manifest.load_module("generators", cell.traffic["generator"])
    blob = pickle.dumps(gen.build_chain(
        f"sweep-{args.seed}", args.headers, f"{args.seed}/sweep",
        cell.config), protocol=pickle.HIGHEST_PROTOCOL)
    ks = [int(k) for k in args.ks.split(",")]
    # once through, unmeasured: lazy imports, the device's first transfers
    rows = {k: [] for k in ks}
    for rep in range(-1, args.reps):
        for k in (ks if rep % 2 == 0 else ks[::-1]):
            light_client.TILE_CHUNKS = k
            reset_shared_cache()
            node = driver.client_of(cell.config, pickle.loads(blob))
            elapsed, raised, c = driver._catch_up(node)
            if raised is not None or node["store"].latest().height != \
                    args.headers:
                print(f"[sweep] k={k} FAILED: {raised!r}", flush=True)
                return 1
            if rep < 0:
                break
            rate = stats.rate(args.headers - 1, elapsed)
            rows[k].append(rate)
            print(f"[sweep] k={k} rep={rep} headers_per_s={rate:.2f} "
                  f"tiles={c['light_tiles']} flushes={c['light_flushes']} "
                  f"device_lanes={c['light_device_lanes']} "
                  f"native_lanes={c['light_native_lanes']} "
                  f"dispatches={c['dispatches']}", flush=True)
    print(json.dumps({"bucket": boot["batch"], "headers": args.headers,
                      "headers_per_s_median": {
                          k: stats.median(v) for k, v in rows.items()},
                      "headers_per_s": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
