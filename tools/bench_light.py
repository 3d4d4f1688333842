"""Light-client verification benchmark (the BASELINE.json "light
client: sequential verify of SignedHeaders, 150 validators" config;
reference light/client_benchmark_test.go:24-75 — harness-only there
too, sequential vs bisection over a mock chain).

Generates an N-block chain with a V-validator set, then times a light
client catching up to the tip BOTH ways:
  sequential — verify every header 2..N (adjacent rule each step);
  bisection  — skipping verification with the 1/3-trust rule (static
               valset: one jump).
Reports headers/s for the sequential pass and total wall for each.
Since PR 36 the sequential pass is measured by the benchmark's cell
`light-seq-150.tip-catch-up` (`benchmark/drivers/light_catchup.py`, a
skewed set, a chain no process has verified, judged against a plain
reference); this script's numbers are nobody's record.

--farm A/B (docs/FARM.md): N already-subscribed clients at staggered
trusted heights all verify the tip —
  sequential — N independent LightClients, one after another, the
               shared SigCache RESET between them (each models its own
               process, paying its full bisection);
  farm       — one VerificationFarm, the N requests planned host-side
               and their signature lanes coalesced/deduped into shared
               batches.
Session setup is untimed on both sides: the A/B measures the
steady-state verify workload. In --farm mode --validators defaults to
60 (below types/validation.BATCH_VERIFY_THRESHOLD) so BOTH sides run
the native per-signature CPU path — larger sets would jit the XLA:CPU
RLC bucket mid-measurement (docs/PERF.md "known compile hazard").

Usage (on a TPU host; exits non-zero with no TPU):
    python tools/bench_light.py [--blocks 64] [--validators 150] [--json]
    python tools/bench_light.py --farm
        [--clients 32] [--blocks 64] [--validators 60] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_farm(args, chain, now, backend):
    """The --farm A/B: N coalesced sessions vs N sequential clients."""
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.chain_gen import ChainLightProvider
    from cometbft_tpu.farm import VerificationFarm
    from cometbft_tpu.farm.batcher import FarmBatcher
    from cometbft_tpu.light.client import LightClient, TrustOptions
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.pipeline.cache import SigCache, reset_shared_cache

    tip = chain.max_height()
    n = args.clients
    # staggered trusted heights across the lower half of the chain
    roots = [1 + (i * 3) % max(1, tip // 2) for i in range(n)]

    # --- sequential: N independent clients, each its own "process" ---
    clients = []
    for h0 in roots:
        opts = TrustOptions(period_seconds=30 * 24 * 3600, height=h0,
                            hash=chain.blocks[h0 - 1].hash())
        reset_shared_cache()  # init must not warm the next client
        clients.append(LightClient(
            chain.chain_id, opts, ChainLightProvider(chain), [],
            LightStore(MemDB()), now_fn=lambda: now))
    t = time.monotonic()
    for client in clients:
        reset_shared_cache()  # each client pays its own verification
        lb = client.verify_light_block_at_height(tip)
        assert lb.height == tip
    seq_s = time.monotonic() - t
    reset_shared_cache()

    # --- farm: the same N requests, coalesced ------------------------
    cache = SigCache(1 << 20)
    farm = VerificationFarm(
        chain.chain_id, ChainLightProvider(chain), cache=cache,
        batcher=FarmBatcher(cache=cache, coalesce_window_s=0.0),
        now_fn=lambda: now)
    sessions = [farm.subscribe(h0, chain.blocks[h0 - 1].hash(),
                               30 * 24 * 3600) for h0 in roots]
    farm.batcher.flush()
    t = time.monotonic()
    pendings = [farm.begin_verify(s.session_id, tip) for s in sessions]
    farm.batcher.flush()
    for p in pendings:
        out = farm.finish_verify(p)
        assert out["height"] == tip
    farm_s = time.monotonic() - t

    st = farm.status()
    rec = {
        "metric": "light_farm_ab",
        "clients": n,
        "blocks": args.blocks,
        "validators": args.validators,
        "sequential_seconds": round(seq_s, 4),
        "farm_seconds": round(farm_s, 4),
        "speedup": round(seq_s / farm_s, 2) if farm_s else 0.0,
        "sequential_clients_per_sec": round(n / seq_s, 1) if seq_s
        else 0.0,
        "farm_clients_per_sec": round(n / farm_s, 1) if farm_s else 0.0,
        "farm_batches": st["batches"],
        "farm_max_batch_width": st["max_batch_width"],
        "farm_dedup_batch_hits": st["dedup_batch_hits"],
        "farm_cache_hit_rate": st["cache_hit_rate"],
        "lanes_by_backend": st["lanes_by_backend"],
        "backend": backend,
    }
    if args.json:
        print(json.dumps(rec))
    else:
        print(f"light farm A/B: {n} clients to tip {args.blocks} — "
              f"sequential {seq_s:.3f}s, farm {farm_s:.3f}s "
              f"({rec['speedup']}x; widest batch "
              f"{st['max_batch_width']} lanes, cache hit rate "
              f"{st['cache_hit_rate']})")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--validators", type=int, default=None)
    ap.add_argument("--farm", action="store_true",
                    help="A/B: N coalesced farm clients vs N "
                         "sequential independent clients")
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.validators is None:
        # --farm keeps BOTH sides on the native per-sig path (module
        # docstring); the classic bench keeps its BASELINE config
        args.validators = 60 if args.farm else 150

    # measured on the chip or not at all (exits non-zero with no TPU)
    from bench import require_tpu
    backend = require_tpu()["kind"]

    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.chain_gen import (ChainLightProvider,
                                               generate_chain)
    from cometbft_tpu.light.client import LightClient, TrustOptions
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.types.proto import Timestamp

    t0 = time.monotonic()
    print(f"[bench_light] generating {args.blocks} blocks x "
          f"{args.validators} validators...", file=sys.stderr, flush=True)
    chain = generate_chain(n_blocks=args.blocks,
                           n_validators=args.validators)
    print(f"[bench_light] chain in {time.monotonic() - t0:.1f}s",
          file=sys.stderr, flush=True)

    now = Timestamp(1_700_000_000 + chain.max_height() + 5, 0)
    if args.farm:
        return bench_farm(args, chain, now, backend)
    opts = TrustOptions(period_seconds=30 * 24 * 3600, height=1,
                        hash=chain.blocks[0].hash())

    def catchup(sequential: bool) -> float:
        client = LightClient(chain.chain_id, opts,
                             ChainLightProvider(chain), [],
                             LightStore(MemDB()), sequential=sequential,
                             now_fn=lambda: now)
        t = time.monotonic()
        lb = client.verify_light_block_at_height(chain.max_height())
        dt = time.monotonic() - t
        assert lb.height == chain.max_height()
        return dt

    seq_s = catchup(sequential=True)
    # first bisection may pay a one-time jit of the 64-lane RLC bucket
    # (minutes on XLA:CPU, docs/PERF.md); the steady-state number is
    # the warm second pass
    cold_bis_s = catchup(sequential=False)
    bis_s = catchup(sequential=False)
    headers = args.blocks - 1  # sequential verifies 2..N

    rec = {
        "metric": "light_client_verify",
        "sequential_headers_per_sec": round(headers / seq_s, 1),
        "sequential_seconds": round(seq_s, 3),
        "bisection_seconds": round(bis_s, 3),
        "bisection_cold_seconds": round(cold_bis_s, 3),
        "unit": "headers/s",
        "blocks": args.blocks,
        "validators": args.validators,
        "sigs_per_commit": args.validators,
        "backend": backend,
    }
    if args.json:
        print(json.dumps(rec))
    else:
        print(f"light client: sequential {rec['sequential_headers_per_sec']}"
              f" headers/s ({seq_s:.2f}s for {headers} headers x "
              f"{args.validators} sigs), bisection to tip {bis_s:.3f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
