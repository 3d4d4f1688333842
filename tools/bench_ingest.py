"""bench_ingest: A/B the batched admission pipeline against sequential
check_tx on a fixed-latency stub device (the device-RTT model bench.py
--pipeline and the blocksync A/B already use).

Both sides run the REAL IngestPipeline over a real CListMempool; the
only difference is coalescing: the batched side submits a whole wave
and flushes ONE coalesced signature batch, the sequential side flushes
after every tx — the width-1 degenerate case, so both pay identical
per-dispatch device latency and the delta is purely amortization. Tx
signatures are the flash-crowd MAC stub (deterministic, microseconds)
so the measurement isolates the admission path, not pure-Python curve
math.

A third (untimed) burst phase offers 2x the queue cap in one wave so
the shed path actually fires and the reported shed rate is a measured
number, not a zero.

Emits ONE JSON line (bench_light schema): metric/value/unit plus the
sequential baseline, the speedup, p50/p90 admission latency and the
shed rate — the latter read back from IngestMetrics, the same counters
a production node exports.

Usage:
    python tools/bench_ingest.py [--clients 256] [--rounds 6]
        [--latency 0.002] [--trace] [--json]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cometbft_tpu.ingest import IngestPipeline, IngestShed  # noqa: E402
from cometbft_tpu.libs.metrics import Registry  # noqa: E402
from cometbft_tpu.libs.metrics_gen import IngestMetrics  # noqa: E402
from cometbft_tpu.mempool.mempool import CListMempool  # noqa: E402
from cometbft_tpu.pipeline.cache import SigCache  # noqa: E402
from cometbft_tpu.simnet.flash_crowd import (_signed,  # noqa: E402
                                             mac_backend)


class FixedLatencyBackend:
    """Verify backend stub: each DISPATCH costs `latency` seconds (the
    device round trip), verdicts come from the deterministic MAC rule.
    Batched admission pays it once per flush, sequential once per tx."""

    def __init__(self, latency_s: float):
        self.latency_s = latency_s
        self.dispatches = 0

    def __call__(self, lanes):
        self.dispatches += 1
        time.sleep(self.latency_s)
        oks, _ = mac_backend(lanes)
        return oks, "stub-device"


def _gen_txs(n: int, tag: str):
    return [_signed(hashlib.sha256(f"{tag}:{i % 64}".encode()).digest(),
                    f"{tag}{i}=v{i}".encode())
            for i in range(n)]


def _mk_pipeline(backend, cap=1 << 16):
    metrics = IngestMetrics(Registry())
    mp = CListMempool(lambda tx: (0, 1), size=1 << 20,
                      max_txs_bytes=1 << 30, cache_size=1 << 20)
    pipe = IngestPipeline(mp, cache=SigCache(1 << 17), batch=True,
                          max_pending=cap, coalesce_window_s=0.0,
                          verify_backend=backend, metrics=metrics)
    return pipe, metrics


def run(clients: int, rounds: int, latency_s: float,
        trace: bool = False) -> dict:
    from cometbft_tpu import trace as _trace
    if trace:
        _trace.enable(seed=0)
    else:
        _trace.disable()
    n = clients * rounds
    print(f"[bench_ingest] generating {n} MAC-signed txs...",
          file=sys.stderr, flush=True)

    # --- batched side ------------------------------------------------------
    backend = FixedLatencyBackend(latency_s)
    pipe, metrics = _mk_pipeline(backend)
    txs = _gen_txs(n, "b")
    t0 = time.perf_counter()
    for r in range(rounds):
        wave = [pipe.submit(tx) for tx in txs[r * clients:(r + 1) * clients]]
        pipe.flush()
        assert all(t.code == 0 for t in wave)
    batched_dt = time.perf_counter() - t0
    batched_rate = n / batched_dt
    q = pipe.latency_quantiles()

    # --- sequential side (flush per tx: width-1 batches, same stub) --------
    seq_backend = FixedLatencyBackend(latency_s)
    seq_pipe, _seq_metrics = _mk_pipeline(seq_backend)
    # bound the sequential side's wall time (~2s of stub latency is
    # plenty to measure a per-tx-dispatch rate)
    seq_n = n if latency_s <= 0 else max(1, min(n, int(2.0 / latency_s)))
    seq_txs = _gen_txs(seq_n, "s")
    t0 = time.perf_counter()
    for tx in seq_txs:
        ticket = seq_pipe.submit(tx)
        seq_pipe.flush()
        assert ticket.code == 0
    seq_dt = time.perf_counter() - t0
    seq_rate = seq_n / seq_dt

    # --- untimed burst: pin a nonzero shed rate ----------------------------
    cap = max(8, clients // 2)
    burst_backend = FixedLatencyBackend(0.0)
    burst_pipe, burst_metrics = _mk_pipeline(burst_backend, cap=cap)
    offered = 2 * cap
    for tx in _gen_txs(offered, "o"):
        try:
            burst_pipe.submit(tx)
        except IngestShed:
            pass
    burst_pipe.flush()
    shed = burst_metrics.shed.value()

    return {
        "metric": "ingest_admission_throughput",
        "value": round(batched_rate, 1),
        "unit": "tx/s",
        "backend": "cpu-stub",
        "clients": clients,
        "rounds": rounds,
        "stub_latency_s": latency_s,
        "sequential_tx_s": round(seq_rate, 1),
        "speedup_vs_sequential": round(batched_rate / seq_rate, 2),
        "p50_admission_s": round(q["p50"], 6),
        "p90_admission_s": round(q["p90"], 6),
        "batched_dispatches": backend.dispatches,
        "admitted": int(metrics.admitted.value()),
        "burst_offered": offered,
        "burst_shed": int(shed),
        "shed_rate": round(shed / offered, 3),
        "trace": trace,
        "trace_spans": int(_trace.shared_recorder().stats()["recorded"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=256,
                    help="txs per coalescing wave")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--latency", type=float, default=0.002,
                    help="stub device round-trip seconds per dispatch")
    ap.add_argument("--trace", action="store_true",
                    help="enable the flight recorder for the timed run "
                         "(measures tracing-on overhead; default measures "
                         "the disabled no-op path)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    rep = run(args.clients, args.rounds, args.latency, trace=args.trace)
    print(f"[bench_ingest] batched {rep['value']} tx/s vs sequential "
          f"{rep['sequential_tx_s']} tx/s -> "
          f"{rep['speedup_vs_sequential']}x; p90 admission "
          f"{rep['p90_admission_s']}s; shed rate {rep['shed_rate']}",
          file=sys.stderr, flush=True)
    print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
