"""Headline benchmark: batched ed25519 signature verification throughput.

Measures the north-star metric (BASELINE.json): verified sigs/sec on one
chip, cross-block tiling — a (commits x validators) tile of real
signatures, matching blocksync catch-up with a 200-validator set
(reference internal/blocksync/reactor.go:483, baseline ~78k sigs/s CPU
batch-1024, docs/references/rfc/tendermint-core/rfc-018:187-189).

Prints ONE JSON line on stdout: {"metric", "value", "unit",
"vs_baseline", "device"}; all diagnostics/progress go to stderr. The
kernel and mesh modes measure on the chip or not at all: with no TPU
they exit non-zero (a CPU number is never written under a device
metric's name). ONE process measures — it owns the chip from its first
JAX call until it exits — so there is no probe child, no measure child
and no retry on another kernel or a smaller batch: what fails, fails.

Env knobs: BENCH_BATCH (default 8192), BENCH_ITERS (default 4),
BENCH_KERNEL=xla|pallas (default: what the node dispatches to).
"""

import json
import os
import sys
import time

# XLA's HLO passes recurse deeply on the RLC kernel graph: at the
# default 8MB thread stack the batch-4096 compile OVERFLOWS (observed:
# SIGSEGV at the stack guard, dmesg "error 6" inside libjax_common).
# pthread stacks size themselves from RLIMIT_STACK at thread creation,
# so raise it before anything builds a compiler thread pool.
try:
    import resource
    _soft, _hard = resource.getrlimit(resource.RLIMIT_STACK)
    _want = 512 * 1024 * 1024
    if _hard != resource.RLIM_INFINITY:
        _want = min(_want, _hard)
    if _soft != resource.RLIM_INFINITY and _soft < _want:
        resource.setrlimit(resource.RLIMIT_STACK, (_want, _hard))
except (ImportError, ValueError, OSError):  # pragma: no cover
    pass

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from cometbft_tpu.libs.jax_cache import enable_compile_cache  # noqa: E402

BASELINE_SIGS_PER_SEC = 78_000.0  # CPU curve25519-voi, 1024-sig batches

def require_tpu():
    """The device this process measures on — a TPU, or exit non-zero.
    Initialises the backend: the caller owns the chip from here on."""
    enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU here (JAX backend is {dev.platform!r}); a "
              "device metric is measured on the chip or not at all",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _log(msg):
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


def _gen_signatures(n, n_validators=200, msg_len=122, seed=7):
    """n signatures from a 200-key validator set over vote-sized messages.

    Uses the fast C signer when available (signature generation is host
    tooling, not the measured path), falling back to the big-int oracle.
    """
    import numpy as np
    rng = np.random.default_rng(seed)
    msgs = [rng.integers(0, 256, size=msg_len, dtype=np.uint8).tobytes()
            for _ in range(n)]
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey)
        from cryptography.hazmat.primitives import serialization
        keys = [Ed25519PrivateKey.generate() for _ in range(n_validators)]
        raw = lambda k: k.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw)
        pubs_by_val = [raw(k) for k in keys]
        pubs, sigs = [], []
        for i, m in enumerate(msgs):
            v = i % n_validators
            pubs.append(pubs_by_val[v])
            sigs.append(keys[v].sign(m))
    except ImportError:  # pragma: no cover
        from cometbft_tpu.crypto import ref_ed25519 as ref
        seeds = [bytes([int(b) for b in rng.integers(0, 256, 32)])
                 for _ in range(n_validators)]
        pubs_by_val = [ref.pubkey_from_seed(s) for s in seeds]
        pubs, sigs = [], []
        for i, m in enumerate(msgs):
            v = i % n_validators
            pubs.append(pubs_by_val[v])
            sigs.append(ref.sign(seeds[v], m))
    return pubs, msgs, sigs


def measure(batch, iters):
    """Time the RLC kernel on the already-initialized default backend.

    BENCH_KERNEL=xla|pallas picks the point-stage implementation;
    default: pallas on TPU backends, xla elsewhere (the pallas mosaic
    kernels target the chip). Returns (sigs_per_sec, compile_secs)."""
    import numpy as np
    import jax
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.ops.ed25519 import (
        prepare_batch, make_rlc_coefficients)

    which = os.environ.get("BENCH_KERNEL") or \
        ("pallas" if e5.use_pallas_rlc() else "xla")
    kernel = (e5.verify_rlc_kernel_pallas if which == "pallas"
              else e5.verify_rlc_kernel)
    _log(f"kernel: {which}")

    _log(f"generating {batch} signatures (200-validator set)...")
    pubs, msgs, sigs = _gen_signatures(batch)
    _log("packing batch...")
    pub, sig, hb, hn, ok_mask = prepare_batch(pubs, msgs, sigs, batch, 128)
    assert ok_mask.all()
    dev = jax.devices()[0]
    pub, sig, hb, hn = (jax.device_put(x, dev) for x in (pub, sig, hb, hn))

    # the production fast path: one random-linear-combination equation per
    # tile (fresh coefficients every flush, as the verifier requires)
    _log("compiling + warming RLC kernel (first compile can take "
         "tens of seconds; persistent cache is on for TPU)...")
    tc = time.monotonic()
    z = make_rlc_coefficients(batch)
    bok, sok = kernel(pub, sig, hb, hn, z)  # compile + warm
    compile_secs = time.monotonic() - tc
    assert bool(bok) and np.asarray(sok).all(), "warmup verification failed"
    _log(f"warm in {compile_secs:.1f}s; timing {iters} iterations...")

    t0 = time.perf_counter()
    for i in range(iters):
        z = make_rlc_coefficients(batch)
        bok, out = kernel(pub, sig, hb, hn, z)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    assert bool(bok)
    _log(f"{iters} x {batch} sigs in {dt:.3f}s")
    return batch * iters / dt, compile_secs, which


def _measure_mode(batch: int, iters: int) -> int:
    """Compile, measure, print ONE JSON line — in this process, on the
    TPU it owns."""
    device = require_tpu()
    _log(f"measure[{batch}]: device: {device}")
    from cometbft_tpu.libs.jax_cache import ledger
    sigs_per_sec, compile_secs, which = measure(batch, iters)
    warm_before = ledger().seen(f"rlc-{which}", batch)
    ledger().record(f"rlc-{which}", batch, compile_secs)
    rec = {
        "metric": "ed25519_batch_verify_throughput",
        "value": round(sigs_per_sec, 1),
        "unit": "sigs/s",
        "vs_baseline": round(sigs_per_sec / BASELINE_SIGS_PER_SEC, 3),
        "batch": batch,
        "device": device,
        # which point-stage implementation produced the number
        "kernel": which,
        # compile-cache attribution (ledger keyed kernel|bucket):
        # whether this (kernel, batch) was previously recorded warm,
        # and what the compile actually cost this run
        "compile_s": round(compile_secs, 2),
        "compile_cache": {"seen_before": warm_before,
                          **ledger().attribution()},
    }
    print(json.dumps(rec), flush=True)
    return 0


def _pipeline_mode() -> int:
    """`bench.py --pipeline`: END-TO-END catch-up sigs/s (the actual
    north-star metric) over a generated chain, A/B sync-vs-pipelined.

    The device is a fixed-latency stub (pipeline/scheduler.
    FixedLatencyBackend), so this is a HOST-side A/B of the scheduler,
    not a device measurement: the stub answers all-true `latency`
    seconds after each dispatch. The synchronous
    baseline is the pipeline_depth=1 degenerate case over the SAME stub,
    so both sides pay identical per-tile device latency and the delta is
    purely the overlap. Emits ONE JSON line with the kernel-bench schema
    (metric/value/unit/vs_baseline + diagnostics keys).

    Env knobs: BENCH_PIPE_BLOCKS (96), BENCH_PIPE_VALS (32),
    BENCH_PIPE_TILE (8), BENCH_PIPE_DEPTH (4),
    BENCH_PIPE_LATENCY (s, 0.15 — the measured r4 device time for a
    production 32-block x 200-validator tile: 6400 lanes at the
    42.7k sigs/s on record from 2026-07, docs/PERF.md; applied as a
    fixed per-dispatch cost).
    """
    n_blocks = int(os.environ.get("BENCH_PIPE_BLOCKS", "96"))
    n_vals = int(os.environ.get("BENCH_PIPE_VALS", "32"))
    tile = int(os.environ.get("BENCH_PIPE_TILE", "8"))
    depth = int(os.environ.get("BENCH_PIPE_DEPTH", "4"))
    latency = float(os.environ.get("BENCH_PIPE_LATENCY", "0.15"))

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.engine.chain_gen import (LocalChainSource,
                                               generate_chain)
    from cometbft_tpu.pipeline.scheduler import (FixedLatencyBackend,
                                                 PipelinedBlocksync)
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore

    _log(f"generating {n_blocks}-block chain, {n_vals} validators...")
    chain = generate_chain(n_blocks=n_blocks, n_validators=n_vals,
                           txs_per_block=1)
    n_sigs = n_blocks * n_vals

    def run_depth(k: int) -> float:
        app = KVStoreApplication()
        app.init_chain(chain.chain_id, 1, [], b"")
        db = MemDB()
        store = BlockStore(db)
        executor = BlockExecutor(app, state_store=StateStore(db),
                                 block_store=store)
        state = State.from_genesis(chain.genesis)
        reactor = BlocksyncReactor(
            executor, store, LocalChainSource(chain), chain.chain_id,
            tile_size=tile, batch_size=0)
        pipe = PipelinedBlocksync(
            reactor, depth=k, backend=FixedLatencyBackend(latency))
        t0 = time.perf_counter()
        try:
            while state.last_block_height < n_blocks:
                state = pipe.run(state, n_blocks)
        finally:
            pipe.close()
        dt = time.perf_counter() - t0
        assert state.last_block_height == n_blocks
        assert reactor.stats.blocks_applied == n_blocks
        _log(f"depth={k}: {n_sigs} sigs in {dt:.3f}s "
             f"({n_sigs / dt:,.0f} sigs/s)")
        return n_sigs / dt

    sync_rate = run_depth(1)
    pipe_rate = run_depth(depth)
    rec = {
        "metric": "blocksync_catchup_throughput",
        "value": round(pipe_rate, 1),
        "unit": "sigs/s",
        "vs_baseline": round(pipe_rate / BASELINE_SIGS_PER_SEC, 3),
        "backend": "cpu-stub",
        "depth": depth,
        "tile_size": tile,
        "stub_latency_s": latency,
        "sync_sigs_per_sec": round(sync_rate, 1),
        "speedup_vs_sync": round(pipe_rate / sync_rate, 2),
        "blocks": n_blocks,
        "validators": n_vals,
    }
    print(json.dumps(rec), flush=True)
    return 0


def _aggsig_mode(miller_backend: str = "fast") -> int:
    """`bench.py --aggsig [--miller-backend oracle|fast|kernel]`:
    pick the Miller-loop implementation for the BLS legs, restore
    process state afterwards, and ALWAYS emit the one JSON line —
    a kernel failure degrades to the CPU path inside the
    supervisor-attached PairingChecker (probe/backoff discipline,
    device/health), and even a setup crash still prints an error
    record so sweep harnesses never lose the datapoint.

      oracle — the slow per-pair r-loop Miller product (pre-PR
               baseline, kept as the correctness oracle);
      fast   — the host optimal-ate loop (default production path);
      kernel — the fused ops/bls12 Miller + final-exp device call
               (COMETBFT_TPU_AGGSIG_KERNEL=1 semantics; on XLA:CPU
               this pays the multi-minute scan compile the ledger
               attributes under bls-miller@bucket|platform)."""
    import cometbft_tpu.crypto.bls12381 as bls_mod
    from cometbft_tpu.aggsig.verify import (ENV_KERNEL,
                                            reset_shared_finalexp)
    if miller_backend not in ("oracle", "fast", "kernel"):
        _log(f"unknown --miller-backend {miller_backend!r} "
             "(expected oracle|fast|kernel)")
        return 2
    restore = (bls_mod.miller_product, bls_mod.miller_loop)
    if miller_backend == "oracle":
        bls_mod.miller_product = bls_mod.miller_product_slow
        bls_mod.miller_loop = bls_mod.miller_loop_slow
    elif miller_backend == "kernel":
        os.environ[ENV_KERNEL] = "1"
    reset_shared_finalexp()     # re-decide the backend under the knob
    try:
        return _aggsig_bench(miller_backend)
    except Exception as exc:  # noqa: BLE001 — the JSON line must land
        print(json.dumps({"metric": "aggsig_catchup_commit_verify",
                          "miller_backend": miller_backend,
                          "error": f"{type(exc).__name__}: {exc}"}),
              flush=True)
        return 1
    finally:
        bls_mod.miller_product, bls_mod.miller_loop = restore
        if miller_backend == "kernel":
            os.environ.pop(ENV_KERNEL, None)
        reset_shared_finalexp()


def _aggsig_bench(miller_backend: str) -> int:
    """200-validator blocksync catch-up A/B —
    ed25519 batch verification vs the BLS aggregate-commit fast path
    (ROADMAP item 2, docs/AGGSIG.md).

    Three measured sides over same-shape generated chains:
      * ed25519: the existing native catch-up path (the production
        baseline these chains run today);
      * BLS aggregate: AggregatedCommit seals through the real
        blocksync marshal/settle route — per commit the pairing work
        is O(1) (two Miller loops + ONE final exponentiation when the
        quorum is co-timed), read off crypto/bls12381.OP_COUNTERS;
      * BLS per-signature: a measured sample of individual verifies,
        projected to the full set — the O(n) reference the aggregate
        replaces (2n Miller loops + n final exponentiations).

    One-time costs are attributed separately: proof-of-possession
    admission (amortized over each key's lifetime) and chain
    generation. Emits ONE JSON line (kernel-bench schema) including
    pairings-per-commit and the compile-cache ledger attribution.

    Env knobs: BENCH_AGG_VALS (200), BENCH_AGG_BLOCKS (4),
    BENCH_AGG_SAMPLE (4, per-sig sample size)."""
    n_vals = int(os.environ.get("BENCH_AGG_VALS", "200"))
    n_blocks = int(os.environ.get("BENCH_AGG_BLOCKS", "4"))
    sample = max(1, min(int(os.environ.get("BENCH_AGG_SAMPLE", "4")),
                        n_vals))

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.aggsig.aggregate import (register_pops_batch,
                                               reset_pop_registry)
    from cometbft_tpu.aggsig.verify import shared_pairing
    from cometbft_tpu.crypto.bls12381 import OP_COUNTERS
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.engine.chain_gen import (LocalChainSource,
                                               generate_chain)
    from cometbft_tpu.libs.jax_cache import ledger
    from cometbft_tpu.pipeline.cache import reset_shared_cache
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore
    from cometbft_tpu.types.agg_commit import AggregatedCommit

    pc = shared_pairing()
    if pc.backend == "kernel" and pc.supervisor is None:
        # probe/backoff supervision for the device path: a tripping or
        # corrupt kernel degrades every checker to CPU and the trip is
        # visible in the emitted record instead of killing the bench
        from cometbft_tpu.device.health import DeviceSupervisor
        sup = DeviceSupervisor()
        pc.supervisor = sup
        pc.finalexp.supervisor = sup
    _log(f"miller backend: {miller_backend} "
         f"(pairing checker backend: {pc.backend})")

    def catchup(chain) -> float:
        app = KVStoreApplication()
        app.init_chain(chain.chain_id, 1, [], b"")
        db = MemDB()
        store = BlockStore(db)
        executor = BlockExecutor(app, state_store=StateStore(db),
                                 block_store=store)
        state = State.from_genesis(chain.genesis)
        reactor = BlocksyncReactor(
            executor, store, LocalChainSource(chain), chain.chain_id,
            tile_size=8, batch_size=0)
        reset_shared_cache()
        t0 = time.perf_counter()
        state = reactor.sync(state)
        dt = time.perf_counter() - t0
        assert state.last_block_height == chain.max_height()
        return dt

    _log(f"generating {n_blocks}-block ed25519 chain, "
         f"{n_vals} validators...")
    ed_chain = generate_chain(n_blocks=n_blocks, n_validators=n_vals,
                              txs_per_block=1)
    ed_s = catchup(ed_chain)
    _log(f"ed25519 catch-up: {n_blocks * n_vals} sigs in {ed_s:.2f}s")

    _log(f"generating {n_blocks}-block BLS chain (aggregated seals)...")
    t0 = time.perf_counter()
    bls_chain = generate_chain(
        n_blocks=n_blocks, n_validators=n_vals, txs_per_block=1,
        key_type="bls12_381", aggregate=True)
    gen_s = time.perf_counter() - t0
    for c in bls_chain.seen_commits:
        assert isinstance(c, AggregatedCommit)

    # one-time PoP admission cost (batched RLC multi-pairing),
    # measured against a cleared registry
    reset_pop_registry()
    t0 = time.perf_counter()
    assert register_pops_batch(bls_chain.genesis.bls_pops)
    pop_s = time.perf_counter() - t0
    _log(f"PoP admission: {n_vals} keys in {pop_s:.2f}s "
         f"({pop_s / n_vals * 1000:.0f} ms/key, one-time)")

    c0 = dict(OP_COUNTERS)
    agg_s = catchup(bls_chain)
    millers = OP_COUNTERS["miller_loops"] - c0["miller_loops"]
    fexps = OP_COUNTERS["final_exps"] - c0["final_exps"]
    _log(f"BLS aggregate catch-up: {n_blocks} commits "
         f"({n_vals} signers each) in {agg_s:.2f}s — "
         f"{millers} Miller loops, {fexps} final exps total")

    # per-signature BLS reference, measured on a sample
    from cometbft_tpu.types.vote import Vote, PRECOMMIT_TYPE
    from cometbft_tpu.types.proto import Timestamp
    vals0 = bls_chain.valsets[0]
    t0 = time.perf_counter()
    checked = 0
    for i in range(sample):
        val = vals0.validators[i]
        key = bls_chain.keys[val.address]
        vote = Vote(type_=PRECOMMIT_TYPE, height=1, round=0,
                    block_id=bls_chain.block_ids[0],
                    timestamp=Timestamp(1_700_000_001, 1_000_000 + i),
                    validator_address=val.address, validator_index=i)
        sig = key.sign(vote.sign_bytes(bls_chain.chain_id))
        t_sig = time.perf_counter()
        assert val.pub_key.verify_signature(
            vote.sign_bytes(bls_chain.chain_id), sig)
        checked += 1
        del t_sig
    per_sig_s = (time.perf_counter() - t0) / checked
    projected_commit_s = per_sig_s * n_vals

    agg_commit_s = agg_s / n_blocks
    rec = {
        "metric": "aggsig_catchup_commit_verify",
        "value": round(agg_commit_s, 3),
        "unit": "s/commit",
        "vs_baseline": round(projected_commit_s / agg_commit_s, 1),
        "backend": pc.backend,
        "miller_backend": miller_backend,
        "kernel_quarantined": pc.quarantined,
        "validators": n_vals,
        "blocks": n_blocks,
        "pairings_per_commit": {
            "aggregate_miller_loops": round(millers / n_blocks, 2),
            "aggregate_final_exps": round(fexps / n_blocks, 2),
            "per_sig_miller_loops": 2 * n_vals,
            "per_sig_final_exps": n_vals,
        },
        "bls_aggregate_catchup_s": round(agg_s, 3),
        "bls_per_sig_s_measured": round(per_sig_s, 3),
        "bls_per_sig_commit_s_projected": round(projected_commit_s, 1),
        "speedup_vs_per_sig": round(projected_commit_s / agg_commit_s, 1),
        "ed25519_catchup_s": round(ed_s, 3),
        "ed25519_sigs_per_sec": round(n_blocks * n_vals / ed_s, 1),
        "pop_admission_s_total": round(pop_s, 2),
        "chain_gen_s": round(gen_s, 2),
        "compile_cache": ledger().attribution(),
    }
    print(json.dumps(rec), flush=True)
    return 0


def _sealsync_mode() -> int:
    """`bench.py --sealsync`: seal-adoption vs full-blocksync catch-up
    A/B (docs/SEALSYNC.md). ALWAYS emits the one JSON line — even a
    setup crash prints an error record so sweep harnesses never lose
    the datapoint."""
    try:
        return _sealsync_bench()
    except Exception as exc:  # noqa: BLE001 — the JSON line must land
        print(json.dumps({"metric": "sealsync_time_to_decided",
                          "error": f"{type(exc).__name__}: {exc}"}),
              flush=True)
        return 1


def _sealsync_bench() -> int:
    """Wide-valset catch-up A/B — aggregate-seal adoption vs full
    blocksync over the SAME generated BLS chain (ROADMAP item 2,
    docs/SEALSYNC.md).

    Side A (sealsync): SealAdopter walks the seal chain, pairs only
    the skip-schedule pivots, and installs every decided height as an
    adopted-seal record — time-to-decided, no block bodies. Then the
    body BACKFILL leg: a real BlocksyncReactor catch-up riding the
    adopter's SigCache, where every adopted commit must be a
    whole-aggregate cache hit (zero extra pairings).

    Side B (baseline): plain full blocksync from scratch — one
    aggregate pairing per commit plus body execution, the path a
    laggard pays today.

    Adoption runs FIRST so any one-time compile/warmup lands on side
    A's clock — the reported speedup is conservative. Emits ONE JSON
    line (kernel-bench schema) including per-side pairing-op deltas
    and the compile-cache ledger attribution.

    Env knobs: BENCH_SEAL_VALS (200), BENCH_SEAL_BLOCKS (8),
    BENCH_SEAL_SKIP (4, pivot cadence)."""
    n_vals = int(os.environ.get("BENCH_SEAL_VALS", "200"))
    n_blocks = int(os.environ.get("BENCH_SEAL_BLOCKS", "8"))
    max_skip = int(os.environ.get("BENCH_SEAL_SKIP", "4"))

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.aggsig.aggregate import reset_pop_registry
    from cometbft_tpu.aggsig.verify import shared_pairing
    from cometbft_tpu.crypto.bls12381 import OP_COUNTERS
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.engine.chain_gen import (ChainSealSource,
                                               LocalChainSource,
                                               generate_chain)
    from cometbft_tpu.libs.jax_cache import ledger
    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.libs.metrics_gen import SealsyncMetrics
    from cometbft_tpu.pipeline.cache import SigCache, reset_shared_cache
    from cometbft_tpu.sealsync import SealAdopter
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore
    from cometbft_tpu.types.agg_commit import AggregatedCommit

    pc = shared_pairing()
    _log(f"pairing checker backend: {pc.backend}")

    _log(f"generating {n_blocks}-block BLS chain (aggregated seals), "
         f"{n_vals} validators...")
    t0 = time.perf_counter()
    chain = generate_chain(
        n_blocks=n_blocks, n_validators=n_vals, txs_per_block=1,
        key_type="bls12_381", aggregate=True)
    gen_s = time.perf_counter() - t0
    for c in chain.seen_commits:
        assert isinstance(c, AggregatedCommit)
    tip = chain.max_height()

    def catchup(store, cache) -> float:
        """Real blocksync catch-up into `store`; `cache` is the
        marshal-route SigCache (the adopter's on the backfill leg,
        None on the baseline)."""
        app = KVStoreApplication()
        app.init_chain(chain.chain_id, 1, [], b"")
        executor = BlockExecutor(app, state_store=StateStore(MemDB()),
                                 block_store=store)
        state = State.from_genesis(chain.genesis)
        reactor = BlocksyncReactor(
            executor, store, LocalChainSource(chain), chain.chain_id,
            tile_size=8, batch_size=0, cache=cache)
        t0 = time.perf_counter()
        state = reactor.sync(state)
        dt = time.perf_counter() - t0
        assert state.last_block_height == tip
        return dt

    # ---- side A: seal adoption (time-to-decided), then backfill ----
    reset_pop_registry()
    reset_shared_cache()
    a_state = State.from_genesis(chain.genesis)  # registers PoPs
    a_store = BlockStore(MemDB())
    a_cache = SigCache(65536)
    metrics = SealsyncMetrics(Registry())
    adopter = SealAdopter(
        chain.chain_id, a_store, ChainSealSource(chain),
        tile_size=8, max_skip=max_skip, cache=a_cache, shards=1,
        metrics=metrics)
    c0 = dict(OP_COUNTERS)
    t0 = time.perf_counter()
    adopted = adopter.adopt(a_state)
    adopt_s = time.perf_counter() - t0
    adopt_millers = OP_COUNTERS["miller_loops"] - c0["miller_loops"]
    adopt_fexps = OP_COUNTERS["final_exps"] - c0["final_exps"]
    assert adopted == tip and a_store.adopted_tip() == tip
    pivots = int(metrics.pivots_verified.value())
    skipped = int(metrics.pairings_skipped.value())
    _log(f"seal adoption: decided through h={adopted} in "
         f"{adopt_s:.2f}s — {pivots} pivot pairings, "
         f"{skipped} heights adopted without pairing")

    c0 = dict(OP_COUNTERS)
    backfill_s = catchup(a_store, a_cache)
    bf_millers = OP_COUNTERS["miller_loops"] - c0["miller_loops"]
    bf_fexps = OP_COUNTERS["final_exps"] - c0["final_exps"]
    _log(f"body backfill (adopter cache): {backfill_s:.2f}s — "
         f"{bf_millers} Miller loops, {bf_fexps} final exps "
         f"(adopted commits must be cache hits)")

    # ---- side B: full blocksync from scratch (the baseline) ----
    reset_pop_registry()
    reset_shared_cache()
    c0 = dict(OP_COUNTERS)
    blocksync_s = catchup(BlockStore(MemDB()), None)
    bs_millers = OP_COUNTERS["miller_loops"] - c0["miller_loops"]
    bs_fexps = OP_COUNTERS["final_exps"] - c0["final_exps"]
    _log(f"full blocksync: {blocksync_s:.2f}s — {bs_millers} Miller "
         f"loops, {bs_fexps} final exps")

    rec = {
        "metric": "sealsync_time_to_decided",
        "value": round(adopt_s, 3),
        "unit": "s",
        "vs_baseline": round(blocksync_s / adopt_s, 1),
        "backend": pc.backend,
        "validators": n_vals,
        "blocks": n_blocks,
        "max_skip": max_skip,
        "adopt_s": round(adopt_s, 3),
        "backfill_s": round(backfill_s, 3),
        "adopt_plus_backfill_s": round(adopt_s + backfill_s, 3),
        "blocksync_s": round(blocksync_s, 3),
        "speedup_decided": round(blocksync_s / adopt_s, 1),
        "speedup_full": round(blocksync_s / (adopt_s + backfill_s), 2),
        "pivot_pairings": pivots,
        "heights_adopted_without_pairing": skipped,
        "pairing_ops": {
            "adopt_miller_loops": adopt_millers,
            "adopt_final_exps": adopt_fexps,
            "backfill_miller_loops": bf_millers,
            "backfill_final_exps": bf_fexps,
            "blocksync_miller_loops": bs_millers,
            "blocksync_final_exps": bs_fexps,
        },
        "chain_gen_s": round(gen_s, 2),
        "compile_cache": ledger().attribution(),
    }
    print(json.dumps(rec), flush=True)
    return 0


def _measure_mesh_mode(n_devices: int, iters: int) -> int:
    """Build the (commit, sig) topology over `n_devices` of this
    process's TPU devices, warm the planned bucket (ledger-recorded
    under the mesh-shape kernel key), and time sharded dispatches
    through the real MeshExecutor. One JSON line on stdout."""
    device = require_tpu()
    from collections import Counter
    from cometbft_tpu.libs.jax_cache import ledger
    from cometbft_tpu.mesh import MeshExecutor, MeshTopology
    from cometbft_tpu.mesh.planner import lanes_kernel_name

    from cometbft_tpu.device.health import CANARY_LANES
    width = int(os.environ.get("BENCH_MESH_WIDTH", "512"))
    topology = MeshTopology(n_devices=n_devices)
    view = topology.view()
    n_devices = view.n_shards
    ex = MeshExecutor(topology, threaded=False)
    n_real = max(1, (width - CANARY_LANES) * view.n_shards)
    kernel = lanes_kernel_name(view.shape)
    bucket = width * view.n_shards
    warm_before = ledger().seen(kernel, bucket)
    _log(f"mesh[{n_devices}]: shape {view.shape[0]}x{view.shape[1]}, "
         f"bucket {bucket} ({width}/shard), warming...")
    t0 = time.monotonic()
    ex.warm([width], probe=False)  # a bench never regrows
    compile_s = time.monotonic() - t0
    _log(f"mesh[{n_devices}]: warm in {compile_s:.1f}s; generating "
         f"{n_real} signatures...")
    pubs, msgs, sigs = _gen_signatures(n_real)
    # one untimed dispatch of the REAL batch: generic first-call
    # warm-up (device transfer paths, host marshalling caches) so the
    # timed loop measures steady state only
    t0 = time.monotonic()
    ex.verify(pubs, msgs, sigs)
    compile_s += time.monotonic() - t0
    t0 = time.perf_counter()
    fut = None
    for _ in range(iters):
        fut = ex.submit(pubs, msgs, sigs)
        out = fut.result()
    dt = time.perf_counter() - t0
    assert all(out), "bench lanes must all verify"
    per_shard = Counter(fut.shards)
    ex.close()
    rec = {
        "metric": "mesh_verify_throughput",
        "unit": "sigs/s",
        "device": device,
        "devices": n_devices,
        "shape": list(view.shape),
        "sigs_per_sec": round(n_real * iters / dt, 1),
        "bucket": bucket,
        "lanes_per_dispatch": n_real,
        "compile_s": round(compile_s, 2),
        "ledger_warm_before": warm_before,
        # per-shard result attribution: every lane's verdict names the
        # shard that produced it (device/protocol trailer semantics)
        "per_shard_lanes": {str(k): v
                            for k, v in sorted(per_shard.items())},
    }
    print(json.dumps(rec), flush=True)
    return 0


def main():
    batch = int(os.environ.get("BENCH_BATCH", "8192"))
    iters = int(os.environ.get("BENCH_ITERS", "4"))
    return _measure_mode(batch, iters)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--pipeline":
        sys.exit(_pipeline_mode())
    if len(sys.argv) > 1 and sys.argv[1] == "--aggsig":
        mb = "fast"
        if "--miller-backend" in sys.argv[2:]:
            i = sys.argv.index("--miller-backend")
            mb = sys.argv[i + 1] if i + 1 < len(sys.argv) else ""
        sys.exit(_aggsig_mode(mb))
    if len(sys.argv) > 1 and sys.argv[1] == "--sealsync":
        sys.exit(_sealsync_mode())
    if len(sys.argv) > 1 and sys.argv[1] == "--mesh":
        # every local TPU device, or BENCH_MESH_DEVICES of them
        sys.exit(_measure_mesh_mode(
            int(os.environ.get("BENCH_MESH_DEVICES", "0")) or None,
            int(os.environ.get("BENCH_ITERS", "4"))))
    sys.exit(main())
