"""What a node does at boot before its first verification, and the
counters of the device path that every driver reads: shared by the
drivers, each of which drives one entry of the program.

Nothing here chooses a value: the lane bucket is
`Node._device_batch_size()` (512 on a TPU, 0 = native on a CPU), and the
only kernels compiled are the pair `prewarm_verify_kernels` compiles for
that bucket, as `Node._prewarm_kernels` does."""

from __future__ import annotations

import time


def boot() -> dict:
    """Compile cache on, kernels of the node's bucket warm. Returns the
    bucket and the seconds the warm took (0.0 with no device path)."""
    from cometbft_tpu.libs.jax_cache import enable_compile_cache
    from cometbft_tpu.node.node import Node
    enable_compile_cache()
    batch = Node._device_batch_size()
    prewarm_s = 0.0
    if batch > 0:
        from cometbft_tpu.ops.ed25519 import prewarm_verify_kernels
        t0 = time.perf_counter()
        prewarm_verify_kernels(batch_size=batch)
        prewarm_s = time.perf_counter() - t0
    return {"batch": batch, "prewarm_s": prewarm_s}


def device_counters() -> dict:
    """A snapshot of the dispatch path's counters; the drivers report
    the difference over the window."""
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.pipeline.cache import shared_cache
    cache = shared_cache()
    canary = e5.canary_stats()
    with cache._lock:
        hits, misses = dict(cache.hits), dict(cache.misses)
    return {"dispatches": e5._dispatches, "canary_runs": canary["runs"],
            "canary_trips": canary["trips"],
            "pallas_degraded": int(e5.pallas_degraded()),
            "sigcache_hits": hits, "sigcache_misses": misses}


def delta(before: dict, after: dict, path: str) -> dict:
    return {
        "dispatches": after["dispatches"] - before["dispatches"],
        "canary_runs": after["canary_runs"] - before["canary_runs"],
        "canary_trips": after["canary_trips"],
        "pallas_degraded": after["pallas_degraded"],
        "sigcache_hits": (after["sigcache_hits"].get(path, 0)
                          - before["sigcache_hits"].get(path, 0)),
        "sigcache_misses": (after["sigcache_misses"].get(path, 0)
                            - before["sigcache_misses"].get(path, 0)),
    }


def implied_chunks(lane_counts, batch: int) -> int:
    """Pallas dispatches that flushes of these lane counts imply: none
    without a device bucket, none for a flush under the program's
    batch threshold (it verifies natively), else one per bucket-wide
    chunk."""
    from cometbft_tpu.types.validation import BATCH_VERIFY_THRESHOLD
    if batch <= 0:
        return 0
    return sum(-(-n // batch) for n in lane_counts
               if n >= BATCH_VERIFY_THRESHOLD)


def hash_blocks(msg_len: int) -> int:
    """SHA-512 blocks of H(R || A || M) for a message of `msg_len`
    bytes: 64 bytes of R and A, the message, 0x80 and a 16-byte length."""
    return (64 + msg_len + 17 + 127) // 128
