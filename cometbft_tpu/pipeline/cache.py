"""Bounded verified-signature cache shared across verification paths.

Consensus gossip re-delivers the same precommit many times, blocksync
re-fetches tile-boundary blocks, and the light client re-verifies
commits blocksync already checked — each re-verification is a wasted
device lane (or a ~400µs host verify). The cache records signatures
that VERIFIED TRUE, keyed by (pubkey, sign_bytes, sig): the sign bytes
embed chain id, height, round, and type, so a hit is exactly "this key
already verified these bytes under this chain" — never a cross-context
confusion. Failed signatures are never cached (attribution paths handle
them), so a hit can only skip work, never flip a verdict.

A batch is looked up under one lock (`SigCache.lookup`), which hands
back each lane's key, and the lanes that then verify true are inserted
by those keys under one lock (`SigCache.insert`): a lane is hashed once.

Intake paths attribute hits/misses per label ("blocksync", "vote",
"commit") — the raw material of the pipeline_sigcache_{hits,misses}
Prometheus counters (libs/metrics_defs.PipelineMetrics). Capacity is
LRU-bounded; COMETBFT_TPU_SIGCACHE_CAPACITY overrides the default
(0 disables the process-wide shared cache entirely).
"""

from __future__ import annotations

import contextlib
import hashlib
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple, Union

from ..libs.env import env_int

DEFAULT_CAPACITY = 65536
ENV_CAPACITY = "COMETBFT_TPU_SIGCACHE_CAPACITY"


def _key(pub: bytes, sign_bytes: bytes, sig: bytes) -> bytes:
    # length-prefixed concat: no ambiguity between field boundaries
    h = hashlib.sha256()
    for part in (pub, sign_bytes, sig):
        h.update(len(part).to_bytes(4, "big"))
        h.update(part)
    return h.digest()


def _tally(path, hits: List[bool]) -> List[Tuple[str, int, int]]:
    """(path, hits, misses) of a lookup's answers, one a path."""
    if isinstance(path, str):
        n = hits.count(True)
        return [(path, n, len(hits) - n)]
    tally: Dict[str, List[int]] = {}
    for p, hit in zip(path, hits):
        tally.setdefault(p, [0, 0])[0 if hit else 1] += 1
    return [(p, h, m) for p, (h, m) in tally.items()]


class SigCache:
    """Thread-safe LRU of verified-true signatures.

    A site that looks a batch of lanes up and later inserts those that
    verified true goes through `lookup` and `insert`: each lane's key is
    computed once, at its lookup, and each half takes the lock once for
    the whole batch. `seen` and `add` are their one-lane forms, for the
    callers that look a signature up alone (`seen` written out: such a
    caller builds no lists)."""

    # guarded-by: _lock: _entries, hits, misses, evictions
    # guarded-by: _lock: inserted, inserted_keyed
    # (enforced by tools/staticcheck's guarded-by rule: any access to
    # the attributes above outside `with self._lock` is a lint error)

    def __init__(self, capacity: int = DEFAULT_CAPACITY, metrics=None):
        self.capacity = capacity
        self.metrics = metrics  # libs/metrics_gen.PipelineMetrics or None
        self._lock = threading.Lock()
        self._entries: "OrderedDict[bytes, None]" = OrderedDict()
        self.evictions = 0
        self.hits: Dict[str, int] = {}
        self.misses: Dict[str, int] = {}
        # lanes inserted, and of them those whose key came from their
        # lookup (`insert`) rather than computed again (`add`)
        self.inserted = 0
        self.inserted_keyed = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @staticmethod
    def key(pub: bytes, sign_bytes: bytes, sig: bytes) -> bytes:
        """Stable digest of one signature triple — the cache's own
        entry key, exposed so the farm batcher's intra-batch dedup
        collapses identical lanes under the same identity."""
        return _key(pub, sign_bytes, sig)

    def seen(self, pub: bytes, sign_bytes: bytes, sig: bytes,
             path: str = "unknown") -> bool:
        """True iff this exact signature previously verified TRUE.
        Counts a hit or miss against `path`."""
        if self.capacity <= 0:
            return False
        k = _key(pub, sign_bytes, sig)
        with self._lock:
            hit = k in self._entries
            if hit:
                self._entries.move_to_end(k)
                self.hits[path] = self.hits.get(path, 0) + 1
            else:
                self.misses[path] = self.misses.get(path, 0) + 1
        m = self.metrics
        if m is not None:
            (m.cache_hits if hit else m.cache_misses).inc(path=path)
        return hit

    def lookup(self, lanes: Sequence[Tuple[bytes, bytes, bytes]],
               path: Union[str, Sequence[str]] = "unknown"
               ) -> Tuple[List[bytes], List[bool]]:
        """(keys, hits) of (pub, sign_bytes, sig) lanes: each lane's key,
        to hand to `insert` once it verifies true, and whether it
        previously verified TRUE — what `seen` answers of it, in order,
        under one lock. `path` is the lanes' attribution label, or one
        label a lane."""
        keys = [_key(pub, sign_bytes, sig) for pub, sign_bytes, sig in lanes]
        if self.capacity <= 0:
            return keys, [False] * len(keys)
        with self._lock:
            entries = self._entries
            hits = [k in entries for k in keys]
            for k, hit in zip(keys, hits):
                if hit:
                    entries.move_to_end(k)
            tally = _tally(path, hits)
            for p, h, m in tally:
                if h:
                    self.hits[p] = self.hits.get(p, 0) + h
                if m:
                    self.misses[p] = self.misses.get(p, 0) + m
        metrics = self.metrics
        if metrics is not None:
            for p, h, m in tally:
                if h:
                    metrics.cache_hits.inc(h, path=p)
                if m:
                    metrics.cache_misses.inc(m, path=p)
        return keys, hits

    def add(self, pub: bytes, sign_bytes: bytes, sig: bytes) -> None:
        """Record a signature that verified TRUE. Never call for a
        failed verification."""
        self._insert([_key(pub, sign_bytes, sig)], keyed=False)

    def insert(self, keys: Sequence[bytes]) -> None:
        """Record, in order, the signatures whose `lookup` keys these
        are; each verified TRUE. Never pass the key of a failed
        verification."""
        self._insert(keys, keyed=True)

    def _insert(self, keys: Sequence[bytes], keyed: bool) -> None:
        if self.capacity <= 0:
            return
        evicted = 0
        with self._lock:
            entries = self._entries
            for k in keys:
                entries[k] = None
                entries.move_to_end(k)
            while len(entries) > self.capacity:
                entries.popitem(last=False)
                evicted += 1
            self.evictions += evicted
            self.inserted += len(keys)
            if keyed:
                self.inserted_keyed += len(keys)
        if evicted and self.metrics is not None:
            self.metrics.cache_evictions.inc(evicted)

    def insert_counts(self) -> Tuple[int, int]:
        """(lanes inserted, of them those inserted with their lookup's
        key) since the cache was made or cleared."""
        with self._lock:
            return self.inserted, self.inserted_keyed

    def hit_rate(self, path: Optional[str] = None) -> float:
        """Hits / (hits + misses), overall or for one intake path."""
        with self._lock:
            if path is None:
                h, m = sum(self.hits.values()), sum(self.misses.values())
            else:
                h, m = self.hits.get(path, 0), self.misses.get(path, 0)
        return h / (h + m) if h + m else 0.0

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits.clear()
            self.misses.clear()
            self.evictions = 0
            self.inserted = self.inserted_keyed = 0


@contextlib.contextmanager
def insert_span_attrs(cache: Optional[SigCache], span):
    """Sets on `span` the lanes `cache` inserted while the block ran
    (`sigcache_inserted`) and, of them, those inserted with their
    lookup's key (`sigcache_inserted_keyed`). The counts are the
    cache's, so the block must be its only inserter meanwhile, as the
    catch-up settle and the light client's save are."""
    if cache is None:
        yield
        return
    inserted, keyed = cache.insert_counts()
    try:
        yield
    finally:
        now_inserted, now_keyed = cache.insert_counts()
        span.set_attr("sigcache_inserted", now_inserted - inserted)
        span.set_attr("sigcache_inserted_keyed", now_keyed - keyed)


_shared: Optional[SigCache] = None
_shared_lock = threading.Lock()


def shared_cache() -> SigCache:
    """Process-wide cache instance (consensus vote intake, light client,
    and any blocksync engine not given its own). Capacity from
    COMETBFT_TPU_SIGCACHE_CAPACITY at first use; 0 yields a disabled
    (always-miss, never-store) instance."""
    global _shared
    with _shared_lock:
        if _shared is None:
            _shared = SigCache(env_int(ENV_CAPACITY, DEFAULT_CAPACITY))
        return _shared


def reset_shared_cache() -> None:
    """Drop the shared instance (tests; also re-reads the env knob)."""
    global _shared
    with _shared_lock:
        _shared = None
