"""The device sniff, the persistent compile-cache setup and the compile
ledger.

`is_device_platform()` is the ONE answer to "does this process verify
on a TPU?": the platform of the initialised JAX backend, chosen by JAX
itself (a TPU host with JAX_PLATFORMS unset answers "tpu"; tests and
control-plane children run with JAX_PLATFORMS=cpu in their environment
and answer "cpu"). Asking initialises the backend, and a chip belongs
to one process until that process exits — so a launcher that leaves the
chip to a child asks nothing here, and hands the child its platform
through the child's environment.

Every entry point (tests, graft entry, tools) calls
`enable_compile_cache()` so the cache location and threshold stay
consistent.

The CompileLedger (ROADMAP item-5 residual) persists which
(kernel, shape-bucket) pairs have compiled on which platform/jax
version and how long each compile took — so bench and device-server
runs can attribute hit/miss/cold-compile in their JSON instead of
silently eating a multi-minute XLA compile, and the mesh and BLS
cold-shape gates can tell what this process already compiled. It is
on no serve path of `crypto/` or `farm/`: where ed25519 lanes verify
is `crypto/keys.kernel_width()`'s answer, from the platform alone. On
device platforms the jax persistent cache holds the actual
executables; the ledger is the keying + attribution layer over it
(XLA:CPU executables are never persisted — machine-feature reloads
risk SIGILL — so on cpu a "seen" entry predicts a warm in-process
recompile cost, not an artifact reload).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def raise_compiler_stack_limit() -> None:
    """Root-cause mitigation for the XLA:CPU SIGSEGV at batch >= 256
    (docs/PERF.md "known compile hazard"): XLA's HLO passes recurse
    deeply on the RLC kernel graph and OVERFLOW the default 8MB
    pthread stack (observed: SIGSEGV at the stack guard page inside
    libjax_common). pthreads size their stacks from RLIMIT_STACK at
    thread creation, so raising the soft limit BEFORE the compiler
    thread pool exists removes the crash. Called from
    enable_compile_cache so every entry point gets it; a no-op when
    the limit is already high or the pool already exists."""
    try:
        import resource
        soft, hard = resource.getrlimit(resource.RLIMIT_STACK)
        want = 512 * 1024 * 1024
        if hard != resource.RLIM_INFINITY:
            want = min(want, hard)
        if soft != resource.RLIM_INFINITY and soft < want:
            resource.setrlimit(resource.RLIMIT_STACK, (want, hard))
    except (ImportError, ValueError, OSError):  # pragma: no cover
        pass


DEVICE_SERVER_ENV = "COMETBFT_TPU_DEVICE_SERVER"
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def backend_platform() -> str:
    """Platform of this process's JAX backend ("tpu", "cpu", ...) as
    JAX itself resolved it. INITIALISES the backend: from here on this
    process holds the chip, if there is one.

    A process told where the host's device server lives
    (COMETBFT_TPU_DEVICE_SERVER) is that server's CLIENT, and the
    server is the chip's one owner: whatever JAX the client still runs
    in-process (sub-threshold batches, the fallback while the server
    is unreachable) is held to the CPU backend."""
    import jax
    if os.environ.get(DEVICE_SERVER_ENV):
        jax.config.update("jax_platforms", "cpu")
    return jax.default_backend()


def is_device_platform() -> bool:
    """True when this process verifies on a TPU it owns."""
    return backend_platform() == "tpu"


def compile_cache_plan(platform: str, env_dir: str | None
                       ) -> tuple[bool, str | None]:
    """(cache on?, directory to set in code or None) — the decision
    `enable_compile_cache` applies, pure so it can be tested with any
    platform name.

    The persistent cache is TPU-only: XLA:CPU AOT executables record
    machine features that fail the host check when another process
    reloads them ("could lead to SIGILL" — and mesh executables DO
    segfault, in both the serialize and deserialize paths), so on cpu
    every process recompiles. On a TPU the cache lives where
    JAX_COMPILATION_CACHE_DIR says — JAX reads that variable itself,
    the code then sets no directory — and otherwise at the fixed
    `<checkout>/.jax_cache` (the path is part of the cache key: a
    directory that moves never hits)."""
    if platform != "tpu":
        return False, None
    return True, (None if env_dir else DEFAULT_CACHE_DIR)


def enable_compile_cache() -> None:
    raise_compiler_stack_limit()
    import jax
    on, directory = compile_cache_plan(
        backend_platform(), os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not on:
        disable_persistent_cache()
        return
    if directory is not None:
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def cache_dir() -> str:
    """Where the persistent cache (and the ledger beside it) lives."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


class CompileLedger:
    """On-disk record of (kernel, shape-bucket) compiles.

    Entries are keyed "kernel|bucket|platform|jax-version" so a ledger
    written against one backend or jax build never mispredicts
    another. All methods are best-effort on I/O errors: the ledger
    must never be able to fail a measurement run."""

    # guarded-by: _lock: _entries, hits, misses, _proc_warm
    def __init__(self, path: str | None = None):
        self.path = path or os.path.join(cache_dir(), "ledger.json")
        self._lock = threading.Lock()
        self.hits = 0       # compile_guard entries already in the ledger
        self.misses = 0     # cold entries recorded this process
        # keys THIS process compiled (or guarded through) — the only
        # warmth that is cheap on XLA:CPU, where executables are never
        # persisted and an on-disk entry predicts a full recompile
        self._proc_warm: set = set()
        try:
            with open(self.path) as f:
                self._entries: dict = json.load(f)
        except (OSError, ValueError):
            self._entries = {}

    def _save(self, entries: dict) -> None:
        """Persist a snapshot (passed in so every self._entries access
        stays lexically under the lock), MERGED over the on-disk state:
        concurrent writers (a device server alongside a node) each
        contribute their keys
        instead of the last writer erasing the others'. Our entries win
        only on key conflict."""
        try:
            try:
                with open(self.path) as f:
                    merged = json.load(f)
            except (OSError, ValueError):
                merged = {}
            merged.update(entries)
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass

    @staticmethod
    def _env(platform: str | None = None) -> str:
        try:
            import jax
            ver = jax.__version__
        except Exception:  # noqa: BLE001 — ledger must never fail callers
            ver = "?"
        return f"{platform or backend_platform()}|{ver}"

    def key(self, kernel: str, bucket: int,
            platform: str | None = None) -> str:
        """Entry key; `platform` overrides the process's own backend
        platform (tests key entries for a platform they do not run)."""
        return f"{kernel}|{bucket}|{self._env(platform)}"

    def seen(self, kernel: str, bucket: int,
             platform: str | None = None) -> bool:
        with self._lock:
            return self.key(kernel, bucket, platform) in self._entries

    def warm_in_process(self, kernel: str, bucket: int) -> bool:
        """True when THIS process already compiled (kernel, bucket) —
        its jit cache makes the next dispatch to that bucket cheap.
        This is deliberately NOT `seen()`: on cpu a ledger entry from
        another process only predicts the recorded compile_s all over
        again, so the cold-shape gates (mesh/executor.is_warm,
        aggsig/aggregate) open on process-local warmth alone."""
        with self._lock:
            return self.key(kernel, bucket) in self._proc_warm

    def record(self, kernel: str, bucket: int, compile_s: float) -> None:
        with self._lock:
            self._proc_warm.add(self.key(kernel, bucket))
            self._entries[self.key(kernel, bucket)] = {
                "kernel": kernel, "bucket": bucket,
                "compile_s": round(float(compile_s), 3),
                "recorded_unix": int(time.time()),  # staticcheck: allow(wallclock)
            }
            self._save(dict(self._entries))

    @contextlib.contextmanager
    def compile_guard(self, kernel: str, bucket: int):
        """Wrap a possibly-compiling call: attributes a ledger hit or
        miss, times the first-touch cost, and records it on SUCCESS.
        A raising guard records nothing: a transient runtime failure
        (transport error mid-warm) is not a compile."""
        warm = self.seen(kernel, bucket)
        t0 = time.monotonic()  # staticcheck: allow(wallclock)
        yield
        dt = time.monotonic() - t0  # staticcheck: allow(wallclock)
        with self._lock:
            if warm:
                self.hits += 1
            else:
                self.misses += 1
            self._proc_warm.add(self.key(kernel, bucket))
        if not warm:
            self.record(kernel, bucket, dt)

    def attribution(self) -> dict:
        """Process-level summary for bench JSON."""
        with self._lock:
            return {"ledger": self.path, "hits": self.hits,
                    "misses": self.misses}


_ledger: CompileLedger | None = None
_ledger_lock = threading.Lock()


def ledger() -> CompileLedger:
    global _ledger
    with _ledger_lock:
        if _ledger is None:
            _ledger = CompileLedger()
        return _ledger


def reset_ledger(path: str | None = None) -> None:
    """Point the process at a fresh ledger (tests)."""
    global _ledger
    with _ledger_lock:
        _ledger = CompileLedger(path) if path else None


def disable_persistent_cache() -> None:
    """Turn the on-disk compile cache off for the rest of the process.

    The flag alone is NOT enough once anything has compiled: jax
    memoizes the is-cache-enabled decision globally at first compile,
    so the memo must be reset too (observed: a process that compiled
    plenty beforehand still cache-WROTE a sharded executable — and
    segfaulted serializing it — despite the flag being False)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
