"""Mesh executor: non-blocking sharded dispatch behind the
submit()/future seam.

This is the production data plane the dry-run proved out
(MULTICHIP_r05.json): batches enter through the same non-blocking
`submit(pubs, msgs, sigs) -> future` contract as
`device.client.DeviceClient` / the pipeline backends, get planned onto
a ledger-warm bucket (mesh/planner.py), and run lane-sharded over the
serving mesh view (mesh/topology.py). The pipeline scheduler reads
`n_shards` to size its bounded queue, so the PR-2 K-tiles-in-flight
win and N-chip sharding compose: K tiles in flight PER SHARD.

Verdict safety is the PR-3 contract, per shard: every dispatch carries
per-shard canary + pad rows with known expected verdicts; any shard
that answers them wrong is reported to the ShardSupervisor (mask +
re-factor smaller) and the WHOLE batch re-verifies on the native CPU
path — a corrupt verdict can never reach the caller, and a single
sick chip shrinks the mesh instead of benching the node. Masked
shards are re-probed on the supervisor's backoff schedule from the
dispatch loop itself (a known-answer pair on the MASKED chip's own
device); a correct probe grows the mesh back.

The verify backend is a seam (`verify_backend(view, plan, pubs, msgs,
sigs) -> bucket-row verdicts`): the default `JaxMeshBackend` runs the
real shard_map kernels (single-shard views route through the plain
`ops.ed25519.verify_batch` bucket — the (1,1) degenerate case pays no
shard_map overhead and shares the server's warm kernels); simnet and
the unit tests inject deterministic stubs, exactly like the pipeline
scheduler's backend fixtures.

Futures carry per-lane shard attribution (`MeshFuture.shards`: the
global shard id that verified each lane, or CPU_SHARD for the
canary-failure re-verify path) — the device server forwards it to
clients as the protocol's attribution trailer.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..device.health import CANARY_LANES
from ..device.protocol import CPU_SHARD
from ..libs.jax_cache import ledger
from ..trace import shared_tracer
from .planner import (LanePlan, lanes_kernel_name, plan_lanes,
                      shard_width_for)
from .shard_health import ShardSupervisor
from .topology import MeshTopology, MeshView

__all__ = ["CPU_SHARD", "JaxMeshBackend", "MeshExecutor", "MeshFuture",
           "MeshOverloaded"]


class MeshOverloaded(Exception):
    """The executor's bounded dispatch queue is full — explicit
    backpressure, same stance as farm/ingest QueueFull."""


class MeshFuture:
    """Result handle for one submitted batch (the DeviceFuture shape
    the pipeline's dispatch stage expects: done/cancel/result)."""

    def __init__(self, n_lanes: int):
        self.n_lanes = n_lanes
        self.shards: Optional[List[int]] = None  # set with the result
        self._ev = threading.Event()
        self._out: Optional[List[bool]] = None
        self._exc: Optional[BaseException] = None
        self._cancelled = False

    def done(self) -> bool:
        return self._ev.is_set()

    def cancel(self) -> None:
        self._cancelled = True

    def set_result(self, out: List[bool]) -> None:
        self._out = out
        self._ev.set()

    def set_exception(self, exc: BaseException) -> None:
        self._exc = exc
        self._ev.set()

    def result(self, timeout: Optional[float] = None) -> List[bool]:
        if not self._ev.wait(timeout):
            raise TimeoutError("mesh dispatch still pending")
        if self._exc is not None:
            raise self._exc
        return self._out


def _native_verify(pubs: Sequence[bytes], msgs: Sequence[bytes],
                   sigs: Sequence[bytes]) -> List[bool]:
    """The trusted CPU re-verify path (per-sig native, never a jit):
    what a canary-failed or cold-shape batch falls back to. ONE
    implementation tree-wide: crypto/keys.verify_native, the loop
    blocksync and the pipeline drain already use."""
    from ..crypto.keys import verify_native
    return [bool(v) for v in verify_native(pubs, msgs, sigs)]


class JaxMeshBackend:
    """The real device path: lane-sharded Straus verify over the
    view's jax Mesh, compiled once per (generation, bucket, msg-cap)
    and recorded in the CompileLedger under the mesh-shape kernel key.

    Single-shard views take `ops.ed25519.verify_batch` on the padded
    bucket instead — byte-identical verdict semantics, no shard_map,
    and it shares the `ed25519-rlc` kernels the device server already
    warms (the (1,1) degenerate case of the topology)."""

    def __init__(self):
        # keyed by (shard_ids, bucket, cap) — the DEVICE SET, not the
        # topology generation: regrowing back to an identical set must
        # reuse the boot-compiled executable, not retrace it (the
        # persistent compile cache is off for mesh executables, so an
        # eviction here means a full recompile)
        self._cache: dict = {}        # key -> jit fn
        self._warm: set = set()       # keys whose first CALL completed
        self._probe_cache: dict = {}  # id(device) -> jit fn

    @staticmethod
    def _msg_cap(msgs: Sequence[bytes]) -> int:
        """Message-capacity bucket, FLOORED at 128 (vote sign-bytes
        are ~110-130B): canary-sized warm batches (31B) and live
        commit traffic then share ONE compiled variant per (bucket)
        instead of splitting into cap-64/cap-128 kernels — warm()
        genuinely covers the first live flush. Longer messages still
        double up (the server's max_msg_len bounds them)."""
        cap = 128
        longest = max((len(m) for m in msgs), default=0)
        while cap < longest:
            cap *= 2
        return cap

    def key(self, view: MeshView, plan: LanePlan,
            msgs: Sequence[bytes]) -> tuple:
        return (view.shard_ids, plan.bucket, self._msg_cap(msgs))

    def is_warm(self, view: MeshView, plan: LanePlan,
                msgs: Sequence[bytes]) -> bool:
        """True when this exact (device set, bucket, msg-cap) has
        completed a call in this process — i.e. dispatching it again
        is cheap. The executor consults this to keep cold mesh
        compiles OFF the live dispatch thread."""
        if view.n_shards == 1:
            # the (1,1) route rides verify_batch: warm when either
            # THIS backend already ran the bucket (mesh-lanes@1x1
            # guard) or the process compiled the underlying
            # ed25519-rlc bucket (server _warm, node prewarm) — a
            # mesh degraded all the way to one chip must not bypass
            # the cold-shape gate into a live multi-minute
            # verify_batch compile
            lg = ledger()
            return (lg.warm_in_process(lanes_kernel_name((1, 1)),
                                       plan.bucket)
                    or lg.warm_in_process("ed25519-rlc", plan.bucket))
        return self.key(view, plan, msgs) in self._warm

    def __call__(self, view: MeshView, plan: LanePlan,
                 pubs: Sequence[bytes], msgs: Sequence[bytes],
                 sigs: Sequence[bytes]) -> np.ndarray:
        if view.n_shards == 1:
            from ..ops.ed25519 import verify_batch
            with ledger().compile_guard(lanes_kernel_name(view.shape),
                                        plan.bucket):
                return verify_batch(list(pubs), list(msgs), list(sigs),
                                    batch_size=plan.bucket)
        from ..ops.ed25519 import prepare_batch
        key = self.key(view, plan, msgs)
        cap = key[2]
        fn = self._cache.get(key)
        if fn is None:
            from ..parallel.verify import make_lanes_sharded_verifier
            fn = make_lanes_sharded_verifier(view.jax_mesh())
            self._cache[key] = fn
        pub, sig, hb, hn, ok_mask = prepare_batch(
            list(pubs), list(msgs), list(sigs), plan.bucket, cap)
        with ledger().compile_guard(lanes_kernel_name(view.shape),
                                    plan.bucket):
            out = np.asarray(fn(pub, sig, hb, hn))
        self._warm.add(key)
        return out & ok_mask

    def probe_fn(self, device):
        """Known-answer verify pinned to ONE device — a (1, 1) mesh
        over the masked chip itself, so a passing probe proves THAT
        chip computes correct verdicts (running the probe on the
        default device would prove nothing about the quarantined
        one)."""
        def run(pubs, msgs, sigs):
            from ..ops.ed25519 import prepare_batch
            plan = plan_lanes(len(pubs), 1, canary=False)
            p, m, s = plan.build(pubs, msgs, sigs)
            cap = self._msg_cap(m)
            fn = self._probe_cache.get((id(device), plan.bucket, cap))
            if fn is None:
                from ..parallel.mesh import make_mesh
                from ..parallel.verify import make_lanes_sharded_verifier
                fn = make_lanes_sharded_verifier(
                    make_mesh(devices=[device]))
                self._probe_cache[(id(device), plan.bucket, cap)] = fn
            pub, sig, hb, hn, ok_mask = prepare_batch(
                p, m, s, plan.bucket, cap)
            with ledger().compile_guard(lanes_kernel_name((1, 1)),
                                        plan.bucket):
                out = np.asarray(fn(pub, sig, hb, hn)) & ok_mask
            real, _bad = plan.extract(out)
            return real
        return run


class MeshExecutor:
    """Bounded-queue dispatch loop over the serving mesh view."""

    def __init__(self, topology: MeshTopology,
                 supervisor: Optional[ShardSupervisor] = None,
                 canary: bool = True, tiles_per_shard: int = 4,
                 verify_backend: Optional[Callable] = None,
                 probe_backend: Optional[Callable] = None,
                 metrics=None, log=None, threaded: bool = True):
        self.topology = topology
        self.supervisor = supervisor or ShardSupervisor(topology,
                                                        metrics=metrics,
                                                        log=log)
        self.canary = canary
        self.tiles_per_shard = max(1, tiles_per_shard)
        self._backend = verify_backend
        self._probe_backend = probe_backend
        self.metrics = metrics
        self.log = log
        # hard cap leaves headroom over the scheduler's own bound so a
        # depth-sized burst plus probes never bounces spuriously
        self._q: "queue.Queue" = queue.Queue(
            maxsize=2 * self.tiles_per_shard * topology.n_devices)
        self._stop = threading.Event()
        self._bg_warm: Optional[threading.Thread] = None
        self._thread: Optional[threading.Thread] = None
        if threaded:
            self._thread = threading.Thread(target=self._run,
                                            name="mesh-dispatch",
                                            daemon=True)
            self._thread.start()

    # --- sizing hints (pipeline/scheduler reads these) --------------------

    @property
    def n_shards(self) -> int:
        return self.topology.view().n_shards

    def depth_hint(self) -> int:
        """Tiles the pipeline should keep in flight: K per shard."""
        return self.tiles_per_shard * self.n_shards

    @property
    def queue_capacity(self) -> int:
        """Hard cap on queued dispatches — the pipeline scheduler
        clamps its in-flight bound to this so a deep pipeline_depth
        config can never overflow the executor into MeshOverloaded
        trips (which the watchdog would latch as a wedge)."""
        return self._q.maxsize

    # --- warm planning ----------------------------------------------------

    def warm(self, widths: Sequence[int] = (),
             probe: bool = True) -> None:
        """Compile the planned shape buckets BEFORE serving traffic
        (device/server._warm discipline): one dispatch per width over
        the current view, plus (with `probe`) the (1,1) regrow-probe
        shape — all recorded in the CompileLedger under mesh-shape
        keys, so the hot path and future processes can predict warm vs
        cold. `probe=False` skips the probe compile for callers that
        never regrow (bench measurement children)."""
        from ..device.health import canary_pair
        good, _bad = canary_pair()
        view = self.topology.view()
        reserve = CANARY_LANES if self.canary else 0
        widths = list(widths) or [shard_width_for(1, view.n_shards,
                                                  self.canary)]
        if self._backend is None:
            self._backend = JaxMeshBackend()
        for width in widths:
            n_real = max(1, (width - reserve) * view.n_shards)
            plan = plan_lanes(n_real, view.n_shards, self.canary)
            batch = ([good[0]] * n_real, [good[1]] * n_real,
                     [good[2]] * n_real)
            # straight through the backend (NOT submit): warm is the
            # one caller allowed to pay a cold compile, and the
            # dispatch path's cold-shape gate would otherwise route
            # this to CPU without compiling anything
            rows = self._backend(view, plan, *plan.build(*batch))
            out, bad = plan.extract(rows)
            if not all(out) or bad:
                raise RuntimeError("mesh warm-up verification failed")
        if probe and view.n_shards > 1 and self._probe_backend is None:
            be = self._jax_backend()
            if be is not None:
                # warm the single-device probe path for EVERY chip:
                # probe_fn jits per device, a regrow probe runs on the
                # masked chip's OWN device, and any chip can be the
                # one that falls out — a cold probe compile inside a
                # backoff window would stall the dispatch loop for the
                # very minutes this warm exists to prevent
                for shard in range(self.topology.n_devices):
                    fn = be.probe_fn(self.topology.device(shard))
                    if fn([good[0]], [good[1]], [good[2]]) != [True]:
                        raise RuntimeError(
                            f"mesh probe warm-up failed on shard "
                            f"{shard}")

    def _jax_backend(self) -> Optional[JaxMeshBackend]:
        if self._backend is None:
            self._backend = JaxMeshBackend()
        be = self._backend
        return be if isinstance(be, JaxMeshBackend) else None

    # --- the submit seam --------------------------------------------------

    def submit(self, pubs: Sequence[bytes], msgs: Sequence[bytes],
               sigs: Sequence[bytes], ctx=None) -> MeshFuture:
        """Non-blocking dispatch; raises MeshOverloaded when the
        bounded queue is full (the caller sheds or verifies locally —
        never silent unbounded queueing). `ctx` is the submitter's
        trace context (Span/TraceContext/None) — it rides the queue
        tuple to the dispatch thread, never a thread-local."""
        if not pubs:
            raise ValueError("empty batch")
        if self._stop.is_set():
            # a closed executor must refuse, not enqueue onto a queue
            # nothing will ever drain (a caller blocked in result()
            # with no timeout would hang forever)
            raise ConnectionError("mesh executor closed")
        fut = MeshFuture(len(pubs))
        if self._thread is None:
            # single-threaded mode (threaded=False): dispatch on the
            # CALLER's thread, probes included — deterministic for the
            # mesh-degrade simnet scenario and the bench, where probe
            # timing must be a pure function of the virtual clock, not
            # a race against a worker's poll loop
            self._maybe_probe()
            try:
                out, shards = self._dispatch(list(pubs), list(msgs),
                                             list(sigs), ctx=ctx)
                fut.shards = shards
                fut.set_result(out)
            except BaseException as e:  # noqa: BLE001 — via future
                fut.set_exception(e)
            return fut
        try:
            self._q.put_nowait((fut, list(pubs), list(msgs), list(sigs),
                                ctx))
        except queue.Full:
            # the enqueue failed, so nothing will ever resolve this
            # future — close it out before walking away
            fut.cancel()
            raise MeshOverloaded(
                f"mesh dispatch queue full "
                f"({self._q.maxsize} tiles)") from None
        return fut

    def verify(self, pubs, msgs, sigs,
               timeout: Optional[float] = None) -> List[bool]:
        """Blocking submit + wait (bench / tests)."""
        return self.submit(pubs, msgs, sigs).result(timeout)

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            # fail queued-but-undispatched futures so no caller hangs
            # in result() on work that will never run; put_nowait only
            # (the worker exits on _stop within its 0.2s poll even if
            # the sentinel does not fit a full queue)
            try:
                self._q.put_nowait(None)
            except queue.Full:
                pass
            self._thread.join(timeout=5.0)
            while True:
                try:
                    item = self._q.get_nowait()
                except queue.Empty:
                    break
                if item is not None and not item[0].done():
                    item[0].set_exception(
                        ConnectionError("mesh executor closed"))

    # --- the dispatch loop ------------------------------------------------

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                item = self._q.get(timeout=0.2)
            except queue.Empty:
                self._maybe_probe()
                continue
            if item is None:
                return
            fut, pubs, msgs, sigs, ctx = item
            self._maybe_probe()
            if fut._cancelled:
                continue
            try:
                out, shards = self._dispatch(pubs, msgs, sigs, ctx=ctx)
                fut.shards = shards
                fut.set_result(out)
            except BaseException as e:  # noqa: BLE001 — surfaced via
                # the future; the pipeline watchdog / caller decides
                fut.set_exception(e)

    def _dispatch(self, pubs, msgs, sigs, ctx=None
                  ) -> Tuple[List[bool], List[int]]:
        if self._backend is None:
            self._backend = JaxMeshBackend()
        view = self.topology.view()
        plan = plan_lanes(len(pubs), view.n_shards, self.canary)
        tracer = shared_tracer()
        with tracer.start("mesh.dispatch", parent=ctx,
                          lanes=len(pubs),
                          shards=view.n_shards) as span:
            be = self._jax_backend()
            if be is not None and not be.is_warm(view, plan, msgs):
                # a shape this process never compiled (a just-degraded
                # or just-regrown factoring whose bucket the boot warm
                # could not know): NEVER compile it on the live
                # dispatch thread — minutes of XLA would stall every
                # tile and trip the watchdog. Serve this batch on the
                # trusted native path and compile the new shape in the
                # background; dispatches re-enter the mesh once it is
                # warm.
                self._warm_in_background(view, plan, pubs, msgs, sigs)
                if self.metrics is not None:
                    self.metrics.tiles.inc()
                    self.metrics.lanes.inc(len(pubs), backend="cpu")
                with tracer.start("mesh.cpu_reverify", parent=span,
                                  reason="cold-shape"):
                    out = _native_verify(pubs, msgs, sigs)
                return out, [CPU_SHARD] * len(pubs)
            if tracer.enabled:
                # per-shard child spans: how the plan factored this
                # batch over the serving view (lane counts per shard)
                per_shard = [0] * view.n_shards
                for i in range(len(pubs)):
                    per_shard[plan.shard_of(i)] += 1
                for s, n in enumerate(per_shard):
                    tracer.start("mesh.shard", parent=span,
                                 shard=view.shard_ids[s], lanes=n).end()
            padded = plan.build(pubs, msgs, sigs)
            rows = self._backend(view, plan, *padded)
            real, bad_shards = plan.extract(rows)
            if self.metrics is not None:
                self.metrics.tiles.inc()
            if not bad_shards:
                if self.metrics is not None:
                    self.metrics.lanes.inc(len(pubs), backend="mesh")
                shards = [view.shard_ids[plan.shard_of(i)]
                          for i in range(len(pubs))]
                return real, shards
            # one or more shards answered canary/pad rows wrong: mask
            # each (mesh re-factors smaller), and THIS batch
            # re-verifies on the trusted CPU path — no shard verdict
            # from a batch containing a lying chip is ever surfaced
            span.event("canary-failure",
                       shards=[view.shard_ids[s] for s in bad_shards])
            for s in bad_shards:
                self.supervisor.report_shard_corruption(
                    view.shard_ids[s],
                    f"canary/pad rows wrong "
                    f"(view {view.shape[0]}x{view.shape[1]})")
            if self.metrics is not None:
                self.metrics.lanes.inc(len(pubs), backend="cpu")
            with tracer.start("mesh.cpu_reverify", parent=span,
                              reason="canary-failure"):
                out = _native_verify(pubs, msgs, sigs)
            return out, [CPU_SHARD] * len(pubs)

    def _maybe_probe(self) -> None:
        """Run EVERY due regrow probe this turn: probe_due() claims
        each due shard (adds it to the supervisor's in-probe set), so
        skipping one here would strand it claimed-but-never-probed and
        it could never rejoin. The set is bounded by the device count
        and windows are backoff-spaced, so a turn probes at most a
        handful of known-answer pairs."""
        for shard in self.supervisor.probe_due():
            if self._probe_backend is not None:
                verify_fn = lambda p, m, s: self._probe_backend(  # noqa: E731
                    shard, p, m, s)
            else:
                be = self._jax_backend()
                if be is not None:
                    verify_fn = be.probe_fn(self.topology.device(shard))
                else:
                    # stub backend without a probe seam: probe through
                    # the full backend on a single-shard (1,1)
                    # sub-view of the masked shard
                    verify_fn = lambda p, m, s: self._stub_probe(  # noqa: E731
                        shard, p, m, s)
            self.supervisor.probe(shard, verify_fn)

    def _warm_in_background(self, view: MeshView, plan: LanePlan,
                            pubs, msgs, sigs) -> None:
        """Compile one cold (device set, bucket, msg-cap) off the
        dispatch thread. At most one background warm at a time (mesh
        compiles serialize inside XLA anyway); only the dispatch
        thread touches _bg_warm, so no lock."""
        if self._bg_warm is not None and self._bg_warm.is_alive():
            return
        backend = self._backend
        batch = plan.build(list(pubs), list(msgs), list(sigs))

        def run():
            try:
                backend(view, plan, *batch)
            except Exception:  # noqa: BLE001 — a failed warm just
                # keeps the shape cold; dispatches stay on CPU
                pass
        self._bg_warm = threading.Thread(target=run, name="mesh-warm",
                                         daemon=True)
        self._bg_warm.start()

    def _stub_probe(self, shard: int, pubs, msgs, sigs):
        sub = MeshView(shard_ids=(shard,), shape=(1, 1),
                       generation=-1 - shard,
                       devices=(self.topology.device(shard),))
        plan = plan_lanes(len(pubs), 1, canary=False)
        real, _bad = plan.extract(
            self._backend(sub, plan, *plan.build(pubs, msgs, sigs)))
        return real

    def status(self) -> dict:
        st = self.supervisor.status()
        st["tiles_per_shard"] = self.tiles_per_shard
        st["depth_hint"] = self.depth_hint()
        return st
