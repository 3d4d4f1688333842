"""Bounded-queue staged scheduler: K verification tiles in flight.

The synchronous blocksync loop (engine/blocksync._sync_tile) serializes
fetch → marshal → verify → apply, so the host idles while the device
verifies and the device idles while the host works. Here the stages
pipeline — the standard answer for verification engines (the FPGA ECDSA
engine of arXiv:2112.02229 overlaps decode/marshal with curve compute):

    fetch    — engine/pool.py lookahead keeps the wire busy already;
               the scheduler pulls whole tile ranges ahead of apply
    marshal  — engine/blocksync.marshal_commit (the lifted standalone
               form of TiledCommitVerifier._add_commit), run on the
               host for tile N+1 while tile N verifies
    dispatch — non-blocking submit to a verify backend: the in-process
               dispatch thread (LocalAsyncBackend — JAX device work for
               tile N overlaps host marshal of tile N+1), the device
               server's DeviceClient.submit() future seam, or a stub
    apply    — strictly SEQUENTIAL, in height order, with the same
               `_verified_seal` digest check and respeculation rules as
               the synchronous loop

Safety is unchanged from the synchronous path because apply is the only
stage that touches state, and it runs the identical per-height checks
(engine/blocksync._apply_one): speculative marshal across a validator-set
change re-verifies on hash mismatch exactly as the current tile loop
does. `depth=1` IS the synchronous path, one tile at a time.

Wedge handling: every dispatch is bounded by the DeviceWatchdog; a
deadline miss drains this and all in-flight tiles to the CPU fallback
(native per-signature verify) so a wedged device degrades catch-up
speed, never liveness. With a DeviceSupervisor attached (device/
health.py) the drain is no longer a one-way door: the scheduler probes
the suspect device with a cheap known-answer batch once per backoff
window and resumes device dispatch when the supervisor returns to
HEALTHY. The supervisor also arms canary lanes — a known-good and
known-bad signature spliced into every device batch and stripped from
the results; a canary verdict mismatch quarantines the device (terminal)
and re-verifies that whole batch on CPU, so device results are never
trusted un-canaried.
"""

from __future__ import annotations

import contextlib
import inspect
import queue
import sys
import threading
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..device import health
from ..engine.blocksync import (BlocksyncReactor, SyncStalled,
                                TileApplyError, TileEntry, marshal_commit,
                                settle_tile, verify_lanes)
from ..libs.fail import fail_point
from ..state.execution import BlockValidationError
from ..state.state import VALSET_ENCODINGS, State
from ..trace import ctx_of, shared_tracer
from ..types.block import SIG_ENCODINGS, SIGN_BYTES_TEMPLATES
from .cache import insert_span_attrs


# --- futures + verify backends ------------------------------------------------

class VerifyFuture:
    """Minimal future for verify dispatches: result(timeout) returns the
    per-lane verdict sequence or raises (TimeoutError on deadline,
    whatever the backend set otherwise)."""

    def __init__(self):
        self._ev = threading.Event()
        self._out = None
        self._exc: Optional[BaseException] = None

    def set_result(self, out) -> None:
        self._out = out
        self._ev.set()

    def set_exception(self, exc: BaseException) -> None:
        self._exc = exc
        self._ev.set()

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("verify dispatch still pending")
        if self._exc is not None:
            raise self._exc
        return self._out


class LocalAsyncBackend:
    """In-process async dispatch: one daemon thread runs the verify
    function (ops/ed25519 via engine/blocksync.verify_lanes) so
    submit() returns immediately — JAX device dispatch of tile N
    overlaps host marshal of tile N+1. A verify crash lands in the
    future as an exception; the watchdog turns it into a CPU fallback.

    Who waits for whom: the thread sends a tile's chunks to the device
    one after the other and reads their verdicts back once, after the
    last (`ops.ed25519._verify_batch_loop`), then sets the future; the
    main thread asks for the future `depth - 1` tiles later and should
    find it set. The two share the interpreter lock: every step of the
    thread that lets go of it (entropy, a transfer, an execute, a
    read-back) has to get it back from a main thread that is running
    Python, which is why `PipelinedBlocksync.run` shortens the switch
    interval while it owns such a backend."""

    def __init__(self, verify_fn, name: str = "pipeline-verify"):
        self._verify = verify_fn
        self._q: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def submit(self, pubs, msgs, sigs) -> VerifyFuture:
        fut = VerifyFuture()
        self._q.put((fut, pubs, msgs, sigs))
        return fut

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                fut, pubs, msgs, sigs = self._q.get(timeout=0.1)
            except queue.Empty:
                continue
            try:
                fut.set_result(self._verify(pubs, msgs, sigs))
            except BaseException as e:  # noqa: BLE001 — surface via future
                fut.set_exception(e)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        # fail queued-but-undispatched work: a caller blocked in
        # result() with no timeout would otherwise hang forever on a
        # future the (now stopped) worker will never resolve
        while True:
            try:
                fut, _pubs, _msgs, _sigs = self._q.get_nowait()
            except queue.Empty:
                break
            if not fut.done():
                fut.set_exception(
                    ConnectionError("verify backend closed"))


class ReconnectBlocked(health.AccountedTransportError):
    """shared_client() could not produce a link: either the connect
    attempt failed (that failure already reported a trip to the
    supervisor) or the half-open backoff window is still closed (no
    attempt was made, so there is no new failure to report). Either
    way neither the dispatch fallback nor supervisor.probe() may
    report a second trip — doing so would double-count one outage and
    deepen the backoff twice."""


class DeviceClientBackend:
    """Dispatch to the host's TPU-owner device server through the
    non-blocking DeviceClient.submit() seam; result() adapts the
    (batch_ok, oks) wire answer to a plain verdict sequence."""

    class _Adapter:
        def __init__(self, fut):
            self._fut = fut

        def done(self) -> bool:
            return self._fut.done()

        def cancel(self) -> None:
            self._fut.cancel()

        def result(self, timeout: Optional[float] = None):
            _batch_ok, oks = self._fut.result(timeout)
            return oks

    def __init__(self, client):
        self._client = client

    def submit(self, pubs, msgs, sigs, ctx=None):
        c = self._client
        if c is None or c._dead is not None:
            # ride the supervisor-gated reconnect: shared_client()
            # drops dead links and honors the half-open backoff — this
            # is what lets a probe reach a RESTARTED device server
            # instead of re-trying the socket this backend was built on
            from ..device.client import shared_client
            c = shared_client()
            if c is None:
                raise ReconnectBlocked(
                    "device link down, no reconnect")
            self._client = c
        # ctx is an opt-in keyword: a reconnect can hand us any client
        # implementation (tests inject plain-signature stubs), so only
        # forward trace context to clients that declare it
        if ctx is not None and "ctx" in inspect.signature(
                c.submit).parameters:
            return self._Adapter(c.submit(pubs, msgs, sigs, ctx=ctx))
        return self._Adapter(c.submit(pubs, msgs, sigs))

    def close(self) -> None:
        pass  # the client is shared process-wide; never closed here


class FixedLatencyBackend:
    """Bench/test stub of an RTT-bound device: every dispatch answers a
    fixed latency after submit, independent of other in-flight
    dispatches (a remote device's cost is dominated by round-trip +
    queueing, not lane occupancy). verify_fn=None answers all-true (valid-chain
    benchmarks); otherwise verdicts are computed in the timer thread."""

    def __init__(self, latency_s: float, verify_fn=None):
        self.latency_s = latency_s
        self._verify = verify_fn
        self.dispatches = 0

    def submit(self, pubs, msgs, sigs) -> VerifyFuture:
        self.dispatches += 1
        fut = VerifyFuture()

        def fire():
            try:
                out = (self._verify(pubs, msgs, sigs)
                       if self._verify is not None
                       else [True] * len(pubs))
                fut.set_result(out)
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)
        t = threading.Timer(self.latency_s, fire)
        t.daemon = True
        t.start()
        return fut

    def close(self) -> None:
        pass


class HangingBackend:
    """The wedge fixture: dispatches never answer (until release())."""

    def __init__(self):
        self._pending: List[Tuple[VerifyFuture, int]] = []
        self.dispatches = 0

    def submit(self, pubs, msgs, sigs) -> VerifyFuture:
        self.dispatches += 1
        fut = VerifyFuture()
        self._pending.append((fut, len(pubs)))
        return fut

    def release(self) -> None:
        for fut, n in self._pending:
            if not fut.done():
                fut.set_result([True] * n)

    def close(self) -> None:
        self.release()  # unblock anything still waiting


class FlakyBackend:
    """Transient-stall fixture (the device-flap model): the first
    `fail_dispatches` submits raise ConnectionError, after which every
    submit answers synchronously with CPU-computed verdicts — so a
    supervisor's half-open probe succeeds once the flap passes and the
    scheduler resumes device dispatch. Synchronous resolution keeps
    simnet logs byte-identical (no wall-clock timer threads)."""

    def __init__(self, fail_dispatches: int = 1, verify_fn=None):
        self._verify = verify_fn or (
            lambda p, m, s: verify_lanes(p, m, s, 0))
        self.fail_left = fail_dispatches
        self.dispatches = 0
        self.served = 0  # successful answers (post-recovery activity)

    def submit(self, pubs, msgs, sigs) -> VerifyFuture:
        self.dispatches += 1
        if self.fail_left > 0:
            self.fail_left -= 1
            raise ConnectionError("device stalled (flap)")
        fut = VerifyFuture()
        fut.set_result(self._verify(pubs, msgs, sigs))
        self.served += 1
        return fut

    def close(self) -> None:
        pass


class CorruptBackend:
    """The silently-corrupt device model: answers every lane True
    regardless of the signature — exactly the failure a canary lane
    exists to catch (the known-bad canary comes back True). Answers
    synchronously for simnet determinism."""

    def __init__(self):
        self.dispatches = 0
        self.served = 0

    def submit(self, pubs, msgs, sigs) -> VerifyFuture:
        self.dispatches += 1
        self.served += 1
        fut = VerifyFuture()
        fut.set_result([True] * len(pubs))
        return fut

    def close(self) -> None:
        pass


# --- the scheduler ------------------------------------------------------------

# CPython hands the interpreter lock to a thread that asks for it only
# after this long (5 ms by default) when the holder runs Python without
# a pause, as the main thread's marshal and apply do: every step of the
# dispatch thread that let go of the lock then waits that long to go on.
# PERF.md section 6 (PR 35) has the runs on the chip that chose the value.
_SWITCH_INTERVAL_S = 0.0002


@contextlib.contextmanager
def _host_stage_span(tracer, name: str, parent):
    """A main-thread stage's span, carrying the CommitSig wire
    encodings the stage computed and the ones it reused (fetch pays a
    commit's one encoding in make_part_set, apply meets it three times
    more: last_commit_hash and the `C:`/`SC:` store keys), and the
    validator-set encodings likewise (StateStore.save asks four a block
    applied, fetch none). The counters are process-wide; the two stages
    never overlap and nothing else on the catch-up path encodes a
    CommitSig or a validator set."""
    computed, reused = SIG_ENCODINGS
    vs_computed, vs_reused = VALSET_ENCODINGS
    with tracer.start(name, parent=parent) as span:
        try:
            yield span
        finally:
            span.set_attr("sig_enc_computed", SIG_ENCODINGS[0] - computed)
            span.set_attr("sig_enc_reused", SIG_ENCODINGS[1] - reused)
            span.set_attr("valset_enc_computed",
                          VALSET_ENCODINGS[0] - vs_computed)
            span.set_attr("valset_enc_reused",
                          VALSET_ENCODINGS[1] - vs_reused)


@dataclass
class _Tile:
    start: int
    end: int
    fetched: Dict[int, tuple]
    entries: List[TileEntry]
    metas: list
    pubs: List[bytes]
    msgs: List[bytes]
    sigs: List[bytes]
    keys: List[bytes]                # each lane's sigcache key
    future: object = None            # None => out already final
    out: Optional[np.ndarray] = None
    valset_break: int = 0            # height whose header announced a
    #                                  new valset (0: none in this tile)
    n_canaries: int = 0              # canary lanes appended at dispatch
    span: object = None              # trace span: build..settle lifetime

    @property
    def n_lanes(self) -> int:
        return len(self.pubs)


class PipelinedBlocksync:
    """Runs a BlocksyncReactor's catch-up with `depth` tiles in flight.

    Constructed by BlocksyncReactor.sync() when pipeline_depth > 1; the
    reactor owns source/executor/store/stats/_verified_seal so the two
    paths share every stage implementation and all bookkeeping."""

    def __init__(self, reactor: BlocksyncReactor, depth: int = 4,
                 backend=None, watchdog=None, metrics=None,
                 supervisor=None):
        self.r = reactor
        self._own_backend = backend is None
        self.backend = backend or LocalAsyncBackend(
            lambda p, m, s: verify_lanes(
                p, m, s, reactor.verifier.batch_size))
        # the bounded queue sizes from the backend's SHARD count: a
        # mesh backend (mesh/executor.MeshExecutor exposes n_shards)
        # needs K tiles in flight PER SHARD for the PR-2 pipeline win
        # and N-chip sharding to compose — depth alone would leave
        # N-1 shards idle between tiles. Single-chip backends report
        # (or default to) 1 shard and keep the old semantics exactly.
        # Clamped to the backend's bounded dispatch queue: a deep
        # pipeline_depth config must shrink here, not overflow the
        # executor into MeshOverloaded trips the watchdog would latch
        # as a wedge.
        shards = max(1, int(getattr(self.backend, "n_shards", 1)))
        depth = max(1, depth) * shards
        cap = getattr(self.backend, "queue_capacity", None)
        if isinstance(cap, int) and cap > 0:
            depth = min(depth, cap)
        self.depth = depth
        # ctx propagation is opt-in per backend (mesh + device client
        # backends take ctx=; the LocalAsyncBackend and injected test
        # backends keep their plain 3-arg submit) — decided once here
        self._backend_takes_ctx = (
            "ctx" in inspect.signature(self.backend.submit).parameters)
        self.watchdog = watchdog
        self.metrics = metrics
        self.supervisor = supervisor  # device/health.DeviceSupervisor
        if supervisor is not None and watchdog is not None \
                and watchdog.supervisor is None:
            watchdog.supervisor = supervisor
        # `pipeline.ban`: opened at a ban, closed by the first block a
        # later pass applies, so it outlives the run() that opened it
        self._ban_span = None

    def close(self) -> None:
        if self._ban_span is not None:
            # the sync gave up before a refetched block applied
            self._ban_span.set_attr("outcome", "gave-up")
            self._ban_span.end()
            self._ban_span = None
        if self._own_backend:
            self.backend.close()

    # --- stages -----------------------------------------------------------

    def _build_tile(self, start: int, target: int, spec_vals) -> _Tile:
        """fetch + marshal + dispatch for one tile (raises SyncStalled
        when the source cannot serve the range)."""
        tracer = shared_tracer()
        tspan = tracer.start("pipeline.tile", start=start)
        try:
            return self._build_tile_traced(start, target, spec_vals,
                                           tracer, tspan)
        except BaseException:
            tspan.set_attr("outcome", "error")
            tspan.end()
            raise

    def _build_tile_traced(self, start, target, spec_vals, tracer,
                           tspan) -> _Tile:
        self._occupy("fetch", 1)
        try:
            with _host_stage_span(tracer, "pipeline.fetch", tspan):
                fetched, end = self.r._fetch_range(start, target)
        finally:
            self._occupy("fetch", 0)

        self._occupy("marshal", 1)
        marshal_span = tracer.start("pipeline.marshal", parent=tspan)
        templates, templated = SIGN_BYTES_TEMPLATES
        try:
            spec_hash = spec_vals.hash()
            entries: List[TileEntry] = []
            valset_break = 0
            for h in range(start, end + 1):
                block, _parts, bid = fetched[h]
                if block.header.validators_hash != spec_hash:
                    # valset changes: heights from here respeculate at
                    # apply against the true set, and the scheduler
                    # stops filling until the pipeline drains
                    valset_break = h
                    break
                entries.append(TileEntry(
                    height=h, block=block, block_id=bid, valset=spec_vals,
                    commit=fetched[h + 1][0].last_commit))
            pubs: List[bytes] = []
            msgs: List[bytes] = []
            sigs: List[bytes] = []
            keys: List[bytes] = []
            metas = [marshal_commit(self.r.verifier.chain_id, e, pubs,
                                    msgs, sigs, self.r.cache, keys)
                     for e in entries]
        finally:
            # as _host_stage_span's: process-wide counters, this
            # thread's delta (a commit's first lane builds its
            # sign-bytes template, the others are served from it)
            marshal_span.set_attr("sign_bytes_templates",
                                  SIGN_BYTES_TEMPLATES[0] - templates)
            marshal_span.set_attr("sign_bytes_templated",
                                  SIGN_BYTES_TEMPLATES[1] - templated)
            marshal_span.end()
            self._occupy("marshal", 0)

        tile = _Tile(start=start, end=end, fetched=fetched,
                     entries=entries, metas=metas, pubs=pubs, msgs=msgs,
                     sigs=sigs, keys=keys, valset_break=valset_break, span=tspan)
        tspan.set_attr("end", end)
        tspan.set_attr("lanes", len(pubs))
        if not pubs:
            tile.out = np.zeros((0,), dtype=bool)  # all cached/absent
        elif self._device_blocked():
            # wedged/suspect/quarantined (and no probe recovered it):
            # don't even dispatch — drain this tile straight to the CPU
            if self.watchdog is not None:
                self.watchdog._fallback()
            with tracer.start("pipeline.cpu_drain", parent=tspan,
                              reason="device-blocked"):
                tile.out = self._cpu_verify(pubs, msgs, sigs)
        else:
            d_pubs, d_msgs, d_sigs = pubs, msgs, sigs
            if self.supervisor is not None and self.supervisor.canary:
                # canary lanes ride every device batch; tile.pubs stays
                # canary-free for the CPU re-verify path
                d_pubs, d_msgs, d_sigs = health.splice_canaries(
                    pubs, msgs, sigs)
                tile.n_canaries = health.CANARY_LANES
            fail_point("pipeline:dispatch")
            try:
                if self._backend_takes_ctx:
                    tile.future = self.backend.submit(
                        d_pubs, d_msgs, d_sigs, ctx=tspan)
                else:
                    tile.future = self.backend.submit(
                        d_pubs, d_msgs, d_sigs)
            except Exception as e:  # noqa: BLE001 — a dead device link
                # at submit degrades exactly like a deadline miss;
                # ReconnectBlocked was already accounted inside
                # shared_client(), so only count the fallback for it
                tile.n_canaries = 0
                accounted = isinstance(e, health.AccountedTransportError)
                if self.watchdog is not None:
                    if not accounted:
                        self.watchdog._trip(e)
                    self.watchdog._fallback()
                elif self.supervisor is not None and not accounted:
                    self.supervisor.report_trip(e)
                with tracer.start("pipeline.cpu_drain", parent=tspan,
                                  reason="submit-error"):
                    tile.out = self._cpu_verify(pubs, msgs, sigs)
                return tile
            if self.metrics is not None:
                self.metrics.tiles_dispatched.inc()
        return tile

    def _device_blocked(self) -> bool:
        """Decide whether this tile may dispatch to the device. The
        supervisor path is half-open: a due probe runs ONE cheap
        known-answer batch against the backend; success resumes device
        dispatch immediately (this very tile)."""
        sup = self.supervisor
        if sup is None:
            return self.watchdog is not None and self.watchdog.wedged
        if sup.can_dispatch():
            return False
        if sup.probe_due():
            return not sup.probe(self._probe_verify)
        return True

    def _probe_verify(self, pubs, msgs, sigs):
        """supervisor.probe adapter: one backend round trip under the
        probe deadline; exceptions (timeout, transport) propagate to
        the supervisor, which deepens the backoff."""
        fut = self.backend.submit(pubs, msgs, sigs)
        try:
            return fut.result(self.supervisor.probe_deadline_s)
        except BaseException:
            cancel = getattr(fut, "cancel", None)
            if cancel is not None:
                cancel()
            raise

    @staticmethod
    def _cpu_verify(pubs, msgs, sigs) -> np.ndarray:
        # the watchdog's drain target: native per-sig verify, never a
        # device (or jit-compile) dependency
        return verify_lanes(pubs, msgs, sigs, 0)

    @staticmethod
    def _cancel(tile: "_Tile") -> None:
        """Abandon a dispatched tile's future (nothing will collect the
        answer — without this, DeviceClient retains late verdicts in
        _results forever)."""
        fut = tile.future
        if fut is not None:
            cancel = getattr(fut, "cancel", None)
            if cancel is not None:
                cancel()

    @classmethod
    def _abandon(cls, tile: "_Tile") -> None:
        """Throw away a speculated tile that will never settle (a ban, an
        escape): its future cancelled, its `pipeline.tile` span ended
        with `outcome` = abandoned, so that it reaches the ring."""
        cls._cancel(tile)
        if tile.span is not None:
            tile.span.set_attr("outcome", "abandoned")
            tile.span.end()
            tile.span = None

    def _settle(self, tile: _Tile) -> None:
        """Resolve the tile's verdicts and map them onto
        entry.commit_ok. The tile was handed to the backend `depth - 1`
        tiles ago, so its verdicts should be on the host already:
        `pipeline.settle.wait` is what the main thread still sleeps for
        them (under the watchdog deadline; CPU fallback on wedge), the
        rest of `pipeline.settle` is `settle_tile`'s host work."""
        tracer = shared_tracer()
        sspan = tracer.start("pipeline.settle", parent=tile.span)
        try:
            if tile.out is None:
                total = tile.n_lanes + tile.n_canaries
                with tracer.start("pipeline.settle.wait", parent=sspan):
                    if self.watchdog is not None:
                        out = self.watchdog.result(tile.future, total)
                    else:
                        out = tile.future.result()
                if out is None:  # wedged: drain tile to the CPU
                    self._cancel(tile)
                    with tracer.start("pipeline.cpu_drain", parent=sspan,
                                      reason="watchdog-wedge"):
                        out = self._cpu_verify(
                            tile.pubs, tile.msgs, tile.sigs)
                else:
                    out = self._canary_check(tile, out, sspan)
                tile.out = np.asarray(out, dtype=bool)
            with insert_span_attrs(self.r.cache, sspan):
                settle_tile(tile.metas, tile.out, tile.pubs, tile.msgs,
                            tile.sigs, self.r.cache, tile.keys)
            if tile.entries:
                self.r.stats.tiles_flushed += 1
                self.r.stats.sigs_verified += sum(
                    1 for e in tile.entries for cs in e.commit.signatures
                    if not cs.absent_())
        finally:
            sspan.end()
            if tile.span is not None:
                tile.span.end()
                tile.span = None

    def _canary_check(self, tile: _Tile, out, sspan=None):
        """Strip + verify this tile's canary lanes. A mismatch means
        the device returned corrupt VERDICTS (not a transport failure):
        quarantine it and re-verify the whole batch on CPU — a device
        answer is never trusted un-canaried. A correct answer reports
        success (PROBING → HEALTHY after a mid-probe full batch)."""
        if not tile.n_canaries:
            return out
        ok, stripped = health.check_canaries(out, tile.n_lanes)
        if ok:
            if self.supervisor is not None:
                self.supervisor.report_success()
            return stripped
        if sspan is not None:
            sspan.event("canary-failure", tile=tile.start)
        if self.supervisor is not None:
            self.supervisor.report_corruption(
                f"tile {tile.start}..{tile.end} canary mismatch")
        if self.watchdog is not None:
            self.watchdog._fallback()  # count the drain like a wedge
        with shared_tracer().start("pipeline.cpu_drain", parent=sspan,
                                   reason="canary-failure"):
            return self._cpu_verify(tile.pubs, tile.msgs, tile.sigs)

    def _occupy(self, stage: str, n: int) -> None:
        if self.metrics is not None:
            self.metrics.stage_occupancy.set(n, stage=stage)

    def _inflight_gauge(self, n: int) -> None:
        if self.metrics is not None:
            self.metrics.tiles_in_flight.set(n)
            self.metrics.stage_occupancy.set(n, stage="dispatch")

    # --- the run loop -----------------------------------------------------

    def run(self, state: State, target: int) -> State:
        """One catch-up pass: process tiles until target or failure.
        Mirrors _sync_tile's contract: on a bad block the peer is
        banned and either the partially-advanced state returns (caller
        retries the remainder) or BlockValidationError raises when
        nothing was applied this pass.

        While it owns an in-process `LocalAsyncBackend`, the pass runs
        under `_SWITCH_INTERVAL_S`: the dispatch thread gets the
        interpreter lock when it asks, so a tile's verdicts are on the
        host before `_settle` asks for them. The interval is the
        process's; the former value is back however the pass ends. A
        backend that is not in-process leaves it alone."""
        former = sys.getswitchinterval()
        if not self._own_backend or former <= _SWITCH_INTERVAL_S:
            return self._run_tiles(state, target)
        sys.setswitchinterval(_SWITCH_INTERVAL_S)
        try:
            return self._run_tiles(state, target)
        finally:
            # CPython keeps whole microseconds and truncates: half of one
            # more brings back the very value that was read
            sys.setswitchinterval(former + 5e-7)

    def _run_tiles(self, state: State, target: int) -> State:
        r = self.r
        tracer = shared_tracer()
        inflight: "deque[_Tile]" = deque()
        spec_vals = state.validators
        next_start = state.last_block_height + 1
        applied_any = False
        # valset change seen: drain before refilling. The open
        # `pipeline.barrier` span IS the flag (a no-op span with tracing
        # off): from the tile that sees the change to the resumption of
        # speculation from the new set
        barrier = None
        try:
            while state.last_block_height < target or inflight:
                # fill: keep up to `depth` tiles fetched+marshaled+
                # dispatched ahead of the apply stage
                while (barrier is None and len(inflight) < self.depth
                       and next_start <= target):
                    try:
                        tile = self._build_tile(next_start, target,
                                                spec_vals)
                    except SyncStalled:
                        if not inflight:
                            raise
                        break  # drain what we have; refill retries fetch
                    inflight.append(tile)
                    next_start = tile.end + 1
                    if tile.valset_break:
                        barrier = tracer.start(
                            "pipeline.barrier",
                            change_height=tile.valset_break,
                            tiles_drained=len(inflight))
                self._inflight_gauge(len(inflight))
                if not inflight:
                    if state.last_block_height >= target:
                        break
                    # barrier drained (or stall): resume speculation from
                    # the now-current validator set
                    if barrier is not None:
                        barrier.end()
                        barrier = None
                    spec_vals = state.validators
                    continue

                tile = inflight.popleft()
                self._inflight_gauge(len(inflight))
                tile_ctx = ctx_of(tile.span)  # _settle ends the span
                self._settle(tile)
                self._occupy("apply", 1)
                try:
                    with _host_stage_span(tracer, "pipeline.apply",
                                          tile_ctx):
                        by_height = {e.height: e for e in tile.entries}
                        h = tile.start
                        while h <= tile.end:
                            block, parts, block_id = tile.fetched[h]
                            seal_commit = tile.fetched[h + 1][0].last_commit
                            try:
                                state = r._apply_one(
                                    state, h, block, parts, block_id,
                                    seal_commit, by_height.get(h))
                            except TileApplyError as f:
                                r.source.ban(h)
                                r.stats.bans += 1
                                if self._ban_span is None:
                                    self._ban_span = tracer.start(
                                        "pipeline.ban", height=h,
                                        tiles_cancelled=len(inflight),
                                        lanes_abandoned=sum(
                                            t.n_lanes for t in inflight))
                                # drop everything speculative: the
                                # remainder refetches (possibly
                                # re-routed) in a fresh pass; cancel
                                # abandoned dispatches so the device
                                # client doesn't retain their answers
                                for t in inflight:
                                    self._abandon(t)
                                inflight.clear()
                                if applied_any:
                                    return state
                                raise BlockValidationError(str(f)) from f
                            applied_any = True
                            if self._ban_span is not None:
                                self._ban_span.set_attr("refetched", h)
                                self._ban_span.end()
                                self._ban_span = None
                            h += 1
                finally:
                    self._occupy("apply", 0)
                if barrier is not None and not inflight:
                    barrier.end()
                    barrier = None
                    spec_vals = state.validators
                    next_start = state.last_block_height + 1
        except BaseException:
            # an escape with tiles still speculated (a _settle crash, a
            # SyncStalled with nothing applied) must not strand their
            # dispatches — cancel so the device client drops the
            # answers instead of retaining them for nobody
            for t in inflight:
                self._abandon(t)
            inflight.clear()
            raise
        finally:
            self._inflight_gauge(0)
            if barrier is not None:
                # a ban or an escape cut the barrier short: the next
                # pass meets the same change again
                barrier.set_attr("outcome", "cut-short")
                barrier.end()
        return state
