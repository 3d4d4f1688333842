"""Verify device server: the persistent process that OWNS the TPU and
serves batched ed25519 verification to every other process on the host
(SURVEY §7 step 2 "device server"; the reference's analog boundary is
Go → cgo → curve25519-voi in-process — on TPU the device must be held
by one process, so the boundary becomes a local socket).

Design, TPU-first:
- kernels compile ONCE per bucket size at startup (static shapes);
- requests from all connections accumulate in a queue and are flushed
  as one device tile (cross-request coalescing — the accumulate-and-
  flush stance SURVEY §7 prescribes for every verify call site: many
  small commits become one large lane-parallel batch);
- per-lane verdicts are routed back per request, so one bad signature
  in client A's commit never forces client B into a retry.
"""

from __future__ import annotations

import os
import queue
import socket
import threading
from dataclasses import dataclass
from typing import List, Optional

from ..trace import shared_tracer
from ..trace.context import TraceContext
from .health import CANARY_LANES
from .protocol import (decode_request, decode_request_trace,
                       encode_response, recv_frame, send_frame)


@dataclass
class _Job:
    sock: socket.socket
    lock: threading.Lock  # per-connection write lock
    req_id: int
    pubs: List[bytes]
    msgs: List[bytes]
    sigs: List[bytes]
    ctx: Optional[TraceContext] = None  # request trace trailer


class DeviceServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 bucket: int = 1024, max_msg_len: int = 256,
                 flush_us: int = 200, mesh: bool = False,
                 mesh_devices: int = 0, sig_parallel: int = 0,
                 tiles_per_shard: int = 4):
        from ..libs.jax_cache import is_device_platform
        if not is_device_platform() and bucket > 64:
            # XLA:CPU crashes (compiler stack overflow) building the
            # RLC kernel at batch >=256 and takes minutes at 64+
            # (docs/PERF.md); a CPU-backed dev server clamps rather
            # than dying inside _warm
            bucket = 64
        self.bucket = bucket
        self.max_msg_len = max_msg_len
        self.flush_s = flush_us / 1e6
        # mesh mode: the server owns EVERY local device as one
        # (commit, sig) verification mesh (mesh/ — docs/MESH.md)
        # instead of a single chip; responses then carry the per-lane
        # shard attribution trailer. The (1,1) single-device case is
        # served by the same executor (its degenerate path), so one
        # code path covers both deployments.
        self.mesh = mesh
        self.mesh_devices = mesh_devices
        self.sig_parallel = sig_parallel
        self.tiles_per_shard = tiles_per_shard
        self._mesh_exec = None  # mesh.MeshExecutor once warmed
        self._jobs: "queue.Queue[Optional[_Job]]" = queue.Queue()
        self._listener = socket.socket()
        self._listener.setsockopt(socket.SOL_SOCKET,
                                  socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(64)
        self.addr = self._listener.getsockname()
        self._stop = threading.Event()
        self.stats = {"requests": 0, "signatures": 0, "flushes": 0}

    # --- device side ----------------------------------------------------------

    def _warm(self) -> None:
        """Compile BOTH kernels for the configured bucket before
        accepting traffic (first-compile latency must not land on a
        live commit): the RLC fast path, and — by feeding one tampered
        signature — the per-lane attribution fallback it degrades to."""
        from ..libs.jax_cache import enable_compile_cache
        enable_compile_cache()
        from ..ops.ed25519 import verify_batch
        seed = b"\x01" * 32
        from ..crypto import ref_ed25519 as ref
        pub = ref.pubkey_from_seed(seed)
        sig = ref.sign(seed, b"warm")
        # corrupt a LOW byte of s (offset 32..63): the signature stays
        # structurally valid so the RLC batch EQUATION fails and the
        # per-lane fallback actually compiles. (Corrupting R made the
        # lane fail at decompression — struct_ok already attributes
        # that without the fallback, which then first compiled minutes
        # into a live commit verification.)
        bad = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        # compile-ledger attribution (ROADMAP item-5 residual): the
        # warm cost is keyed (kernel, bucket) so later server/bench
        # runs can predict a warm reload vs a multi-minute cold
        # compile, and a compiler crash marks the bucket bad instead
        # of being rediscovered next round
        from ..libs.jax_cache import ledger
        with ledger().compile_guard("ed25519-rlc", self.bucket):
            verify_batch([pub], [b"warm"], [sig], batch_size=self.bucket)
        with ledger().compile_guard("ed25519-rlc-fallback", self.bucket):
            verify_batch([pub], [b"warm"], [bad], batch_size=self.bucket)
        if self.mesh:
            self._warm_mesh()

    def _warm_mesh(self) -> None:
        """Build + warm the mesh executor: topology over the local
        devices, planned bucket compiles recorded in the CompileLedger
        under mesh-shape keys (mesh compiles are minutes, not
        milliseconds — they may NEVER land on a live flush)."""
        from ..mesh import MeshExecutor, MeshTopology
        from ..mesh.planner import width_ladder
        topology = MeshTopology(
            n_devices=self.mesh_devices or None,
            sig_parallel=self.sig_parallel or None)
        self._mesh_exec = MeshExecutor(
            topology, tiles_per_shard=self.tiles_per_shard)
        # warm the whole width LADDER for the widest flush the writer
        # can coalesce — NOT just self.bucket: the flush loop checks
        # `lanes < bucket` BEFORE adding the next job, and one job may
        # itself carry bucket + CANARY_LANES lanes, so a flush can
        # reach (bucket - 1) + bucket + CANARY_LANES lanes. Every
        # reachable bucket must compile before traffic — a cold mesh
        # compile inside a live flush is minutes.
        self._mesh_exec.warm(width_ladder(
            2 * self.bucket + CANARY_LANES,
            topology.view().n_shards, canary=True))
        self.stats["mesh_shards"] = topology.view().n_shards

    def _flush(self, jobs: List[_Job]) -> None:
        pubs: List[bytes] = []
        msgs: List[bytes] = []
        sigs: List[bytes] = []
        for j in jobs:
            pubs.extend(j.pubs)
            msgs.extend(j.msgs)
            sigs.extend(j.sigs)
        # one flush serves many requests (coalescing seam): the flush
        # span is a root that LINKS each submitting client's trailer
        # ctx, mirroring the ingest-flush/ticket relationship
        span = shared_tracer().start("device.flush", jobs=len(jobs),
                                     lanes=len(pubs))
        for j in jobs:
            span.link(j.ctx)
        shards = None
        try:
            if self._mesh_exec is not None:
                # the mesh data plane: lanes sharded over every device,
                # per-shard canaries checked inside the executor (a
                # lying shard is masked + the batch re-verifies on CPU
                # before any verdict reaches a client), per-lane
                # attribution returned in the response trailer. Bounded
                # wait + closed-executor handling: stop() can close the
                # executor while this worker drains its final batch,
                # and an unbounded result() would hang the flush thread
                # forever
                from .client import deadline_for
                try:
                    fut = self._mesh_exec.submit(pubs, msgs, sigs,
                                                 ctx=span)
                    oks = fut.result(deadline_for(len(pubs)))
                    shards = fut.shards
                except (ConnectionError, TimeoutError):
                    if self._stop.is_set():
                        return  # shutting down: clients are going away
                    raise
            else:
                # a message longer than the warmed shapes' (a vote
                # extension) is verified natively, never compiled for
                from ..ops.ed25519 import verify_batch_warm
                oks = verify_batch_warm(pubs, msgs, sigs, self.bucket)
        finally:
            span.end()
        self.stats["flushes"] += 1
        self.stats["signatures"] += len(pubs)
        off = 0
        for j in jobs:
            part = [bool(v) for v in oks[off:off + len(j.pubs)]]
            job_shards = (None if shards is None
                          else shards[off:off + len(j.pubs)])
            off += len(j.pubs)
            resp = encode_response(j.req_id, all(part), part,
                                   shards=job_shards)
            try:
                with j.lock:
                    send_frame(j.sock, resp)
            except OSError:
                pass  # client gone; its lanes were still verified

    def _device_routine(self) -> None:
        """Single device writer: accumulate jobs, flush as one tile.
        A failing flush (mesh dispatch timeout, backend crash) must
        never kill this thread — it is the server's ONLY writer, and
        a dead writer leaves every future client hanging silently.
        The failed batch answers UNPROCESSABLE (zero lanes) so those
        clients fall back to local verification."""
        while not self._stop.is_set():
            try:
                job = self._jobs.get(timeout=0.5)
            except queue.Empty:
                continue
            if job is None:
                return
            batch = [job]
            lanes = len(job.pubs)
            # coalesce whatever arrives within the flush window, up to
            # the bucket capacity
            deadline = _now() + self.flush_s
            drain = False
            while lanes < self.bucket:
                try:
                    nxt = self._jobs.get(timeout=max(
                        0.0, deadline - _now()))
                except queue.Empty:
                    break
                if nxt is None:
                    drain = True
                    break
                batch.append(nxt)
                lanes += len(nxt.pubs)
            try:
                self._flush(batch)
            except Exception as e:  # noqa: BLE001 — answer, survive
                for j in batch:
                    try:
                        with j.lock:
                            send_frame(j.sock, encode_response(
                                j.req_id, False, []))
                    except OSError:
                        pass
                print(f"device server: flush failed "
                      f"({type(e).__name__}: {e}); batch answered "
                      f"UNPROCESSABLE", flush=True)
            if drain:
                return

    def _unprocessable(self, pubs: List[bytes], msgs: List[bytes]
                       ) -> bool:
        """Reject what the compiled bucket cannot serve. Canary lanes
        (device/health) ride ON TOP of a caller's bucket-sized payload,
        so the lane cap grants them headroom — without it, a batch that
        exactly filled the bucket before canaries would bounce as
        UNPROCESSABLE and trip the supervisor into a SUSPECT/HEALTHY
        flap. verify_batch chunks past the bucket; the kernel shape
        never changes."""
        return (any(len(m) > self.max_msg_len for m in msgs)
                or len(pubs) > self.bucket + CANARY_LANES)

    # --- socket side ----------------------------------------------------------

    def _serve_conn(self, sock: socket.socket) -> None:
        wlock = threading.Lock()
        try:
            while not self._stop.is_set():
                payload = recv_frame(sock)
                req_id, pubs, msgs, sigs = decode_request(payload)
                ids = decode_request_trace(payload)
                ctx = TraceContext(*ids) if ids is not None else None
                self.stats["requests"] += 1
                # oversized messages / batches can't ride the compiled
                # bucket: answer UNPROCESSABLE (zero lanes for a
                # nonzero request — distinct from per-lane failure, so
                # clients fall back locally instead of treating valid
                # signatures as forged)
                if self._unprocessable(pubs, msgs):
                    with wlock:
                        send_frame(sock, encode_response(
                            req_id, False, []))
                    continue
                self._jobs.put(_Job(sock, wlock, req_id, pubs, msgs,
                                    sigs, ctx))
        except (ConnectionError, OSError, ValueError):
            pass  # garbage or lost peer: drop the connection cleanly
        finally:
            try:
                sock.close()
            except OSError:
                pass

    def start(self) -> None:
        self._warm()
        threading.Thread(target=self._device_routine,
                         name="device-flush", daemon=True).start()

        def accept_loop():
            while not self._stop.is_set():
                try:
                    sock, _ = self._listener.accept()
                except OSError:
                    return
                threading.Thread(target=self._serve_conn, args=(sock,),
                                 daemon=True).start()

        threading.Thread(target=accept_loop, name="device-accept",
                         daemon=True).start()

    def stop(self) -> None:
        self._stop.set()
        self._jobs.put(None)
        if self._mesh_exec is not None:
            self._mesh_exec.close()
        try:
            self._listener.close()
        except OSError:
            pass


def _now() -> float:
    # deliberately wall clock: the device server is a standalone
    # process whose batch deadlines track real elapsed time; simnet
    # never runs it in-process (stub backends stand in for it)
    import time
    return time.monotonic()  # staticcheck: allow(wallclock)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="device-server")
    ap.add_argument("--laddr", default="127.0.0.1:28657")
    ap.add_argument("--bucket", type=int, default=1024)
    ap.add_argument("--max-msg-len", type=int, default=256)
    ap.add_argument("--mesh", action="store_true",
                    help="own every local device as one (commit, sig) "
                         "verification mesh (docs/MESH.md)")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="devices to mesh (0 = all local)")
    ap.add_argument("--sig-parallel", type=int, default=0,
                    help="mesh sig-axis width (0 = auto)")
    ap.add_argument("--tiles-per-shard", type=int, default=4)
    args = ap.parse_args(argv)
    # this process is the chip's owner, nobody's client — even when it
    # inherits the address its clients are pointed at
    import os
    from ..libs.jax_cache import DEVICE_SERVER_ENV, enable_compile_cache
    os.environ.pop(DEVICE_SERVER_ENV, None)
    enable_compile_cache()
    host, _, port = args.laddr.rpartition(":")
    srv = DeviceServer(host or "127.0.0.1", int(port),
                       bucket=args.bucket,
                       max_msg_len=args.max_msg_len,
                       mesh=args.mesh, mesh_devices=args.mesh_devices,
                       sig_parallel=args.sig_parallel,
                       tiles_per_shard=args.tiles_per_shard)
    srv.start()
    import jax
    what = (f"mesh={srv.stats.get('mesh_shards')}-shards" if args.mesh
            else f"device={jax.devices()[0]}")
    print(f"device server on {srv.addr} {what} "
          f"bucket={srv.bucket}", flush=True)
    try:
        import time
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
