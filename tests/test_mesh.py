"""mesh/ host-side unit tests: topology factoring + degrade/regrow,
planner bucket/canary layout, shard supervisor arc, executor verdict
containment, scheduler queue sizing, and the protocol attribution
trailer — all WITHOUT building any multi-device executable (the
fresh-interpreter jax checks live in tests/_mesh_harness.py, driven by
tests/test_parallel.py, because multi-device XLA:CPU executables
segfault in a compile-heavy process — docs/PERF.md)."""

import numpy as np
import pytest

from cometbft_tpu.crypto import ref_ed25519 as ref
from cometbft_tpu.crypto.keys import Ed25519PubKey
from cometbft_tpu.mesh import (CPU_SHARD, MeshExecutor, MeshOverloaded,
                               MeshShapeError, MeshTopology, plan_grid,
                               plan_lanes)
from cometbft_tpu.mesh.shard_health import ShardSupervisor
from cometbft_tpu.parallel.mesh import factor_mesh_shape


def _batch(n, seed=11, msg_len=40):
    import random
    rng = random.Random(seed)
    pubs, msgs, sigs = [], [], []
    for _ in range(n):
        sd = bytes(rng.randrange(256) for _ in range(32))
        m = bytes(rng.randrange(256) for _ in range(msg_len))
        pubs.append(ref.pubkey_from_seed(sd))
        msgs.append(m)
        sigs.append(ref.sign(sd, m))
    return pubs, msgs, sigs


def _native_rows(pubs, msgs, sigs):
    return [Ed25519PubKey(p).verify_signature(m, s)
            for p, m, s in zip(pubs, msgs, sigs)]


# --- topology -----------------------------------------------------------------

def test_factoring_rule():
    assert factor_mesh_shape(8) == (4, 2)
    assert factor_mesh_shape(6) == (3, 2)
    assert factor_mesh_shape(4) == (2, 2)
    assert factor_mesh_shape(7) == (7, 1)
    assert factor_mesh_shape(1) == (1, 1)
    assert factor_mesh_shape(8, sig_parallel=4) == (2, 4)


def test_factoring_raises_typed_error():
    """The satellite fix: a typed MeshShapeError (ValueError), never a
    bare assert that `python -O` would skip — node boot must get a
    config error."""
    with pytest.raises(MeshShapeError):
        factor_mesh_shape(0)
    with pytest.raises(MeshShapeError):
        factor_mesh_shape(8, sig_parallel=3)
    with pytest.raises(ValueError):  # MeshShapeError IS a ValueError
        factor_mesh_shape(8, sig_parallel=-1)


def test_config_rejects_impossible_mesh():
    from cometbft_tpu.config import Config
    cfg = Config()
    cfg.device.mesh_devices = 8
    cfg.device.mesh_sig_parallel = 3
    with pytest.raises(ValueError):
        cfg.validate_basic()


def test_topology_refactor_matrix():
    """The 8 -> 6 -> 4 -> 1 degrade matrix: every masking re-factors
    to a servable shape, shard ids survive mask/unmask cycles, and
    the generation bumps on every change."""
    t = MeshTopology(devices=list(range(8)))
    assert t.view().shape == (4, 2) and t.view().n_shards == 8
    g0 = t.generation
    v = t.mask(3)
    assert (v.n_shards, v.shape) == (7, (7, 1))
    assert 3 not in v.shard_ids
    v = t.mask(5)
    assert (v.n_shards, v.shape) == (6, (3, 2))
    v = t.mask(1)
    v = t.mask(7)
    assert (v.n_shards, v.shape) == (4, (2, 2))
    for s in (0, 2, 4):
        v = t.mask(s)
    assert (v.n_shards, v.shape) == (1, (1, 1))
    assert v.shard_ids == (6,)
    # masking the LAST shard is refused: zero shards is the node-level
    # supervisor's call, not topology's
    with pytest.raises(MeshShapeError):
        t.mask(6)
    for s in (0, 1, 2, 3, 4, 5, 7):
        v = t.unmask(s)
    assert (v.n_shards, v.shape) == (8, (4, 2))
    assert t.generation > g0


def test_topology_keeps_configured_sig_parallel_while_it_divides():
    t = MeshTopology(devices=list(range(8)), sig_parallel=4)
    assert t.view().shape == (2, 4)
    t.mask(0)  # 7 devices: sig=4 no longer divides -> auto (7, 1)
    assert t.view().shape == (7, 1)
    t.unmask(0)
    assert t.view().shape == (2, 4)


# --- planner ------------------------------------------------------------------

def test_lane_plan_layout_round_trip():
    plan = plan_lanes(20, 8, canary=True)
    assert plan.shard_width == 8 and plan.bucket == 64
    assert plan.real_per_shard == 6
    # lanes fill shard slices contiguously; canaries hold the tail
    assert plan.row_of(0) == 0 and plan.row_of(5) == 5
    assert plan.row_of(6) == 8 and plan.shard_of(6) == 1
    pubs, msgs, sigs = _batch(20)
    p, m, s = plan.build(pubs, msgs, sigs)
    assert len(p) == 64
    real, bad = plan.extract(_native_rows(p, m, s))
    assert real == [True] * 20 and bad == []


def test_lane_plan_attributes_tampered_lane_not_shard():
    plan = plan_lanes(20, 8, canary=True)
    pubs, msgs, sigs = _batch(20)
    sigs[7] = bytes(64)
    rows = _native_rows(*plan.build(pubs, msgs, sigs))
    real, bad = plan.extract(rows)
    assert real[7] is False or not real[7]
    assert sum(1 for v in real if not v) == 1
    assert bad == []  # a bad SIGNATURE is not a bad SHARD


def test_lane_plan_catches_corrupt_shard():
    plan = plan_lanes(20, 8, canary=True)
    pubs, msgs, sigs = _batch(20)
    rows = _native_rows(*plan.build(pubs, msgs, sigs))
    # shard 2 answers all-True; its known-bad canary row flips
    for r in range(2 * 8, 3 * 8):
        rows[r] = True
    real, bad = plan.extract(rows)
    assert bad == [2]
    # an all-FALSE shard is caught by its good canary / pad rows
    rows = _native_rows(*plan.build(pubs, msgs, sigs))
    for r in range(5 * 8, 6 * 8):
        rows[r] = False
    _real, bad = plan.extract(rows)
    assert bad == [5]


def test_lane_plan_length_mismatch_distrusts_everything():
    plan = plan_lanes(4, 2, canary=True)
    real, bad = plan.extract([True] * (plan.bucket - 1))
    assert real == [] and bad == [0, 1]


def test_lane_plan_no_canary_mode():
    plan = plan_lanes(16, 2, canary=False)
    assert plan.real_per_shard == plan.shard_width
    pubs, msgs, sigs = _batch(16)
    real, bad = plan.extract(_native_rows(*plan.build(pubs, msgs, sigs)))
    assert real == [True] * 16 and bad == []


def test_grid_plan_pads_and_tallies_exact_int64():
    """The exact power-plane tally survives padding and every
    factoring of the refactor matrix — Cosmos-scale powers (> 2^24,
    where a float32 tally silently rounds) with low-bit fingerprints,
    pure host math (the device psum is int32 plane sums, modeled here
    exactly)."""
    C, V = 4, 4
    power = (10_000_000_000_000
             + np.arange(1, C * V + 1, dtype=np.int64).reshape(C, V))
    ok = np.ones((C, V), dtype=bool)
    ok[1, 2] = False
    ok[3, 0] = False
    want = np.where(ok, power, 0).sum(axis=1)
    for shape in ((4, 2), (3, 2), (2, 2), (1, 1), (7, 1)):
        gp = plan_grid(C, V, shape)
        assert gp.padded_commits % shape[0] == 0
        assert gp.padded_validators % shape[1] == 0
        planes = gp.power_planes(power)          # (C', V', 4) i32
        ok_p = gp.pad_grid(ok)                   # padded ok
        # the device-side tally: per-lane plane select + int32 sum
        sums = np.where(ok_p[..., None], planes, 0).sum(
            axis=1, dtype=np.int32)              # (C', 4)
        assert (gp.tally(sums) == want).all(), shape


# --- shard supervisor ---------------------------------------------------------

def test_shard_supervisor_masks_and_regrows():
    clock = [0.0]
    topo = MeshTopology(devices=list(range(4)))
    sup = ShardSupervisor(topo, backoff_base_s=1.0,
                          clock=lambda: clock[0])
    assert sup.report_shard_corruption(2, "test")
    assert topo.masked() == (2,)
    assert topo.view().shape == (3, 1)
    assert sup.probe_due() == []          # window not elapsed
    clock[0] = 5.0
    assert sup.probe_due() == [2]
    assert sup.probe_due() == []          # claim is one-shot
    # failed probe deepens the backoff and keeps the mask
    assert not sup.probe(2, lambda p, m, s: [True, True])
    assert topo.masked() == (2,)
    clock[0] = 50.0
    assert sup.probe_due() == [2]
    assert sup.probe(2, lambda p, m, s: _native_rows(p, m, s))
    assert topo.masked() == ()
    assert topo.view().shape == (2, 2)
    assert sup.regrows == 1 and sup.quarantines == 1


def test_shard_supervisor_last_shard_escalates_to_node_quarantine():
    from cometbft_tpu.device import health
    health.reset_shared_supervisor()
    try:
        topo = MeshTopology(devices=[0])
        sup = ShardSupervisor(topo, clock=lambda: 0.0)
        assert not sup.report_shard_corruption(0, "last one")
        assert topo.masked() == ()  # never masked to zero
        assert health.shared_supervisor().quarantined()
    finally:
        health.reset_shared_supervisor()


# --- executor -----------------------------------------------------------------

class _CorruptibleStub:
    """All-true corruption on the sick shards' slices; native verdicts
    elsewhere (the simnet mesh-degrade backend shape)."""

    def __init__(self, sick=()):
        self.sick = set(sick)

    def __call__(self, view, plan, pubs, msgs, sigs):
        rows = _native_rows(pubs, msgs, sigs)
        for si, gid in enumerate(view.shard_ids):
            if gid in self.sick:
                for r in range(si * plan.shard_width,
                               (si + 1) * plan.shard_width):
                    rows[r] = True
        return rows


def test_executor_contains_corruption_and_regrows():
    clock = [0.0]
    stub = _CorruptibleStub(sick={2})
    topo = MeshTopology(devices=list(range(8)))
    sup = ShardSupervisor(topo, backoff_base_s=1.0,
                          clock=lambda: clock[0])

    def probe_backend(shard, p, m, s):
        return ([True] * len(p) if shard in stub.sick
                else _native_rows(p, m, s))

    ex = MeshExecutor(topo, supervisor=sup, verify_backend=stub,
                      probe_backend=probe_backend, threaded=False)
    pubs, msgs, sigs = _batch(20)
    sigs[3] = bytes(64)  # one genuinely bad signature
    fut = ex.submit(pubs, msgs, sigs)
    out = fut.result(0)
    # containment: verdicts equal native truth DESPITE the lying shard
    assert out == _native_rows(pubs, msgs, sigs)
    assert fut.shards == [CPU_SHARD] * 20  # CPU re-verify attributed
    assert topo.masked() == (2,)
    # next dispatch serves on the 7-shard mesh with real attribution
    fut = ex.submit(pubs, msgs, sigs)
    assert fut.result(0) == _native_rows(pubs, msgs, sigs)
    assert CPU_SHARD not in fut.shards
    assert 2 not in fut.shards
    # heal + probe window -> regrow to 8 shards
    stub.sick.clear()
    clock[0] = 10.0
    fut = ex.submit(pubs, msgs, sigs)
    assert fut.result(0) == _native_rows(pubs, msgs, sigs)
    assert topo.masked() == ()
    assert ex.n_shards == 8 and ex.depth_hint() == 32
    ex.close()


def test_executor_bounded_queue_sheds():
    import threading
    entered = threading.Event()
    gate = threading.Event()

    def blocking_backend(view, plan, pubs, msgs, sigs):
        entered.set()
        gate.wait(10)
        return _native_rows(pubs, msgs, sigs)

    topo = MeshTopology(devices=[0, 1])
    ex = MeshExecutor(topo, verify_backend=blocking_backend,
                      tiles_per_shard=1, threaded=True)
    pubs, msgs, sigs = _batch(1)
    first = ex.submit(pubs, msgs, sigs)  # worker takes it and blocks
    assert entered.wait(5)
    for _ in range(ex.queue_capacity):
        ex.submit(pubs, msgs, sigs)
    with pytest.raises(MeshOverloaded):
        ex.submit(pubs, msgs, sigs)
    gate.set()
    assert first.result(10) == _native_rows(pubs, msgs, sigs)
    ex.close()
    # and a CLOSED executor refuses instead of enqueueing dead work
    with pytest.raises(ConnectionError):
        ex.submit(pubs, msgs, sigs)


def test_executor_close_fails_queued_futures():
    """close() must resolve abandoned queued futures (a caller blocked
    in result() with no timeout would otherwise hang forever)."""
    from cometbft_tpu.mesh.executor import MeshFuture
    topo = MeshTopology(devices=[0, 1])
    ex = MeshExecutor(topo, verify_backend=_CorruptibleStub(),
                      threaded=True)
    ex.close()  # worker exits
    fut = MeshFuture(1)
    ex._q.put_nowait((fut, [b"x" * 32], [b"m"], [b"s" * 64]))
    ex.close()  # idempotent; drains + fails the stranded future
    with pytest.raises(ConnectionError):
        fut.result(0)


def test_scheduler_sizes_queue_from_shard_count():
    """pipeline/scheduler: depth means K tiles PER SHARD when the
    backend exposes n_shards; single-chip backends keep depth
    unchanged."""
    from cometbft_tpu.engine.chain_gen import (LocalChainSource,
                                               generate_chain)
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.pipeline.scheduler import (FixedLatencyBackend,
                                                 PipelinedBlocksync)
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore

    chain = generate_chain(n_blocks=4, n_validators=4, txs_per_block=1)
    app = KVStoreApplication()
    app.init_chain(chain.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    executor = BlockExecutor(app, state_store=StateStore(db),
                             block_store=store)
    reactor = BlocksyncReactor(executor, store,
                               LocalChainSource(chain), chain.chain_id,
                               tile_size=2, batch_size=0)
    single = FixedLatencyBackend(0.0)
    pipe = PipelinedBlocksync(reactor, depth=3, backend=single)
    assert pipe.depth == 3
    pipe.close()
    sharded = FixedLatencyBackend(0.0)
    sharded.n_shards = 8
    pipe = PipelinedBlocksync(reactor, depth=3, backend=sharded)
    assert pipe.depth == 24
    pipe.close()
    # a backend with a bounded dispatch queue clamps the depth — a
    # deep pipeline_depth must never overflow into MeshOverloaded
    sharded.queue_capacity = 5
    pipe = PipelinedBlocksync(reactor, depth=16, backend=sharded)
    assert pipe.depth == 5
    pipe.close()
    # and the sharded-depth pipeline still syncs correctly
    state = State.from_genesis(chain.genesis)
    pipe = PipelinedBlocksync(reactor, depth=2, backend=sharded)
    state = pipe.run(state, 4)
    pipe.close()
    assert state.last_block_height == 4


# --- protocol attribution trailer ---------------------------------------------

def test_protocol_shard_trailer_round_trip():
    from cometbft_tpu.device.protocol import (decode_response,
                                              decode_response_shards,
                                              encode_response)
    p = encode_response(9, False, [True, False, True],
                        shards=[0, 3, CPU_SHARD])
    assert decode_response(p) == (9, False, [True, False, True])
    assert decode_response_shards(p) == [0, 3, CPU_SHARD]
    # v1 response: no trailer -> None, verdicts unaffected
    p1 = encode_response(9, True, [True, True])
    assert decode_response(p1) == (9, True, [True, True])
    assert decode_response_shards(p1) is None
    # misaligned trailer is malformed, not silently misattributed
    with pytest.raises(ValueError):
        decode_response_shards(p[:-1])
    with pytest.raises(ValueError):
        encode_response(9, True, [True], shards=[1, 2])


def test_device_server_mesh_flush_attributes_shards():
    """The server's mesh data plane end-to-end over a real socket:
    responses carry the per-lane attribution trailer, and a corrupt
    shard's batch comes back CPU-attributed with true verdicts."""
    import socket
    import threading
    from cometbft_tpu.device.protocol import (decode_response,
                                              decode_response_shards,
                                              encode_request,
                                              recv_frame, send_frame)
    from cometbft_tpu.device.server import DeviceServer

    srv = DeviceServer(bucket=64)
    stub = _CorruptibleStub(sick={1})
    topo = MeshTopology(devices=list(range(4)))
    sup = ShardSupervisor(topo, backoff_base_s=1e9,
                          clock=lambda: 0.0)
    srv._mesh_exec = MeshExecutor(topo, supervisor=sup,
                                  verify_backend=stub, threaded=False)
    # serve without _warm (the stub replaces the device entirely)
    threading.Thread(target=srv._device_routine, daemon=True).start()

    def accept_loop():
        try:
            sock, _ = srv._listener.accept()
        except OSError:
            return
        srv._serve_conn(sock)
    threading.Thread(target=accept_loop, daemon=True).start()
    try:
        pubs, msgs, sigs = _batch(6)
        sigs[4] = bytes(64)
        cli = socket.create_connection(srv.addr, timeout=10)
        cli.settimeout(30)
        send_frame(cli, encode_request(1, pubs, msgs, sigs))
        payload = recv_frame(cli)
        req_id, batch_ok, oks = decode_response(payload)
        shards = decode_response_shards(payload)
        assert req_id == 1 and not batch_ok
        assert oks == _native_rows(pubs, msgs, sigs)
        assert shards == [CPU_SHARD] * 6  # corrupt shard -> CPU
        assert topo.masked() == (1,)
        # second request: served by the re-factored 3-shard mesh
        send_frame(cli, encode_request(2, pubs, msgs, sigs))
        payload = recv_frame(cli)
        _rid, _bok, oks2 = decode_response(payload)
        shards2 = decode_response_shards(payload)
        assert oks2 == oks
        assert shards2 is not None and CPU_SHARD not in shards2
        assert 1 not in shards2
        cli.close()
    finally:
        srv.stop()


# --- farm kernel residual -----------------------------------------------------

def test_farm_fallback_routes_wide_batches_through_the_warmed_bucket(
        monkeypatch):
    """A wide farm batch with no device server rides the batch kernel
    at the one warmed bucket (`crypto/keys.kernel_width`, the Pallas
    tile) on a device platform and verifies natively on a CPU one;
    a narrow batch stays native on either — with the backend label
    ('kernel' vs 'cpu') that FarmMetrics.lanes_verified records."""
    from cometbft_tpu.farm.batcher import _fallback_verify
    from cometbft_tpu.farm.planner import Lane
    from cometbft_tpu.libs import jax_cache
    from cometbft_tpu.pipeline.cache import SigCache
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.ops.pallas_verify import TILE

    pubs, msgs, sigs = _batch(128, seed=5)
    lanes = [Lane(p, m, s, Ed25519PubKey(p), i, SigCache.key(p, m, s))
             for i, (p, m, s) in enumerate(zip(pubs, msgs, sigs))]
    calls = []

    def fake_verify_batch(p, m, s, batch_size=None, **kw):
        calls.append((len(p), batch_size))
        return np.array(_native_rows(p, m, s))
    monkeypatch.setattr(e5, "verify_batch", fake_verify_batch)

    # a CPU backend jits nothing: native, however wide
    assert jax_cache.backend_platform() == "cpu"
    oks, backend = _fallback_verify(lanes)
    assert backend == "cpu" and not calls
    assert oks == [True] * 128
    # a device: the kernel, at the tile and not at the batch's own width
    monkeypatch.setattr(jax_cache, "backend_platform", lambda: "tpu")
    oks, backend = _fallback_verify(lanes)
    assert backend == "kernel"
    assert calls == [(128, TILE)]
    assert oks == [True] * 128
    # narrow batches stay native even there
    oks, backend = _fallback_verify(lanes[:16])
    assert backend == "cpu" and len(calls) == 1
