"""The hand-over of a tile between the dispatch thread and the main
thread (PR 35). `ops/ed25519._verify_batch_loop` sends a call's chunks
to the device without reading one back between them and reads the
verdicts once, after the last: held here, with `dispatch` and `fallback`
stubbed (no kernel is jitted), against the chunk-by-chunk loop it
replaced. `PipelinedBlocksync.run` shortens the interpreter's switch
interval while it owns the in-process backend, and only then."""

import secrets
import sys

import numpy as np
import pytest

from cometbft_tpu import trace as program_trace
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.engine.blocksync import BlocksyncReactor, SyncStalled
from cometbft_tpu.engine.chain_gen import LocalChainSource, generate_chain
from cometbft_tpu.ops import ed25519 as e5
from cometbft_tpu.pipeline import scheduler
from cometbft_tpu.state.execution import BlockExecutor, BlockValidationError
from cometbft_tpu.state.state import State, StateStore
from cometbft_tpu.store.blockstore import BlockStore

pytestmark = pytest.mark.pipeline

BUCKET = 4


class Verdict:
    """A stub `batch_ok` that records when it is read."""

    def __init__(self, ok, log, k):
        self.ok, self.log, self.k = ok, log, k

    def __bool__(self):
        self.log.append(("read", self.k))
        return self.ok


class Kernels:
    """Stub `dispatch` / `fallback`: chunk `failing`'s equation fails
    and its second lane is the one to blame; every chunk's third lane is
    structurally refused. The log holds what was called, in order."""

    def __init__(self, failing=None, raise_at=None):
        self.failing, self.raise_at = failing, raise_at
        self.log, self.z, self.handed = [], [], {}

    def dispatch(self, pub_a, sig_a, hb, hn, z):
        k = len(self.z)
        if k == self.raise_at:
            raise RuntimeError(f"device lost at chunk {k}")
        self.log.append(("dispatch", k))
        self.z.append(np.array(z))
        self.handed[id(pub_a)] = k
        struct_ok = np.ones(BUCKET, dtype=bool)
        struct_ok[2] = False
        return Verdict(k != self.failing, self.log, k), struct_ok

    def fallback(self, pub_a, sig_a, hb, hn):
        # attribution is handed the arrays its chunk was dispatched with
        self.log.append(("per-lane", self.handed.get(id(pub_a))))
        out = np.ones(BUCKET, dtype=bool)
        out[1] = False
        return out


def _lanes(n):
    pub, sig, msg = e5._dummy()
    pubs, msgs, sigs = [pub] * n, [msg] * n, [sig] * n
    if n:
        pubs[0] = pub[:31]          # malformed: masked on the host
    return pubs, msgs, sigs


def _chunk_by_chunk(pubs, msgs, sigs, kernels, strict=False):
    """The loop as it was before PR 35, the reference for verdicts and
    counters: prepare, dispatch, read back, attribute, next chunk."""
    outs, counted = [], dict.fromkeys(e5.batch_stats(), 0)
    for lo in range(0, len(pubs), BUCKET):
        hi = min(lo + BUCKET, len(pubs))
        arrays = e5.prepare_batch(pubs[lo:hi], msgs[lo:hi], sigs[lo:hi],
                                  BUCKET, 64)
        out = None
        if not strict:
            batch_ok, struct_ok = kernels.dispatch(
                *arrays[:4], e5.make_rlc_coefficients(BUCKET))
            if bool(batch_ok):
                out = struct_ok
        counted["chunks"] += 1
        counted["lanes"] += hi - lo
        counted["hash_blocks_real"] += sum(
            e5.hash_blocks_needed(len(m)) for m in msgs[lo:hi])
        counted["hash_blocks_dispatched"] += BUCKET * arrays[2].shape[1]
        if not strict and out is None:
            counted["attributed_chunks"] += 1
            counted["attributed_lanes"] += hi - lo
        if out is None:
            out = kernels.fallback(*arrays[:4])
        outs.append(out[:hi - lo] & arrays[4][:hi - lo])
    return np.concatenate(outs), counted


def _loop(pubs, msgs, sigs, kernels, strict=False):
    before = e5.batch_stats()
    out = e5._verify_batch_loop(
        pubs, msgs, sigs, BUCKET,
        None if strict else kernels.dispatch, kernels.fallback)
    after = e5.batch_stats()
    return out, {k: after[k] - before[k] for k in after}


def test_every_chunk_is_dispatched_before_the_first_verdict_is_read():
    k = Kernels()
    _loop(*_lanes(4 * BUCKET), k)
    assert k.log == [("dispatch", i) for i in range(4)] \
        + [("read", i) for i in range(4)]


def _failing(chunks):
    return sorted({("first", 0), ("middle", chunks // 2),
                   ("last", chunks - 1), ("none", None)},
                  key=lambda c: c[0])


@pytest.mark.parametrize("chunks, where, failing", [
    (chunks, where, failing) for chunks in (1, 2, 7)
    for where, failing in _failing(chunks)])
def test_verdicts_and_counters_equal_the_chunk_by_chunk_loops(
        chunks, where, failing):
    lanes = _lanes(chunks * BUCKET - 1)       # the last chunk is padded
    want, counted = _chunk_by_chunk(*lanes, Kernels(failing))
    k = Kernels(failing)
    got, delta = _loop(*lanes, k)
    assert list(got) == list(want) and delta == counted
    assert not got[0]                         # the malformed lane
    assert delta["attributed_chunks"] == (failing is not None)
    # exactly the failed chunk went through the per-lane kernel, with
    # the arrays it was dispatched with, after the last verdict before it
    per_lane = [e for e in k.log if e[0] == "per-lane"]
    assert per_lane == ([] if failing is None else [("per-lane", failing)])
    if failing is not None:
        assert k.log.index(("per-lane", failing)) \
            == k.log.index(("read", failing)) + 1


@pytest.mark.parametrize("chunks", [1, 3])
def test_strict_mode_is_unchanged(chunks, monkeypatch):
    """No RLC pass: no coefficient is drawn, every chunk goes through
    the per-lane kernel in order and counts as no attribution."""
    monkeypatch.setattr(e5, "make_rlc_coefficients",
                        lambda *a, **k: pytest.fail("drawn in strict mode"))
    lanes = _lanes(chunks * BUCKET - 2)
    want, counted = _chunk_by_chunk(*lanes, Kernels(), strict=True)
    k = Kernels()
    got, delta = _loop(*lanes, k, strict=True)
    assert list(got) == list(want) and delta == counted
    assert k.log == [("per-lane", None)] * chunks
    assert delta["attributed_chunks"] == delta["attributed_lanes"] == 0


@pytest.mark.parametrize("raise_at", [0, 1, 3])
def test_a_dispatch_that_raises_propagates_and_counts_nothing(raise_at):
    k = Kernels(raise_at=raise_at)
    before = e5.batch_stats()
    with pytest.raises(RuntimeError, match=f"chunk {raise_at}"):
        e5._verify_batch_loop(*_lanes(4 * BUCKET), BUCKET, k.dispatch,
                              k.fallback)
    assert e5.batch_stats() == before
    assert k.log == [("dispatch", i) for i in range(raise_at)]


def test_no_two_chunks_and_no_two_calls_share_a_coefficient_row(
        monkeypatch):
    """One draw of OS entropy a call, 16 bytes a lane of every chunk,
    padding lanes included; every dispatch is handed rows of its own."""
    drawn = []
    real = secrets.token_bytes

    def token_bytes(n):
        drawn.append(real(n))
        return drawn[-1]

    monkeypatch.setattr(secrets, "token_bytes", token_bytes)
    k = Kernels()
    _loop(*_lanes(4 * BUCKET - 3), k)
    _loop(*_lanes(2 * BUCKET), k)
    assert [len(d) for d in drawn] == [16 * BUCKET * 4, 16 * BUCKET * 2]
    assert all(z.shape == (BUCKET, 8) and z.dtype == np.int32
               and (z >= 0).all() and (z < 1 << 16).all() for z in k.z)
    rows = [tuple(row) for z in k.z for row in z]
    assert len(rows) == 6 * BUCKET == len(set(rows))
    # the rows are the draw itself, cut in order: nothing derived
    for draw, z in zip(drawn, (k.z[:4], k.z[4:])):
        limbs = np.frombuffer(draw, dtype="<u2").reshape(-1, 8)
        assert (np.concatenate(z) == limbs).all()


def test_more_than_sixteen_chunks_are_read_back_in_windows():
    chunks = 2 * e5._MAX_UNREAD_CHUNKS + 3
    k = Kernels(failing=e5._MAX_UNREAD_CHUNKS + 1)
    lanes = _lanes(chunks * BUCKET)
    want, counted = _chunk_by_chunk(*lanes, Kernels(k.failing))
    program_trace.enable(seed=0, ring=1 << 10)
    try:
        got, delta = _loop(*lanes, k)
        spans = program_trace.shared_recorder().snapshot()
    finally:
        program_trace.disable()
    assert list(got) == list(want) and delta == counted
    unread = most = 0
    for what, _k in k.log:
        unread += {"dispatch": 1, "read": -1}.get(what, 0)
        most = max(most, unread)
    assert most == e5._MAX_UNREAD_CHUNKS and unread == 0
    assert [e for e in k.log if e[0] == "read"] \
        == [("read", i) for i in range(chunks)]
    readbacks = [s["attrs"] for s in spans if s["name"] == "ed25519.readback"]
    assert readbacks == [
        {"chunks": 16, "lanes": 16 * BUCKET, "attributed_chunks": 0},
        {"chunks": 16, "lanes": 16 * BUCKET, "attributed_chunks": 1},
        {"chunks": 3, "lanes": 3 * BUCKET, "attributed_chunks": 0}]


def test_the_spans_of_a_call():
    """One `ed25519.prepare` a chunk, one `ed25519.readback` a call,
    from the last chunk's dispatch on; strict mode reads nothing back."""
    program_trace.enable(seed=0, ring=1 << 10)
    try:
        _loop(*_lanes(3 * BUCKET - 1), Kernels(failing=2))
        _loop(*_lanes(BUCKET), Kernels(), strict=True)
        spans = program_trace.shared_recorder().snapshot()
    finally:
        program_trace.disable()
    prepares = [s for s in spans if s["name"] == "ed25519.prepare"]
    first, strict = [s for s in spans if s["name"] == "ed25519.readback"]
    assert [s["attrs"]["lanes"] for s in prepares] == [4, 4, 3, 4]
    assert first["attrs"] == {"chunks": 3, "lanes": 11,
                              "attributed_chunks": 1}
    assert first["t0"] >= prepares[2]["t1"]
    assert strict["attrs"] == {"chunks": 0, "lanes": 4,
                               "attributed_chunks": 0}
    program_trace.disable()
    _loop(*_lanes(BUCKET), Kernels())
    assert program_trace.shared_recorder().snapshot() == []


# --- the interpreter lock, inside PipelinedBlocksync.run ----------------------

CHAIN = generate_chain(n_blocks=8, n_validators=4, seed=35)


class WatchedSource(LocalChainSource):
    """Records the switch interval the pass runs under."""

    def __init__(self, *a, serve_up_to=None, **kw):
        super().__init__(*a, **kw)
        self.seen, self.serve_up_to = set(), serve_up_to

    def fetch(self, height):
        self.seen.add(sys.getswitchinterval())
        if self.serve_up_to is not None and height > self.serve_up_to:
            return None
        return super().fetch(height)


def _reactor(src, backend=None, max_retries=3):
    app = KVStoreApplication()
    app.init_chain(CHAIN.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    executor = BlockExecutor(app, state_store=StateStore(db),
                             block_store=store)
    return BlocksyncReactor(
        executor, store, src, CHAIN.chain_id, tile_size=4, batch_size=64,
        max_retries=max_retries, pipeline_depth=4, backend=backend)


@pytest.fixture
def former():
    """A switch interval that is neither the default nor the pass's."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(0.0031)
    try:
        yield sys.getswitchinterval()
    finally:
        sys.setswitchinterval(was)


def test_the_former_switch_interval_is_back_after_a_sync(former):
    src = WatchedSource(CHAIN)
    state = _reactor(src).sync(State.from_genesis(CHAIN.genesis))
    assert state.last_block_height == 8
    assert list(src.seen) == [pytest.approx(scheduler._SWITCH_INTERVAL_S)]
    assert sys.getswitchinterval() == former


def test_the_former_switch_interval_is_back_after_a_ban(former):
    class Stubborn(WatchedSource):
        def ban(self, height):
            self.banned.append(height)      # and goes on lying

    src = Stubborn(CHAIN, corrupt_heights={2: "sig"})
    reactor = _reactor(src, max_retries=1)
    with pytest.raises(BlockValidationError):
        reactor.sync(State.from_genesis(CHAIN.genesis))
    assert reactor.stats.bans >= 1
    assert list(src.seen) == [pytest.approx(scheduler._SWITCH_INTERVAL_S)]
    assert sys.getswitchinterval() == former


def test_the_former_switch_interval_is_back_after_a_stall(former):
    src = WatchedSource(CHAIN, serve_up_to=0)
    with pytest.raises(SyncStalled):
        _reactor(src, max_retries=0).sync(State.from_genesis(CHAIN.genesis))
    assert list(src.seen) == [pytest.approx(scheduler._SWITCH_INTERVAL_S)]
    assert sys.getswitchinterval() == former


def test_a_longer_switch_interval_is_never_set():
    """A process that already runs under a shorter one keeps it."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(scheduler._SWITCH_INTERVAL_S / 4)
    try:
        shorter = sys.getswitchinterval()
        src = WatchedSource(CHAIN)
        _reactor(src).sync(State.from_genesis(CHAIN.genesis))
        assert src.seen == {shorter} and sys.getswitchinterval() == shorter
    finally:
        sys.setswitchinterval(was)


def test_an_injected_backend_leaves_the_switch_interval_alone(former):
    backend = scheduler.FixedLatencyBackend(0.001)
    src = WatchedSource(CHAIN)
    state = _reactor(src, backend=backend).sync(
        State.from_genesis(CHAIN.genesis))
    assert state.last_block_height == 8 and backend.dispatches == 2
    assert src.seen == {former} and sys.getswitchinterval() == former
