"""Fresh-interpreter harness for mesh/shard_map checks.

Multi-device XLA:CPU executables segfault when built in a process that
has already compiled many single-device kernels (reproduced at
tests/test_parallel.py, as it was, in rounds 2-3), so every mesh test runs here, in
a subprocess, exactly like the driver's own `__graft_entry__.py dryrun`
pattern. The isolation is from the pytest worker, not of one mode from
another: the modes of a group (tests/test_parallel_*.py) share ONE
interpreter and the sharded executables it has built — XLA:CPU
executables are never persisted, so every fresh interpreter pays its
compiles in full. Not collected by pytest (no test_ prefix).

Usage: python tests/_mesh_harness.py MODE [MODE ...]
Runs the modes in order and prints, for each, one line "OK <mode>" or
"FAIL <mode>" followed by its traceback; a mode that raises does not
stop the ones after it. Exits 0 whenever the interpreter survived —
each test case reads its own line (the `mesh_harness` fixture of
tests/conftest.py).
"""

import os
import sys
import time
import traceback

# the suite's conftest pins the 8-device CPU platform for this interpreter
# too, BEFORE any device access, and puts the repo on the path
import conftest  # noqa: F401
from _kernel_shape import KERNEL_LANES

# lanes of the sharded RLC / per-lane executables that the `rlc` and
# `blocksync` modes share: 2 a device on the 8-device mesh
MESH_LANES = 16


def _compile_side_by_side(jobs):
    """[(jitted, args)] -> [compiled executable], in order. Each
    sharded compile is a minute or more of XLA:CPU on ONE core, off the
    interpreter lock, so the executables a mode needs are built side by
    side: four cost little more wall time than one. They are run one
    after the other, by the caller."""
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(len(jobs)) as pool:
        return list(pool.map(
            lambda job: job[0].lower(*job[1]).compile(), jobs))


_grid_compiled = {}  # mesh -> compiled grid verifier


def _grid_executables(jobs):
    """The compiled grid verifier (parallel.verify.make_sharded_verifier)
    for each (mesh, args) of `jobs`, in order, built once a mesh and
    interpreter: `refactor` meets the (4,2) one `tally` built (every mode
    here gives a mesh one grid shape; another is a TypeError)."""
    from cometbft_tpu.parallel.verify import make_sharded_verifier
    missing = {mesh: (make_sharded_verifier(mesh), args)
               for mesh, args in jobs if mesh not in _grid_compiled}
    if missing:
        _grid_compiled.update(zip(
            missing, _compile_side_by_side(list(missing.values()))))
    return [_grid_compiled[mesh] for mesh, _args in jobs]


def _batch(n, msg_len=40, seed=3):
    import random
    from cometbft_tpu.crypto import ref_ed25519 as ref
    rng = random.Random(seed)
    pubs, msgs, sigs = [], [], []
    for _ in range(n):
        sd = bytes([rng.randrange(256) for _ in range(32)])
        m = bytes([rng.randrange(256) for _ in range(msg_len)])
        pubs.append(ref.pubkey_from_seed(sd))
        msgs.append(m)
        sigs.append(ref.sign(sd, m))
    return pubs, msgs, sigs


def run_tally():
    """Sharded (commit, sig) grid verify with per-commit power tally,
    including per-lane failure attribution (two corrupted signatures).
    Powers are Cosmos-scale (> 2^24, where a float32 tally would
    silently round) to pin the exact int64-via-planes accounting."""
    import jax
    import numpy as np
    from cometbft_tpu.ops.ed25519 import prepare_batch
    from cometbft_tpu.parallel.mesh import make_mesh
    from cometbft_tpu.parallel.verify import (
        combine_power_planes, split_power_planes)

    assert len(jax.devices()) == 8
    mesh = make_mesh(8)  # (4 commit-parallel, 2 sig-parallel)
    C, V = 4, 4
    pubs, msgs, sigs = _batch(C * V)
    # corrupt one signature in commit 1 and one in commit 3
    sigs[1 * V + 2] = bytes(64)
    sigs[3 * V + 0] = sigs[3 * V + 0][:63] + bytes([sigs[3 * V + 0][63] ^ 1])
    pub, sig, hb, hn, _ = prepare_batch(pubs, msgs, sigs, C * V, 64)
    grid = lambda x: x.reshape(C, V, *x.shape[1:])
    # 10^13-scale staked power + a low-bit fingerprint per validator:
    # any f32 rounding anywhere would corrupt the low bits
    power = (10_000_000_000_000
             + np.arange(1, C * V + 1, dtype=np.int64).reshape(C, V))

    args = (grid(pub), grid(sig), grid(hb), grid(hn),
            split_power_planes(power))
    (run,) = _grid_executables([(mesh, args)])
    ok, planes = run(*args)
    ok = np.asarray(ok)
    tally = combine_power_planes(np.asarray(planes))

    want_ok = np.ones((C, V), dtype=bool)
    want_ok[1, 2] = False
    want_ok[3, 0] = False
    assert (ok == want_ok).all()
    want_tally = np.where(want_ok, power, 0).sum(axis=1)
    assert (tally == want_tally).all(), (tally, want_tally)


def run_rlc():
    """Sharded RLC fast path: a clean batch passes the one-equation
    verify; a batch with one tampered lane fails it and the sharded
    per-lane fallback attributes the exact lane."""
    import numpy as np
    from cometbft_tpu.ops.ed25519 import (
        make_rlc_coefficients, prepare_batch)
    from cometbft_tpu.parallel.mesh import make_mesh
    from cometbft_tpu.parallel.verify import (
        _mesh_state, make_lanes_sharded_verifier,
        make_rlc_sharded_verifier)

    mesh = make_mesh(8)
    N = MESH_LANES
    pubs, msgs, sigs = _batch(N)
    pub, sig, hb, hn, _ = prepare_batch(pubs, msgs, sigs, N, 64)
    z = make_rlc_coefficients(N)
    rlc, lanes = _compile_side_by_side([
        (make_rlc_sharded_verifier(mesh), (pub, sig, hb, hn, z)),
        (make_lanes_sharded_verifier(mesh), (pub, sig, hb, hn))])

    bok, sok = rlc(pub, sig, hb, hn, z)
    assert bool(bok) and np.asarray(sok).all()

    # tamper lane 5's s (structurally valid, equation fails)
    bad = np.array(sig, copy=True)
    bad[5, 32] ^= 1
    bok, sok = rlc(pub, bad, hb, hn, z)
    assert not bool(bok)
    assert np.asarray(sok).all()  # still structurally fine

    out = np.asarray(lanes(pub, bad, hb, hn))
    want = np.ones(N, dtype=bool)
    want[5] = False
    assert (out == want).all(), out
    # the pair now compiled at (MESH_LANES, 2 hash blocks) is the pair
    # verify_batch_mesh builds for itself on first use: leave it where
    # `blocksync`, later in this interpreter, finds it (as executables
    # of that one shape: another shape is a TypeError, not a compile)
    _mesh_state.update(mesh=mesh, rlc=rlc, lanes=lanes)


def run_blocksync():
    """Multi-device blocksync: TiledCommitVerifier routed through the
    mesh (COMETBFT_TPU_MESH_VERIFY=1) syncs a real generated chain
    through the real executor — the production data plane sharded, not
    a kernel demo (VERDICT r4 weak #4)."""
    import cometbft_tpu.parallel.verify as pv
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.engine.chain_gen import (
        LocalChainSource, generate_chain)
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore
    from cometbft_tpu.types.validation import BATCH_VERIFY_THRESHOLD

    # one 10-block tile of 8 validators = 80 sigs >= the batch
    # threshold, so the tile actually dispatches to the mesh, in five
    # chunks of MESH_LANES (2 lanes a device: the width is not the
    # point, and it is the shape `rlc` leaves warm)
    chain = generate_chain(n_blocks=10, n_validators=8)
    assert 10 * 8 >= BATCH_VERIFY_THRESHOLD
    app = KVStoreApplication()
    app.init_chain(chain.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    sstore = StateStore(db)
    executor = BlockExecutor(app, state_store=sstore, block_store=store)
    state = State.from_genesis(chain.genesis)
    reactor = BlocksyncReactor(
        executor, store, LocalChainSource(chain), chain.chain_id,
        tile_size=10, batch_size=MESH_LANES)
    dispatched = []
    real = pv.verify_batch_mesh

    def counted(pubs, msgs, sigs, batch_size=None):
        dispatched.append((len(pubs), batch_size))
        return real(pubs, msgs, sigs, batch_size=batch_size)

    os.environ["COMETBFT_TPU_MESH_VERIFY"] = "1"
    pv.verify_batch_mesh = counted
    try:
        state = reactor.sync(state)
    finally:
        pv.verify_batch_mesh = real
        del os.environ["COMETBFT_TPU_MESH_VERIFY"]
    assert state.last_block_height == 10, state.last_block_height
    assert reactor.stats.tiles_flushed >= 1
    assert dispatched == [(80, MESH_LANES)], \
        f"mesh path was not dispatched as planned: {dispatched}"
    assert "mesh" in pv._mesh_state


def run_graft():
    """entry() compiles+verifies on one device, then the full multichip
    dryrun — in THIS process order (single-device jit first, then the
    8-device mesh), the exact sequence that used to segfault in-suite."""
    import jax
    import numpy as np
    import __graft_entry__ as g
    fn, args = g.entry()
    out = np.asarray(jax.jit(fn)(*args))
    assert out[:8].all()          # the 8 real signatures
    g.dryrun_multichip(8)


def run_equiv():
    """Sharded-vs-single-chip verdict equivalence (ISSUE 12
    acceptance): commit lanes marshaled from clean / tampered /
    valset-change chains verify IDENTICALLY through (a) the
    single-chip ops.ed25519 batch kernel and (b) the mesh executor
    over the 8-device mesh — per-lane verdicts, per-commit verdicts,
    and tallies. Then a real PipelinedBlocksync catch-up runs with
    the MeshExecutor as its verify backend (depth sized from the
    shard count) — the production wiring, not a kernel demo."""
    import numpy as np
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.engine.blocksync import (TileEntry, marshal_commit,
                                               settle_tile)
    from cometbft_tpu.engine.chain_gen import generate_chain
    from cometbft_tpu.mesh import MeshExecutor, MeshTopology
    from cometbft_tpu.ops.ed25519 import verify_batch

    new_key = Ed25519PrivKey(b"\x99" * 32)
    val_tx = b"val:" + new_key.pub_key().bytes_().hex().encode() + b"!15"
    chains = {
        "clean": generate_chain(6, 4, seed=3, txs_per_block=1),
        "valset-change": generate_chain(
            6, 4, seed=5, txs_per_block=1,
            val_tx_heights={3: val_tx}, extra_keys=[new_key]),
    }
    ex = MeshExecutor(MeshTopology(), threaded=False)
    assert ex.n_shards == 8
    # warm the (4,2) bucket first: the executor's cold-shape gate
    # routes never-compiled shapes to the CPU fallback, and this
    # harness exists to exercise the MESH kernels
    ex.warm(probe=False)

    def marshal(chain, tamper=False):
        pubs, msgs, sigs = [], [], []
        entries = [TileEntry(height=h, block=chain.blocks[h - 1],
                             block_id=chain.block_ids[h - 1],
                             valset=chain.valsets[h - 1],
                             commit=chain.seen_commits[h - 1])
                   for h in range(1, len(chain.blocks) + 1)]
        metas = [marshal_commit(chain.chain_id, e, pubs, msgs, sigs)
                 for e in entries]
        if tamper:  # flip a signature bit in every third lane
            for i in range(0, len(sigs), 3):
                sigs[i] = bytes([sigs[i][0] ^ 1]) + sigs[i][1:]
        return entries, metas, pubs, msgs, sigs

    for name, chain in chains.items():
        for tamper in (False, True):
            entries, metas, pubs, msgs, sigs = marshal(chain, tamper)
            assert pubs, "no lanes marshaled"
            single = [bool(v) for v in verify_batch(
                pubs, msgs, sigs, batch_size=KERNEL_LANES)]
            fut = ex.submit(pubs, msgs, sigs)
            mesh = fut.result(300)
            from cometbft_tpu.mesh.executor import CPU_SHARD
            assert CPU_SHARD not in fut.shards, \
                "mesh dispatch fell back to CPU (shape not warm?)"
            assert mesh == single, (name, tamper)
            # per-commit verdicts settle identically from either path
            settle_tile(metas, np.array(single), pubs, msgs, sigs)
            want_ok = [e.commit_ok for e in entries]
            _entries2, metas2, p2, m2, s2 = marshal(chain, tamper)
            settle_tile(metas2, np.array(mesh), p2, m2, s2)
            got_ok = [e.commit_ok for e, _r, _n in metas2]
            assert got_ok == want_ok == ([True] * len(want_ok)
                                         if not tamper
                                         else [False] * len(want_ok)), \
                (name, tamper, got_ok, want_ok)

    # the production wiring: blocksync catch-up with the mesh executor
    # as the pipeline's verify backend (queue sized per shard)
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.engine.chain_gen import LocalChainSource
    from cometbft_tpu.pipeline.scheduler import PipelinedBlocksync
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore

    chain = chains["clean"]
    app = KVStoreApplication()
    app.init_chain(chain.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    executor = BlockExecutor(app, state_store=StateStore(db),
                             block_store=store)
    state = State.from_genesis(chain.genesis)
    reactor = BlocksyncReactor(
        executor, store, LocalChainSource(chain), chain.chain_id,
        tile_size=2, batch_size=0)
    pipe = PipelinedBlocksync(reactor, depth=1, backend=ex)
    assert pipe.depth == 8  # 1 per shard x 8 shards
    state = pipe.run(state, 6)
    pipe.close()
    assert state.last_block_height == 6
    ex.close()


def run_refactor():
    """Mesh-refactor matrix with the REAL sharded grid kernel: the
    same (commits, validators) batch with Cosmos-scale powers and two
    tampered lanes verifies on 8 -> 6 -> 4 -> 1-device factorings via
    topology masking, and the int64 power tally is bit-exact across
    every factoring (padding included — the 6-device (3,2) shape pads
    the commit axis)."""
    import numpy as np
    from cometbft_tpu.mesh import MeshTopology, plan_grid
    from cometbft_tpu.ops.ed25519 import prepare_batch

    C, V = 4, 4
    pubs, msgs, sigs = _batch(C * V)
    sigs[1 * V + 2] = bytes(64)
    sigs[3 * V + 0] = sigs[3 * V + 0][:63] \
        + bytes([sigs[3 * V + 0][63] ^ 1])
    pub, sig, hb, hn, _ = prepare_batch(pubs, msgs, sigs, C * V, 64)
    grid = lambda x: x.reshape(C, V, *x.shape[1:])
    power = (10_000_000_000_000
             + np.arange(1, C * V + 1, dtype=np.int64).reshape(C, V))
    want_ok = np.ones((C, V), dtype=bool)
    want_ok[1, 2] = False
    want_ok[3, 0] = False
    want_tally = np.where(want_ok, power, 0).sum(axis=1)

    topo = MeshTopology()
    plans = []
    for n_target, to_mask in ((8, ()), (6, (3, 5)), (4, (1, 7)),
                              (1, (2, 4, 6))):
        for s in to_mask:
            topo.mask(s)
        view = topo.view()
        assert view.n_shards == n_target, (n_target, view)
        gp = plan_grid(C, V, view.shape)
        plans.append((n_target, gp, view.jax_mesh(),
                      (gp.pad_grid(grid(pub)), gp.pad_grid(grid(sig)),
                       gp.pad_grid(grid(hb)),
                       gp.pad_grid(grid(hn), fill=1),
                       gp.power_planes(power))))
    runs = _grid_executables([plan[2:] for plan in plans])
    for (n_target, gp, _mesh, args), run in zip(plans, runs):
        ok, planes = run(*args)
        ok = gp.unpad_ok(np.asarray(ok))
        tally = gp.tally(np.asarray(planes))
        assert (ok == want_ok).all(), (n_target, ok)
        assert (tally == want_tally).all(), (n_target, tally,
                                             want_tally)


MODES = {"tally": run_tally, "graft": run_graft, "rlc": run_rlc,
         "blocksync": run_blocksync, "equiv": run_equiv,
         "refactor": run_refactor}


def main(modes):
    for which in modes:
        t0 = time.monotonic()
        try:
            MODES[which]()
        except Exception:  # noqa: BLE001 — report, go on to the next
            print("FAIL", which)
            traceback.print_exc(file=sys.stdout)
        else:
            print("OK", which)
        print(f"# {which}: {time.monotonic() - t0:.0f} s", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
