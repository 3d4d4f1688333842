"""The quickest proof that the system still starts on the chip.

One process — the only one that touches JAX — drives the main path once
at the 200-validator blocksync deployment of BASELINE.json (chain length
cut from 100k blocks to 128 for time): commit signatures verified on one
TPU chip through BlocksyncReactor -> PipelinedBlocksync ->
ops.ed25519.verify_batch -> _rlc_dispatch -> the Pallas RLC kernels,
built with the values Node itself uses. Default run, one chip:

  1. kernels   each Pallas kernel and verify_rlc_kernel_pallas at the
               node's lane bucket, COMPILED (not interpreted), compared
               lane for lane with the XLA kernels and the big-int oracle
               on real signatures incl. ZIP-215 edge encodings
  2. catch-up  128 blocks x 200 validators synced on the device vs the
               native per-signature sync of the same chain
  3. guarantee a mid-chain commit with one corrupted signature is never
               applied (verify-before-apply), the bad lane attributed by
               the per-lane kernel on the device
  4. seam      one 150-validator commit through types.validation
               .verify_commit -> crypto.batch -> Ed25519BatchVerifier
               takes the device branch, verdict equal to native

`--chips 4` runs ONLY the mesh path and what it is compared with: the
same chain's lanes through parallel.verify.verify_batch_mesh and
mesh.MeshExecutor on a (2, 2) mesh vs the single-device verdicts.

Everything is printed on earlier lines; the LAST stdout line is one JSON
object {"ok": ..., "device": {"platform", "kind", "count"}} with the
device as JAX reports it. Exit 0 only when every phase passed on a TPU;
timings printed here are smoke timings, not a benchmark.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_BLOCKS = 128          # BASELINE blocksync deployment, length cut for time
N_VALIDATORS = 200
TILE_BLOCKS = 16        # node/node.py: BlocksyncReactor(tile_size=16)
SEAM_VALIDATORS = 150
N_REAL_SIGS = 300       # phase 1: real signatures besides the edge lanes
MSG_CAP = 128           # the vote-sized message bucket (2 hash blocks)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Timer:
    def __enter__(self):
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.s = time.monotonic() - self.t0


# --- phase 1: the kernels, compiled ------------------------------------------

def edge_lanes():
    """(pub, msg, sig, note) lanes exercising ZIP-215's corners; the
    expected verdict of each comes from the big-int oracle, never from
    this table."""
    from cometbft_tpu.crypto import ref_ed25519 as ref
    seed = b"\x5a" * 32
    pub, msg = ref.pubkey_from_seed(seed), b"zip215 edge lane"
    sig = ref.sign(seed, msg)
    ident = (1).to_bytes(32, "little")                 # (0, 1), canonical
    ident_nc = (ref.P + 1).to_bytes(32, "little")      # y = p + 1
    ident_neg0 = bytes(ident[:31]) + bytes([ident[31] | 0x80])  # x = -0
    order2 = (ref.P - 1).to_bytes(32, "little")        # (0, -1)
    zero_s = b"\x00" * 32
    s_plus_l = (int.from_bytes(sig[32:], "little") + ref.L
                ).to_bytes(32, "little")
    off_curve = (2**255 - 2).to_bytes(32, "little")
    return [
        (ident, msg, ident + zero_s, "small-order A and R, s=0"),
        (ident_nc, msg, ident_nc + zero_s, "non-canonical y = p+1"),
        (ident_neg0, msg, ident + zero_s, "x = 0 with the sign bit set"),
        (order2, msg, order2 + zero_s, "order-2 A and R"),
        (pub, msg, sig[:32] + s_plus_l, "s >= L (non-canonical scalar)"),
        (pub, msg, off_curve + sig[32:], "R not on the curve"),
        (off_curve, msg, sig, "A not on the curve"),
        (pub, msg, sig[:40] + bytes([sig[40] ^ 1]) + sig[41:],
         "tampered s (structurally valid)"),
        (pub, msg + b"!", sig, "wrong message"),
    ]


def real_lanes(n: int, seed: int):
    import random
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    rng = random.Random(seed)
    lanes = []
    for _ in range(n):
        key = Ed25519PrivKey(bytes(rng.randrange(256) for _ in range(32)))
        msg = bytes(rng.randrange(256)
                    for _ in range(rng.randrange(90, MSG_CAP)))
        lanes.append((key.pub_key().bytes_(), msg, key.sign(msg), "real"))
    return lanes


def _affine(packed, lane):
    """Affine (x, y) of lane `lane` of a packed (4, 16, N) point."""
    from cometbft_tpu.crypto import ref_ed25519 as ref
    from cometbft_tpu.ops.field import int_from_limbs
    x, y, z = (int_from_limbs(packed[c][:, lane]) % ref.P for c in range(3))
    zi = pow(z, ref.P - 2, ref.P)
    return x * zi % ref.P, y * zi % ref.P


def _ref_affine(pt):
    from cometbft_tpu.crypto import ref_ed25519 as ref
    zi = pow(pt[2], ref.P - 2, ref.P)
    return pt[0] * zi % ref.P, pt[1] * zi % ref.P


def _pack_ref(points):
    """Big-int extended points -> packed (4, 16, N) int32 limbs."""
    import numpy as np
    from cometbft_tpu.crypto import ref_ed25519 as ref
    from cometbft_tpu.ops.field import limbs_from_int
    out = np.zeros((4, 16, len(points)), np.int32)
    for lane, pt in enumerate(points):
        x, y = _ref_affine(pt)
        for c, v in enumerate((x, y, 1, x * y % ref.P)):
            out[c, :, lane] = limbs_from_int(v)
    return out


def _digits(v: int, n: int):
    return [(v >> (4 * i)) & 15 for i in range(n)]


def phase_kernels(bucket: int, seed: int) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor
    from cometbft_tpu.crypto import ref_ed25519 as ref
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.ops import edwards as ed
    from cometbft_tpu.ops import pallas_verify as pv

    check(bucket % pv.TILE == 0, f"bucket {bucket} not TILE-aligned")
    lanes = real_lanes(N_REAL_SIGS, seed) + edge_lanes()
    pubs, msgs, sigs, notes = (list(c) for c in zip(*lanes))
    with Timer() as t:
        want = np.array([ref.verify(p, m, s)
                         for p, m, s in zip(pubs, msgs, sigs)])
    log(f"[kernels] {len(lanes)} lanes ({N_REAL_SIGS} real + "
        f"{len(lanes) - N_REAL_SIGS} edge) through the big-int oracle "
        f"in {t.s:.1f}s: {int(want.sum())} valid")
    for note, ok in list(zip(notes, want))[N_REAL_SIGS:]:
        log(f"[kernels]   edge lane {note!r}: oracle says {bool(ok)}")
    check(want[:N_REAL_SIGS].all(), "oracle rejected a real signature")

    def batch(sel):
        return e5.prepare_batch([pubs[i] for i in sel],
                                [msgs[i] for i in sel],
                                [sigs[i] for i in sel], bucket, MSG_CAP)

    good = [i for i in range(len(lanes)) if want[i]]
    mixed = list(range(len(lanes)))
    z = e5.make_rlc_coefficients(bucket, np.random.default_rng(seed))

    # the two XLA reference kernels compile for minutes; start them now,
    # beside the Pallas compiles (compilation releases the GIL)
    pool = ThreadPoolExecutor(2)
    g_pub, g_sig, g_hb, g_hn, _ = batch(good)
    m_pub, m_sig, m_hb, m_hn, m_mask = batch(mixed)

    def timed(fn, *a, **kw):
        with Timer() as tt:
            out = jax.block_until_ready(fn(*a, **kw))
        return out, tt.s

    xla_rlc = pool.submit(timed, e5.verify_rlc_kernel,
                          g_pub, g_sig, g_hb, g_hn, z)
    xla_lane = pool.submit(timed, e5.verify_kernel,
                           m_pub, m_sig, m_hb, m_hn, zip215=True)

    # -- pt_decompress_tiled vs the oracle, every lane ----------------------
    enc = jnp.asarray(np.ascontiguousarray(m_pub.T), jnp.int32)  # (32, N)
    (a_pt, a_ok), s = timed(pv.pt_decompress_tiled, enc)
    log(f"[kernels] pt_decompress_tiled@{bucket}: first call {s:.1f}s")
    a_pt, a_ok = np.asarray(a_pt), np.asarray(a_ok)
    for lane in range(bucket):
        pt = ref.pt_decompress(bytes(m_pub[lane]), zip215=True)
        check(bool(a_ok[lane]) == (pt is not None),
              f"decompress validity differs at lane {lane}")
        if pt is not None:
            check(_affine(a_pt, lane) == _ref_affine(pt),
                  f"decompress point differs at lane {lane}")
    r_enc = jnp.asarray(np.ascontiguousarray(m_sig[:, :32].T), jnp.int32)
    r_pt, r_ok = (np.asarray(v) for v in pv.pt_decompress_tiled(r_enc))

    # -- pt_add_tiled vs the oracle on A + R --------------------------------
    both = [i for i in range(bucket) if a_ok[i] and r_ok[i]]
    out, s = timed(pv.pt_add_tiled, jnp.asarray(a_pt), jnp.asarray(r_pt))
    log(f"[kernels] pt_add_tiled@{bucket}: first call {s:.1f}s")
    out = np.asarray(out)
    for lane in both:
        exp = ref.pt_add(ref.pt_decompress(bytes(m_pub[lane])),
                         ref.pt_decompress(bytes(m_sig[lane, :32])))
        check(_affine(out, lane) == _ref_affine(exp),
              f"pt_add differs at lane {lane}")

    # -- rlc_window_sums + rlc_epilogue driven by host-computed scalars -----
    # the RLC equation of the all-valid batch, every scalar from hashlib
    # and python ints: windows of -A by the digits of t_i = z_i k_i,
    # windows of -R by the digits of z_i, S = sum z_i s_i
    zs = [sum(int(z[i, j]) << (16 * j) for j in range(8))
          for i in range(bucket)]
    a_pts, r_pts, ts, s_sum = [], [], [], 0
    for i in range(bucket):
        pk, sg = bytes(g_pub[i]), bytes(g_sig[i])
        nb = int(g_hn[i])
        body = bytes(g_hb[i, :nb].reshape(-1))
        mlen = int.from_bytes(body[-16:], "big") // 8
        k = ref.sc_reduce(hashlib.sha512(body[:mlen]).digest())
        a_pts.append(ref.pt_neg(ref.pt_decompress(pk)))
        r_pts.append(ref.pt_neg(ref.pt_decompress(sg[:32])))
        ts.append(zs[i] * k % ref.L)
        s_sum = (s_sum + zs[i] * int.from_bytes(sg[32:], "little")) % ref.L
    t_dig = np.array([_digits(t, 64) for t in ts], np.int32).T
    z_dig = np.array([_digits(v, 32) for v in zs], np.int32).T
    sums, s = timed(pv.rlc_window_sums, jnp.asarray(_pack_ref(a_pts)),
                    jnp.asarray(_pack_ref(r_pts)), jnp.asarray(t_dig),
                    jnp.asarray(z_dig))
    log(f"[kernels] rlc_window_sums@{bucket}: first call {s:.1f}s")
    sums = np.asarray(sums)                 # (G, 96, 4, 16, TAIL)
    check(sums.shape == (bucket // pv.TILE, 96, 4, 16, pv.TAIL),
          f"window sums shape {sums.shape}")
    for w in (0, 7, 63, 64, 95):
        pts, digs = (a_pts, t_dig[w]) if w < 64 else (r_pts, z_dig[w - 64])
        exp = ref.pt_mul(0, ref.BASE)
        for pt, d in zip(pts, digs):
            exp = ref.pt_add(exp, ref.pt_mul(int(d), pt))
        got = ref.pt_mul(0, ref.BASE)
        for g in range(sums.shape[0]):
            for lane in range(pv.TAIL):
                x, y = _affine(sums[g, w], lane)
                got = ref.pt_add(got, (x, y, 1, x * y % ref.P))
        check(_ref_affine(got) == _ref_affine(exp),
              f"window {w} partial sums differ from the oracle")
    folded = jnp.transpose(jnp.asarray(sums), (2, 3, 1, 0, 4)).reshape(
        4, 16, 96, -1)
    b_tab = jnp.asarray(ed.small_base_table())
    ok, s = timed(pv.rlc_epilogue, folded, b_tab,
                  jnp.asarray(_digits(s_sum, 64), jnp.int32))
    log(f"[kernels] rlc_epilogue@{bucket}: first call {s:.1f}s")
    check(bool(ok), "epilogue rejected the all-valid equation")
    bad_s = jnp.asarray(_digits((s_sum + 1) % ref.L, 64), jnp.int32)
    check(not bool(pv.rlc_epilogue(folded, b_tab, bad_s)),
          "epilogue accepted S+1")

    # -- the jitted pallas RLC kernel vs the XLA RLC kernel and the oracle --
    (p_ok, p_struct), s = timed(e5.verify_rlc_kernel_pallas,
                                g_pub, g_sig, g_hb, g_hn, z)
    log(f"[kernels] verify_rlc_kernel_pallas@{bucket}: first call {s:.1f}s")
    (x_ok, x_struct), s = xla_rlc.result()
    log(f"[kernels] verify_rlc_kernel@{bucket} (XLA reference): "
        f"first call {s:.1f}s")
    check(bool(p_ok) and bool(x_ok), "all-valid batch rejected")
    check((np.asarray(p_struct) == np.asarray(x_struct)).all(),
          "struct lanes differ on the all-valid batch")
    p_ok, p_struct = e5.verify_rlc_kernel_pallas(m_pub, m_sig, m_hb, m_hn, z)
    x_ok, x_struct = e5.verify_rlc_kernel(m_pub, m_sig, m_hb, m_hn, z)
    check(not bool(p_ok) and not bool(x_ok),
          "batch with invalid lanes accepted")
    check((np.asarray(p_struct) == np.asarray(x_struct)).all(),
          "struct lanes differ on the mixed batch")
    # struct-bad lanes only: they drop out, the equation holds
    def struct_bad(i):
        return (ref.pt_decompress(pubs[i]) is None
                or ref.pt_decompress(sigs[i][:32]) is None
                or int.from_bytes(sigs[i][32:], "little") >= ref.L)

    sel = [i for i in mixed if want[i] or struct_bad(i)]
    check(len(sel) > len(good), "no struct-bad lane in the edge set")
    s_pub, s_sig, s_hb, s_hn, _ = batch(sel)
    p_ok, p_struct = e5.verify_rlc_kernel_pallas(s_pub, s_sig, s_hb, s_hn, z)
    x_ok, x_struct = e5.verify_rlc_kernel(s_pub, s_sig, s_hb, s_hn, z)
    check(bool(p_ok) and bool(x_ok), "struct-masked batch rejected")
    check((np.asarray(p_struct) == np.asarray(x_struct)).all(),
          "struct lanes differ on the struct-masked batch")
    check(list(np.asarray(p_struct)[:len(sel)])
          == [bool(want[i]) for i in sel],
          "struct mask differs from the oracle")

    # -- per-lane attribution kernel vs the oracle, every lane --------------
    lane_ok, s = xla_lane.result()
    log(f"[kernels] verify_kernel@{bucket} (per-lane attribution): "
        f"first call {s:.1f}s")
    got = np.asarray(lane_ok)[:len(lanes)] & m_mask[:len(lanes)]
    check((got == want).all(), "per-lane kernel differs from the oracle at "
          f"lanes {np.flatnonzero(got != want).tolist()}")
    # and the host API end to end (RLC fails -> per-lane attribution)
    got = e5.verify_batch(pubs, msgs, sigs, batch_size=bucket)
    check((got == want).all(), "verify_batch differs from the oracle")
    pool.shutdown()
    log("[kernels] OK")


# --- phases 2 and 3: catch-up and its guarantee --------------------------------

def fresh_node(chain):
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.store.blockstore import BlockStore
    app = KVStoreApplication()
    app.init_chain(chain.chain_id, 1, [], b"")
    db = MemDB()
    store = BlockStore(db)
    executor = BlockExecutor(app, state_store=StateStore(db),
                             block_store=store)
    return executor, store, State.from_genesis(chain.genesis)


def node_reactor(chain, source, batch: int):
    """A BlocksyncReactor built the way Node._sync_then_consensus builds
    it: tile_size 16, the node's device batch, the configured pipeline
    depth, the in-process backend under a DeviceWatchdog."""
    from cometbft_tpu.config import BlockSyncConfig
    from cometbft_tpu.engine.blocksync import BlocksyncReactor
    from cometbft_tpu.pipeline.watchdog import DeviceWatchdog
    executor, store, state = fresh_node(chain)
    depth = BlockSyncConfig().pipeline_depth if batch > 0 else 1
    watchdog = DeviceWatchdog() if depth > 1 else None
    reactor = BlocksyncReactor(
        executor, store, source, chain.chain_id, tile_size=TILE_BLOCKS,
        batch_size=batch, pipeline_depth=depth, watchdog=watchdog)
    return reactor, store, state, watchdog


def device_health(watchdog, what: str) -> None:
    from cometbft_tpu.ops import ed25519 as e5
    check(watchdog.trips == 0 and watchdog.fallbacks == 0,
          f"{what}: watchdog trips={watchdog.trips} "
          f"fallbacks={watchdog.fallbacks} ({watchdog.last_error!r})")
    check(not e5.pallas_degraded(), f"{what}: pallas latched broken")
    stats = e5.canary_stats()
    check(stats["trips"] == 0 and stats["runs"] >= 1,
          f"{what}: canary {stats}")


def phase_catchup(chain, batch: int) -> None:
    from cometbft_tpu.engine.chain_gen import LocalChainSource
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.types.validation import BATCH_VERIFY_THRESHOLD

    n_sigs = sum(1 for c in chain.seen_commits for cs in c.signatures
                 if not cs.absent_())
    reactor, store, state, watchdog = node_reactor(
        chain, LocalChainSource(chain), batch)
    before = e5._dispatches
    with Timer() as t_dev:
        state = reactor.sync(state)
    check(state.last_block_height == N_BLOCKS,
          f"device sync stopped at {state.last_block_height}")
    check(reactor.stats.sigs_verified == n_sigs,
          f"sigs_verified {reactor.stats.sigs_verified} != {n_sigs}")
    # every tile is far above the threshold, so none took verify_lanes'
    # native branch; each 512-lane chunk is one pallas dispatch
    tile_lanes = [sum(1 for c in chain.seen_commits[lo:lo + TILE_BLOCKS]
                      for cs in c.signatures if not cs.absent_())
                  for lo in range(0, N_BLOCKS, TILE_BLOCKS)]
    check(min(tile_lanes) >= BATCH_VERIFY_THRESHOLD, "a tile went native")
    chunks = sum(-(-n // batch) for n in tile_lanes)
    check(e5._dispatches - before == chunks,
          f"{e5._dispatches - before} pallas dispatches, want {chunks}")
    device_health(watchdog, "catch-up")

    ref_reactor, ref_store, ref_state, _ = node_reactor(
        chain, LocalChainSource(chain), 0)
    with Timer() as t_nat:
        ref_state = ref_reactor.sync(ref_state)
    check(ref_state.last_block_height == N_BLOCKS, "native sync fell short")
    check(state.app_hash == ref_state.app_hash, "app hash differs")
    check(store.load_block(N_BLOCKS).hash()
          == ref_store.load_block(N_BLOCKS).hash()
          == chain.blocks[-1].hash(), "last block hash differs")
    for name, t in (("device", t_dev), ("native", t_nat)):
        log(f"[catch-up] smoke timing, not a benchmark: {name} sync "
            f"{n_sigs} sigs in {t.s:.2f}s = {n_sigs / t.s:.0f} sigs/s")
    log(f"[catch-up] OK height={state.last_block_height} "
        f"app_hash={state.app_hash.hex()} pallas_dispatches={chunks}")


def phase_guarantee(chain, batch: int) -> None:
    from cometbft_tpu.engine.blocksync import SyncStalled
    from cometbft_tpu.engine.chain_gen import LocalChainSource
    from cometbft_tpu.state.execution import BlockValidationError
    from cometbft_tpu.types.block import Block, Commit, CommitSig

    bad_height = N_BLOCKS // 2 + 3      # mid-chain, mid-tile
    bad_index = N_VALIDATORS // 3

    class TamperingSource(LocalChainSource):
        """Serves the commit sealing `bad_height` with one signature's s
        corrupted (structurally valid: only the batch EQUATION fails, so
        attribution falls to the per-lane kernel) — and keeps doing so
        after a ban."""

        def fetch(self, height):
            got = super().fetch(height)
            if got is None or height != bad_height + 1:
                return got
            block, block_id = got
            lc = block.last_commit
            sigs = list(lc.signatures)
            cs = sigs[bad_index]
            sig = cs.signature
            sigs[bad_index] = CommitSig(
                cs.block_id_flag, cs.validator_address, cs.timestamp,
                sig[:40] + bytes([sig[40] ^ 1]) + sig[41:])
            return Block(header=block.header, data=block.data,
                         last_commit=Commit(lc.height, lc.round,
                                            lc.block_id, sigs)), block_id

        def ban(self, height):
            self.banned.append(height)

    source = TamperingSource(chain)
    reactor, store, state, watchdog = node_reactor(chain, source, batch)
    try:
        reactor.sync(state)
    except (BlockValidationError, SyncStalled) as e:
        log(f"[guarantee] sync refused: {type(e).__name__}: {e}")
    else:
        raise AssertionError("tampered chain synced to the tip")
    check(store.height() < bad_height,
          f"block {bad_height} applied (store at {store.height()})")
    check(source.banned, "the corrupt peer was never banned")
    device_health(watchdog, "guarantee")
    log(f"[guarantee] OK store stopped at {store.height()} < {bad_height}, "
        f"banned heights {sorted(set(source.banned))}")


# --- phase 4: the crypto.batch seam ---------------------------------------------

def phase_seam(seed: int) -> None:
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.engine.chain_gen import generate_chain
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.types import validation
    from cometbft_tpu.types.block import Commit, CommitSig

    chain = generate_chain(n_blocks=1, n_validators=SEAM_VALIDATORS,
                           seed=seed + 1)
    vals, commit = chain.valsets[0], chain.seen_commits[0]
    bid = chain.block_ids[0]

    def native(c):
        return [Ed25519PubKey(vals.get_by_index(i).pub_key.bytes_())
                .verify_signature(c.vote_sign_bytes(chain.chain_id, i),
                                  cs.signature)
                for i, cs in enumerate(c.signatures)]

    before = e5._dispatches
    validation.verify_commit(chain.chain_id, vals, bid, 1, commit)
    check(e5._dispatches > before,
          "verify_commit did not reach the pallas dispatch")
    check(all(native(commit)), "native rejects the clean commit")

    sigs = list(commit.signatures)
    cs, sig = sigs[7], sigs[7].signature
    sigs[7] = CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp,
                        sig[:40] + bytes([sig[40] ^ 1]) + sig[41:])
    bad = Commit(commit.height, commit.round, commit.block_id, sigs)
    try:
        validation.verify_commit(chain.chain_id, vals, bid, 1, bad)
    except validation.CommitVerificationError as e:
        log(f"[seam] tampered commit refused: {type(e).__name__}")
    else:
        raise AssertionError("tampered commit verified")
    check(native(bad).count(False) == 1 and not native(bad)[7],
          "native attributes differently")
    check(not e5.pallas_degraded(), "pallas latched broken")
    log(f"[seam] OK {SEAM_VALIDATORS}-validator commit verified on the "
        "device branch, verdicts equal to native")


# --- --chips 4: the mesh path and its single-device comparison -------------------

def phase_mesh(chain, batch: int) -> None:
    import numpy as np
    import jax
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.engine.blocksync import TileEntry, marshal_commit
    from cometbft_tpu.mesh import MeshExecutor, MeshTopology
    from cometbft_tpu.mesh.planner import shard_width_for
    from cometbft_tpu.ops import ed25519 as e5
    from cometbft_tpu.parallel import verify as pverify
    from cometbft_tpu.parallel.mesh import make_mesh

    check(jax.device_count() == 4, f"{jax.device_count()} devices, want 4")
    tiles = []          # one (pubs, msgs, sigs) per 16-block tile
    for lo in range(0, N_BLOCKS, TILE_BLOCKS):
        pubs, msgs, sigs = [], [], []
        for i in range(lo, min(lo + TILE_BLOCKS, N_BLOCKS)):
            marshal_commit(chain.chain_id,
                           TileEntry(i + 1, chain.blocks[i],
                                     chain.block_ids[i], chain.valsets[i],
                                     chain.seen_commits[i]),
                           pubs, msgs, sigs)
        tiles.append((pubs, msgs, sigs))
    pubs, msgs, sigs = (sum((t[c] for t in tiles), []) for c in range(3))
    log(f"[mesh] {len(pubs)} lanes in {len(tiles)} tiles")

    # -- single device: the pallas RLC path on device 0 ---------------------
    with Timer() as t:
        single = e5.verify_batch(pubs, msgs, sigs, batch_size=batch)
    log(f"[mesh] smoke timing: single-device verify_batch {t.s:.1f}s "
        "(compile included)")
    check(single.all(), "single device rejected a chain signature")

    # -- the sharded RLC equation over the (2, 2) mesh ----------------------
    # built the way verify_batch_mesh builds it, compiled ahead of time
    # so the COMPILED text can be read without a second compile
    mesh = make_mesh()
    check(dict(mesh.shape) == {"commit": 2, "sig": 2},
          f"mesh shape {dict(mesh.shape)}")
    check(len({d.id for d in mesh.devices.flat}) == 4, "mesh reuses a device")
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((batch, 32), np.uint8), ((batch, 64), np.uint8),
        ((batch, 2, 128), np.uint8), ((batch,), np.int32),
        ((batch, 8), np.int32))]
    with Timer() as t:
        rlc = pverify.make_rlc_sharded_verifier(mesh).lower(
            *shapes).compile()
    log(f"[mesh] sharded RLC@{batch} compiled in {t.s:.1f}s")
    check("all-gather" in rlc.as_text(),
          "no all-gather in the compiled sharded RLC")
    pverify._mesh_state.update(
        mesh=mesh, rlc=rlc,
        lanes=pverify.make_lanes_sharded_verifier(mesh))
    os.environ["COMETBFT_TPU_MESH_VERIFY"] = "1"
    try:
        check(pverify.mesh_available(), "mesh not available")
        with Timer() as t:
            sharded = pverify.verify_batch_mesh(pubs, msgs, sigs,
                                                batch_size=batch)
    finally:
        del os.environ["COMETBFT_TPU_MESH_VERIFY"]
    log(f"[mesh] smoke timing: verify_batch_mesh {t.s:.1f}s")
    check((sharded == single).all(), "sharded verdicts differ")

    # one tampered lane in every device's slice: the sharded equation
    # must fail exactly as the single-device one does
    per_dev = batch // 4
    bad = [d * per_dev + per_dev // 2 for d in range(4)]
    c_sigs = list(sigs[:batch])
    for i in bad:
        c_sigs[i] = c_sigs[i][:40] + bytes([c_sigs[i][40] ^ 1]) \
            + c_sigs[i][41:]
    args = e5.prepare_batch(pubs[:batch], msgs[:batch], c_sigs, batch,
                            MSG_CAP)[:4]
    z = e5.make_rlc_coefficients(batch)
    s_ok, s_struct = e5.verify_rlc_kernel_pallas(*args, z)
    m_ok, m_struct = rlc(*args, z)
    check(not bool(s_ok) and not bool(m_ok), "tampered chunk accepted")
    check((np.asarray(s_struct) == np.asarray(m_struct)).all(),
          "struct lanes differ between single device and mesh")
    devs = {sh.device.id for sh in m_struct.addressable_shards}
    check(len(devs) == 4, f"sharded lanes live on devices {devs}")
    log(f"[mesh] sharded RLC: {per_dev} lanes per device on devices "
        f"{sorted(devs)}, all-gather in the compiled text, tampered chunk "
        "refused by both")

    # -- MeshExecutor: per-lane verdicts with shard attribution -------------
    t_sigs = list(sigs)
    tampered = {7, len(sigs) // 2, len(sigs) - 3}
    for i in tampered:
        t_sigs[i] = t_sigs[i][:40] + bytes([t_sigs[i][40] ^ 1]) \
            + t_sigs[i][41:]
    want = single.copy()
    for i in tampered:
        check(not Ed25519PubKey(pubs[i]).verify_signature(msgs[i],
                                                          t_sigs[i]),
              "native accepts a tampered lane")
        want[i] = False
    executor = MeshExecutor(MeshTopology(n_devices=4, sig_parallel=2))
    try:
        width = shard_width_for(len(tiles[0][0]), 4, executor.canary)
        with Timer() as t:
            executor.warm(widths=[width], probe=False)
        log(f"[mesh] executor warm, shard width {width}: {t.s:.1f}s")
        got, shards, lo = [], [], 0
        with Timer() as t:
            for tp, tm, _ts in tiles:
                fut = executor.submit(tp, tm, t_sigs[lo:lo + len(tp)])
                got += list(fut.result(600))
                shards += list(fut.shards)
                lo += len(tp)
        log(f"[mesh] smoke timing: MeshExecutor {len(got)} lanes {t.s:.1f}s")
        check((np.asarray(got, bool) == want).all(),
              "executor verdicts differ from the single-device verdicts")
        counts = {s: shards.count(s) for s in sorted(set(shards))}
        log(f"[mesh] lanes attributed per shard: {counts}")
        check(set(counts) == {0, 1, 2, 3} and min(counts.values()) > 0,
              f"shard attribution {counts}")
    finally:
        executor.close()
    log("[mesh] OK")


# --- entry -----------------------------------------------------------------------

def run(args, result: dict) -> None:
    import jax
    from cometbft_tpu.libs.jax_cache import (enable_compile_cache,
                                             is_device_platform)
    enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"[device] {device} jax {jax.__version__} "
        f"cache_dir={jax.config.jax_compilation_cache_dir!r} "
        f"JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}")
    result["device"] = device
    if not is_device_platform():
        log(f"[device] platform {dev.platform!r} is not a TPU: nothing to "
            "prove here")
        return
    check(device["count"] == args.chips,
          f"{device['count']} chips visible, --chips {args.chips}")

    from cometbft_tpu.engine.chain_gen import generate_chain
    from cometbft_tpu.node.node import Node
    from cometbft_tpu.ops.ed25519 import (prewarm_verify_kernels,
                                          use_pallas_rlc)
    batch = Node._device_batch_size()
    check(batch > 0 and use_pallas_rlc(),
          f"node chose batch={batch} pallas={use_pallas_rlc()} on a TPU")
    check(jax.config.jax_enable_compilation_cache, "compile cache is off")
    log(f"[device] node bucket {batch} lanes, pallas on, compile cache on")

    def make_chain():
        # executing the chain verifies every last_commit through the
        # crypto.batch seam — on this backend, the device
        with Timer() as t:
            chain = generate_chain(n_blocks=N_BLOCKS,
                                   n_validators=N_VALIDATORS, seed=args.seed)
        log(f"[chain] {N_BLOCKS} blocks x {N_VALIDATORS} validators "
            f"(seed {args.seed}) in {t.s:.1f}s")
        return chain

    with Timer() as total:
        if args.chips == 4:
            phase_mesh(make_chain(), batch)
        else:
            phase_kernels(batch, args.seed)
            # what Node does before engine.sync: both kernels of the
            # bucket compiled before a watchdog deadline is armed
            with Timer() as t:
                prewarm_verify_kernels(batch_size=batch)
            log(f"[catch-up] prewarm (node boot step) {t.s:.1f}s")
            chain = make_chain()
            phase_catchup(chain, batch)
            phase_guarantee(chain, batch)
            phase_seam(args.seed)
    log(f"[done] all phases passed in {total.s:.1f}s")
    result["ok"] = True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the mesh path and its comparison")
    ap.add_argument("--seed", type=int, default=22)
    args = ap.parse_args()
    result = {"ok": False, "device": None}
    try:
        run(args, result)
    except BaseException:  # noqa: BLE001 — the verdict line must print
        import traceback
        traceback.print_exc()
        sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
