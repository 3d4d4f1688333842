"""farm/ — the light-client verification farm (docs/FARM.md):
planner equivalence with the in-process LightClient, cross-session
coalescing + dedup, bounded-queue backpressure/shed, forged-header
rejection, the device seam's canary/fallback behavior, the light_*
RPC endpoints, metricsgen counters, and the spec-oracle bridge."""

import pytest

from cometbft_tpu.db.kv import MemDB
from cometbft_tpu.engine.chain_gen import ChainLightProvider, generate_chain
from cometbft_tpu.farm import (FarmOverloaded, UnknownSession,
                               VerificationFarm, VerifyRejected)
from cometbft_tpu.farm.batcher import FarmBatcher, QueueFull
from cometbft_tpu.farm.session import SessionManager
from cometbft_tpu.light.client import LightClient, TrustOptions
from cometbft_tpu.light.store import LightStore
from cometbft_tpu.pipeline.cache import SigCache
from cometbft_tpu.types.proto import Timestamp

CHAIN_LEN = 16
TRUST_PERIOD = 10 ** 9


@pytest.fixture(scope="module")
def chain():
    return generate_chain(CHAIN_LEN, n_validators=5, txs_per_block=1)


def _now(chain):
    return Timestamp(1_700_000_000 + chain.max_height() + 5, 0)


def _farm(chain, provider=None, **kw):
    cache = kw.pop("cache", None) or SigCache(65536)
    batcher = kw.pop("batcher", None) or FarmBatcher(
        cache=cache, coalesce_window_s=0.0)
    return VerificationFarm(chain.chain_id,
                            provider or ChainLightProvider(chain),
                            cache=cache, batcher=batcher,
                            now_fn=lambda: _now(chain), **kw)


def _light_client(chain, h0=1):
    opts = TrustOptions(period_seconds=TRUST_PERIOD, height=h0,
                        hash=chain.blocks[h0 - 1].hash())
    return LightClient(chain.chain_id, opts, ChainLightProvider(chain),
                      [], LightStore(MemDB()),
                      now_fn=lambda: _now(chain))


# --- equivalence with the in-process light client ---------------------------


def test_farm_accepts_what_light_client_accepts(chain):
    """Static valset: one skipping jump — farm and LightClient land on
    the identical trusted header."""
    farm = _farm(chain)
    s = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    out = farm.verify(s.session_id, chain.max_height())
    lc = _light_client(chain)
    lb = lc.verify_light_block_at_height(chain.max_height())
    assert out["hash"] == lb.header.hash().hex()
    assert out["steps"] == 1  # single non-adjacent jump
    assert s.latest().height == chain.max_height()


def test_farm_bisects_across_valset_rotation():
    """Rotate >2/3 of the power mid-chain: the farm's planner must
    walk the same pivot chain the LightClient's _verify_skipping does
    and store the same intermediate headers."""
    import random

    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.engine.chain_gen import make_genesis

    rng = random.Random(99)
    new_keys = [Ed25519PrivKey(bytes(rng.randrange(256)
                                     for _ in range(32)))
                for _ in range(6)]
    _, orig_keys = make_genesis(4, seed=1)
    val_txs = {}
    for i, k in enumerate(new_keys):
        val_txs[5 + i] = (b"val:" + k.pub_key().bytes_().hex().encode()
                          + b"!40")
    for i, k in enumerate(orig_keys.values()):
        val_txs[11 + i] = (b"val:" + k.pub_key().bytes_().hex().encode()
                           + b"!0")
    rot = generate_chain(20, n_validators=4, val_tx_heights=val_txs,
                         extra_keys=new_keys, txs_per_block=1)

    farm = _farm(rot)
    s = farm.subscribe(1, rot.blocks[0].hash(), TRUST_PERIOD)
    out = farm.verify(s.session_id, rot.max_height())
    assert out["height"] == rot.max_height()
    assert out["steps"] > 1, "rotation must force bisection"

    opts = TrustOptions(period_seconds=TRUST_PERIOD, height=1,
                        hash=rot.blocks[0].hash())
    lc = LightClient(rot.chain_id, opts, ChainLightProvider(rot), [],
                     LightStore(MemDB()),
                     now_fn=lambda: Timestamp(
                         1_700_000_000 + rot.max_height() + 5, 0))
    lc.verify_light_block_at_height(rot.max_height())
    farm_heights = [h for h in range(1, rot.max_height() + 1)
                    if s.store.light_block(h) is not None]
    lc_heights = [h for h in range(1, rot.max_height() + 1)
                  if lc.trusted_light_block(h) is not None]
    assert farm_heights == lc_heights


def test_expired_trust_rejected(chain):
    farm = _farm(chain)
    s = farm.subscribe(1, chain.blocks[0].hash(), 1)  # 1s period
    with pytest.raises(VerifyRejected, match="expired"):
        farm.verify(s.session_id, chain.max_height())


def test_forward_only_and_store_fast_path(chain):
    farm = _farm(chain)
    s = farm.subscribe(5, chain.blocks[4].hash(), TRUST_PERIOD)
    farm.verify(s.session_id, chain.max_height())
    # a height already trusted is served from the session store
    out = farm.verify(s.session_id, chain.max_height())
    assert out["steps"] == 0
    # below the latest trusted (and unstored): forward-only policy
    with pytest.raises(VerifyRejected, match="forward"):
        farm.verify(s.session_id, 3)


def test_bad_trust_root_rejected(chain):
    farm = _farm(chain)
    with pytest.raises(VerifyRejected, match="hash"):
        farm.subscribe(1, b"\x13" * 32, TRUST_PERIOD)
    assert len(farm.sessions) == 0


# --- coalescing, dedup, backpressure ----------------------------------------


def test_cross_session_dedup(chain):
    """Second session verifying the same tip costs ZERO fresh lanes —
    every signature is already in the verified cache."""
    farm = _farm(chain)
    s1 = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    farm.verify(s1.session_id, chain.max_height())
    lanes_before = sum(farm.batcher.lanes_by_backend.values())
    s2 = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    farm.verify(s2.session_id, chain.max_height())
    assert sum(farm.batcher.lanes_by_backend.values()) == lanes_before
    assert farm.cache.hit_rate("farm") > 0


def test_wave_coalesces_into_one_batch(chain):
    """A wave of begin_verify calls + one flush = ONE shared batch
    whose width is the unique-lane count, not the per-client sum."""
    farm = _farm(chain)
    sessions = [farm.subscribe(1 + i % 4, chain.blocks[i % 4].hash(),
                               TRUST_PERIOD) for i in range(8)]
    farm.batcher.flush()
    batches_before = farm.batcher.batches
    pendings = [farm.begin_verify(s.session_id, chain.max_height())
                for s in sessions]
    width = farm.batcher.flush()
    for p in pendings:
        assert farm.finish_verify(p)["height"] == chain.max_height()
    assert farm.batcher.batches == batches_before + 1
    # 5 validators, power 10 each: own-commit early-exits at 4 lanes,
    # trusting at 2 (subset) — 8 clients coalesce to 4 unique lanes
    assert width == 4
    assert farm.batcher.dedup_batch_hits > 0


def test_lane_queue_shed(chain):
    # a root commit check at 5 validators plans 4 lanes (> 2/3 of 50
    # power = 4 signers); a 3-lane queue must shed it
    farm = _farm(chain, batcher=FarmBatcher(cache=SigCache(65536),
                                            coalesce_window_s=0.0,
                                            max_pending_lanes=3))
    with pytest.raises(FarmOverloaded):
        farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    assert farm.batcher.shed == 1
    # shed must not leak a half-open session
    assert len(farm.sessions) == 0


def test_shed_releases_queued_lane_budget(chain):
    """A request that sheds mid-plan must withdraw its already-queued
    checks — orphaned lanes would strand the bounded queue's budget
    (nothing flushes a shed request) and livelock the farm into
    shedding every later request while idle."""
    cache = SigCache(65536)
    # 5 validators, power 10: trusting plans 2 lanes, own-commit 4 —
    # a 5-lane queue admits the trusting check, then sheds on own
    farm = _farm(chain, cache=cache,
                 batcher=FarmBatcher(cache=cache, coalesce_window_s=0.0,
                                     max_pending_lanes=5))
    # subscribe fits (4 lanes), then drain the queue
    s = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    with pytest.raises(FarmOverloaded):
        farm.begin_verify(s.session_id, chain.max_height())
    assert farm.batcher._pending_lanes == 0, \
        "shed request leaked queued lanes"
    # the farm is NOT livelocked: a fitting request still succeeds
    s2 = farm.subscribe(2, chain.blocks[1].hash(), TRUST_PERIOD)
    assert s2.latest().height == 2


def test_session_cap_shed(chain):
    farm = _farm(chain, sessions=SessionManager(max_sessions=1))
    farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    with pytest.raises(FarmOverloaded):
        farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)


def test_unknown_session(chain):
    farm = _farm(chain)
    with pytest.raises(UnknownSession):
        farm.verify("s999", chain.max_height())


# --- forged inputs -----------------------------------------------------------


def test_forged_signature_rejected_by_lane_verdict(chain):
    """A provider serving a bit-flipped commit signature: the planner
    cannot see it (threshold tallies are address-based), but the
    coalesced batch's lane verdict must reject — and the session's
    trust state must not advance."""
    from cometbft_tpu.simnet.light_farm import TamperingProvider

    prov = TamperingProvider(chain)
    farm = _farm(chain, provider=prov)
    s = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    prov.armed = {chain.max_height(): "sig"}
    with pytest.raises(VerifyRejected):
        farm.verify(s.session_id, chain.max_height())
    assert s.latest().height == 1
    prov.armed = {}
    out = farm.verify(s.session_id, chain.max_height())
    assert out["height"] == chain.max_height()


def test_forged_header_rejected_host_side(chain):
    from cometbft_tpu.simnet.light_farm import TamperingProvider

    prov = TamperingProvider(chain)
    farm = _farm(chain, provider=prov)
    s = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    batches_before = farm.batcher.batches
    prov.armed = {chain.max_height(): "hash"}
    with pytest.raises(VerifyRejected):
        farm.verify(s.session_id, chain.max_height())
    # rejected by validate_basic BEFORE any lane was queued
    assert farm.batcher.batches == batches_before
    assert s.latest().height == 1


# --- the device seam ---------------------------------------------------------


def test_backend_failure_fails_tickets_not_hangs(chain):
    """A backend that answers the wrong lane count must fail every
    waiting ticket (and surface), never strand an RPC thread."""
    def broken(lanes):
        return [True], "device"

    cache = SigCache(65536)
    farm = _farm(chain, batcher=FarmBatcher(
        cache=cache, coalesce_window_s=0.0, verify_backend=broken),
        cache=cache)
    with pytest.raises(Exception):
        farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)


def test_device_backend_attribution(chain):
    """An injected 'device' backend is attributed per batch; verdicts
    flow into the cache exactly like CPU ones."""
    from cometbft_tpu.farm.batcher import _native_verify

    def fake_device(lanes):
        oks, _ = _native_verify(lanes)
        return oks, "device"

    cache = SigCache(65536)
    farm = _farm(chain, batcher=FarmBatcher(
        cache=cache, coalesce_window_s=0.0,
        verify_backend=fake_device), cache=cache)
    s = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    farm.verify(s.session_id, chain.max_height())
    assert set(farm.batcher.lanes_by_backend) == {"device"}
    assert farm.status()["lanes_by_backend"]["device"] > 0


def test_default_backend_cpu_without_device(chain, monkeypatch):
    """With no COMETBFT_TPU_DEVICE_SERVER, the default backend runs
    the native per-sig CPU path and attributes it as such."""
    monkeypatch.delenv("COMETBFT_TPU_DEVICE_SERVER", raising=False)
    farm = _farm(chain)
    s = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    farm.verify(s.session_id, chain.max_height())
    assert set(farm.batcher.lanes_by_backend) == {"cpu"}


# --- metrics + spec oracle ---------------------------------------------------


def test_farm_metrics(chain):
    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.libs.metrics_gen import FarmMetrics

    reg = Registry()
    metrics = FarmMetrics(reg)
    cache = SigCache(65536)
    farm = VerificationFarm(
        chain.chain_id, ChainLightProvider(chain), cache=cache,
        sessions=SessionManager(max_sessions=2, metrics=metrics),
        batcher=FarmBatcher(cache=cache, coalesce_window_s=0.0,
                            metrics=metrics),
        metrics=metrics, now_fn=lambda: _now(chain))
    s = farm.subscribe(1, chain.blocks[0].hash(), TRUST_PERIOD)
    farm.verify(s.session_id, chain.max_height())
    farm.subscribe(2, chain.blocks[1].hash(), TRUST_PERIOD)
    with pytest.raises(FarmOverloaded):
        farm.subscribe(3, chain.blocks[2].hash(), TRUST_PERIOD)
    text = reg.expose()
    assert "cometbft_tpu_farm_sessions 2.0" in text
    assert "cometbft_tpu_farm_headers_accepted 1.0" in text
    assert 'cometbft_tpu_farm_lanes_verified{backend="cpu"}' in text
    assert 'cometbft_tpu_farm_dedup_hits{kind="batch"}' in text
    assert "cometbft_tpu_farm_shed_total 1.0" in text


def test_decisions_satisfy_spec_oracle(chain):
    from tools.check_light_spec import check_decisions

    farm = _farm(chain)
    for i in range(4):
        s = farm.subscribe(1 + i, chain.blocks[i].hash(), TRUST_PERIOD)
        farm.verify(s.session_id, chain.max_height())
    records = farm.drain_decisions()
    assert records
    assert check_decisions(records) == []
    # negative fixture: the oracle must actually be able to object
    bad = dict(records[0])
    bad["own_signed"] = bad["own_total"] * 2 // 3  # == floor: not >
    assert check_decisions([bad])
    bad2 = dict(records[0])
    if not bad2["adjacent"]:
        bad2["trusted_signed"] = 0
        assert check_decisions([bad2])


# --- RPC endpoints -----------------------------------------------------------


def test_farm_rpc_endpoints(chain):
    from cometbft_tpu.rpc.client import RPCClient, RPCClientError
    from cometbft_tpu.rpc.server import RPCEnvironment, RPCServer

    cache = SigCache(65536)
    farm = _farm(chain, cache=cache,
                 batcher=FarmBatcher(cache=cache,
                                     coalesce_window_s=0.001),
                 sessions=SessionManager(max_sessions=2))
    srv = RPCServer(RPCEnvironment(chain.chain_id, farm=farm))
    srv.start()
    try:
        c = RPCClient(*srv.addr)
        r = c.call("light_subscribe", height=1,
                   hash=chain.blocks[0].hash().hex(),
                   trusting_period=TRUST_PERIOD)
        sid = r["session"]
        assert r["latest_height"] == 1
        out = c.call("light_verify", session=sid,
                     height=chain.max_height())
        assert out["height"] == chain.max_height()
        assert out["hash"] == chain.blocks[-1].hash().hex()
        st = c.call("light_status")
        assert st["sessions"] == 1 and st["headers_accepted"] == 1
        assert c.call("light_status", session=sid)["latest_height"] \
            == chain.max_height()
        # error mapping: unknown session
        with pytest.raises(RPCClientError, match="-32602"):
            c.call("light_verify", session="nope")
        # error mapping: acceptance-rule rejection (backwards height)
        with pytest.raises(RPCClientError, match="-32001"):
            c.call("light_verify", session=sid, height=2)
        # error mapping: shed (session cap 2)
        c.call("light_subscribe", height=1,
               hash=chain.blocks[0].hash().hex(),
               trusting_period=TRUST_PERIOD)
        with pytest.raises(RPCClientError, match="-32005"):
            c.call("light_subscribe", height=1,
                   hash=chain.blocks[0].hash().hex(),
                   trusting_period=TRUST_PERIOD)
        assert c.call("light_unsubscribe", session=sid)["dropped"]
    finally:
        srv.stop()


def test_farm_routes_unmounted_without_farm(chain):
    from cometbft_tpu.rpc.client import RPCClient, RPCClientError
    from cometbft_tpu.rpc.server import RPCEnvironment, RPCServer

    srv = RPCServer(RPCEnvironment(chain.chain_id))
    srv.start()
    try:
        with pytest.raises(RPCClientError, match="-32601"):
            RPCClient(*srv.addr).call("light_status")
    finally:
        srv.stop()


def test_concurrent_rpc_clients_coalesce(chain):
    """Concurrent light_verify calls from real RPC worker threads
    coalesce through the batcher window and ALL succeed."""
    import threading

    from cometbft_tpu.rpc.client import RPCClient
    from cometbft_tpu.rpc.server import RPCEnvironment, RPCServer

    cache = SigCache(65536)
    farm = _farm(chain, cache=cache,
                 batcher=FarmBatcher(cache=cache,
                                     coalesce_window_s=0.01))
    srv = RPCServer(RPCEnvironment(chain.chain_id, farm=farm))
    srv.start()
    try:
        c = RPCClient(*srv.addr)
        sids = [c.call("light_subscribe", height=1 + i,
                       hash=chain.blocks[i].hash().hex(),
                       trusting_period=TRUST_PERIOD)["session"]
                for i in range(6)]
        outs = {}

        def hit(sid):
            outs[sid] = RPCClient(*srv.addr).call(
                "light_verify", session=sid,
                height=chain.max_height())

        threads = [threading.Thread(target=hit, args=(sid,))
                   for sid in sids]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert len(outs) == 6
        assert all(o["height"] == chain.max_height()
                   for o in outs.values())
    finally:
        srv.stop()


def test_node_serves_farm_routes(tmp_path):
    """[rpc] light_farm on a LIVE single-validator node: subscribe at
    height 1 over JSON-RPC, verify forward to a committed height, and
    read farm status — the whole product surface end to end."""
    import os
    import time

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import Config, ConsensusTimeoutsConfig
    from cometbft_tpu.node.node import Node, save_genesis
    from cometbft_tpu.privval.file import FilePV
    from cometbft_tpu.rpc.client import RPCClient
    from cometbft_tpu.state.state import GenesisDoc
    from cometbft_tpu.types.validator import Validator

    pv = FilePV.generate(None)
    gen = GenesisDoc(chain_id="farm-net",
                     genesis_time=Timestamp.now(),
                     validators=[Validator(pv.get_pub_key(), 10)])
    root = tmp_path / "farmnode"
    os.makedirs(root / "config", exist_ok=True)
    cfg = Config(root_dir=str(root))
    cfg.base.db_backend = "memdb"
    cfg.rpc.light_farm = True
    cfg.consensus = ConsensusTimeoutsConfig(
        timeout_propose=500, timeout_propose_delta=250,
        timeout_prevote=250, timeout_prevote_delta=150,
        timeout_precommit=250, timeout_precommit_delta=150,
        timeout_commit=50, wal_file="data/cs.wal")
    save_genesis(gen, str(root / "config/genesis.json"))
    node = Node(cfg, KVStoreApplication(), genesis=gen,
                priv_validator=pv)
    try:
        node.start()
        deadline = time.monotonic() + 60
        while node.consensus.state.last_block_height < 4:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        c = RPCClient(*node.rpc_server.addr)
        root_hash = c.header(1)["header_hash"] \
            if "header_hash" in c.header(1) else None
        if root_hash is None:
            # derive the trust root hash from the commit route (the
            # commit's block_id pins the header)
            sh = c.commit(1)["signed_header"]
            root_hash = sh["commit"]["block_id"]["hash"]
        r = c.call("light_subscribe", height=1, hash=root_hash,
                   trusting_period=10 ** 6)
        sid = r["session"]
        # verify to a height whose canonical commit is stored (tip-1)
        target = node.consensus.state.last_block_height - 1
        out = c.call("light_verify", session=sid, height=target)
        assert out["height"] == target
        st = c.call("light_status")
        assert st["sessions"] == 1
        assert st["headers_accepted"] >= 1
        # the node's farm shares the process-wide SigCache: the vote
        # intake already verified these signatures, so the farm serves
        # the whole request from cache — zero fresh lanes (the
        # docs/FARM.md "free-rider" synergy). Either way, SOME
        # verification evidence must exist.
        assert (sum(st["lanes_by_backend"].values()) > 0
                or st["cache_hit_rate"] > 0)
    finally:
        node.stop()


def test_batcher_queue_full_direct(chain):
    """QueueFull is raised at submit time, never silently dropped."""
    from cometbft_tpu.farm import planner

    cache = SigCache(65536)
    b = FarmBatcher(cache=cache, max_pending_lanes=2,
                    coalesce_window_s=0.0)
    commit = chain.seen_commits[-1]
    check = planner.plan_commit_light(
        chain.chain_id, chain.valsets[-1], commit.block_id,
        chain.max_height(), commit, cache)
    assert len(check.lanes) > 2
    with pytest.raises(QueueFull):
        b.submit(check)


# --- adaptive coalescing window (ROADMAP item 4 headroom) ---------------------


def test_coalesce_wait_plateau_flushes_at_half_window():
    """Tail-latency pin: a lone submitter (pending width never grows)
    flushes after 2 of the 4 sub-polls — half the fixed window — while
    the fixed knob stays the ceiling for a still-growing batch."""
    import threading
    import time as _t
    from cometbft_tpu.farm.batcher import (ADAPTIVE_POLLS, coalesce_wait)

    window = 0.4
    ev = threading.Event()  # never set: nobody else flushes

    # plateau: width constant -> early flush at 2 polls (window/2)
    t0 = _t.perf_counter()
    fired = coalesce_wait(ev, window, lambda: 3, adaptive=True)
    plateau_dt = _t.perf_counter() - t0
    assert fired is False
    assert plateau_dt < window * 0.9  # strictly beat the fixed window
    assert plateau_dt >= window / ADAPTIVE_POLLS * 0.5

    # growing batch: width changes every poll -> wait the full ceiling
    widths = iter(range(100))
    t0 = _t.perf_counter()
    fired = coalesce_wait(ev, window, lambda: next(widths), adaptive=True)
    growing_dt = _t.perf_counter() - t0
    assert fired is False
    assert growing_dt >= window * 0.95

    # the adaptive path is the tail-latency improvement
    assert plateau_dt < growing_dt / 1.5

    # non-adaptive: the original fixed wait
    t0 = _t.perf_counter()
    assert coalesce_wait(ev, window, lambda: 3, adaptive=False) is False
    assert _t.perf_counter() - t0 >= window * 0.95

    # a resolving event short-circuits immediately in either mode
    ev.set()
    assert coalesce_wait(ev, window, lambda: 3, adaptive=True) is True
    assert coalesce_wait(ev, 0.0, lambda: 3, adaptive=True) is True


def test_farm_wait_adaptive_early_flush(chain):
    """FarmBatcher.wait with the adaptive window flushes a plateaued
    queue well before the fixed window elapses (and still resolves the
    ticket correctly)."""
    import time as _t
    from cometbft_tpu.farm import planner

    window = 0.4
    cache = SigCache(65536)
    b = FarmBatcher(cache=cache, coalesce_window_s=window, adaptive=True)
    commit = chain.seen_commits[-1]
    check = planner.plan_commit_light(
        chain.chain_id, chain.valsets[-1], commit.block_id,
        chain.max_height(), commit, cache)
    ticket = b.submit(check)
    t0 = _t.perf_counter()
    b.wait([ticket])
    dt = _t.perf_counter() - t0
    assert ticket.ok()
    assert dt < window * 0.9, \
        f"adaptive wait took {dt:.3f}s, fixed window is {window}s"


def test_farm_planner_refuses_aggregate_seals():
    """An aggregate seal is one pairing check, not signature lanes, and
    the farm's batcher verifies lanes only: the farm's planner refuses a
    hop so sealed, where the light client's (`whole_seals`) verifies it
    whole while planning."""
    from cometbft_tpu.farm import planner as farm_planner
    from cometbft_tpu.light import planner as light_planner
    from cometbft_tpu.light import verifier
    from cometbft_tpu.types.validation import DEFAULT_TRUST_LEVEL
    agg = generate_chain(n_blocks=4, n_validators=4, txs_per_block=1,
                         chain_id="farm-agg", seed=7, key_type="bls12_381",
                         aggregate=True)
    provider = ChainLightProvider(agg)
    trusted, target = provider.light_block(1), provider.light_block(3)
    args = (agg.chain_id, trusted, target, provider.light_block,
            Timestamp(1_700_000_000 + 100, 0), TRUST_PERIOD,
            DEFAULT_TRUST_LEVEL, SigCache(1024))
    with pytest.raises(verifier.ErrInvalidHeader,
                       match="trusting verify failed: invalid signature"):
        list(farm_planner.plan_update(*args))
    steps = list(light_planner.plan_update(*args, "light", whole_seals=True))
    assert [(s.lb.height, s.checks) for s in steps] == [(3, [])]
