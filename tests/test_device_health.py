"""device/health.py — the verification-backend health supervisor
(HEALTHY → SUSPECT → PROBING → HEALTHY | QUARANTINED), canary lanes,
and their wiring into the pipeline scheduler, watchdog, and
RemoteBatchVerifier (docs/PIPELINE.md "Device health supervision").

Pins the properties the subsystem exists for:
- recovery: a transient device stall no longer demotes the node to CPU
  verification forever — a known-answer probe restores device dispatch;
- safety: a device that answers WRONG verdicts is exposed by the canary
  lanes on its very first batch, quarantined terminally, and the whole
  batch is re-verified on CPU — the final verdicts equal the CPU
  reference (the acceptance criterion);
- backoff: probe windows grow exponentially with bounded jitter, and
  client reconnects ride the same half-open windows.
"""

import numpy as np
import pytest
from _kernel_shape import LOCAL_LANES

from cometbft_tpu.device import health
from cometbft_tpu.device.health import (DeviceSupervisor, HEALTHY,
                                        PROBING, QUARANTINED, SUSPECT)
from cometbft_tpu.engine.blocksync import BlocksyncReactor, verify_lanes
from cometbft_tpu.engine.chain_gen import LocalChainSource, generate_chain
from cometbft_tpu.libs.metrics import Registry
from cometbft_tpu.libs.metrics_gen import DeviceMetrics
from cometbft_tpu.pipeline.scheduler import (CorruptBackend, FlakyBackend,
                                             VerifyFuture)
from cometbft_tpu.pipeline.watchdog import DeviceWatchdog

pytestmark = pytest.mark.pipeline

CHAIN = generate_chain(n_blocks=8, n_validators=4, txs_per_block=1)


@pytest.fixture(autouse=True)
def _fresh_shared_supervisor():
    """The shared supervisor is process-global; never leak QUARANTINED
    (or backoff windows) into other test modules."""
    health.reset_shared_supervisor()
    yield
    health.reset_shared_supervisor()


def _cpu_verify(p, m, s):
    return verify_lanes(p, m, s, 0)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _sup(**kw):
    kw.setdefault("backoff_base_s", 1.0)
    kw.setdefault("backoff_cap_s", 8.0)
    kw.setdefault("probe_deadline_s", 0.5)
    return DeviceSupervisor(**kw)


# --- state machine -----------------------------------------------------------

def test_trip_probe_recover_cycle():
    clock = FakeClock()
    sup = _sup(clock=clock)
    assert sup.state == HEALTHY and sup.can_dispatch()
    sup.report_trip(ConnectionError("stall"))
    assert sup.state == SUSPECT and not sup.can_dispatch()
    # first trip allows an immediate half-open attempt
    assert sup.probe_due() and sup.allow_connect()
    assert sup.probe(_cpu_verify)
    assert sup.state == HEALTHY and sup.can_dispatch()
    assert sup.probes == 1 and sup.trips == 1


def test_backoff_grows_exponentially_with_cap():
    clock = FakeClock()
    sup = _sup(clock=clock, backoff_base_s=1.0, backoff_cap_s=4.0)
    sup.report_trip(ConnectionError("1"))    # window 0: immediate
    windows = []
    for i in range(5):
        sup.report_trip(ConnectionError(str(i + 2)))
        windows.append(sup._next_probe_at - clock.t)
    # base, 2·base, 4·base then capped at 4.0 — each within +25% jitter
    for got, nominal in zip(windows, [1.0, 2.0, 4.0, 4.0, 4.0]):
        assert nominal <= got <= nominal * 1.25, (got, nominal)
    # not due until the window elapses
    assert not sup.probe_due() and not sup.allow_connect()
    clock.t += windows[-1] + 0.001
    assert sup.probe_due() and sup.allow_connect()


def test_probe_transport_error_deepens_backoff():
    clock = FakeClock()
    sup = _sup(clock=clock)
    sup.report_trip(ConnectionError("x"))

    def failing(p, m, s):
        raise TimeoutError("still wedged")
    assert not sup.probe(failing)
    assert sup.state == SUSPECT
    assert sup._next_probe_at > clock.t  # real backoff window now
    assert not sup.probe_due()


def test_probe_accounted_failure_reports_one_trip():
    """A failed reconnect INSIDE a probe (shared_client reports the
    trip, then raises AccountedTransportError) must not be counted a
    second time by probe()'s except clause — double-reporting would
    deepen the backoff two steps per outage."""
    clock = FakeClock()
    sup = _sup(clock=clock)
    sup.report_trip(ConnectionError("x"))
    assert sup.trips == 1

    def failing_reconnect(p, m, s):
        sup.report_trip(OSError("connect refused"))
        raise health.AccountedTransportError("link down, no reconnect")
    assert not sup.probe(failing_reconnect)
    assert sup.trips == 2  # the inner report only, not probe()'s too
    assert sup.state == SUSPECT


def test_probe_losing_window_race_cannot_latch_probing():
    """An accounted failure that made NO device contact (a concurrent
    verifier consumed the half-open window, so shared_client raised
    without reporting any trip) must return the state to SUSPECT —
    stranding it in PROBING would disable probe_due() forever and
    silently reinstate the sticky wedge this subsystem removes."""
    clock = FakeClock()
    sup = _sup(clock=clock)
    sup.report_trip(ConnectionError("x"))

    def window_lost(p, m, s):
        # simulates allow_connect()==False inside the probe's
        # reconnect: nothing was attempted, nothing was reported
        raise health.AccountedTransportError("window consumed")
    assert not sup.probe(window_lost)
    assert sup.state == SUSPECT  # not PROBING
    assert sup.trips == 1        # no phantom trip either
    # the next elapsed window can probe again
    clock.t = sup._next_probe_at + 0.01
    assert sup.probe_due()
    assert sup.probe(_cpu_verify)
    assert sup.state == HEALTHY


def test_reconnect_blocked_is_accounted(monkeypatch):
    """DeviceClientBackend.submit's ReconnectBlocked carries the
    already-accounted marker, so neither the dispatch fallback nor
    supervisor.probe() reports a second trip for it."""
    import cometbft_tpu.device.client as device_client
    from cometbft_tpu.pipeline.scheduler import (DeviceClientBackend,
                                                 ReconnectBlocked)
    monkeypatch.setattr(device_client, "shared_client", lambda: None)
    backend = DeviceClientBackend(None)
    with pytest.raises(ReconnectBlocked):
        backend.submit([b"p"], [b"m"], [b"s"])
    assert issubclass(ReconnectBlocked, health.AccountedTransportError)


def test_corruption_is_terminal():
    sup = _sup(clock=FakeClock())
    sup.report_corruption("flipped verdicts")
    assert sup.state == QUARANTINED and sup.quarantined()
    assert sup.quarantines == 1 and sup.canary_failures == 1
    assert not sup.allow_connect() and not sup.probe_due()
    # nothing un-quarantines: not success, not probes, not trips
    sup.report_success()
    sup.report_trip(ConnectionError("y"))
    assert sup.state == QUARANTINED
    assert not sup.probe(_cpu_verify)


def test_probe_with_wrong_verdicts_quarantines():
    sup = _sup(clock=FakeClock())
    sup.report_trip(ConnectionError("x"))
    assert not sup.probe(lambda p, m, s: [True, True])  # bad canary "ok"
    assert sup.state == QUARANTINED


def test_supervisor_metrics_wiring():
    reg = Registry()
    sup = _sup(clock=FakeClock(), metrics=DeviceMetrics(reg))
    sup.report_trip(ConnectionError("x"))
    assert sup.metrics.health_state.value() == SUSPECT
    sup.probe(_cpu_verify)
    assert sup.metrics.health_state.value() == HEALTHY
    assert sup.metrics.probes_total.value() == 1
    sup.report_corruption("lie")
    assert sup.metrics.health_state.value() == QUARANTINED
    assert sup.metrics.quarantines_total.value() == 1
    assert sup.metrics.canary_failures.value() == 1
    text = reg.expose()
    for name in ("device_health_state", "device_probes_total",
                 "device_quarantines_total", "device_canary_failures"):
        assert name in text


def test_configure_first_wins():
    from cometbft_tpu.config import DeviceConfig
    sup = _sup(clock=FakeClock())
    sup.configure(DeviceConfig(probe_backoff_base_ms=100,
                               probe_backoff_cap_ms=1000,
                               probe_deadline_ms=250, canary=False))
    assert sup.backoff_base_s == pytest.approx(0.1)
    assert sup.canary is False
    sup.configure(DeviceConfig(probe_backoff_base_ms=900))
    assert sup.backoff_base_s == pytest.approx(0.1)  # first config wins


def test_env_knobs(monkeypatch):
    monkeypatch.setenv(health.ENV_BACKOFF_BASE, "0.25")
    monkeypatch.setenv(health.ENV_BACKOFF_CAP, "2.5")
    monkeypatch.setenv(health.ENV_CANARY, "off")
    sup = DeviceSupervisor(clock=FakeClock())
    assert sup.backoff_base_s == pytest.approx(0.25)
    assert sup.backoff_cap_s == pytest.approx(2.5)
    assert sup.canary is False
    # malformed degrades to defaults (libs/env shared guard)
    monkeypatch.setenv(health.ENV_BACKOFF_BASE, "fast")
    sup2 = DeviceSupervisor(clock=FakeClock())
    assert sup2.backoff_base_s == pytest.approx(
        health.DEFAULT_BACKOFF_BASE_S)


# --- canary lanes ------------------------------------------------------------

def test_canary_pair_is_known_answer():
    good, bad = health.canary_pair()
    out = _cpu_verify([good[0], bad[0]], [good[1], bad[1]],
                      [good[2], bad[2]])
    assert list(out) == [True, False]


def test_splice_and_check_roundtrip():
    p, m, s = health.splice_canaries([b"p"], [b"m"], [b"s"])
    assert len(p) == 1 + health.CANARY_LANES
    ok, body = health.check_canaries([False, True, False])
    assert ok and body == [False]
    for tail in ([True, True], [False, False], [False, True]):
        ok, _body = health.check_canaries([True] + tail)
        assert not ok


# --- watchdog + scheduler integration ----------------------------------------

def _sync(chain, depth, src=None, backend=None, watchdog=None,
          supervisor=None, tile=2):
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
    src = src or LocalChainSource(chain)
    reactor = BlocksyncReactor(
        executor, store, src, chain.chain_id, tile_size=tile,
        batch_size=64, pipeline_depth=depth, backend=backend,
        watchdog=watchdog, supervisor=supervisor)
    state = reactor.sync(State.from_genesis(chain.genesis))
    return state, reactor, src, app


def test_watchdog_recovers_through_supervisor():
    """The PR-2 one-way door is gone: a supervisor-backed watchdog
    trips to SUSPECT, the scheduler probes the recovered device, and
    device dispatch RESUMES (backend keeps serving batches)."""
    # wall clock (the sync loop runs in real time) with near-zero
    # backoff so the recovery probe is due by the next tile
    sup = _sup(backoff_base_s=1e-6, backoff_cap_s=0.001)
    backend = FlakyBackend(fail_dispatches=1)
    wd = DeviceWatchdog(base_deadline_s=0.5, per_sig_s=0.0,
                        supervisor=sup)
    state, reactor, _src, _app = _sync(
        CHAIN, depth=2, backend=backend, watchdog=wd, supervisor=sup,
        tile=1)
    assert state.last_block_height == 8
    assert sup.state == HEALTHY
    assert sup.trips >= 1 and sup.probes >= 1
    assert backend.served >= 2  # probe + at least one post-recovery tile
    assert not wd.wedged  # the supervisor re-armed the watchdog


def test_corrupt_backend_verdicts_equal_cpu_reference():
    """Acceptance criterion: a corrupt device stub flips one lane (the
    known-bad canary comes back True on an otherwise-clean chain); the
    canary mismatch quarantines the device, the batch re-verifies on
    CPU, and the final verdicts/app state equal the CPU reference."""
    ref_state, ref_reactor, _s, ref_app = _sync(CHAIN, depth=1)
    sup = _sup(clock=FakeClock())
    wd = DeviceWatchdog(base_deadline_s=0.5, per_sig_s=0.0,
                        supervisor=sup)
    state, reactor, _src, app = _sync(
        CHAIN, depth=2, backend=CorruptBackend(), watchdog=wd,
        supervisor=sup)
    assert state.last_block_height == ref_state.last_block_height == 8
    assert state.app_hash == ref_state.app_hash
    assert app.state == ref_app.state
    assert sup.state == QUARANTINED
    assert sup.quarantines == 1 and sup.canary_failures == 1


def test_corrupt_backend_cannot_admit_tampered_sig():
    """The headline safety property: the device claims a FORGED
    signature is valid (all-true answers), but the canary quarantine
    re-verifies on CPU and the bad block is still banned — zero
    corrupted verdicts reach the apply/commit path."""
    sup = _sup(clock=FakeClock())
    wd = DeviceWatchdog(base_deadline_s=0.5, per_sig_s=0.0,
                        supervisor=sup)
    src = LocalChainSource(CHAIN, corrupt_heights={5: "sig"})
    state, _r, src, _a = _sync(CHAIN, depth=2, src=src,
                               backend=CorruptBackend(), watchdog=wd,
                               supervisor=sup)
    assert state.last_block_height == 8
    assert src.banned  # the forged-commit peer was caught and banned
    assert sup.state == QUARANTINED


def test_canary_lanes_ride_every_device_batch():
    """Clean run with a healthy (verdict-computing) backend: every
    dispatched batch carries exactly CANARY_LANES extra lanes, results
    are stripped, and verdicts match the CPU path."""
    seen = []

    class Recording:
        def submit(self, p, m, s):
            seen.append(len(p))
            fut = VerifyFuture()
            fut.set_result(_cpu_verify(p, m, s))
            return fut

        def close(self):
            pass

    sup = _sup(clock=FakeClock())
    wd = DeviceWatchdog(base_deadline_s=0.5, per_sig_s=0.0,
                        supervisor=sup)
    state, reactor, _s, _a = _sync(CHAIN, depth=2, backend=Recording(),
                                   watchdog=wd, supervisor=sup, tile=2)
    assert state.last_block_height == 8
    assert sup.state == HEALTHY and sup.quarantines == 0
    # 2 blocks/tile × 4 validators = 8 real lanes + 2 canaries
    assert seen and all(n == 8 + health.CANARY_LANES for n in seen)


# --- RemoteBatchVerifier canary + reconnect ----------------------------------

def _triples(n, seed=11):
    import random
    from cometbft_tpu.crypto import ref_ed25519 as ref
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        sd = bytes([rng.randrange(256) for _ in range(32)])
        msg = bytes([rng.randrange(256) for _ in range(32)])
        out.append((ref.pubkey_from_seed(sd), msg, ref.sign(sd, msg)))
    return out


def test_remote_verifier_strips_canaries_on_honest_client():
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.device.client import RemoteBatchVerifier

    class HonestClient:
        def __init__(self):
            self.lane_counts = []

        def verify(self, p, m, s):
            self.lane_counts.append(len(p))
            oks = [bool(v) for v in _cpu_verify(p, m, s)]
            return all(oks), oks

    sup = _sup(clock=FakeClock())
    client = HonestClient()
    rbv = RemoteBatchVerifier(client, supervisor=sup)
    triples = _triples(3)
    for p, m, s in triples:
        rbv.add(Ed25519PubKey(p), m, s)
    ok, oks = rbv.verify()
    assert ok and oks == [True] * 3  # canaries stripped, batch_ok fixed
    assert client.lane_counts == [3 + health.CANARY_LANES]
    assert sup.state == HEALTHY


def test_remote_verifier_quarantines_lying_client_and_goes_local():
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.device.client import RemoteBatchVerifier

    class LyingClient:
        def __init__(self):
            self.calls = 0

        def verify(self, p, m, s):
            self.calls += 1
            return True, [True] * len(p)  # flips the known-bad canary

    sup = _sup(clock=FakeClock())
    client = LyingClient()
    rbv = RemoteBatchVerifier(client, supervisor=sup)
    # LOCAL_LANES of them: going local is the point, not the kernel,
    # and a CPU backend verifies natively at any width (_kernel_shape.py)
    triples = _triples(LOCAL_LANES, seed=12)
    # tamper one real signature: the lying device would have admitted it
    bad_sig = bytes([triples[1][2][0] ^ 1]) + triples[1][2][1:]
    for i, (p, m, s) in enumerate(triples):
        rbv.add(Ed25519PubKey(p), m, bad_sig if i == 1 else s)
    ok, oks = rbv.verify()
    # the LOCAL (CPU) reference
    assert not ok and oks == [i != 1 for i in range(LOCAL_LANES)]
    assert client.calls == 1
    assert sup.state == QUARANTINED
    # quarantined: the next verify never touches the device again
    ok2, oks2 = rbv.verify()
    assert (ok2, oks2) == (ok, oks) and client.calls == 1


def test_device_client_backend_reconnects_via_shared_client(monkeypatch):
    """The pipeline's device backend must not pin the socket it was
    built on: once that client is dead, submits (and supervisor probes)
    re-resolve through shared_client() — the supervisor-gated reconnect
    — so a restarted device server is actually reachable again."""
    import cometbft_tpu.device.client as dc
    from cometbft_tpu.pipeline.scheduler import DeviceClientBackend

    class StubClient:
        def __init__(self):
            self._dead = None
            self.submits = 0

        def submit(self, p, m, s):
            self.submits += 1

            class F:
                pass
            return F()

    dead = StubClient()
    dead._dead = ConnectionError("gone")
    fresh = StubClient()
    monkeypatch.setattr(dc, "shared_client", lambda: fresh)
    be = DeviceClientBackend(dead)
    be.submit([b"p"], [b"m"], [b"s"])
    assert fresh.submits == 1 and dead.submits == 0
    assert be._client is fresh
    # no reconnect available (backoff window / quarantine): the submit
    # raises, which the watchdog treats exactly like a dead link
    fresh._dead = ConnectionError("gone too")
    monkeypatch.setattr(dc, "shared_client", lambda: None)
    with pytest.raises(ConnectionError):
        be.submit([b"p"], [b"m"], [b"s"])


def test_shared_client_respects_quarantine_and_backoff(monkeypatch):
    import cometbft_tpu.device.client as dc
    clock = FakeClock()
    sup = _sup(clock=clock, backoff_base_s=10.0)
    monkeypatch.setattr(health, "_shared", sup)
    monkeypatch.setattr(dc, "_shared", None)
    monkeypatch.setenv(dc.ENV_VAR, "127.0.0.1:1")  # nothing listens
    # first failure burns the immediate half-open attempt...
    assert dc.shared_client() is None
    assert sup.trips == 1
    # ...the second connect attempt is allowed at once (window 0), and
    # from then on attempts are skipped until the backoff elapses
    assert dc.shared_client() is None
    assert sup.trips == 2
    assert dc.shared_client() is None
    assert sup.trips == 2  # no third connect attempt: backoff window
    clock.t += 13.0
    assert dc.shared_client() is None
    assert sup.trips == 3  # window elapsed: one more half-open attempt
    # quarantine pins the client to None even with a live server addr
    sup.report_corruption("lie")
    clock.t += 100.0
    assert dc.shared_client() is None
    assert sup.trips == 3
