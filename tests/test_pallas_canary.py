"""Mosaic-miscompile canary tests (ops/ed25519._run_canary).

A pallas kernel that crashes raises (test_pallas_failure_propagates); a
silent miscompile returning batch_ok=True on a batch with an invalid
lane would accept a forged signature (the reference's batch verifier
must never accept what per-sig verify rejects, types/validation.go:
306-315). The canary re-runs every Nth dispatch with one lane's s
corrupted and demands a False verdict. These tests stub the pallas
kernel (no mosaic on the CPU test platform) to prove:

  1. a corrupted-verdict stub (always True) trips the sticky fallback
     and the verify still returns CORRECT results via the XLA kernel;
  2. an honest stub does not trip, and pallas stays live.
"""

import numpy as np
import pytest
from _kernel_shape import KERNEL_LANES

from cometbft_tpu.crypto import ref_ed25519 as ref
from cometbft_tpu.ops import ed25519 as e5
from cometbft_tpu.ops import pallas_verify as pv


BATCH = KERNEL_LANES  # the suite's one compiled shape (_kernel_shape.py)


@pytest.fixture
def pallas_env(monkeypatch):
    """Route _rlc_dispatch to the 'pallas' kernel on the CPU platform:
    force the platform gate on, shrink TILE so BATCH is aligned, and
    reset the sticky latch + counters around each test."""
    monkeypatch.setattr(e5, "use_pallas_rlc", lambda: True)
    monkeypatch.setattr(pv, "TILE", BATCH)
    monkeypatch.setattr(e5, "_pallas_broken", False)
    monkeypatch.setattr(e5, "_dispatches", 0)
    monkeypatch.setattr(e5, "_shape_dispatches", {})
    monkeypatch.setattr(e5, "_canary", {"runs": 0, "trips": 0})
    yield


def _batch(n=3):
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = bytes([i + 1]) * 32
        msg = b"canary message %d" % i
        pubs.append(ref.pubkey_from_seed(seed))
        msgs.append(msg)
        sigs.append(ref.sign(seed, msg))
    return pubs, msgs, sigs


def test_corrupted_verdict_stub_trips_canary(pallas_env, monkeypatch):
    # miscompile simulation: claims every batch verifies
    def lying_kernel(pub, sig, hb, hn, z):
        return np.bool_(True), np.ones((pub.shape[0],), dtype=bool)

    monkeypatch.setattr(e5, "verify_rlc_kernel_pallas", lying_kernel)
    pubs, msgs, sigs = _batch()
    got = e5.verify_batch(pubs, msgs, sigs, batch_size=BATCH)
    # the canary fired on the first dispatch, caught the lie, disabled
    # pallas, and the XLA kernel produced the real (correct) verdicts
    assert e5.canary_stats() == {"runs": 1, "trips": 1}
    assert e5._pallas_broken
    assert got.all()

    # a tampered real batch must now reject via the XLA path
    bad = sigs[:1] + [sigs[1][:33] + bytes([sigs[1][33] ^ 1]) + sigs[1][34:]]
    got = e5.verify_batch(pubs[:2], msgs[:2], bad, batch_size=BATCH)
    assert got[0] and not got[1]


def test_honest_kernel_passes_canary(pallas_env, monkeypatch):
    # honest 'pallas' stand-in: the proven XLA kernel
    monkeypatch.setattr(e5, "verify_rlc_kernel_pallas",
                        e5.verify_rlc_kernel)
    pubs, msgs, sigs = _batch()
    got = e5.verify_batch(pubs, msgs, sigs, batch_size=BATCH)
    assert got.all()
    assert e5.canary_stats() == {"runs": 1, "trips": 0}
    assert not e5._pallas_broken
    # subsequent dispatches inside the interval skip the canary
    got = e5.verify_batch(pubs, msgs, sigs, batch_size=BATCH)
    assert got.all()
    assert e5.canary_stats()["runs"] == 1


def test_pallas_failure_propagates(pallas_env, monkeypatch):
    """A pallas kernel that fails to lower, compile or run is a bug in
    the tree, not a condition to route around: _rlc_dispatch re-raises
    instead of latching onto the XLA kernel and answering with ITS
    verdicts — a device host can never serve XLA under pallas's name.
    The latch stays clear, so the failure repeats on the next dispatch
    rather than hiding after the first."""
    def refused(pub, sig, hb, hn, z):
        raise NotImplementedError(
            "Unimplemented primitive in Pallas TPU lowering: scatter")

    monkeypatch.setattr(e5, "verify_rlc_kernel_pallas", refused)
    pubs, msgs, sigs = _batch()
    for _ in range(2):
        with pytest.raises(NotImplementedError, match="scatter"):
            e5.verify_batch(pubs, msgs, sigs, batch_size=BATCH)
        assert not e5._pallas_broken and not e5.pallas_degraded()
    assert e5.canary_stats()["trips"] == 0


def test_canary_batch_construction(pallas_env):
    """The canary batch is constant, structurally valid in EVERY lane
    (so struct-masking can never hide the tamper — the round-4 false-
    trip hazard), and invalid only in the last lane's s."""
    pub_a, sig_a, hb, hn, z = e5._canary_batch(BATCH, 2)
    dpub, dsig, dmsg = e5._dummy()
    # all lanes carry the dummy pubkey; good lanes the dummy signature
    assert (pub_a == np.frombuffer(dpub, dtype=np.uint8)).all()
    assert (sig_a[:-1] == np.frombuffer(dsig, dtype=np.uint8)).all()
    # last lane: exactly one byte differs and s stays canonical
    diff = np.argwhere(
        sig_a[-1] != np.frombuffer(dsig, dtype=np.uint8))
    assert diff.shape[0] == 1 and diff[0][0] == 32
    s = int.from_bytes(bytes(sig_a[-1, 32:64]), "little")
    assert s < ref.L
    # shape matches the requested bucket and the host big-int oracle
    # agrees the tampered lane is invalid
    assert hb.shape == (BATCH, 2, 128)
    assert ref.verify(dpub, dmsg, dsig)
    assert not ref.verify(dpub, dmsg, bytes(sig_a[-1]))
    # cached: same bucket returns the identical object
    assert e5._canary_batch(BATCH, 2)[1] is sig_a


def test_device_server_warm_runs_canary(pallas_env, monkeypatch):
    """VERDICT r5 item 2 'wired into device/server.py': the device
    server's _warm goes through verify_batch -> _rlc_dispatch, whose
    FIRST dispatch is always a canary round — so a lying pallas kernel
    is caught before the server accepts any traffic."""
    def lying_kernel(pub, sig, hb, hn, z):
        return np.bool_(True), np.ones((pub.shape[0],), dtype=bool)

    monkeypatch.setattr(e5, "verify_rlc_kernel_pallas", lying_kernel)
    from cometbft_tpu.device.server import DeviceServer
    srv = DeviceServer(bucket=BATCH)
    try:
        srv._warm()
        assert e5.canary_stats()["runs"] >= 1
        assert e5.canary_stats()["trips"] == 1
        assert e5._pallas_broken  # server now serves via the XLA kernel
    finally:
        srv.stop()  # __init__ bound the listener even though we never
        #             started the accept loop


def test_callback_gauge_exposes_canary():
    from cometbft_tpu.libs.metrics import Registry
    reg = Registry()
    reg.callback_gauge("crypto_pallas_canary_trips",
                       "trips", fn=lambda: e5.canary_stats()["trips"])
    text = reg.expose()
    assert "cometbft_tpu_crypto_pallas_canary_trips" in text
