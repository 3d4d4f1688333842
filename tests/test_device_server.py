"""Verify device server: the persistent TPU-owner process serving
batched verification over a local socket (SURVEY §7 step 2; §5.8's
host↔device boundary). Covers the wire protocol, the Python client,
cross-request coalescing, the crypto/batch env-gated offload seam, and
the C client shim."""

import os
import threading

import pytest
from _kernel_shape import KERNEL_LANES, KERNEL_MSG_CAP

from cometbft_tpu.crypto import ref_ed25519 as ref
from cometbft_tpu.device.client import DeviceClient, RemoteBatchVerifier
from cometbft_tpu.device.protocol import (decode_request, decode_response,
                                          encode_request, encode_response)
from cometbft_tpu.device.server import DeviceServer


@pytest.fixture(autouse=True)
def _fresh_shared_supervisor():
    """shared_client()/RemoteBatchVerifier consult the process-wide
    health supervisor (device/health.py); keep its state (backoff
    windows, quarantine) from leaking between tests/modules."""
    from cometbft_tpu.device.health import reset_shared_supervisor
    reset_shared_supervisor()
    yield
    reset_shared_supervisor()


def _sigs(n, seed=9, msg_len=40):
    import random
    rng = random.Random(seed)
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        sd = bytes([rng.randrange(256) for _ in range(32)])
        m = bytes([rng.randrange(256) for _ in range(msg_len)])
        pubs.append(ref.pubkey_from_seed(sd))
        msgs.append(m)
        sigs.append(ref.sign(sd, m))
    return pubs, msgs, sigs


def test_protocol_roundtrip():
    pubs, msgs, sigs = _sigs(3)
    req = encode_request(7, pubs, msgs, sigs)
    rid, p2, m2, s2 = decode_request(req)
    assert (rid, p2, m2, s2) == (7, pubs, msgs, sigs)
    resp = encode_response(7, False, [True, False, True])
    assert decode_response(resp) == (7, False, [True, False, True])


@pytest.fixture(scope="module")
def server():
    # the suite's one compiled shape (_kernel_shape.py): start() warms the
    # kernel pair at it, and the local fallbacks below land on it too
    srv = DeviceServer(bucket=KERNEL_LANES, max_msg_len=64, flush_us=2000)
    srv.start()
    yield srv
    srv.stop()


def test_client_verify_and_attribution(server):
    pubs, msgs, sigs = _sigs(KERNEL_LANES)
    bad = bytearray(sigs[3])
    bad[5] ^= 0xFF
    sigs[3] = bytes(bad)
    client = DeviceClient(*server.addr)
    try:
        batch_ok, oks = client.verify(pubs, msgs, sigs)
        assert not batch_ok
        assert oks == [True] * 3 + [False] + [True] * (KERNEL_LANES - 4)
    finally:
        client.close()


def test_concurrent_clients_coalesce(server):
    """Two clients' requests land in one device flush when they arrive
    within the window — the cross-process accumulate-and-flush tile."""
    flushes_before = server.stats["flushes"]
    pubs, msgs, sigs = _sigs(6, seed=21)
    results = {}

    def go(name, lo, hi):
        c = DeviceClient(*server.addr)
        try:
            results[name] = c.verify(pubs[lo:hi], msgs[lo:hi],
                                     sigs[lo:hi])
        finally:
            c.close()

    ts = [threading.Thread(target=go, args=("a", 0, 3)),
          threading.Thread(target=go, args=("b", 3, 6))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results["a"] == (True, [True] * 3)
    assert results["b"] == (True, [True] * 3)
    # at most 2 flushes for the two requests; 1 when coalesced
    assert server.stats["flushes"] - flushes_before <= 2


def test_oversized_message_unprocessable_falls_back(server):
    """Unprocessable batches are signalled distinctly (NOT as per-lane
    failures — that would brand valid signatures forged), and the batch
    seam degrades to local verification."""
    from cometbft_tpu.device.client import DeviceUnprocessable
    # a full bucket; the long message is beyond the server's
    # max_msg_len and within the capacity of the compiled shape
    # (the local verifier it degrades to is native on a CPU backend)
    pubs, msgs, sigs = _sigs(KERNEL_LANES, seed=33)
    seed = b"\x21" * 32
    msgs[1] = b"\x01" * KERNEL_MSG_CAP
    assert len(msgs[1]) > server.max_msg_len
    pubs[1] = ref.pubkey_from_seed(seed)
    sigs[1] = ref.sign(seed, msgs[1])
    client = DeviceClient(*server.addr)
    try:
        with pytest.raises(DeviceUnprocessable):
            client.verify(pubs, msgs, sigs)
        rbv = RemoteBatchVerifier(client)
        from cometbft_tpu.crypto.keys import Ed25519PubKey
        for p, m, s in zip(pubs, msgs, sigs):
            rbv.add(Ed25519PubKey(p), m, s)
        batch_ok, oks = rbv.verify()  # local fallback
        assert batch_ok and oks == [True] * KERNEL_LANES
    finally:
        client.close()


def test_bucket_cap_grants_canary_headroom():
    """A payload that exactly fills the bucket must still be
    processable after health.splice_canaries appends its two lanes —
    otherwise every full batch would bounce as UNPROCESSABLE and flap
    the supervisor — while anything beyond the canary headroom (or an
    oversized message) stays rejected. Predicate-level test: no kernel
    compile, no traffic."""
    from cometbft_tpu.device import health
    srv = DeviceServer(bucket=KERNEL_LANES, max_msg_len=64)
    try:
        pubs = [b"\x01" * 32] * srv.bucket
        msgs = [b"m" * 31] * srv.bucket
        sigs = [b"\x02" * 64] * srv.bucket
        d_pubs, d_msgs, _d_sigs = health.splice_canaries(pubs, msgs,
                                                         sigs)
        assert not srv._unprocessable(d_pubs, d_msgs)
        assert srv._unprocessable(d_pubs + pubs[:1], d_msgs + msgs[:1])
        assert srv._unprocessable(pubs, [b"\x01" * 65] + msgs[1:])
    finally:
        srv._listener.close()


def test_dead_server_falls_back_locally(monkeypatch):
    """crypto/batch with a dead device address degrades to in-process
    verification instead of failing the verify path."""
    import cometbft_tpu.device.client as dc
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    monkeypatch.setenv(dc.ENV_VAR, "127.0.0.1:1")  # nothing listens
    monkeypatch.setattr(dc, "_shared", None)
    pubs, msgs, sigs = _sigs(KERNEL_LANES, seed=70)
    bv, ok = crypto_batch.create_batch_verifier(Ed25519PubKey(pubs[0]))
    assert ok  # local verifier (connect refused) or remote w/ fallback
    for p, m, s in zip(pubs, msgs, sigs):
        bv.add(Ed25519PubKey(p), m, s)
    batch_ok, oks = bv.verify()
    assert batch_ok and oks == [True] * KERNEL_LANES
    monkeypatch.setattr(dc, "_shared", None)


def test_batch_seam_offloads_via_env(server, monkeypatch):
    import cometbft_tpu.device.client as dc
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    monkeypatch.setenv(dc.ENV_VAR, f"127.0.0.1:{server.addr[1]}")
    monkeypatch.setattr(dc, "_shared", None)
    try:
        pubs, msgs, sigs = _sigs(4, seed=40)
        bv, ok = crypto_batch.create_batch_verifier(
            Ed25519PubKey(pubs[0]))
        assert ok and isinstance(bv, RemoteBatchVerifier)
        for p, m, s in zip(pubs, msgs, sigs):
            bv.add(Ed25519PubKey(p), m, s)
        batch_ok, oks = bv.verify()
        assert batch_ok and oks == [True] * 4
    finally:
        monkeypatch.setattr(dc, "_shared", None)


def test_c_shim_end_to_end(server):
    from cometbft_tpu.device.native import (NativeDeviceClient,
                                            native_available)
    if not native_available():
        pytest.skip("no g++ toolchain")
    pubs, msgs, sigs = _sigs(5, seed=55)
    bad = bytearray(sigs[0])
    bad[9] ^= 0x40
    sigs[0] = bytes(bad)
    c = NativeDeviceClient("127.0.0.1", server.addr[1])
    try:
        batch_ok, oks = c.verify(pubs, msgs, sigs)
        assert not batch_ok
        assert oks == [False, True, True, True, True]
    finally:
        c.close()
