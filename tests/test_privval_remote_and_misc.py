"""Remote signer over an encrypted socket, fail-point crash injection,
armored keys (reference privval/signer_*_test.go, internal/fail,
crypto/armor)."""

import os
import subprocess
import sys

import pytest

from cometbft_tpu.crypto.armor import (ArmorError, encrypt_armor_privkey,
                                       unarmor_decrypt_privkey)
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.privval.file import DoubleSignError, FilePV
from cometbft_tpu.privval.remote import SignerClient, SignerServer
from cometbft_tpu.types.block import BlockID, PartSetHeader
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.vote import Proposal, Vote, PREVOTE_TYPE


def test_remote_signer_end_to_end(tmp_path):
    # the remote signer link is a SecretConnection (X25519/ChaCha20)
    pytest.importorskip("cryptography")
    pv = FilePV.generate(str(tmp_path / "pv.json"))
    pv._save()
    client = SignerClient()
    server = SignerServer(pv, *client.addr)
    server.start()
    try:
        # identity through the link
        assert client.get_pub_key().bytes_() == pv.get_pub_key().bytes_()

        bid = BlockID(b"\x21" * 32, PartSetHeader(1, b"\x22" * 32))
        vote = Vote(type_=PREVOTE_TYPE, height=3, round=0, block_id=bid,
                    timestamp=Timestamp(50, 0),
                    validator_address=pv.address(), validator_index=0)
        client.sign_vote("remote-chain", vote)
        assert pv.get_pub_key().verify_signature(
            vote.sign_bytes("remote-chain"), vote.signature)

        # the guard lives with the key: conflicting sign refused REMOTELY
        other = Vote(type_=PREVOTE_TYPE, height=3, round=0,
                     block_id=BlockID(b"\x31" * 32,
                                      PartSetHeader(1, b"\x32" * 32)),
                     timestamp=Timestamp(50, 0),
                     validator_address=pv.address(), validator_index=0)
        with pytest.raises(DoubleSignError):
            client.sign_vote("remote-chain", other)

        prop = Proposal(height=4, round=0, pol_round=-1, block_id=bid,
                        timestamp=Timestamp(51, 0))
        client.sign_proposal("remote-chain", prop)
        assert pv.get_pub_key().verify_signature(
            prop.sign_bytes("remote-chain"), prop.signature)
    finally:
        server.stop()
        client.close()


_FAIL_SCRIPT = r"""
import sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
from cometbft_tpu.libs import fail
fail.set_fail_index({idx})
from cluster import Cluster
import time
c = Cluster(4)
c.start()
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    if all(n.cs.state.last_block_height >= 2 for n in c.nodes):
        print("COMMITTED", flush=True)
        break
    time.sleep(0.05)
c.stop()
"""


def test_fail_point_crashes_process(tmp_path):
    """With a fail index armed, the commit path exits hard mid-commit —
    the generator for every WAL/replay crash class (reference
    FAIL_TEST_INDEX)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _FAIL_SCRIPT.format(repo=repo,
                                 tests=os.path.join(repo, "tests"), idx=0)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 99, (r.returncode, r.stderr[-500:])
    assert "FAIL_POINT hit" in r.stderr
    # sanity: with injection off the same cluster commits
    script_ok = _FAIL_SCRIPT.format(repo=repo,
                                    tests=os.path.join(repo, "tests"),
                                    idx=-1)
    r2 = subprocess.run([sys.executable, "-c", script_ok], env=env,
                        capture_output=True, text=True, timeout=180)
    assert r2.returncode == 0 and "COMMITTED" in r2.stdout, r2.stderr[-500:]


def test_armor_roundtrip_and_rejections():
    pytest.importorskip("cryptography")  # armoring AEAD
    key = Ed25519PrivKey.generate()
    armored = encrypt_armor_privkey(key.seed, "ed25519", "hunter2")
    assert "BEGIN COMETBFT_TPU PRIVATE KEY" in armored
    assert key.seed.hex() not in armored  # actually encrypted
    plain, ktype = unarmor_decrypt_privkey(armored, "hunter2")
    assert plain == key.seed and ktype == "ed25519"
    with pytest.raises(ArmorError):
        unarmor_decrypt_privkey(armored, "wrong-pass")
    with pytest.raises(ArmorError):
        unarmor_decrypt_privkey(armored.replace("pbkdf2", "argon2"),
                                "hunter2")
    # tampered key type breaks the AEAD's associated data binding
    with pytest.raises(ArmorError):
        unarmor_decrypt_privkey(
            armored.replace("type: ed25519", "type: sr25519"), "hunter2")
