"""Operator tooling: WAL dump/rebuild round-trip (reference
scripts/wal2json + json2wal) and the randomized e2e manifest generator
(reference test/e2e/generator)."""

import io
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

from cometbft_tpu.consensus.wal import (EndHeightMessage, WAL,
                                        WALBlockPart, WALTimeout, WALVote)
from cometbft_tpu.types.block import BlockID, PartSetHeader
from cometbft_tpu.types.proto import Timestamp
from cometbft_tpu.types.vote import PRECOMMIT_TYPE, Vote


def _sample_messages():
    vote = Vote(type_=PRECOMMIT_TYPE, height=3, round=1,
                block_id=BlockID(b"\x11" * 32,
                                 PartSetHeader(1, b"\x22" * 32)),
                timestamp=Timestamp(1234, 5678),
                validator_address=b"\x33" * 20, validator_index=2,
                signature=b"\x44" * 64)
    return [WALVote(vote, peer_id="peerX"),
            WALBlockPart(3, 1, 0, b"\x55" * 40, peer_id="peerY"),
            WALTimeout(3, 1, 4, 250),
            EndHeightMessage(3)]


def test_wal_json_roundtrip(tmp_path):
    from wal import json2wal, wal2json

    src = tmp_path / "src.wal"
    w = WAL(str(src))
    for m in _sample_messages():
        w.write_sync(m)
    w.close()

    buf = io.StringIO()
    n = wal2json(str(src), out=buf)
    assert n == 4
    lines = [json.loads(line) for line in
             buf.getvalue().strip().splitlines()]
    assert [d["type"] for d in lines] == ["vote", "block_part",
                                          "timeout", "end_height"]
    assert lines[0]["summary"]["h"] == 3

    jpath = tmp_path / "dump.jsonl"
    jpath.write_text(buf.getvalue())
    dst = tmp_path / "rebuilt.wal"
    assert json2wal(str(jpath), str(dst)) == 4

    orig = list(WAL(str(src)).iter_messages())
    rebuilt = list(WAL(str(dst)).iter_messages())
    # peer ids are delivery metadata, not WAL payload — compare payloads
    assert len(orig) == len(rebuilt)
    for a, b in zip(orig, rebuilt):
        assert type(a) is type(b)
        if isinstance(a, WALVote):
            assert a.vote.encode() == b.vote.encode()
        else:
            assert a == b or (
                isinstance(a, WALBlockPart)
                and (a.height, a.round, a.index, a.part)
                == (b.height, b.round, b.index, b.part))


def test_manifest_generator_deterministic():
    from cometbft_tpu.e2e.generator import generate_manifests
    a = generate_manifests(seed=7, n=5)
    b = generate_manifests(seed=7, n=5)
    assert [(m.validators, m.timeout_commit_ms) for m in a] == \
        [(m.validators, m.timeout_commit_ms) for m in b]
    assert len({m.chain_id for m in a}) == 5
    from cometbft_tpu.e2e.generator import VALIDATOR_CHOICES
    assert all(m.validators in VALIDATOR_CHOICES for m in a)
    # a different seed explores a different point
    c = generate_manifests(seed=8, n=5)
    assert [(m.validators, m.timeout_commit_ms) for m in a] != \
        [(m.validators, m.timeout_commit_ms) for m in c]


# --- chip-only entry points refuse to answer without a chip -------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cpu(script):
    """Run a repo script in a child handed the CPU platform through its
    environment; (returncode, stdout lines)."""
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, script)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_ROOT,
        capture_output=True, text=True, timeout=300)
    return r.returncode, r.stdout.strip().splitlines()


def test_chip_smoke_fails_without_a_tpu():
    """chip_smoke.py on the CPU platform: non-zero exit, and the last
    stdout line is the JSON verdict with ok=false and the device as JAX
    reports it — never a pass on a backend that is not the chip."""
    rc, out = _run_cpu("chip_smoke.py")
    assert rc != 0
    verdict = json.loads(out[-1])
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"
    assert set(verdict["device"]) == {"platform", "kind", "count"}


@pytest.mark.parametrize("script", [
    "bench.py", "tools/bench_light.py", "tools/bench_vote_ingest.py"])
def test_device_benches_exit_nonzero_without_a_tpu(script):
    """A device metric is measured on the chip or not at all: no CPU
    fallback number, no JSON line on stdout."""
    rc, out = _run_cpu(script)
    assert rc != 0
    assert not [ln for ln in out if ln.startswith("{")]
