"""The cell `hub-validator-150-ext.vote-intake-ext`'s own pieces: the
plain reference of a vote extension against the program's sign-bytes and
app; the three readers (`ext_device_lane_share`, `ext_check_ms_per_height`,
`hash_block_fill`) on hand-built counters and spans; the driver at its
tiny size, traced, and its faults (`correct` false under the node that
skips the extension check, under a batch verifier that accepts every
lane of the extension's shape, and under the hub driver's plants)."""

import random
import time
import types

import pytest

from benchmark.harness import runner
from benchmark.harness.manifest import Manifest
from benchmark.reference import canonical_vote_extension as cve
from benchmark.reference import ed25519_ref
from conftest import REPO

CELL = "hub-validator-150-ext.vote-intake-ext"


def run(root, seed, trace=False, plant=""):
    return runner.run_cell(root, CELL, seed, 2.0, trace, time.perf_counter(),
                           look_for_chip=False, in_process_traffic=True,
                           plant=plant)


def over(out):
    return {n for n, row in out["checks"].items()
            if row["value"] > row["limit"]}


# --- the reference ----------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_reference_extension_sign_bytes_and_bytes_equal_the_programs(seed):
    from cometbft_tpu.abci.kvstore import (ExtendingKVStoreApplication,
                                           vote_extension_bytes)
    from cometbft_tpu.types.vote import Vote
    rng = random.Random(seed)
    chain = "c" * rng.randrange(1, 51)
    height, round_ = rng.randrange(1, 2**40), rng.choice([0, 1, 7, 300])
    size = rng.choice([0, 1, 96, 2048, 5000])
    addr = bytes(rng.randrange(256) for _ in range(20))
    ext = cve.extension(height, addr, size)
    assert ext == vote_extension_bytes(height, addr, size)
    assert ExtendingKVStoreApplication(size, addr).extend_vote(height, 0) \
        == ext
    assert cve.extension_sign_bytes(chain, height, round_, ext) == Vote(
        height=height, round=round_, extension=ext).extension_sign_bytes(chain)


def test_reference_verdicts():
    signer = ed25519_ref.Signer(b"\x31" * 32)
    pub, chain, h = signer.pub, "ref-chain", 9
    ext = cve.extension(h, cve.address(pub), 64)
    vote_sb = b"\x0a" * 40
    sig = signer.sign(vote_sb)
    ext_sig = signer.sign(cve.extension_sign_bytes(chain, h, 0, ext))

    def accepts(**kw):
        args = dict(chain_id=chain, pub=pub, vote_sign_bytes=vote_sb,
                    signature=sig, height=h, round_=0, for_block=True,
                    ext=ext, ext_signature=ext_sig, size=64)
        args.update(kw)
        return cve.accepts(**args)
    assert accepts()
    assert not accepts(signature=ed25519_ref.tamper(sig))
    assert not accepts(ext_signature=ed25519_ref.tamper(ext_sig))
    assert not accepts(ext_signature=b"")
    assert not accepts(ext=bytes(64))                   # signature fails
    wrong = bytes(64)
    assert not accepts(ext=wrong, ext_signature=signer.sign(
        cve.extension_sign_bytes(chain, h, 0, wrong)))  # the app refuses
    assert not accepts(for_block=False)                 # nil with data
    assert accepts(for_block=False, ext=b"", ext_signature=b"")


def test_the_generator_signs_every_extension(tiny_root):
    manifest = Manifest(tiny_root)
    cell = manifest.cell(CELL)
    payload = manifest.load_module("generators", "vote_stream_ext").make(
        {"seed": 2**31 + 4071, "seconds": 1.0, "config": cell.config,
         "traffic": cell.traffic})
    size = payload["vote_extension_bytes"]
    assert size == cell.config["vote_extension_bytes"]
    for row in payload["heights"]:
        h = row["height"]
        for pub, sig in zip(payload["pubs"], row["precommit_ext_sigs"]):
            ext = cve.extension(h, cve.address(pub), size)
            assert ed25519_ref.verify(pub, cve.extension_sign_bytes(
                payload["chain_id"], h, 0, ext), sig)


# --- the readers --------------------------------------------------------------------

def _ctx(spans=(), counters=None):
    return types.SimpleNamespace(spans=list(spans),
                                 result={"counters": counters or {},
                                         "facts": {}})


def _span(name, ms, t0=0, **attrs):
    return {"name": name, "t0": t0, "t1": t0 + int(ms * 1e6), "attrs": attrs}


def _reader(name):
    return Manifest(REPO).layer_reader(name).read


def test_counter_readers_on_hand_built_counters():
    c = {"intake_ext_device_lanes": 120, "intake_ext_native_lanes": 30,
         "batch_hash_blocks_real": 950, "batch_hash_blocks_dispatched": 1000}
    assert _reader("ext_device_lane_share.validator")(_ctx(counters=c)) \
        == 80.0
    assert _reader("hash_block_fill.validator")(_ctx(counters=c)) == 95.0
    # a program without the counters, or nothing to read
    for name in ("ext_device_lane_share.validator",
                 "hash_block_fill.validator"):
        assert _reader(name)(_ctx()) is None
    assert _reader("ext_device_lane_share.validator")(_ctx(counters=dict(
        c, intake_ext_device_lanes=0, intake_ext_native_lanes=0))) is None
    assert _reader("hash_block_fill.validator")(_ctx(counters=dict(
        c, batch_hash_blocks_dispatched=0))) is None
    # the device never saw an extension: 0, which is a reading
    assert _reader("ext_device_lane_share.validator")(_ctx(counters=dict(
        c, intake_ext_device_lanes=0))) == 0.0


def test_the_extension_check_reader_sums_by_height(capfd):
    spans = [_span("consensus.ext_check", 0.5, height=7, cache_hit=1),
             _span("consensus.ext_check", 1.5, height=7, cache_hit=0),
             _span("consensus.ext_check", 1.0, height=8, cache_hit=1),
             _span("consensus.ext_check", 4.0, height=9, cache_hit=1),
             _span("vote.verify", 0.1, height=7, path="ext"),
             _span("vote.verify", 0.1, height=7, path="vote")]
    assert _reader("ext_check_ms_per_height.validator")(_ctx(spans)) == 2.0
    assert "4 spans over 3 heights, 3 cache hits; 1 native" in \
        capfd.readouterr().out
    assert _reader("ext_check_ms_per_height.validator")(
        _ctx([_span("vote.verify", 1.0, height=7)])) is None


def test_the_extension_entries_name_their_layer_and_the_cell(doc):
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name, layer, source, unit in (
            ("ext_device_lane_share.validator", "consensus intake",
             "program_counter", "%"),
            ("ext_check_ms_per_height.validator", "consensus intake",
             "program_span", "ms"),
            ("hash_block_fill.validator", "dispatch", "program_counter",
             "%")):
        m = by_name[name]
        assert (m["layer"], m["source"], m["unit"], m["moves"]) == (
            layer, source, unit, "commit_verify_p50_ms")
        assert CELL in m["workloads"]
    for name in ("prewarm_s", "pallas_dispatch_share.commit",
                 "rlc_kernel_us_per_sig.commit",
                 "rlc_kernel_roofline.commit", "device_idle_share.commit",
                 "commit_device_ms.commit", "prepare_ms_per_chunk.commit"):
        assert CELL in by_name[name]["workloads"]
    validator = [m for m in doc["per_layer"]
                 if m["name"].endswith(".validator")
                 and "hub-validator-150.vote-intake" in m["workloads"]]
    assert validator and all(CELL in m["workloads"] for m in validator)
    for m in doc["end_to_end"]:
        if m["name"].startswith("commit_verify_"):
            assert CELL in m["workloads"]


# --- the driver at its tiny sizes -----------------------------------------------------

@pytest.fixture
def flushing(monkeypatch, tiny_root):
    """Eight validators: a run of four precommits is eight lanes, which
    the batched intake flushes once the threshold is theirs."""
    from cometbft_tpu.types import validation
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 4)
    monkeypatch.setattr(
        Manifest(tiny_root).load_module("drivers",
                                        "consensus_vote_intake_ext").base,
        "STUCK_S", 3.0)


def test_the_extension_lanes_flush_in_the_tiny_run(tiny_root, fresh_sigcache,
                                                   flushing, capfd):
    out = run(tiny_root, 2**31 + 4072, trace=True)
    assert out["correct"] and over(out) == set()
    m = out["metrics"]
    assert 0 < m["ext_device_lane_share.validator"]["value"] <= 100
    assert m["ext_check_ms_per_height.validator"]["value"] > 0
    # no lane reaches the batch loop on a CPU: nothing to read
    assert "hash_block_fill.validator" not in m
    assert "consensus.ext_check" in capfd.readouterr().out


@pytest.mark.parametrize("plant, must_fail", [
    ("skip_extension_check", "app_ext_accepted_off"),
    ("accept_all", "probe_altered_admitted"),
    # the forged extensions cross the flush at the long shape
    ("long_lanes_accepted", "ext_probe_ext_sig_altered_counted"),
])
def test_vote_intake_ext_with_a_fault_is_not_correct(
        tiny_root, fresh_sigcache, flushing, plant, must_fail):
    out = run(tiny_root, 2**31 + 4073, plant=plant)
    assert not out["correct"] and must_fail in over(out)
