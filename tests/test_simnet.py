"""simnet: the deterministic in-process multi-node simulator
(cometbft_tpu/simnet, docs/SIMNET.md).

The defining property — same seed => byte-identical event log — is
pinned here, along with seed divergence, crash-restart WAL replay
convergence, byzantine equivocation evidence flow, and a fast
seed-sweep smoke across the whole scenario catalog. The 100-seed
sweep is slow-marked; CI runs the quick versions.
"""

import pytest

from cometbft_tpu.simnet.scenarios import SCENARIOS, run_scenario, sweep

pytestmark = pytest.mark.sim


def test_same_seed_identical_event_log():
    a = run_scenario("partition-heal", 11, quick=True)
    b = run_scenario("partition-heal", 11, quick=True)
    assert a.ok, a.violations
    assert a.digest == b.digest
    assert a.log_lines == b.log_lines
    assert a.max_height >= 3


def test_different_seeds_diverge():
    a = run_scenario("baseline", 1, quick=True)
    b = run_scenario("baseline", 2, quick=True)
    assert a.ok and b.ok
    assert a.digest != b.digest


def test_crash_restart_replays_wal_to_same_app_hash():
    r = run_scenario("crash-restart", 5, quick=True)
    assert r.ok, r.violations
    assert r.crashes == 1 and r.restarts == 1
    # the restarted node converged: nodes at equal heights hold equal
    # app hashes (also invariant-checked inside the run)
    by_h = {}
    for idx, h in r.heights.items():
        by_h.setdefault(h, set()).add(r.app_hashes[idx])
    assert all(len(hashes) == 1 for hashes in by_h.values())


def test_byzantine_equivocation_produces_evidence():
    r = run_scenario("byzantine-proposer", 3, quick=True)
    assert r.ok, r.violations
    # the forged duplicate votes must surface as committed evidence
    assert r.evidence_seen > 0


def test_blocksync_lag_catches_up():
    r = run_scenario("blocksync-lag", 1, quick=True)
    assert r.ok, r.violations
    assert any("blocksync" in line for line in r.log_lines)


def test_blocksync_wedge_completes_via_watchdog():
    """Mid-sync device wedge: the late joiner's pipelined blocksync
    engine dispatches to a backend that never answers; the watchdog
    must drain every tile to the CPU fallback and the sync must still
    complete (liveness through a wedged device)."""
    r = run_scenario("blocksync-wedge", 1, quick=True)
    assert r.ok, r.violations
    wedge = [ln for ln in r.log_lines if "blocksync_wedge" in ln]
    assert wedge and "wedged=1" in wedge[0]
    assert any("blocksync " in ln for ln in r.log_lines)


def test_blocksync_wedge_event_log_deterministic():
    """The wall-clock watchdog must not leak nondeterminism into the
    per-seed event log: two runs of the same seed stay byte-identical
    (the simnet defining property, through the wedge path)."""
    a = run_scenario("blocksync-wedge", 4, quick=True)
    b = run_scenario("blocksync-wedge", 4, quick=True)
    assert a.ok, a.violations
    assert a.digest == b.digest
    assert a.log_lines == b.log_lines


def test_device_flap_recovers_to_device_dispatch():
    """The supervisor arc end-to-end: wedge (trips) → CPU fallback
    (wedge fallbacks) → half-open probe → HEALTHY → the backend serves
    batches again (served > probes proves real tiles dispatched after
    recovery, not just the probe)."""
    r = run_scenario("device-flap", 1, quick=True)
    assert r.ok, r.violations
    dev = [ln for ln in r.log_lines if "blocksync_device" in ln]
    assert dev, "no blocksync_device log line"
    line = dev[0]
    assert "state=healthy" in line
    assert "quarantines=0" in line
    assert "trips=2" in line and "probes=2" in line
    assert "served=3" in line  # 1 successful probe + 2 device tiles
    wedge = [ln for ln in r.log_lines if "blocksync_wedge" in ln]
    assert wedge and "wedged=0" in wedge[0]  # NOT a one-way door


def test_device_flap_event_log_deterministic():
    a = run_scenario("device-flap", 4, quick=True)
    b = run_scenario("device-flap", 4, quick=True)
    assert a.ok, a.violations
    assert a.digest == b.digest
    assert a.log_lines == b.log_lines


def test_device_corrupt_quarantines_and_completes():
    """A verdict-corrupting device is exposed by the canary lanes on
    its first settled batch, quarantined terminally, and the sync
    completes on the CPU fallback with zero corrupted verdicts reaching
    the apply/commit path (agreement + app-hash invariants hold)."""
    r = run_scenario("device-corrupt", 1, quick=True)
    assert r.ok, r.violations
    dev = [ln for ln in r.log_lines if "blocksync_device" in ln]
    assert dev, "no blocksync_device log line"
    line = dev[0]
    assert "state=quarantined" in line
    assert "quarantines=1" in line and "canary_failures=1" in line
    assert "probes=0" in line  # corruption is terminal: never probed


def test_device_corrupt_event_log_deterministic():
    a = run_scenario("device-corrupt", 4, quick=True)
    b = run_scenario("device-corrupt", 4, quick=True)
    assert a.ok, a.violations
    assert a.digest == b.digest
    assert a.log_lines == b.log_lines


def test_mesh_degrade_quarantine_refactor_regrow():
    """The per-shard arc end-to-end (mesh/shard_health): a corrupt
    shard is exposed by its canary/pad rows and masked (mesh 8 -> 7),
    the adversarial batch surfaces only CPU-re-verified verdicts, the
    blocksync completes on the degraded mesh, and the backoff-
    scheduled probe regrows the shard (7 -> 8) — after which tampered
    signatures are rejected by the mesh verdicts themselves."""
    r = run_scenario("mesh-degrade", 1, quick=True)
    assert r.ok, r.violations
    assert any("QUARANTINED" in ln for ln in r.log_lines)
    assert any(ln.startswith("degraded shape=7x1") for ln in r.log_lines)
    assert any("re-grown" in ln for ln in r.log_lines)
    end = [ln for ln in r.log_lines if ln.startswith("end ")][0]
    assert "quarantines=1" in end and "regrows=1" in end
    # the shadow re-verify: every surfaced verdict == native truth
    assert "shadow_bad=0" in end
    # the adversarial batch during corruption came back CPU-attributed
    adv = [ln for ln in r.log_lines if "phase=adversarial" in ln][0]
    assert "backend=cpu" in adv
    # the post-regrow dispatch serves on the FULL mesh again
    post = [ln for ln in r.log_lines if "phase=post-regrow" in ln][0]
    assert "shape=4x2" in post and "backend=mesh" in post


def test_mesh_degrade_event_log_deterministic():
    a = run_scenario("mesh-degrade", 4, quick=True)
    b = run_scenario("mesh-degrade", 4, quick=True)
    assert a.ok, a.violations
    assert a.digest == b.digest
    assert a.log_lines == b.log_lines
    c = run_scenario("mesh-degrade", 5, quick=True)
    assert c.digest != a.digest


def test_light_farm_scenario():
    """The verification-farm crowd scenario: forged requests reject,
    both bounded-queue shed paths fire, and every accepted header
    passed the LightClient.tla acceptance oracle (a violation would
    fail r.ok)."""
    r = run_scenario("light-farm", 1, quick=True)
    assert r.ok, r.violations
    assert r.stats["delivered"] > 50      # accepted headers
    assert r.stats["blocked"] >= 5        # session-cap + lane sheds
    assert any(line.startswith("forged_rejected")
               for line in r.log_lines)
    assert any(line.startswith("shed") and "subscribe" in line
               for line in r.log_lines)
    assert any(line.startswith("shed") and "burst" in line
               for line in r.log_lines)


def test_light_farm_determinism():
    """Same seed => byte-identical farm event log (batch widths, dedup
    counts, every accept/reject/shed decision)."""
    a = run_scenario("light-farm", 4, quick=True)
    b = run_scenario("light-farm", 4, quick=True)
    assert a.ok, a.violations
    assert a.digest == b.digest
    assert a.log_lines == b.log_lines
    c = run_scenario("light-farm", 5, quick=True)
    assert c.digest != a.digest


def test_flash_crowd_scenario():
    """The admission-crowd scenario: the bounded queue sheds and
    clears, the duplicate filter hits, tampered signatures reject, and
    the mempool FIFO matches the shadow-model replay (a violation
    would fail r.ok)."""
    r = run_scenario("flash-crowd", 1, quick=True)
    assert r.ok, r.violations
    assert r.stats["delivered"] > 100     # admitted txs
    assert r.stats["blocked"] > 0         # queue-cap sheds fired
    assert any(line.startswith("shed") for line in r.log_lines)
    assert any(line.startswith("dup") for line in r.log_lines)
    assert any(line.startswith("resubmit") for line in r.log_lines)
    assert any("kind=badsig" in line for line in r.log_lines)


def test_flash_crowd_determinism():
    """Same seed => byte-identical admission event log (batch widths,
    shed counts, every verdict)."""
    a = run_scenario("flash-crowd", 4, quick=True)
    b = run_scenario("flash-crowd", 4, quick=True)
    assert a.ok, a.violations
    assert a.digest == b.digest
    assert a.log_lines == b.log_lines
    c = run_scenario("flash-crowd", 5, quick=True)
    assert c.digest != a.digest


def test_seed_sweep_smoke():
    """Fast tier-1 sweep (<=20s CPU): one quick seed through each of
    the four headline fault classes. The full catalog runs in the
    slow-marked 100-seed sweep and in `tools/sim_run.py --selftest`."""
    names = ["partition-heal", "crash-restart", "byzantine-proposer",
             "blocksync-lag"]
    results = [run_scenario(n, seed=20 + i, quick=True)
               for i, n in enumerate(names)]
    bad = [r for r in results if not r.ok]
    assert not bad, [r.failure_line() for r in bad]


@pytest.mark.slow
def test_seed_sweep_100():
    results = sweep(range(100), scenario="all", quick=True)
    bad = [r for r in results if not r.ok]
    assert not bad, [r.failure_line() for r in bad]


@pytest.mark.slow
def test_device_health_seed_sweep_100():
    """100 seeds through the device-health scenarios (50 each): every
    flap must end clean (liveness through recovery), every corruption
    must end clean (safety through quarantine + CPU fallback), and the
    invariant probes hold across the whole seed range."""
    results = (sweep(range(50), scenario="device-flap", quick=True)
               + sweep(range(50), scenario="device-corrupt", quick=True))
    bad = [r for r in results if not r.ok]
    assert not bad, [r.failure_line() for r in bad]
    # the corruption arc must have fired in every corrupt run
    for r in results[50:]:
        assert any("state=quarantined" in ln for ln in r.log_lines), \
            (r.scenario, r.seed)


def test_bls_valset_scenario():
    """The aggregate-commit scenario: the real engine commits on a
    uniformly-BLS valset with AggregatedCommit seals, a late joiner
    blocksyncs through the AggSeal marshal route, sync-vs-aggregate
    verdicts agree on every tamper class, and the combined log is
    byte-identical across runs (the second run rides the process-wide
    SigCache, so determinism costs little extra wall time)."""
    a = run_scenario("bls-valset", 1, quick=True)
    assert a.ok, a.failure_line()
    assert a.max_height >= 2
    assert any(line.startswith("agg_seal ") for line in a.log_lines)
    equiv = {line.split()[1] for line in a.log_lines
             if line.startswith("equiv ")}
    assert {"case=clean", "case=tampered-sig", "case=signers-3",
            "case=forged-bitmap", "case=undercount"} <= equiv
    b = run_scenario("bls-valset", 1, quick=True)
    assert b.digest == a.digest and b.log_lines == a.log_lines


def test_seal_adoption_scenario():
    """Aggregate-seal catch-up (sealsync): both forgery modes reject
    at the pivot pairing and adoption still completes via the honest
    retry, the skip schedule elides pairings, backfill is 100% cache
    hits, and the log is byte-identical across runs of one seed."""
    a = run_scenario("seal-adoption", 1, quick=True)
    assert a.ok, a.failure_line()
    forged = {line.split()[1] for line in a.log_lines
              if line.startswith("forge ")}
    assert {"mode=sig", "mode=bitmap"} <= forged
    assert all("rejected=1" in line for line in a.log_lines
               if line.startswith("forge "))
    assert any(line.startswith("backfill cache_hits=")
               and line.split("=")[1].split("/")[0]
               == line.split("/")[1] for line in a.log_lines)
    b = run_scenario("seal-adoption", 1, quick=True)
    assert b.digest == a.digest and b.log_lines == a.log_lines
