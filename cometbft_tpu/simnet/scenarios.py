"""Bundled fault schedules — the scenario DSL catalog.

Each scenario is a `harness.Scenario`: a target height, a virtual
deadline, and a `setup(sim)` that installs faults before any node
starts. Setups compose the same primitives user scenarios would:
`sim.at(ms, fn)` timed actions, `sim.net.set_partition/heal`, link
policies, `sim.crash_at_label` (fail-point crash injection),
`sim.defer + sim.blocksync_join`, and byzantine transport taps.

All bundled scenarios run 4 validators with f=1 — the smallest
committee where one byzantine/faulty node is tolerated.
"""

from __future__ import annotations

import os
from typing import Dict

from ..consensus.reactor import (DATA_CHANNEL, VOTE_CHANNEL, _BLOCK_PART,
                                 _PROPOSAL, _VOTE)
from ..libs import faultio
from ..types.block import BlockID
from ..types.vote import Vote
from .bls_valset import run_bls_valset as _run_bls_valset
from .clock import MS
from .flash_crowd import run_flash_crowd as _run_flash_crowd
from .harness import Scenario, Simulation
from .light_farm import run_light_farm as _run_light_farm
from .mesh_degrade import run_mesh_degrade as _run_mesh_degrade
from .seal_adoption import run_seal_adoption as _run_seal_adoption
from .transport import LinkPolicy


# --- byzantine taps -----------------------------------------------------------

def _equivocation_tap(sim: Simulation, byz: int):
    """Forge a conflicting nil vote for every non-nil vote the byzantine
    node signs, and deliver BOTH to every peer: each correct node then
    witnesses a textbook duplicate-vote equivocation, raises
    ErrVoteConflictingVotes, and feeds the evidence pool/reactor — while
    safety must hold because the other 3 of 4 validators are honest."""
    key = sim.nodes[byz].priv_key
    byz_addr = key.pub_key().address()
    chain_id = sim.gen.chain_id
    done = set()

    def tap(src, dst, ch, raw):
        if src != byz or ch != VOTE_CHANNEL or not raw or raw[0] != _VOTE:
            return raw
        try:
            v = Vote.decode(raw[1:])
        except Exception:  # noqa: BLE001 — not a vote we understand
            return raw
        if v.validator_address != byz_addr or v.block_id.is_nil():
            return raw  # relayed peer vote, or already nil: pass through
        hrt = (v.height, v.round, v.type_)
        if hrt in done:
            return raw
        done.add(hrt)
        forged = Vote(type_=v.type_, height=v.height, round=v.round,
                      block_id=BlockID(), timestamp=v.timestamp,
                      validator_address=v.validator_address,
                      validator_index=v.validator_index)
        forged.signature = key.sign(forged.sign_bytes(chain_id))
        wire = bytes([_VOTE]) + forged.encode()
        sim.log("byz_equivocate", h=v.height, r=v.round, t=v.type_)
        for peer in range(len(sim.nodes)):
            if peer != byz:
                sim.net.send(byz, peer, ch, wire)
        return raw
    return tap


def _withhold_tap(sim: Simulation, byz: int, victims):
    """When the byzantine node is proposer, it hides the proposal and
    its block parts from `victims` — they must prevote nil on timeout
    and recover the block through round-state reconciliation."""
    victims = set(victims)

    def tap(src, dst, ch, raw):
        if (src == byz and ch == DATA_CHANNEL and raw
                and raw[0] in (_PROPOSAL, _BLOCK_PART) and dst in victims):
            return None
        return raw
    return tap


# --- scenario setups ----------------------------------------------------------

def _setup_baseline(sim: Simulation) -> None:
    pass  # default mild latency/jitter, no faults


def _setup_flaky_links(sim: Simulation) -> None:
    sim.net.default_policy = LinkPolicy(
        latency_ns=5 * MS, jitter_ns=25 * MS, drop=0.08,
        reorder=0.15, reorder_extra_ns=60 * MS)


def _setup_partition_heal(sim: Simulation) -> None:
    # isolate node 0: the 3-node majority keeps committing, the minority
    # stalls; after heal the laggard must catch up through the
    # consensus catch-up path (decided-commit + parts serving)
    sim.at(1200, lambda: sim.net.set_partition([[0], [1, 2, 3]]))
    sim.at(3400, sim.net.heal)


def _setup_partition_split(sim: Simulation) -> None:
    # 2/2 split: NEITHER side has a quorum — the whole chain must halt
    # (never fork!) and resume after heal
    sim.at(1500, lambda: sim.net.set_partition([[0, 1], [2, 3]]))
    sim.at(4500, sim.net.heal)


def _setup_crash_restart(sim: Simulation) -> None:
    # crash node 2 at the SECOND crossing of finalize:post-save — the
    # block is persisted, the WAL has no #ENDHEIGHT yet, the app never
    # committed: restart must WAL-replay to the identical app hash
    sim.crash_at_label(2, "finalize:post-save", k=1,
                       restart_after_ms=1800)


def _setup_crash_at_propose(sim: Simulation) -> None:
    # crash a proposer right after privval signed but before the WAL
    # logged the proposal — replay must re-release the same signature
    sim.crash_at_label(1, "propose:signed", k=0, restart_after_ms=1000)


def _setup_byzantine_proposer(sim: Simulation) -> None:
    byz = len(sim.nodes) - 1
    sim.net.taps.append(_withhold_tap(sim, byz, victims={0}))
    sim.net.taps.append(_equivocation_tap(sim, byz))


def _setup_blocksync_lag(sim: Simulation) -> None:
    sim.defer(0)
    sim.at(2400, lambda: sim.blocksync_join(0))


def _setup_device_flap(sim: Simulation) -> None:
    # node 0 joins late; its verify device STALLS transiently (the first
    # two submits raise) and then recovers. The supervisor must take the
    # device HEALTHY → SUSPECT on the trip, CPU-fallback the affected
    # tiles, half-open probe it on the (virtual-time) backoff schedule,
    # and RESUME device dispatch — the wedge is no longer a one-way
    # door. tile_size=1 gives enough dispatch opportunities within a
    # short catch-up for the whole arc to play out.
    from ..pipeline.scheduler import FlakyBackend
    sim.blocksync_opts = {
        "depth": 2, "deadline_s": 0.5, "tile_size": 1,
        "backend_factory": lambda: FlakyBackend(fail_dispatches=2),
        "supervisor": {"backoff_base_s": 0.004, "backoff_cap_s": 0.1,
                       "probe_deadline_s": 0.5, "canary": True}}
    sim.defer(0)
    sim.at(3600, lambda: sim.blocksync_join(0))


def _setup_device_corrupt(sim: Simulation) -> None:
    # node 0 joins late; its verify device ANSWERS but answers WRONG
    # (all-true regardless of the signature). The known-bad canary lane
    # spliced into the first batch must expose it: the supervisor
    # quarantines the device (terminal), the batch is re-verified on
    # CPU, and no corrupted verdict can reach commit verification —
    # every remaining tile verifies on the CPU fallback.
    from ..pipeline.scheduler import CorruptBackend
    sim.blocksync_opts = {
        "depth": 2, "deadline_s": 0.5, "tile_size": 2,
        "backend_factory": CorruptBackend,
        "supervisor": {"backoff_base_s": 0.004, "backoff_cap_s": 0.1,
                       "probe_deadline_s": 0.5, "canary": True}}
    sim.defer(0)
    sim.at(3600, lambda: sim.blocksync_join(0))


def _setup_torn_storage(sim: Simulation) -> None:
    # node 2's block/state DBs live on REAL FileDB files, and a seeded
    # torn-write fault tears its 2nd block-save batch mid-write (the
    # tear offset is a pure function of the seed). The tear crosses the
    # faultio:torn-write fail point, which crash_at_label converts into
    # a modeled crash: the node reboots through the real FileDB
    # reopen-replay (the uncommitted batch tail truncates — all-or-
    # nothing), the doctor reconciles, and the chain must reach the
    # target with the same app hash on all nodes.
    from ..db.kv import FileDB
    node = sim.nodes[2]
    node.db_factory = lambda n, name: FileDB(
        os.path.join(n.dir, f"{name}.db"))
    plan = faultio.FaultPlan(seed=sim.seed)
    plan.torn_write("db:log", nth=2,
                    path_substr=os.path.join("node2", "blockstore"))
    faultio.install(plan)
    sim.crash_at_label(2, faultio.TORN_WRITE_LABEL,
                       restart_after_ms=1800)


def _setup_blocksync_wedge(sim: Simulation) -> None:
    # node 0 joins late and catches up through the PIPELINED blocksync
    # engine whose verify backend never answers (the wedged-device
    # model, docs/PERF.md): the watchdog must drain every tile to the
    # CPU fallback and still complete the sync — a wedged device
    # degrades catch-up speed, never liveness
    from ..pipeline.scheduler import HangingBackend
    sim.blocksync_opts = {"depth": 2, "deadline_s": 0.02,
                          "backend_factory": HangingBackend}
    sim.defer(0)
    sim.at(2400, lambda: sim.blocksync_join(0))


SCENARIOS: Dict[str, Scenario] = {s.name: s for s in [
    Scenario("baseline", "4 honest nodes, mild latency/jitter",
             target_height=5, deadline_ms=60_000,
             setup=_setup_baseline),
    Scenario("flaky-links", "8% drop + heavy jitter + reordering; "
             "reconciliation must preserve liveness",
             target_height=4, deadline_ms=120_000,
             setup=_setup_flaky_links),
    Scenario("partition-heal", "isolate one node, heal, laggard "
             "catches up via decided-commit serving",
             target_height=5, deadline_ms=120_000,
             setup=_setup_partition_heal),
    Scenario("partition-split", "quorumless 2/2 split: chain must halt "
             "without forking, then resume on heal",
             target_height=5, deadline_ms=120_000,
             setup=_setup_partition_split),
    Scenario("crash-restart", "kill a node mid-commit at a fail point; "
             "WAL+store replay to the same app hash",
             target_height=5, deadline_ms=120_000,
             setup=_setup_crash_restart),
    Scenario("crash-propose", "kill a proposer between privval sign and "
             "WAL append; replay re-releases the signature",
             target_height=5, deadline_ms=120_000,
             setup=_setup_crash_at_propose),
    Scenario("byzantine-proposer", "last validator equivocates votes "
             "and withholds proposals from node 0",
             target_height=4, deadline_ms=120_000,
             setup=_setup_byzantine_proposer),
    Scenario("blocksync-lag", "node 0 joins late and catches up through "
             "the real blocksync engine before consensus",
             target_height=6, deadline_ms=120_000,
             setup=_setup_blocksync_lag),
    Scenario("torn-storage", "node 2 runs on FileDB; a seeded torn "
             "write shears a block-save batch mid-buffer, the node "
             "crashes at the tear and reboots through replay + "
             "truncation + the recovery doctor to the same app hash",
             target_height=5, deadline_ms=120_000, quick_target=4,
             setup=_setup_torn_storage),
    Scenario("blocksync-wedge", "late joiner syncs through the pipelined "
             "engine with a hung verify device; the watchdog drains "
             "every tile to the CPU fallback",
             target_height=6, deadline_ms=120_000,
             setup=_setup_blocksync_wedge),
    Scenario("device-flap", "late joiner's verify device stalls then "
             "recovers; the supervisor probes it back to HEALTHY and "
             "device dispatch resumes",
             target_height=8, deadline_ms=120_000, quick_target=5,
             setup=_setup_device_flap),
    Scenario("device-corrupt", "late joiner's verify device answers "
             "wrong verdicts; the canary lanes quarantine it and the "
             "sync completes on the CPU fallback",
             target_height=8, deadline_ms=120_000, quick_target=5,
             setup=_setup_device_corrupt),
    Scenario("light-farm", "hundreds of virtual light clients at "
             "staggered trusted heights outsource verification to the "
             "farm; forged requests reject, bounded queues shed, and "
             "every accepted header is re-judged against the "
             "LightClient.tla acceptance rules",
             target_height=20, deadline_ms=0,
             runner=_run_light_farm),
    Scenario("bls-valset", "the real engine on a uniformly-BLS "
             "validator set: commits seal as BLS aggregates (one "
             "pairing equation each), a late joiner blocksyncs "
             "through the AggSeal marshal route, and sync-vs-"
             "aggregate verdicts must agree on clean / tampered-sig / "
             "forged-bitmap / undercount chains",
             target_height=3, deadline_ms=120_000, quick_target=2,
             runner=_run_bls_valset),
    Scenario("seal-adoption", "a laggard adopts a wide BLS valset "
             "chain from aggregate seals alone (sealsync): the one "
             "corrupt provider's forged seal and forged bitmap both "
             "reject at the pivot pairing, adoption completes via the "
             "honest peer across a mid-chain epoch boundary (PoP-"
             "carrying val-update tx), and body backfill re-pairs "
             "nothing — every adopted commit is a SigCache hit",
             target_height=20, deadline_ms=0, quick_target=8,
             runner=_run_seal_adoption),
    Scenario("mesh-degrade", "one mesh shard answers corrupt canary "
             "verdicts: the shard is quarantined, the mesh re-factors "
             "smaller, a real blocksync completes with zero corrupt "
             "verdicts reaching apply, and the backoff-scheduled "
             "re-probe grows the shard back",
             target_height=24, deadline_ms=0,
             runner=_run_mesh_degrade),
    Scenario("flash-crowd", "thousands of seeded virtual clients burst "
             "signed txs at the batched admission pipeline; the bounded "
             "queue sheds, the duplicate filter hits, tampered "
             "signatures reject, recheck-evicted txs re-enter via the "
             "SigCache, and the mempool's FIFO matches a shadow model "
             "replay",
             target_height=3, deadline_ms=0,
             runner=_run_flash_crowd),
]}


def run_scenario(name: str, seed: int, quick: bool = False,
                 workdir=None):
    """Build + run one simulation; returns harness.SimResult."""
    try:
        scenario = SCENARIOS[name]
    except KeyError:
        raise ValueError(
            f"unknown scenario {name!r}; have: "
            f"{', '.join(sorted(SCENARIOS))}") from None
    if scenario.runner is not None:
        return scenario.runner(scenario, seed, quick=quick,
                               workdir=workdir)
    return Simulation(scenario, seed, workdir=workdir, quick=quick).run()


def sweep(seeds, scenario: str = "all", quick: bool = False):
    """Run one scenario per seed. With scenario='all' the bundle is
    assigned round-robin by seed, so a seed range sweeps every scenario
    while each individual (scenario, seed) line stays replayable."""
    names = sorted(SCENARIOS) if scenario == "all" else [scenario]
    results = []
    for seed in seeds:
        name = names[seed % len(names)]
        results.append(run_scenario(name, seed, quick=quick))
    return results
