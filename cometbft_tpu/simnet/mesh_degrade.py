"""mesh-degrade scenario: one mesh shard answers corrupt verdicts —
the shard is quarantined, the mesh re-factors smaller, catch-up
completes with zero corrupt verdicts reaching apply, and a re-probe
grows the shard back. Deterministic: byte-identical log per seed.

Like light-farm/flash-crowd this runs no network — the simulated
population is the DEVICE MESH. Eight virtual shards serve a real
PipelinedBlocksync catch-up over a generated chain through a real
`mesh.MeshExecutor` (threaded=False: dispatch and regrow probes run
on the scenario thread, so probe timing is a pure function of the
virtual clock). A seeded PRNG picks which shard lies and when it
heals; the stub backend computes true verdicts natively and corrupts
exactly the sick shard's slice (all-True regardless of signature —
the classic silently-corrupt engine of the PR-3 canary design).

Phases:
  adversarial — a batch of TAMPERED signatures is dispatched while
    the sick shard serves: the corrupt shard answers True for its
    slice, the per-shard canary/pad rows expose it, the shard is
    masked (mesh 8 -> 7), and the batch re-verifies on CPU — every
    surfaced verdict is False. A corrupt verdict is structurally
    unable to escape the executor.
  catch-up — a real blocksync (fetch → marshal → mesh dispatch →
    sequential apply) syncs the chain on the degraded mesh; the sick
    chip heals mid-sync and the supervisor's backoff-scheduled
    known-answer probe readmits it (mesh 7 -> 8, logged regrow).
  post-regrow — tampered signatures again, now on the full healthy
    mesh: rejected by the mesh verdicts themselves (backend=mesh, no
    canary trip).

Invariant probes:
  * containment — every verdict any dispatch surfaced equals the
    native ground truth for its lane (the shadow re-verify);
  * the arc — quarantine, refactor to a smaller shape, >= 1 failed
    probe, regrow to the full shape must ALL occur;
  * liveness — the sync reaches the target height on the degraded
    mesh (a sick chip shrinks the mesh, never benches the node).
"""

from __future__ import annotations

import hashlib
import os
import random
import time as _walltime
from typing import List

from .. import trace as _trace
from ..libs import timesource
from ..mesh import MeshExecutor, MeshTopology
from ..mesh.executor import _native_verify as _native
from ..mesh.shard_health import ShardSupervisor
from .harness import SimResult

N_SHARDS = 8


class _CorruptibleMesh:
    """Stub mesh backend: true verdicts everywhere except the sick
    shard's slice, which answers all-True (verdict corruption)."""

    def __init__(self, sick_shard: int):
        self.sick = {sick_shard}
        self.dispatches = 0

    def __call__(self, view, plan, pubs, msgs, sigs):
        self.dispatches += 1
        rows = _native(pubs, msgs, sigs)
        for si, gid in enumerate(view.shard_ids):
            if gid in self.sick:
                for r in range(si * plan.shard_width,
                               (si + 1) * plan.shard_width):
                    rows[r] = True
        return rows


class _MeshSim:
    def __init__(self, scenario, seed: int, quick: bool, workdir=None):
        self.name = scenario.name
        self.seed = seed
        self.workdir = workdir
        self._vclock_ns = 0
        if quick:
            self.n_blocks, self.n_vals, self.tile = 12, 4, 2
        else:
            self.n_blocks, self.n_vals, self.tile = 24, 6, 2
        self.rng = random.Random(f"simnet:{scenario.name}:{seed}")
        self.log_lines: List[str] = []
        self.violations: List[str] = []
        self.clock = 0.0
        self.shadow_checked = 0
        self.shadow_bad = 0

    def log(self, kind: str, **kw) -> None:
        fields = " ".join(f"{k}={v}" for k, v in kw.items())
        self.log_lines.append(f"{kind} {fields}".rstrip())

    def violation(self, msg: str) -> None:
        self.log("violation", msg=msg.replace(" ", "_"))
        self.violations.append(msg)

    # --- wiring -----------------------------------------------------------

    def build(self):
        self.sick = self.rng.randrange(N_SHARDS)
        # the chip heals AFTER this many failed regrow probes (the
        # strict > below guarantees every seed exercises at least one
        # probe that fails and deepens the backoff before the regrow)
        self.heal_after_probes = 1 + self.rng.randrange(2)
        self.stub = _CorruptibleMesh(self.sick)
        self.topology = MeshTopology(devices=list(range(N_SHARDS)))
        self.sup = ShardSupervisor(
            self.topology, backoff_base_s=0.25, backoff_cap_s=2.0,
            clock=lambda: self.clock,
            log=lambda m: self.log("supervisor",
                                   msg=m.replace(" ", "_")),
            jitter_seed=self.seed)
        self.probe_count = 0

        def probe_backend(shard, pubs, msgs, sigs):
            self.probe_count += 1
            if shard in self.stub.sick \
                    and self.probe_count > self.heal_after_probes:
                self.stub.sick.discard(shard)
                self.log("chip_healed", shard=shard)
            self.log("probe", shard=shard, n=self.probe_count,
                     sick=int(shard in self.stub.sick))
            if shard in self.stub.sick:
                return [True] * len(pubs)  # still lying
            return _native(pubs, msgs, sigs)

        self.executor = MeshExecutor(
            self.topology, supervisor=self.sup, verify_backend=self.stub,
            probe_backend=probe_backend, threaded=False)

    def dispatch(self, pubs, msgs, sigs, phase: str) -> List[bool]:
        """One clocked dispatch with the shadow containment check."""
        self.clock += 1.0
        fut = self.executor.submit(pubs, msgs, sigs)
        out = fut.result(0)  # threaded=False: already resolved
        truth = _native(pubs, msgs, sigs)
        self.shadow_checked += len(out)
        if out != truth:
            self.shadow_bad += sum(1 for a, b in zip(out, truth)
                                   if a != b)
            self.violation(f"corrupt verdict surfaced in {phase} "
                           f"dispatch at t={self.clock}")
        from ..mesh.executor import CPU_SHARD
        view = self.topology.view()
        backend = ("cpu" if fut.shards and fut.shards[0] == CPU_SHARD
                   else "mesh")
        self.log("dispatch", phase=phase, t=int(self.clock),
                 lanes=len(pubs), shape=f"{view.shape[0]}x{view.shape[1]}",
                 backend=backend)
        return out

    # --- phases -----------------------------------------------------------

    def _vclock(self) -> int:
        """Counter clock for the trace seam (one virtual millisecond
        per observation): span timestamps, and thus the trace JSONL
        the digest pins, are a pure function of (scenario, seed)."""
        self._vclock_ns += 1_000_000
        return self._vclock_ns

    def run(self) -> SimResult:
        t0 = _walltime.perf_counter()  # staticcheck: allow(wallclock)
        own_clock = not timesource.installed()
        if own_clock:
            timesource.install(self._vclock)
        _tracer, recorder = _trace.enable(seed=self.seed)
        try:
            return self._run_traced(t0, recorder)
        finally:
            _trace.disable()
            if own_clock:
                timesource.reset()

    def _run_traced(self, t0: float, recorder) -> SimResult:
        from ..engine.chain_gen import generate_chain
        from ..pipeline.cache import reset_shared_cache
        # the trace records what the process-wide verified-signature
        # cache answered (`commit.verify`: cache_hits, native_lanes), so
        # a run starts with an empty one: its trace stays a function of
        # (scenario, seed), not of what the process verified before
        reset_shared_cache()
        self.build()
        self.log("start", scenario=self.name, seed=self.seed,
                 blocks=self.n_blocks, vals=self.n_vals,
                 shards=N_SHARDS, sick=self.sick,
                 heal_after=self.heal_after_probes)
        chain = generate_chain(self.n_blocks, self.n_vals,
                               seed=1 + self.seed % 11, txs_per_block=1)

        # phase 1: adversarial batch on the corrupt mesh — containment
        pubs, msgs, sigs = self._tampered_batch(chain, n=24)
        out = self.dispatch(pubs, msgs, sigs, "adversarial")
        if any(out):
            self.violation("tampered signature accepted during "
                           "corruption")
        if self.topology.masked() != (self.sick,):
            self.violation(f"sick shard {self.sick} not quarantined "
                           f"(masked={self.topology.masked()})")
        view = self.topology.view()
        self.log("degraded", shape=f"{view.shape[0]}x{view.shape[1]}",
                 shards=view.n_shards)

        # phase 2: real catch-up on the degraded mesh; heal + regrow
        state = self._sync(chain)
        if state.last_block_height != self.n_blocks:
            self.violation(f"sync stopped at "
                           f"{state.last_block_height}/{self.n_blocks}")
        if self.topology.masked():
            self.violation(f"shard never regrown "
                           f"(masked={self.topology.masked()})")
        if self.sup.regrows < 1:
            self.violation("no regrow recorded")
        if self.sup.probes <= self.sup.regrows:
            # at least one probe must FAIL (deepened backoff) before
            # the regrow — the heal fires only after heal_after_probes
            # failed probes, so a run without a failed probe means the
            # schedule was never exercised
            self.violation("no failed probe before the regrow")

        # phase 3: tampered batch on the regrown full mesh — the mesh
        # verdicts themselves must reject (no canary trip this time)
        quarantines_before = self.sup.quarantines
        pubs, msgs, sigs = self._tampered_batch(chain, n=24, flavor=1)
        out = self.dispatch(pubs, msgs, sigs, "post-regrow")
        if any(out):
            self.violation("tampered signature accepted post-regrow")
        if self.sup.quarantines != quarantines_before:
            self.violation("healthy mesh tripped a canary post-regrow")

        tr = recorder.stats()
        self.log("trace", spans=tr["recorded"], evicted=tr["evicted"],
                 dumps=len(recorder.dumps))
        self.log("end", dispatches=self.stub.dispatches,
                 probes=self.probe_count,
                 quarantines=self.sup.quarantines,
                 regrows=self.sup.regrows,
                 shadow_checked=self.shadow_checked,
                 shadow_bad=self.shadow_bad,
                 violations=len(self.violations))
        digest = hashlib.sha256()
        for line in self.log_lines:
            digest.update(line.encode())
            digest.update(b"\n")
        # the flight-recorder ring rides the pinned per-seed digest
        trace_jsonl = recorder.snapshot_jsonl()
        digest.update(trace_jsonl.encode())
        if self.workdir:
            with open(os.path.join(self.workdir,
                                   f"trace_seed{self.seed}.jsonl"),
                      "w") as f:
                f.write(trace_jsonl)
        return SimResult(
            scenario=self.name, seed=self.seed,
            violations=self.violations, max_height=self.n_blocks,
            heights={}, app_hashes={}, log_lines=self.log_lines,
            digest=digest.hexdigest(),
            # staticcheck: allow(wallclock) — wall_s never enters the log
            wall_s=_walltime.perf_counter() - t0,
            virtual_s=self.clock, commits_per_sim_s=0.0,
            crashes=0, restarts=0, evidence_seen=0, errors=[],
            stats={"delivered": self.shadow_checked,
                   "dropped": self.shadow_bad,
                   "blocked": self.sup.quarantines,
                   "events": self.stub.dispatches})

    def _tampered_batch(self, chain, n: int, flavor: int = 0):
        """n structurally-valid lanes with flipped signature bits —
        all must verify False. Deterministic from the chain's own
        commit signatures."""
        pubs: List[bytes] = []
        msgs: List[bytes] = []
        sigs: List[bytes] = []
        vals = chain.valsets[0]
        commit = chain.seen_commits[0]
        for i in range(n):
            idx = i % len(vals.validators)
            cs = commit.signatures[idx]
            msg = commit.vote_sign_bytes(chain.chain_id, idx)
            sig = bytes([cs.signature[0] ^ (1 + flavor)]) \
                + cs.signature[1:]
            pubs.append(vals.validators[idx].pub_key.bytes_())
            msgs.append(msg + bytes([i]))
            sigs.append(sig)
        return pubs, msgs, sigs

    def _sync(self, chain):
        from ..abci.kvstore import KVStoreApplication
        from ..db.kv import MemDB
        from ..engine.blocksync import BlocksyncReactor
        from ..engine.chain_gen import LocalChainSource
        from ..pipeline.scheduler import PipelinedBlocksync
        from ..state.execution import BlockExecutor
        from ..state.state import State, StateStore
        from ..store.blockstore import BlockStore

        app = KVStoreApplication()
        app.init_chain(chain.chain_id, 1, [], b"")
        db = MemDB()
        store = BlockStore(db)
        executor = BlockExecutor(app, state_store=StateStore(db),
                                 block_store=store)
        state = State.from_genesis(chain.genesis)
        reactor = BlocksyncReactor(
            executor, store, LocalChainSource(chain), chain.chain_id,
            tile_size=self.tile, batch_size=0)
        pipe = PipelinedBlocksync(reactor, depth=1,
                                  backend=_ClockedBackend(self))
        try:
            while state.last_block_height < self.n_blocks:
                state = pipe.run(state, self.n_blocks)
        finally:
            pipe.close()
        return state


class _ClockedBackend:
    """Pipeline backend adapter: every scheduler dispatch goes through
    the scenario's clocked, shadow-checked dispatch()."""

    def __init__(self, sim: _MeshSim):
        self.sim = sim
        # the scheduler sizes its bounded queue from this (K tiles in
        # flight per shard)
        self.n_shards = sim.topology.view().n_shards

    def submit(self, pubs, msgs, sigs):
        out = self.sim.dispatch(pubs, msgs, sigs, "catchup")
        from ..mesh.executor import MeshFuture
        fut = MeshFuture(len(pubs))
        fut.set_result(out)
        return fut

    def close(self) -> None:
        pass


def run_mesh_degrade(scenario, seed: int, quick: bool = False,
                     workdir=None) -> SimResult:
    """Scenario runner (scenarios.py dispatches here; `workdir`, when
    set, receives the run's flight-recorder JSONL)."""
    return _MeshSim(scenario, seed, quick, workdir=workdir).run()
