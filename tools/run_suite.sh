#!/bin/bash
# Full test suite in TWO pytest processes instead of one.
#
# Why: this jaxlib's XLA:CPU backend can SEGFAULT (stack-guard hit in
# libjax_common) in a process that has accumulated many kernel
# compilations — the same failure mode that already forces the
# mesh/pallas tests into fresh interpreters (tests/_mesh_harness.py,
# docs/PERF.md "known compile hazard"). A single `pytest tests/` run
# stacks every in-process compile from ~40 modules into one process
# and can cross the cliff mid-suite; splitting at the alphabetical
# midpoint keeps each process's compile count near round-4 levels.
#
# Usage: bash tools/run_suite.sh [extra pytest args]
set -u
cd "$(dirname "$0")/.."
ARGS=("$@")
FIRST=(tests/test_[a-o]*.py)
SECOND=(tests/test_[p-z]*.py)
rc=0
# project-invariant lint first: cheapest check, and a new finding (or
# a stale baseline entry) should fail the suite before any test burns
# compile time (docs/STATICCHECK.md; fix, pragma, or --fix-baseline).
# BUDGET: the whole-program engine (call graph + lock-order +
# verdict-taint + kernel-discipline + the v3 interval/lifecycle/
# contract rules) must stay under 90s for the full tree or it silently
# makes the suite unrunnable — a breach fails the suite; attribute the
# slow rule with `--format json` (rule_seconds). Measured ~30s with
# kernel-interval (the abstract interpreter) taking ~24s of it.
echo "=== staticcheck: project-invariant linter ===" >&2
sc_t0=$(date +%s)
python -m tools.staticcheck || rc=$?
sc_dt=$(( $(date +%s) - sc_t0 ))
if [ "$sc_dt" -gt 90 ]; then
    echo "staticcheck BUDGET BREACH: full-tree analysis took ${sc_dt}s" \
         "(> 90s) — bisect with: python -m tools.staticcheck" \
         "--format json (rule_seconds)" >&2
    rc=1
fi
# SARIF emitter smoke: the code-scanning output must stay parseable
# (cheap per-file rules only — the full tree already ran above)
python -m tools.staticcheck --rule wallclock --rule raw-env \
    --format sarif | python -c "
import json, sys
d = json.load(sys.stdin)
assert d['version'] == '2.1.0' and d['runs'][0]['tool']['driver'], d
" || rc=$?
# interval proof vs. concrete execution: every ops/ kernel fuzzed with
# inputs sampled inside its assume() intervals under the object-int
# shadow backend — a single int32 escape disproves the kernel-interval
# verdict and fails the suite (tools/interval_fuzz.py; full mode runs
# 3 seeds per kernel, this quick mode one)
echo "=== interval_fuzz: concrete no-overflow differential (quick) ===" >&2
python -m tools.interval_fuzz --quick || rc=$?
echo "=== suite 1/2: ${#FIRST[@]} modules (a-o) ===" >&2
python -m pytest "${FIRST[@]}" -q "${ARGS[@]+"${ARGS[@]}"}" || rc=$?
echo "=== suite 2/2: ${#SECOND[@]} modules (p-z) ===" >&2
python -m pytest "${SECOND[@]}" -q "${ARGS[@]+"${ARGS[@]}"}" || rc=$?
echo "=== simnet selftest (determinism + crash recovery + device health) ===" >&2
python tools/sim_run.py --selftest || rc=$?
# device health supervisor liveness/safety sweep (quick): the flap
# scenario must recover to device dispatch, the corrupt scenario must
# quarantine — across a seed range, not just the selftest's seed 1
echo "=== device-flap / device-corrupt quick sweeps ===" >&2
python tools/sim_run.py --scenario device-flap --seeds 0..4 --quick || rc=$?
python tools/sim_run.py --scenario device-corrupt --seeds 0..4 --quick || rc=$?
# per-shard mesh health (mesh/shard_health): a corrupt shard must
# quarantine + re-factor the mesh smaller, the sync must complete with
# zero corrupt verdicts surfaced, and the re-probe must grow it back —
# byte-identical per seed
echo "=== mesh-degrade quick sweep ===" >&2
python tools/sim_run.py --scenario mesh-degrade --seeds 0..4 --quick || rc=$?
# light-farm smoke: the scenario sweep pins determinism + the spec
# oracle; the bench A/B proves coalescing still beats N sequential
# clients (tiny config — the PERF.md datum is the N=32 run)
echo "=== light-farm quick sweep + farm A/B smoke ===" >&2
python tools/sim_run.py --scenario light-farm --seeds 0..4 --quick || rc=$?
# ingest front door: the flash-crowd sweep pins overload behavior
# (sheds, dup-filter hits, recheck-eviction release) byte-identical
# per seed; the bench A/B proves batched admission still amortizes the
# stub device round trip (tiny config — PERF.md has the full datum)
echo "=== flash-crowd quick sweep + ingest A/B smoke ===" >&2
python tools/sim_run.py --scenario flash-crowd --seeds 0..4 --quick || rc=$?
python tools/bench_ingest.py --clients 64 --rounds 2 --json || rc=$?
# aggsig: the bls-valset sweep pins the aggregate-commit engine run
# byte-identical per seed WITH sync-vs-aggregate verdict equivalence
# (clean / tampered / forged-bitmap / undercount); the bench smoke
# proves the O(1)-pairings-per-commit A/B still emits (tiny config —
# the PERF.md datum is the 200-validator run)
echo "=== bls-valset quick sweep + aggsig A/B smoke ===" >&2
python tools/sim_run.py --scenario bls-valset --seeds 0..2 --quick || rc=$?
BENCH_AGG_VALS=20 BENCH_AGG_BLOCKS=2 BENCH_AGG_SAMPLE=2 \
    python bench.py --aggsig || rc=$?
# sealsync: the seal-adoption sweep pins aggregate-seal catch-up byte-
# identical per seed — forged seal AND forged bitmap reject at the
# pivot pairing, adoption completes via the honest peer across an
# epoch boundary, and backfill re-pairs nothing (every adopted commit
# a SigCache hit); the bench smoke proves the seal-vs-blocksync A/B
# still emits (tiny config — the PERF.md datum is the 200-validator
# run)
echo "=== seal-adoption quick sweep + sealsync A/B smoke ===" >&2
python tools/sim_run.py --scenario seal-adoption --seeds 0..4 --quick || rc=$?
BENCH_SEAL_VALS=16 BENCH_SEAL_BLOCKS=6 \
    python bench.py --sealsync || rc=$?
# miller kernel smoke: the real fused Miller + final-exp scan against
# host math plus the canary-gated PairingChecker arc (slow-marked: one
# bucket-4 scan compile; suite 1/2's unfiltered run covers it too, but
# this keeps the kernel pinned when the caller filtered with -m)
echo "=== fused miller kernel smoke (slow; one scan compile) ===" >&2
python -m pytest tests/test_aggsig.py -q -m slow -k miller || rc=$?
# flight recorder (trace/): the viewer's invariant selftest (export /
# causal-chain / chrome conversion), then a trace-determinism sweep —
# the traced scenarios must emit byte-identical span streams per seed
# (docs/TRACE.md; the full contract suite is tests/test_trace.py in
# suite 2/2, these two re-pin the acceptance surface cheaply)
echo "=== trace_view selftest + trace-determinism sweep ===" >&2
python tools/trace_view.py --selftest || rc=$?
python -m pytest tests/test_trace.py -q \
    -k "deterministic or byte_identical" || rc=$?
# crash-consistent storage: the crash matrix tears a FileDB batch at
# seeded byte offsets (boundary + interior) and crashes at every
# registered storage fail point, asserting replay recovers the exact
# pre-batch state (full sweep = every offset; docs/STORAGE.md); the
# torn-storage sweep pins the same property end-to-end through a live
# node's save_block + reboot + recovery doctor, byte-identical per seed
echo "=== crash matrix (quick) + torn-storage quick sweep ===" >&2
python tools/crash_matrix.py --quick || rc=$?
python tools/sim_run.py --scenario torn-storage --seeds 0..4 --quick || rc=$?
# suite 2/2 already covers the slow-marked pipeline soak on a default
# (unfiltered) run; this explicit step guarantees the depth sweep even
# when the caller filtered the main suites (e.g. -m 'not slow'), so no
# extra ARGS are forwarded here.
echo "=== pipeline depth-sweep soak (K in {1,2,4,8}) ===" >&2
python -m pytest tests/test_pipeline.py -q -m slow || rc=$?
exit $rc
