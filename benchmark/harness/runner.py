"""One run of one cell: set-up, the measured window, the comparison that
decides `correct`, the per-layer readers, the result line."""

from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from . import device as devmod
from . import xplane
from .childgen import ChildGenerator, generate_here
from .manifest import Manifest
from .tracing import TRACE_WINDOW_S, profiled

TRACE_DIR = os.path.join("benchmark_out", "trace")


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclass
class LayerContext:
    """What a per-layer reader may read: the window's counters and
    facts, the program's spans, the reduced device trace, the peaks of
    this device and the roofline modules by name."""
    cell: object
    device: dict
    boot: dict
    result: dict
    spans: list = field(default_factory=list)
    trace: object = None            # xplane.TraceSummary or None
    manifest: object = None

    def peaks(self) -> dict:
        return self.manifest.peaks(self.device["kind"])

    def roofline(self, kernel: str):
        return self.manifest.load_module("rooflines", kernel)


def run_cell(repo_root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, look_for_chip: bool = True,
             in_process_traffic: bool = False, plant: str = "") -> dict:
    """Run one cell once and return the result object of the last line.
    `look_for_chip=False` and `in_process_traffic=True` exist for the
    tests, which drive everything after the look for a chip on a CPU at
    a tiny size; `plant` names one of the driver's `PLANTS`, a fault put
    under the timed path for a control run (tools/control_runs.py). The
    command never passes them."""
    manifest = Manifest(repo_root)
    cell = manifest.cell(workload)
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)      # tracing.py says why
    if look_for_chip:
        dev = devmod.require_tpu(cell.chips)
    else:
        dev = devmod.cpu_device_info()
    log(f"[device] {dev} workload={workload} seed={seed} "
        f"seconds={seconds} trace={int(trace)}")

    params = {"seed": seed, "seconds": seconds, "config": cell.config,
              "traffic": cell.traffic}
    generator = cell.traffic["generator"]
    child = None
    if not in_process_traffic:
        child = ChildGenerator(manifest, generator, params)
    try:
        driver = manifest.load_module("drivers", cell.config["driver"])
        boot = driver.warm()
        log(f"[setup] node bucket {boot['batch']} lanes, prewarm "
            f"{boot['prewarm_s']:.1f}s at {time.perf_counter() - t_start:.1f}s")
        t = time.perf_counter()
        payload = (generate_here(manifest, generator, params)
                   if child is None else child.result())
        log(f"[setup] traffic from generator {generator!r} ready, waited "
            f"{time.perf_counter() - t:.1f}s")
    finally:
        if child is not None:
            child.close()
    undo = driver.PLANTS[plant]() if plant else None
    try:
        return _measure(manifest, cell, dev, driver, boot, payload, seed,
                        seconds, trace, t_start)
    finally:
        if undo is not None:
            undo()


def _measure(manifest, cell, dev, driver, boot, payload, seed, seconds,
             trace, t_start) -> dict:
    workload, repo_root = cell.name, manifest.repo_root
    session = driver.build(cell.config, cell.traffic, payload, boot, seed)
    # the window's whole traffic sits in memory as hundreds of thousands
    # of objects that no node would hold: keep them out of the garbage
    # collector's scans, which would otherwise pause the program inside
    # the window for the harness's sake
    gc.collect()
    gc.freeze()

    spans, summary, trace_dir = [], None, None
    stack = contextlib.ExitStack()
    if trace:
        from cometbft_tpu import trace as program_trace
        program_trace.enable(seed=0, ring=1 << 17)
        stack.callback(program_trace.disable)
        if dev["platform"] == "tpu":
            trace_dir = os.path.join(repo_root, TRACE_DIR, workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
            stack.enter_context(profiled(trace_dir))
    counter = devmod.CompileCounter()
    counter.start()
    setup_s = time.perf_counter() - t_start
    with stack:
        try:
            result = driver.window(session, seconds)
        finally:
            counter.stop()
        if trace:
            spans = program_trace.shared_recorder().snapshot()
    if spans:
        totals: dict = {}
        for sp in spans:
            slot = totals.setdefault(sp["name"], [0.0, 0])
            slot[0] += (sp["t1"] - sp["t0"]) / 1e9
            slot[1] += 1
        log(f"[spans] program spans of the window, name: [seconds, count] "
            f"{json.dumps(totals)}")
    peak = devmod.memory_peak_bytes(cell.chips)
    log(f"[window] {json.dumps(result['facts'])}")
    log(f"[window] counters {json.dumps(result['counters'])} "
        f"traces_in_window={counter.traces} "
        f"compiles_in_window={counter.compiles}")

    checks = driver.judge(session, result, counter.traces + counter.compiles)
    correct = all(value <= limit for _n, value, limit in checks)

    if trace_dir is not None:
        t = time.perf_counter()
        summary = xplane.reduce_file(xplane.find_xplane(trace_dir))
        log(f"[trace] reduced in {time.perf_counter() - t:.1f}s: traced "
            f"{summary.window_s:.3f}s busy {summary.busy_s:.3f}s "
            f"programs {json.dumps(summary.programs)}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    values = dict(result["end_to_end"])
    values["setup_s"] = setup_s
    metrics = {}
    if not trace:
        for m in manifest.end_to_end_for(workload):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx = LayerContext(cell=cell, device=dev, boot=boot, result=result,
                           spans=spans, trace=summary, manifest=manifest)
        for m in manifest.per_layer_for(workload):
            value = manifest.layer_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"[end-to-end in this traced run, not reported] "
            f"{json.dumps(values)}")

    device = dict(dev, memory_peak_bytes=peak)
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


def print_result(out: dict) -> None:
    """Each number compared beside its limit as the last lines on
    standard error; the result as the last line on standard output."""
    sys.stdout.flush()
    for name, row in out["checks"].items():
        flag = "" if row["value"] <= row["limit"] else "   <-- over"
        print(f"[check] {name} = {row['value']} (limit {row['limit']})"
              f"{flag}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
