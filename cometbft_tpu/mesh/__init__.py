"""mesh/ — multi-chip sharded verification as the production path.

ROADMAP item 1 landed: the 8-device `{'commit': 4, 'sig': 2}` RLC+
tally dry-run (MULTICHIP_r05.json, `parallel/{mesh,verify}.py`)
promoted from demo to the serving data plane. Pieces:

  topology.py      device discovery + (commit, sig) factoring over
                   `parallel.mesh.factor_mesh_shape`; degraded
                   sub-mesh re-factoring when shards are masked out;
                   the single-chip (1, 1) degenerate case rides the
                   same code path
  planner.py       pad-and-mask onto ledger-warm shape buckets (mesh
                   compiles — 2m22s in the r05 dry-run — are planned
                   and recorded in libs/jax_cache.CompileLedger under
                   (kernel@CxS, bucket, platform) keys, never taken
                   cold on the hot path); per-shard canary/pad rows;
                   the exact int64 power-plane grid tally
  executor.py      non-blocking mesh dispatch behind the
                   submit()/future seam the pipeline scheduler keeps
                   K tiles in flight through — per shard
  shard_health.py  per-shard canary quarantine extending the PR-3
                   supervisor: a sick chip masks its SHARD and the
                   mesh re-factors smaller instead of benching the
                   node; probed regrow restores it

Wired in: `device/server.py --mesh` serves the mesh with per-shard
result attribution in the protocol; `pipeline/scheduler.py` sizes its
bounded queue from the backend's shard count; node boot reads the
`[device] mesh*` config section (config.DeviceConfig). docs/MESH.md
is the operator story.
"""

from __future__ import annotations

import threading
from typing import Optional

from .executor import (CPU_SHARD, JaxMeshBackend, MeshExecutor,
                       MeshFuture, MeshOverloaded)
from .planner import (GridPlan, LanePlan, grid_kernel_name,
                      lanes_kernel_name, plan_grid, plan_lanes,
                      width_ladder)
from .shard_health import ShardSupervisor
from .topology import MeshShapeError, MeshTopology, MeshView

__all__ = [
    "CPU_SHARD", "GridPlan", "JaxMeshBackend", "LanePlan",
    "MeshExecutor", "MeshFuture", "MeshOverloaded", "MeshShapeError",
    "MeshTopology", "MeshView", "ShardSupervisor", "grid_kernel_name",
    "lanes_kernel_name", "plan_grid", "plan_lanes", "width_ladder",
    "shared_executor", "configure", "mesh_enabled",
    "reset_shared_executor",
]


_shared: Optional[MeshExecutor] = None
_shared_cfg = None
_shared_lock = threading.Lock()


def configure(device_config) -> None:
    """Latch the `[device]` config section for this process (node
    boot; first caller wins, matching device/health.configure)."""
    global _shared_cfg
    with _shared_lock:
        if _shared_cfg is None:
            _shared_cfg = device_config


def mesh_enabled() -> bool:
    """True when the node opted into mesh serving ([device] mesh) AND
    this process owns more than one TPU device."""
    from ..libs.jax_cache import is_device_platform
    with _shared_lock:
        cfg = _shared_cfg
    if cfg is None or not getattr(cfg, "mesh", False):
        return False
    if not is_device_platform():
        return False
    import jax
    return jax.device_count() > 1


# widest blocksync tile the node-boot warm plans for: tile_size 16 x
# a 256-validator set. Wider valsets still work — they just pay one
# recorded compile for the next bucket up on first contact.
WARM_MAX_LANES = 4096


def shared_executor(metrics=None, log=None) -> Optional[MeshExecutor]:
    """The per-process MeshExecutor (None unless mesh_enabled()).
    Shared for the same reason as the device supervisor: every intake
    path must see one shard mask, one topology, one quarantine
    decision.

    The first builder WARMS the planned bucket ladder before the
    executor is handed out (mesh compiles are minutes — a cold one on
    the first live tile would trip the pipeline watchdog mid-compile
    and strand the sync on CPU). Callers run on the blocksync boot
    thread, so consensus boot is not blocked. A warm failure closes
    the executor and disables the mesh for the process (the caller
    falls back to the single-chip path)."""
    global _shared
    if not mesh_enabled():
        return None
    with _shared_lock:
        if _shared is not None:
            return _shared
        cfg = _shared_cfg
    # build + warm OUTSIDE the lock: the warm ladder compiles for
    # minutes, and holding _shared_lock across it would block every
    # configure()/mesh_enabled() caller (another node booting in this
    # process) for the duration. Publish under the lock; a concurrent
    # builder's loser closes its executor.
    topology = MeshTopology(
        n_devices=getattr(cfg, "mesh_devices", 0) or None,
        sig_parallel=getattr(cfg, "mesh_sig_parallel", 0) or None)
    # the [device] mesh_backoff_* knobs configure the per-shard regrow
    # schedule (ms in config, seconds in the supervisor — same split
    # as the node-level probe_backoff_* knobs)
    supervisor = ShardSupervisor(
        topology,
        backoff_base_s=getattr(cfg, "mesh_backoff_base_ms",
                               1000) / 1000.0,
        backoff_cap_s=getattr(cfg, "mesh_backoff_cap_ms",
                              60_000) / 1000.0,
        metrics=metrics, log=log)
    ex = MeshExecutor(
        topology, supervisor=supervisor,
        canary=getattr(cfg, "canary", True),
        tiles_per_shard=getattr(cfg, "mesh_tiles_per_shard", 4),
        metrics=metrics, log=log)
    try:
        ex.warm(width_ladder(WARM_MAX_LANES, topology.view().n_shards,
                             getattr(cfg, "canary", True)))
    except Exception:  # noqa: BLE001 — a backend that cannot warm
        # cannot serve; disable the mesh for the process
        ex.close()
        return None
    with _shared_lock:
        if _shared is None:
            _shared = ex
        else:
            ex.close()
        return _shared


def reset_shared_executor() -> None:
    """Drop the shared instance and configuration (tests)."""
    global _shared, _shared_cfg
    with _shared_lock:
        if _shared is not None:
            _shared.close()
        _shared = None
        _shared_cfg = None
