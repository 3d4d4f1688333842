"""Mesh topology: device discovery, (commit, sig) factoring, and the
degraded-sub-mesh re-factoring that keeps the node serving when chips
fall out.

The factoring itself is `parallel/mesh.factor_mesh_shape` — one rule
decides every shape (8 -> (4,2), 6 -> (3,2), 4 -> (2,2), 1 -> (1,1)),
and the single-chip (1, 1) degenerate case rides the same code path as
the full mesh, so there is no separate "mesh mode" to diverge from the
single-chip one (the fixed-topology-engine stance of arXiv 2112.02229:
the verifier keeps one shape contract; degradation changes WHICH
engine shape is built, never how it is fed).

Shards are identified by their position in the DISCOVERED device list
(shard id == device index at construction), so a shard keeps its
identity across mask/unmask cycles: masking shard 3 out of 8 leaves
shards {0,1,2,4,5,6,7} serving on a 7-device sub-mesh, and a later
regrow restores the original 8-device factoring. Every mask/unmask
bumps a generation counter; executors cache compiled verifiers per
(generation, bucket) snapshot and re-plan when the topology moved.

Device objects are injectable (`devices=` — ints, strings, anything)
so all the factoring/degrade/regrow logic is host-testable without a
backend; only `MeshView.jax_mesh()` touches jax.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

from ..parallel.mesh import MeshShapeError, factor_mesh_shape

__all__ = ["MeshShapeError", "MeshTopology", "MeshView",
           "discover_devices"]


def discover_devices(n_devices: Optional[int] = None) -> list:
    """The local jax device list (optionally truncated). The only jax
    touch in this module's construction path: callers that inject
    `devices=` (simnet, tests) build a topology without a backend."""
    import jax
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return list(devs)


@dataclass(frozen=True)
class MeshView:
    """Immutable snapshot of the serving topology: which shards are
    in the mesh, in which (commit, sig) factoring, at which
    generation. Executors plan and compile against a view, then check
    `topology.generation` before reusing cached state."""

    shard_ids: Tuple[int, ...]       # unmasked shard ids, ascending
    shape: Tuple[int, int]           # (commit_parallel, sig_parallel)
    generation: int
    devices: tuple = field(repr=False, default=())  # parallel to shard_ids

    @property
    def n_shards(self) -> int:
        return len(self.shard_ids)

    def jax_mesh(self):
        """The jax.sharding.Mesh over this view's devices (the only
        jax-touching member; host-only tests never call it)."""
        from ..parallel.mesh import make_mesh
        return make_mesh(sig_parallel=self.shape[1],
                         devices=list(self.devices))


class MeshTopology:
    """Owns the shard mask and the current factoring.

    mask()/unmask() re-factor immediately: a masked shard shrinks the
    mesh to the largest factorable shape over the remaining devices
    (never benches the node — that is the whole point vs the PR-3
    node-level quarantine), and unmask() grows it back. Thread-safe:
    the executor's dispatch thread, the shard-health supervisor, and
    metrics readers all consult one instance."""

    # guarded-by: _lock: _masked, _view
    def __init__(self, devices: Optional[Sequence] = None,
                 n_devices: Optional[int] = None,
                 sig_parallel: Optional[int] = None):
        if devices is None:
            devices = discover_devices(n_devices)
        elif n_devices is not None:
            devices = list(devices)[:n_devices]
        self._devices: List = list(devices)
        if not self._devices:
            raise MeshShapeError("no devices to build a mesh from")
        # the CONFIGURED sig_parallel applies to the full mesh; degraded
        # factorings fall back to auto when it no longer divides (6
        # devices keep sig=2, but 7 must refactor to (7, 1) rather than
        # refuse to serve)
        self._sig_parallel = sig_parallel
        factor_mesh_shape(len(self._devices), sig_parallel)  # validate
        self._lock = threading.Lock()
        with self._lock:
            self._masked: set = set()
            self._generation = 0
            self._view: MeshView = self._refactor()

    # --- views ------------------------------------------------------------

    @property
    def n_devices(self) -> int:
        return len(self._devices)

    def device(self, shard_id: int):
        """The device object behind a shard id (masked or not) — the
        regrow probe targets exactly this chip."""
        return self._devices[shard_id]

    @property
    def generation(self) -> int:
        # lock-free single-int read (same stance as DeviceSupervisor's
        # state accessors): a stale generation only causes one harmless
        # re-plan on the next dispatch
        return self._generation

    def view(self) -> MeshView:
        with self._lock:
            return self._view

    def masked(self) -> Tuple[int, ...]:
        with self._lock:
            return tuple(sorted(self._masked))

    # --- mask / unmask (shard_health drives these) ------------------------

    def mask(self, shard_id: int) -> MeshView:
        """Remove one shard from the serving mesh and re-factor.
        Refuses to mask the LAST shard (MeshShapeError): a node with
        zero shards is the node-level supervisor's decision, not
        topology's — the caller keeps the old view and falls back to
        CPU for the batch at hand."""
        with self._lock:
            if not 0 <= shard_id < len(self._devices):
                raise MeshShapeError(f"no shard {shard_id} in a "
                                     f"{len(self._devices)}-device mesh")
            if len(self._masked) + 1 >= len(self._devices) \
                    and shard_id not in self._masked:
                raise MeshShapeError(
                    "cannot mask the last healthy shard; quarantine "
                    "the backend via device/health instead")
            self._masked.add(shard_id)
            self._view = self._refactor()
            return self._view

    def unmask(self, shard_id: int) -> MeshView:
        with self._lock:
            self._masked.discard(shard_id)
            self._view = self._refactor()
            return self._view

    def _refactor(self) -> MeshView:
        """Rebuild the view over the unmasked devices (caller holds
        the lock). The configured sig_parallel is kept while it still
        divides the healthy count; otherwise the auto rule decides —
        degradation must always produce a servable mesh."""
        ids = tuple(i for i in range(len(self._devices))
                    if i not in self._masked)
        n = len(ids)
        sig = self._sig_parallel
        if sig is not None and (sig <= 0 or n % sig):
            sig = None
        shape = factor_mesh_shape(n, sig)
        self._generation += 1
        return MeshView(
            shard_ids=ids, shape=shape, generation=self._generation,
            devices=tuple(self._devices[i] for i in ids))
