"""What the harness asks of JAX itself: which device this is, how much
memory the window peaked at, and how many programs were traced or
compiled inside the window."""

from __future__ import annotations

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; raises where there is no TPU or
    fewer chips than the cell asks for. Initialises the backend: from
    here on this process holds the chip."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{info['platform']!r}): nothing to measure")
    if info["count"] < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{info['count']}")
    info["count"] = chips
    return info


def cpu_device_info() -> dict:
    """The device line for a run that was told to skip the look for a
    chip (tests only; never printed under a device metric's name)."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts jaxpr traces and backend compiles between start() and
    stop(): inside the measured window both must be 0 (a trace comes
    before every compile, also one that the persistent cache serves)."""

    def __init__(self):
        self.traces = 0
        self.compiles = 0
        self._on = False

    def _listen(self, event, _secs, **_kw):
        if not self._on:
            return
        if event == TRACE_EVENT:
            self.traces += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1

    def start(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True

    def stop(self):
        self._on = False
        from jax._src import monitoring
        try:
            monitoring.unregister_event_duration_listener(self._listen)
        except (AttributeError, ValueError, AssertionError):
            pass  # the listener stays, switched off
