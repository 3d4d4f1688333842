"""The profiler around a traced run's window, and why that window is
short.

A 15 s window of small programs is millions of device events (3,172 per
execution of the RLC verify program), and the profiler needs about 30 µs
to collect and write each one when the window is over: 148 s for the
4.2 M events of a 15 s `hub-live-150.cold-commit` window (PR 25, call 3),
which with the set-up is more than a run may last. Stopping the profiler
from a second thread after 5 s while the window ran on was worse, 130 µs
an event (call 4). So a traced run measures a window of its own of at
most `TRACE_WINDOW_S` seconds — traffic generated for that length, traced
whole — and its per-layer numbers are of that window: in a catch-up the
pipeline's ramp and drain are a larger share of it than of the
end-to-end runs' window."""

from __future__ import annotations

import contextlib

from . import xplane

TRACE_WINDOW_S = 5.0


@contextlib.contextmanager
def profiled(trace_dir: str):
    """Profile what runs inside, bounded by the annotation the reduction
    takes for the window (`xplane.WINDOW`)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # no per-Python-call events
    options.host_tracer_level = 2       # TraceAnnotations stay
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()
