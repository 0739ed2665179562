"""Seconds the backend spent compiling before the window opened: the sum
of ``dur_s`` over the ``compile`` events of stage ``backend_compile``
that end before the window's first ``iterate`` span starts.  On jax 0.9.0
that duration wraps the lookup in the persistent cache
(``pxla._cached_compilation`` times ``compiler.compile_or_get_cached``),
so a program loaded from the cache counts once, with its load time; the
``cache_load`` events beside it say how much of the sum was loading.
Layer: compile cache."""

from benchmark import phases


def read(events, device_trace, cell):
    compiles = phases.compile_events(events, ("backend_compile",))
    bounds = phases.window_bounds(events, cell["window"])
    if not compiles or bounds is None:
        return None
    return sum(e["dur_s"] for e in compiles if e["ts"] < bounds[0])
