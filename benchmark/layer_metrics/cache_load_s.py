"""Seconds the persistent cache took to hand out programs before the
window: the sum of ``dur_s`` over the ``compile`` events of stage
``cache_load``, 0 where every program was compiled.  It is the part of
``compile_s`` that is loading.  Nothing to read from a program that
emits no ``compile`` events.  Layer: compile cache."""

from benchmark import phases


def read(events, device_trace, cell):
    bounds = phases.window_bounds(events, cell["window"])
    if bounds is None or not phases.compile_events(events):
        return None
    return sum(e["dur_s"] for e in phases.compile_events(
        events, ("cache_load",)) if e["ts"] < bounds[0])
