"""``compile`` events of stage ``trace``, ``backend_compile`` or
``cache_load`` inside the window (``phases.window_bounds``): a warmed-up
run has none.  Nothing to read from a program that emits no ``compile``
events.  Layer: compile cache."""

from benchmark import phases

STAGES = ("trace", "backend_compile", "cache_load")


def read(events, device_trace, cell):
    bounds = phases.window_bounds(events, cell["window"])
    if bounds is None or not phases.compile_events(events):
        return None
    return float(sum(bounds[0] <= e["ts"] <= bounds[1]
                     for e in phases.compile_events(events, STAGES)))
