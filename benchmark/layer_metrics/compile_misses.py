"""``compile`` events of stage ``backend_compile`` with ``cache`` ==
``miss`` before the window: programs the persistent cache was asked for
and did not have, so the backend compiled them.  JAX keeps only what
took a second or more to compile, so a small program misses in every
run; a large one that misses in a warm run is the suspect, by its
``program``.  A program whose ``compile`` events carry no verdict
(before PR 51) reads nothing.  Layer: compile cache."""

from benchmark import phases


def read(events, device_trace, cell):
    compiles = [e for e in phases.compile_events(events, ("backend_compile",))
                if "cache" in e]
    bounds = phases.window_bounds(events, cell["window"])
    if not compiles or bounds is None:
        return None
    return float(sum(e["cache"] == "miss" and e["ts"] < bounds[0]
                     for e in compiles))
