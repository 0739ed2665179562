"""Seconds of the ``engine.build`` spans before the window: the host's
planning of the engine chain and the builder thunks of the selected
engine and of the tail (``Lattice._fast_path``), a child of the first
``iterate``.  Layer: dispatch."""

from benchmark import phases, trace


def read(events, device_trace, cell):
    bounds = phases.window_bounds(events, cell["window"])
    built = [e for e in trace.spans(events, "engine.build")
             if bounds is None or e["ts"] <= bounds[0]]
    return sum(e["dur_s"] for e in built) if built else None
