"""Seconds of the ``engine.probe`` spans before the window: the first
call of an engine that has to be probed (``Lattice._probe_first_call``:
a copy of the state, the compile or cache load, the steps, and every
rung of the fallback ladder it walked), fenced on the state.  A program
without the span (before PR 28), or an engine that is not probed, reads
nothing.  Layer: dispatch."""

from benchmark import phases, trace


def read(events, device_trace, cell):
    probes = trace.spans(events, "engine.probe")
    bounds = phases.window_bounds(events, cell["window"])
    if bounds is not None:
        probes = [e for e in probes if e["ts"] <= bounds[0]]
    return sum(e["dur_s"] for e in probes) if probes else None
