"""Share of the window's wall time spent in the case's periodic handlers
(Log, Failcheck, VTK ...; their ``output.*`` spans lie inside).  Layer:
entry.  The benchmark's own clock handler is left out of both sides."""

from benchmark import trace


def read(events, device_trace, cell):
    w = cell["window"]
    spent = [e["dur_s"] for e in trace.spans_in_window(events, "handler", w)
             if e.get("handler") != "acCallPython"]
    wall = w["wall_s"] - w["overhead_s"]
    if not spent or wall <= 0:
        return None
    return 100.0 * sum(spent) / wall
