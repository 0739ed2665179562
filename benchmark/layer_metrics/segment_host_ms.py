"""Median over the window's ``segment`` spans (one pass of ``<Solve>``'s
loop each, the root of everything the pass does) of ``dur_s`` less the
``wait_s`` of the span and all its descendants, in milliseconds: the time
of a segment in which the host was not waiting in a fence, so the
device, fenced, had nothing to run.  A program without the span (before
PR 37) reads nothing.  Layer: entry."""

import statistics

from benchmark import trace


def read(events, device_trace, cell):
    children: dict = {}
    for e in trace.spans(events):
        children.setdefault(e.get("parent"), []).append(e)
    host = []
    for seg in trace.spans_in_window(events, "segment", cell["window"]):
        waited, todo = 0.0, [seg]
        while todo:
            e = todo.pop()
            todo += children.get(e["id"], [])
            waited += e.get("wait_s", 0.0)
        host.append(seg["dur_s"] - waited)
    return 1e3 * statistics.median(host) if host else None
