"""Seconds between the entry of the program's ``main`` (the ``boot``
event's ``t_main``) and the window's opening that no span of the
program covers on the main thread: that time less the union of the
intervals ``[t0, t0 + dur_s]`` of the spans without ``parent``.  A span
event carries no thread, so the roots of other threads are left out by
name (``OTHER_THREADS``).  A program without the ``boot`` event (before
PR 51) reads nothing.  Layer: entry."""

from benchmark import phases, trace

#: spans that are the root of another thread's tree
OTHER_THREADS = ("output.vtk.write",)


def read(events, device_trace, cell):
    boot = next((e for e in events if e.get("kind") == "boot"), None)
    bounds = phases.window_bounds(events, cell["window"])
    if boot is None or bounds is None:
        return None
    lo, hi = boot["t_main"], bounds[0]
    roots = [[e["name"], phases.start_of(e), e["dur_s"]]
             for e in trace.spans(events)
             if e.get("parent") is None and e["name"] not in OTHER_THREADS]
    return (hi - lo) - trace.union_seconds(trace.clip(roots, lo, hi))
