"""Median over the window's ``iterate`` spans of the sum of ``dispatch_s``
of their ``iterate.fused`` and ``iterate.globals_step`` children, in
milliseconds: what launching a segment's programs costs the host, from
entering each span until its jitted call returned, before the fence.  A
program whose spans do not say it (before PR 37) reads nothing.  Layer:
dispatch."""

import statistics

from benchmark import phases, trace

LAUNCHES = ("iterate.fused", "iterate.globals_step")


def read(events, device_trace, cell):
    cost: dict = {}
    for e in trace.spans(events):
        if e.get("name") in LAUNCHES and "dispatch_s" in e:
            cost[e["parent"]] = cost.get(e["parent"], 0.0) + e["dispatch_s"]
    mine = [cost[e["id"]] for e in phases.iterate_spans_in_window(
        events, "iterate", cell["window"]) if e.get("id") in cost]
    return 1e3 * statistics.median(mine) if mine else None
