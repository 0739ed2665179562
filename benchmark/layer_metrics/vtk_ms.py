"""Median ``output.vtk`` span of the window, in milliseconds: quantities
to the host, compression, the file.  Layer: entry."""

import statistics

from benchmark import trace


def read(events, device_trace, cell):
    durs = [e["dur_s"] for e in trace.spans_in_window(
        events, "output.vtk", cell["window"])]
    return 1e3 * statistics.median(durs) if durs else None
