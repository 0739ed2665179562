"""Median ``output.vtk.encode`` span of the window, in milliseconds: the
packing of the arrays on the host, zlib where the case asks for it.
Layer: entry."""

import statistics

from benchmark import trace


def read(events, device_trace, cell):
    durs = [e["dur_s"] for e in trace.spans_in_window(
        events, "output.vtk.encode", cell["window"])]
    return 1e3 * statistics.median(durs) if durs else None
