"""Median ``output.log`` span of the window, in milliseconds: the row's
device-to-host copies (its child ``output.log.fetch``) and the CSV row
(``output.log.write``).  Layer: entry."""

import statistics

from benchmark import trace


def read(events, device_trace, cell):
    durs = [e["dur_s"] for e in trace.spans_in_window(
        events, "output.log", cell["window"])]
    return 1e3 * statistics.median(durs) if durs else None
