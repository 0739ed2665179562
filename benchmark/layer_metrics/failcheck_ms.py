"""Median ``handler`` span of ``<Failcheck>`` in the window, in
milliseconds: every quantity evaluated on the device, brought to the
host and scanned there.  Layer: entry."""

import statistics

from benchmark import trace


def read(events, device_trace, cell):
    durs = [e["dur_s"] for e in trace.spans_in_window(
        events, "handler", cell["window"])
        if e.get("handler") == "cbFailcheck"]
    return 1e3 * statistics.median(durs) if durs else None
