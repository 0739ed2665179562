"""Median ``handler`` span of ``<Sample>`` in the window, in
milliseconds: a whole flush, the copy of the rows a segment's steps
left on the device (its child ``sample.d2h``) and the formatted write
of the block (``output.sample``).  Layer: entry."""

import statistics

from benchmark import trace


def read(events, device_trace, cell):
    durs = [e["dur_s"] for e in trace.spans_in_window(
        events, "handler", cell["window"])
        if e.get("handler") == "cbSample"]
    return 1e3 * statistics.median(durs) if durs else None
