"""Median over the traced ``iterate.globals_step`` annotations (the one
XLA step after each fused call, which produces the globals) of the time
in which the device runs an operation started inside it, averaged over
the chips, in milliseconds.  Layer: XLA step."""

import statistics

from benchmark import phases


def read(events, device_trace, cell):
    steps = phases.device_seconds_in(device_trace, "iterate.globals_step")
    if not steps:
        return None
    return 1e3 * statistics.median(e["busy"] for e in steps)
