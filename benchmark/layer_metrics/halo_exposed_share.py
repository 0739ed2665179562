"""Time on device 0 in which a collective runs and no other operation
does, over the traced span, in percent.  Layer: sharding.  Nothing to
read on one chip."""

from benchmark import trace


def read(events, device_trace, cell):
    if cell["chips"] < 2:
        return None
    lo, hi = trace.traced_span(device_trace)
    return 100.0 * trace.exposed_collective_seconds(device_trace) / (hi - lo)
