"""1 - (union of the device's operation intervals over the traced span),
averaged over the chips, in percent.  Layer: device."""

from benchmark import trace


def read(events, device_trace, cell):
    busy, span = trace.busy_seconds(device_trace)
    return 100.0 * (1.0 - busy / span)
