"""Device time of every operation that is neither a kernel nor a
collective (the trailing XLA globals step of each ``iterate``, copies,
quantity evaluation for the handlers) over the device's busy time, in
percent.  Layer: XLA step."""

from benchmark import trace


def read(events, device_trace, cell):
    t = trace.by_class(device_trace)
    busy = t["kernel"] + t["collective"] + t["other"]
    return 100.0 * t["other"] / busy if busy > 0 else None
