"""Device time of the Pallas kernels (``tpu_custom_call`` operations),
summed over the chips, per node update of the traced span, in
nanoseconds.  Layer: kernels."""

from benchmark import trace


def read(events, device_trace, cell):
    t = trace.by_class(device_trace)
    if not t["calls"]:
        return None
    return 1e9 * t["kernel"] / (cell["nodes"] * cell["traced_steps"])
