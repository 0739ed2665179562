"""Device self time of the operations inside the ``iterate.fused``
annotations that are neither a kernel nor a collective (the wrappers XLA
puts round each kernel call: ghost-row refresh, pads, copies of the scan
carry) over all device time inside them, in percent.  Layer: XLA step."""

from benchmark import phases


def read(events, device_trace, cell):
    t = phases.totals(phases.device_seconds_in(device_trace,
                                               "iterate.fused"))
    total = sum(t.values())
    return 100.0 * t["other"] / total if total > 0 else None
