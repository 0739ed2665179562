"""Bytes one chip sends to its neighbours per lattice step: the sum of
``halo_bytes`` over the sum of ``iters`` of the window's ``iterate.fused``
spans (``parallel/halo.py`` reckons the bytes from the shapes and puts
them on the enclosing span).  The span's field, not the ``halo.bytes``
counter: the harness unsubscribes before the final ``counters`` document
is written.  Layer: sharding."""

from benchmark import phases


def read(events, device_trace, cell):
    fused = [e for e in phases.iterate_spans_in_window(
        events, "iterate.fused", cell["window"]) if "halo_bytes" in e]
    steps = sum(e["iters"] for e in fused)
    return sum(e["halo_bytes"] for e in fused) / steps if steps else None
