"""Bytes of per-iteration planes the fused engine assembles and its
kernel reads a step because of a ``<Control>`` series: the median
``series_bytes_per_step`` of the window's ``iterate.fused`` spans
(``core/lattice.py:Lattice._run_engine`` puts it there from the engine's
account: 0 where the series' values reach the kernel as scalars, the
tuned 2D band; ``2 * len(zonal)`` planes of the lattice on the generic
band's series loop).  None where no span says it (a program from before
the field, a case without a series).  Layer: kernels."""

import statistics

from benchmark import phases


def read(events, device_trace, cell):
    said = [e["series_bytes_per_step"] for e in
            phases.iterate_spans_in_window(events, "iterate.fused",
                                           cell["window"])
            if "series_bytes_per_step" in e]
    return statistics.median(said) if said else None
