"""Seconds of the program's own import blocks before the window: the sum
of the ``startup.import`` spans (the package, ``__main__``'s subcommand
modules, ``control.solver``, the model).  The package's block runs
before ``main`` is entered, so it lies inside ``pre_entry_s`` too.
Layer: entry."""

from benchmark import phases, trace


def read(events, device_trace, cell):
    bounds = phases.window_bounds(events, cell["window"])
    blocks = [e for e in trace.spans(events, "startup.import")
              if bounds is None or e["ts"] <= bounds[0]]
    return sum(e["dur_s"] for e in blocks) if blocks else None
