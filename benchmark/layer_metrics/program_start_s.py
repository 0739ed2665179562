"""Seconds from the devices to the last element before the first
``iterate``: the ``startup.devices``, ``startup.case`` and
``startup.element`` spans that end before the first ``iterate`` span
starts, the roots only (a ``Params`` under its ``Model`` is counted
once, in the ``Model``).  Layer: entry."""

from benchmark import phases, trace

NAMES = ("startup.devices", "startup.case", "startup.element")


def read(events, device_trace, cell):
    first = trace.spans(events, "iterate")[:1]
    mine = [e for e in trace.spans(events)
            if e["name"] in NAMES and e.get("parent") is None
            and (not first or e["ts"] <= phases.start_of(first[0]))]
    return sum(e["dur_s"] for e in mine) if mine else None
