"""The first ``iterate`` span: compile, or load from the persistent
cache, plus the first segment's steps.  Layer: compile cache."""

from benchmark import trace


def read(events, device_trace, cell):
    first = trace.spans(events, "iterate")[:1]
    return first[0]["dur_s"] if first else None
