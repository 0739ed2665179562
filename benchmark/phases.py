"""Device time by the program's phase spans, and the window's place on
the wall clock.  Shared by the readers of the per-layer metrics that PR 26
added (``kernel_wrap_share``, ``globals_step_ms``, ``compile_s``,
``compiles_in_window``, ``halo_bytes_per_step``) and by
``phase_table.py``.

The program's spans (``tclb_tpu/telemetry/spans.py``) are profiler
annotations too, so they lie on the device trace's clock.  ``iterate``
fences before it opens; ``iterate.fused``, ``iterate.globals_step`` and
``quantity.eval`` fence before they close: an operation issued inside one
of their annotations starts and ends inside it.  The profiler's two
clocks agree to about a millisecond only, and not by the same amount in
every run (first chip runs of PR 26: in one run the first operation of a
fused call began 0.6 ms *before* its annotation), so an operation belongs
to the annotation that holds its *middle*: what starts a call's program,
an 18 ms gather in the karman cells, then stays where it was issued.
"""

from __future__ import annotations

import bisect

from benchmark import trace

CLASSES = ("kernel", "collective", "other")


def op_class(name: str) -> str:
    if trace.is_kernel(name):
        return "kernel"
    return "collective" if trace.is_collective(name) else "other"


def annotations(recording: trace.Recording, span_name: str) -> list:
    """``(start, end)`` of the host annotations called ``span_name`` that
    start inside the traced span, in order."""
    lo, hi = trace.traced_span(recording)
    return [(s, s + d) for name, s, d in recording.host
            if name == span_name and lo <= s < hi]


def device_seconds_in(recording: trace.Recording, span_name: str) -> list:
    """One entry per annotation called ``span_name`` inside the traced
    span: ``start``, ``end``, the self seconds (:func:`trace.self_times`)
    of the ``kernel``, ``collective`` and ``other`` operations whose
    middle lies inside it, summed over the chips, and ``busy``, the union
    of those operations' intervals, averaged over the chips."""
    spans = annotations(recording, span_name)
    out = [dict(start=a, end=b, busy=0.0, **dict.fromkeys(CLASSES, 0.0))
           for a, b in spans]
    if not out:
        return out
    starts = [a for a, _ in spans]
    lo, hi = trace.traced_span(recording)
    for evs in recording.devices.values():
        # self_times sorts by (start, longest first): sorted here, its
        # entries come back in this order
        evs = sorted(trace.clip(evs, lo, hi), key=lambda e: (e[1], -e[2]))
        inside: list[list] = [[] for _ in out]
        for ev, (name, _, own) in zip(evs, trace.self_times(evs)):
            middle = ev[1] + 0.5 * ev[2]
            i = bisect.bisect_right(starts, middle) - 1
            if i >= 0 and middle < spans[i][1]:
                out[i][op_class(name)] += own
                inside[i].append(ev)
        for entry, mine in zip(out, inside):
            entry["busy"] += (trace.union_seconds(mine)
                              / len(recording.devices))
    return out


def totals(entries: list) -> dict:
    """The classes of :func:`device_seconds_in` summed over its entries."""
    return {c: sum(e[c] for e in entries) for c in CLASSES}


def start_of(event: dict) -> float:
    """A span event's start on the wall clock; an event from a program
    older than PR 26 has no ``t0``."""
    return event["t0"] if "t0" in event else event["ts"] - event["dur_s"]


def iterate_spans_in_window(events, name: str, window: dict) -> list[dict]:
    """The ``iterate`` spans, or their children called ``name``, of the
    window.  They carry the iteration the segment *started* from, where
    handler spans carry the one it ended at (``trace.spans_in_window``)."""
    return [e for e in trace.spans(events, name)
            if window["first_iteration"] <= e.get("iteration", -1)
            < window["last_iteration"]]


def window_bounds(events, window: dict):
    """Wall-clock ``(start, end)`` of the window: the ``t0`` of its first
    ``iterate`` span and the ``ts`` of its last ``iterate`` or ``handler``
    span (the last segment's handlers run after its ``iterate``).  None
    where the events hold no ``iterate`` span of the window."""
    its = iterate_spans_in_window(events, "iterate", window)
    if not its:
        return None
    last = its + trace.spans_in_window(events, "handler", window)
    return min(start_of(e) for e in its), max(e["ts"] for e in last)


def compile_events(events, stages=None) -> list[dict]:
    """The ``compile`` events of ``stages`` (of any stage by default)."""
    return [e for e in events if e.get("kind") == "compile"
            and (stages is None or e.get("stage") in stages)]
