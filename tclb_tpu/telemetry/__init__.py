"""Structured telemetry for engine-dispatch tracing and perf attribution.

Usage (a trace costs nothing unless asked for):

* ``TCLB_TELEMETRY=trace.jsonl python run.py`` — or
  ``telemetry.enable("trace.jsonl")`` — turns the process-wide JSONL
  sink on; everything below is a strict no-op otherwise;
* ``telemetry.event(kind, **fields)`` — one structured event line;
* ``with telemetry.span("iterate", nodes=n, iters=k) as sp: ...;
  sp.sync(out)`` — honest wall-time (``block_until_ready`` fencing, the
  seconds it blocked in ``wait_s``), derived MLUPS,
  ``jax.profiler.TraceAnnotation`` passthrough;
* ``telemetry.annotate(**fields)`` — add fields to the innermost open
  span from a callee that has none of its own;
* ``telemetry.counter(name)`` — monotonic counters, snapshotted
  periodically and flushed on close;
* ``telemetry.subscribe(fn)`` — fan the event stream out to extra sinks
  (the live metrics registry and the flight recorder in telemetry/live.py
  are subscribers; the monitor endpoint in telemetry/http.py serves
  their snapshots over ``/metrics`` + ``/status``);
* ``python -m tclb_tpu.telemetry report trace.jsonl [--format text|json]
  [--compare other.jsonl] [--job ID]`` — per-engine/per-span aggregation,
  trace diffing, and per-job timelines (see telemetry/report.py).
"""

from tclb_tpu.telemetry.events import (  # noqa: F401
    boot, boot_over, counter, counters, current_job, disable, enable,
    enabled, engine_fallback, engine_selected, event, failcheck,
    job_context, path, set_job, subscribe, unsubscribe)
from tclb_tpu.telemetry.spans import (  # noqa: F401
    NOOP_SPAN, Span, annotate, fuse_of, import_span, off_launch_thread,
    span)
