"""Structured telemetry: a process-wide fan-out of typed events.

The reference ships real observability — the per-iteration globals CSV
(``cbLog``), NaN failchecks (``cbFailcheck``) and in-situ Catalyst
monitoring — but all of it is human-facing output.  This module is the
machine-facing counterpart: one stream of typed events
(``{"kind": ..., "ts": ...}`` per record) fanned out to pluggable sinks.
The original append-only JSONL file sink (``TCLB_TELEMETRY`` /
:func:`enable`) is one subscriber; the live metrics registry and the
flight recorder (:mod:`tclb_tpu.telemetry.live`) are others.

Design constraints:

* **no-op when disabled** — every entry point starts with an ``enabled()``
  check (a single boolean test); nothing is imported, opened, synced or
  allocated while no sink is subscribed, so instrumented hot seams cost
  nothing in production runs that don't ask for a trace or a monitor;
* **process-wide** — one fan-out shared by every Lattice/Solver in the
  process; the JSONL sink is selected via the ``TCLB_TELEMETRY``
  environment variable at import or :func:`enable` at runtime (the
  reference's equivalent switch is its compile-time logging level);
* **append-only JSONL** — one self-describing JSON object per line, so a
  crashed run still yields a readable (truncated) trace and two traces
  diff line-wise.  The file is block-buffered: it is flushed by every
  event emitted while no span is open on its thread (a thread's
  outermost span closing, a ``<Solve>`` segment, is one), by each
  ``counters`` snapshot and on :func:`disable`, so the spans inside a
  segment cost no write each and a killed run keeps whole lines up to
  its last segment;
* **counters survive abnormal exits** — cumulative ``counters`` snapshots
  are emitted every ``COUNTER_SNAPSHOT_S`` seconds (piggybacked on event
  traffic), so a SIGKILLed run's trace still carries counter totals; the
  final flush on :func:`disable` remains authoritative;
* **one process's boot is kept** — what happens before any sink can
  exist (the package's ``startup.import`` span, the ``boot`` record of a
  ``main`` entered with telemetry off) waits in a fixed, small backlog
  and is handed out once: to the subscribers there when ``main`` is
  entered, else to the first one that arrives while it runs
  (:func:`boot`).  Nothing else is recorded while no sink is subscribed.
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Iterator, Optional, TextIO
from contextlib import contextmanager

SCHEMA_VERSION = 1

#: cadence of cumulative ``counters`` snapshots (seconds); snapshots ride
#: on event traffic, so an idle process emits none
COUNTER_SNAPSHOT_S = 5.0

#: arrays larger than this are summarized (shape/dtype) instead of being
#: serialized element-wise into the trace
MAX_INLINE_ELEMS = 64

_lock = threading.RLock()
_subscribers: list[Callable[[dict], None]] = []
_enabled = False                    # single-boolean gate: bool(_subscribers)
_sink: Optional[TextIO] = None      # the JSONL file sink (one subscriber)
_path: Optional[str] = None
_counters: dict[str, float] = {}
_counters_last_emit = 0.0           # monotonic ts of the last snapshot
_atexit_registered = False
_job_local = threading.local()      # per-thread active job id (correlation)
_span_local = threading.local()     # per-thread stack of open spans
_compile_local = threading.local()  # per-thread cache verdict of a compile

#: the most the backlog of the process's boot holds (:func:`boot_event`)
BACKLOG_MAX = 16
_backlog: Optional[list] = []       # None once handed out or main is over
_in_main = False                    # between boot() and boot_over()


def enabled() -> bool:
    """Fast check instrumentation sites gate on (a plain boolean test)."""
    return _enabled


def path() -> Optional[str]:
    """The active JSONL trace path, or None when the file sink is off."""
    return _path


def _json_default(obj: Any):
    # numpy / jax scalars and arrays reach here from instrumentation
    # sites; keep the trace readable rather than crash the run — and
    # never serialize a whole lattice field into one trace line
    shape = getattr(obj, "shape", None)
    size = getattr(obj, "size", None)
    if shape is not None and isinstance(size, int) and size > MAX_INLINE_ELEMS:
        return ("<array shape=%s dtype=%s>"
                % (tuple(shape), getattr(obj, "dtype", "?")))
    for attr in ("item", "tolist"):
        fn = getattr(obj, attr, None)
        if callable(fn):
            try:
                return fn()
            except Exception:  # noqa: BLE001 — e.g. .item() on an array
                continue
    s = str(obj)
    if len(s) > 512:
        s = s[:512] + "...(+%d chars)" % (len(s) - 512)
    return s


# -- sink fan-out ------------------------------------------------------------- #


def subscribe(fn: Callable[[dict], None]) -> None:
    """Register ``fn(doc)`` to receive every event document.  Subscribers
    run under the module lock and must be fast and never call back into
    this module's emitters; exceptions are swallowed per-sink."""
    global _enabled
    with _lock:
        if fn not in _subscribers:
            _subscribers.append(fn)
        _listen_for_compiles()
        _enabled = True
        if _in_main:
            _hand_out_backlog_locked()


def unsubscribe(fn: Callable[[dict], None]) -> None:
    """Remove a subscriber (idempotent); recomputes the enabled gate."""
    global _enabled
    with _lock:
        try:
            _subscribers.remove(fn)
        except ValueError:
            pass
        _enabled = bool(_subscribers)


def _fanout_locked(doc: dict) -> None:
    for fn in list(_subscribers):
        try:
            fn(doc)
        except Exception:  # noqa: BLE001 — one bad sink must not kill others
            pass


def _jsonl_write(doc: dict) -> None:
    if _sink is not None:
        _sink.write(json.dumps(doc, default=_json_default) + "\n")


def enable(trace_path: str) -> None:
    """Open (append) the JSONL sink at ``trace_path`` and start recording.
    Re-enabling with a different path closes the previous sink first."""
    global _sink, _path, _atexit_registered
    with _lock:
        if _sink is not None:
            if _path == trace_path:
                return
            _close_locked()
        d = os.path.dirname(os.path.abspath(trace_path))
        os.makedirs(d, exist_ok=True)
        _sink = open(trace_path, "a")       # flushed by event(), not a line
        _path = trace_path
        # counters are session-scoped: a fresh JSONL session must not
        # inherit bumps recorded while only live sinks were attached
        _counters.clear()
        subscribe(_jsonl_write)
        if not _atexit_registered:
            atexit.register(disable)
            _atexit_registered = True
    from tclb_tpu import __version__
    event("trace_start", schema=SCHEMA_VERSION, version=__version__,
          pid=os.getpid())


def _close_locked() -> None:
    global _sink, _path
    if _sink is None:
        return
    if _counters:
        _fanout_locked({"kind": "counters", "ts": round(time.time(), 6),
                        "counters": dict(_counters), "final": True})
        _counters.clear()
    try:
        _sink.close()
    except Exception:  # noqa: BLE001
        pass
    _sink = None
    _path = None
    unsubscribe(_jsonl_write)


def disable() -> None:
    """Flush counters, close the JSONL sink, and stop file recording
    (idempotent).  Other subscribers (registry, flight recorder) stay,
    but the counter session ends here either way."""
    with _lock:
        _close_locked()
        _counters.clear()


def span_stack() -> list:
    """The spans open on this thread, outermost first (``spans.Span``
    pushes and pops; only touched while telemetry is enabled)."""
    try:
        return _span_local.stack
    except AttributeError:
        _span_local.stack = []
        return _span_local.stack


def event(kind: str, **fields: Any) -> None:
    """Emit one structured event; silently a no-op when disabled.  An
    event other than a span's own, emitted while a span is open on this
    thread, carries that span's ``id`` as ``parent``."""
    if not _enabled:
        return
    doc = {"kind": kind, "ts": round(time.time(), 6)}
    doc.update(fields)
    stack = span_stack()        # a span has left it before its own event
    if stack and kind != "span":
        doc.setdefault("parent", stack[-1].id)
    with _lock:
        snapshot = _maybe_snapshot_counters_locked()
        _fanout_locked(doc)
        if _sink is not None and (snapshot or not stack):
            _sink.flush()


def counter(name: str, inc: float = 1) -> None:
    """Bump a monotonic process counter (snapshotted periodically and
    flushed as a final ``counters`` event when the JSONL sink closes);
    no-op when disabled."""
    global _counters_last_emit
    if not _enabled:
        return
    with _lock:
        if not _counters:
            _counters_last_emit = time.monotonic()
        _counters[name] = _counters.get(name, 0) + inc


def counters() -> dict[str, float]:
    """Snapshot of the live counters (empty when disabled)."""
    with _lock:
        return dict(_counters)


def _maybe_snapshot_counters_locked() -> bool:
    # Counter loss on abnormal exit: the final flush in _close_locked
    # never happens on SIGKILL, so piggyback a cumulative snapshot on
    # event traffic every COUNTER_SNAPSHOT_S seconds.  Snapshots are
    # cumulative, so the report aggregates them with per-session max.
    # True where a snapshot went out.
    global _counters_last_emit
    if not _counters:
        return False
    now = time.monotonic()
    if now - _counters_last_emit < COUNTER_SNAPSHOT_S:
        return False
    _counters_last_emit = now
    _fanout_locked({"kind": "counters", "ts": round(time.time(), 6),
                    "counters": dict(_counters)})
    return True


# -- the process's boot ------------------------------------------------------- #
# The span tree starts where the process does.  Three stamps on the clock
# of ``ts`` and ``t0`` are taken whether telemetry is on or not: the
# process's start, the first line of ``tclb_tpu/__init__.py`` and the
# entry of ``__main__.main``.  They go out as one ``boot`` event.  What
# closes before a sink can exist waits in ``_backlog``.


def process_start() -> Optional[float]:
    """When the kernel started this process, on ``time.time()``'s clock:
    its start in ticks since the machine's boot (field 22 of
    ``/proc/self/stat``) against the machine's uptime now.  None where
    ``/proc`` cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up_s = float(f.read().split()[0])
        return time.time() - up_s + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def boot_event(kind: str, **fields: Any) -> None:
    """An event of the process's boot: emitted like any other where a
    sink is subscribed, kept for :func:`boot` to hand out otherwise
    (up to ``BACKLOG_MAX`` of them, while the backlog is open)."""
    if _enabled:
        event(kind, **fields)
    elif _backlog is not None and len(_backlog) < BACKLOG_MAX:
        _backlog.append({"kind": kind, "ts": round(time.time(), 6),
                         **fields})


def _hand_out_backlog_locked() -> None:
    global _backlog
    kept, _backlog = _backlog, None
    for doc in kept or ():
        _fanout_locked(doc)
    if kept and _sink is not None:
        _sink.flush()


def boot(t_main: float) -> None:
    """``__main__.main`` has been entered, at ``t_main``: the ``boot``
    event (``t_process``, ``t_package``, ``t_main``; ``process_from``
    says ``package`` where the process's start could not be read and
    the package's first line stands in for it), behind whatever the
    backlog holds.  With no sink yet it joins the backlog, and the
    first to subscribe before :func:`boot_over` receives both."""
    global _in_main
    import tclb_tpu
    t_package = tclb_tpu.T_PACKAGE
    t_process = process_start()
    with _lock:
        _in_main = True
        if _enabled:
            _hand_out_backlog_locked()
    boot_event("boot", t_main=round(t_main, 6),
               t_package=round(t_package, 6),
               t_process=round(t_process or t_package, 6),
               process_from="proc" if t_process else "package")


def boot_over() -> None:
    """``main`` returns: a boot nobody asked about is forgotten, and a
    later ``main`` of this process finds no backlog."""
    global _backlog, _in_main
    with _lock:
        _backlog, _in_main = None, False


# -- compilations ------------------------------------------------------------- #
# jax.monitoring hands out every trace, lowering, backend compile and
# persistent-cache load of the process with the function's name.  The
# listeners are registered once, when the first subscriber arrives, and
# gate on the same boolean as everything else here.  On jax 0.9.0 the
# backend_compile duration wraps the persistent-cache lookup, so a load
# shows as a short backend_compile with a cache_load beside it.  The
# cache's own events arrive on the compiling thread before that
# duration: a lookup (``miss`` unless a hit follows) and the hit.  Each
# ``compile`` event carries the verdict as ``cache``: ``hit`` (loaded),
# ``miss`` (looked up, not there, compiled; JAX keeps it only if it took
# a second or more) or ``off`` (no lookup: no cache directory, or a
# stage no cache serves: ``trace``, ``lower``).

_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_CACHE_VERDICTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_compile_listening = False


def _on_compile_duration(name: str, dur_s: float, **kw: Any) -> None:
    if not _enabled:
        return
    stage = _COMPILE_STAGES.get(name)
    if stage is None:
        return
    mine = _compile_local
    if stage == "cache_load":
        # the load has no name of its own: it goes out with the
        # backend_compile it lies in, which says whose it is
        mine.load_s = dur_s
        return
    program, cache = kw.get("fun_name"), "off"
    if stage == "backend_compile":
        cache = getattr(mine, "cache", "off")
        load_s = getattr(mine, "load_s", None)
        mine.cache, mine.load_s = "off", None
        if load_s is not None:
            event("compile", stage="cache_load", program=program,
                  cache=cache, dur_s=round(load_s, 6))
    event("compile", stage=stage, program=program, cache=cache,
          dur_s=round(dur_s, 6))


def _on_compile_event(name: str, **kw: Any) -> None:
    if not _enabled:
        return
    verdict = _CACHE_VERDICTS.get(name)
    if verdict is not None:
        _compile_local.cache = verdict


def _listen_for_compiles() -> None:
    """Called for every new subscriber, and by the package once its
    imports are through: telemetry imports no jax of its own, so a sink
    that ``TCLB_TELEMETRY`` opens finds it not there yet."""
    global _compile_listening
    if _compile_listening or "jax" not in sys.modules:
        return
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_compile_duration)
    monitoring.register_event_listener(_on_compile_event)
    _compile_listening = True


# -- job correlation ---------------------------------------------------------- #
# serve/ threads stamp the job id they are working for; emitters below
# (failcheck) pick it up so post-mortems localize without cross-referencing.


def set_job(job_id: Optional[Any]) -> None:
    """Set (or clear, with None) the active job id for this thread."""
    _job_local.job_id = job_id


def current_job() -> Optional[Any]:
    """The active job id for this thread, or None."""
    return getattr(_job_local, "job_id", None)


@contextmanager
def job_context(job_id: Any) -> Iterator[None]:
    """Scope the active job id for the calling thread."""
    prev = current_job()
    set_job(job_id)
    try:
        yield
    finally:
        set_job(prev)


# -- named emitters ---------------------------------------------------------- #
# The engine dispatch and failcheck sites call these by name so the static
# hygiene gate (analysis.hygiene.scan_dispatch_telemetry) can verify by AST
# that every dispatch decision and fallback is traced.


def engine_selected(engine: str, **fields: Any) -> None:
    """The dispatch chose an engine (``engine='xla'`` for the pure-XLA
    path).  Fields: model, shape, backend, ..."""
    event("engine_selected", engine=engine, **fields)


def engine_fallback(from_engine: str, to_engine: str, cause: str,
                    **fields: Any) -> None:
    """An engine failed its first compile/probe and the dispatch swapped
    in a fallback; ``cause`` is the ``repr`` of the triggering
    exception."""
    event("engine_fallback", **{"from": from_engine, "to": to_engine,
                                "cause": cause, **fields})


def failcheck(**fields: Any) -> None:
    """A NaN/Inf failcheck fired.  Fields: iteration, quantity, n_bad,
    engine.  The active job id (when a serve thread set one) is stamped
    automatically."""
    jid = current_job()
    if jid is not None and "job_id" not in fields:
        fields["job_id"] = jid
    event("failcheck", **fields)


# environment selection: TCLB_TELEMETRY=<path> turns the sink on for the
# whole process (CI sets this around the tier-1 trace smoke)
_env_path = os.environ.get("TCLB_TELEMETRY")
if _env_path:
    enable(_env_path)
del _env_path
