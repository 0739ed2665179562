"""Timing spans with honest walls.

A span measures host wall-time around a region.  JAX dispatch is
asynchronous, so a naive ``perf_counter`` pair times the *enqueue*, not
the work — callers fence with :meth:`Span.sync` (``jax.block_until_ready``
on the region's output) before the span closes.  A fence says what it
waited: the seconds a span's own fences blocked add up in its ``wait_s``
(absent on a span that never fenced; a wait for another thread counts
the same way, :meth:`Span.blocked`), and :meth:`Span.mark` records the
seconds from the span's start to a point inside it (``dispatch_s``: a
jitted call has returned, the fence not yet begun).  A span's *host
time* is ``dur_s`` less its children less ``wait_s``.

When the region carries enough context (``nodes``/``iters`` fields), the
span exit stamps ``mlups`` (``nodes * iters / dt / 1e6``) the way the
reference prints its own MLUPS line (reference src/main.cpp.Rt:100-126).

Spans also wrap ``jax.profiler.TraceAnnotation`` when available, so a
concurrent ``jax.profiler`` capture shows the same region names.  A
thread that launches nothing on the device (a background writer) says so
once (:func:`off_launch_thread`): its spans are annotated
``<thread>/<name>``, so whoever explains the device's idle gaps by the
annotation over them reads only the thread that could have filled them.

Spans nest.  Each carries ``id`` (a process-wide counter), ``parent``
(the ``id`` of the span open on this thread when it was entered, None at
the top) and ``t0`` (its start, on the wall clock of ``ts``), and a child
inherits ``iteration`` and ``job_id`` from its parent unless it is given
its own, so every span of one segment shares that identifier.  The stack
is a thread's own: work handed to another thread starts a new tree
there, whose root is given the identifiers (:meth:`Span.inherited`).
Non-span events emitted inside a span are stamped with its ``id`` as
``parent`` (:func:`events.event`).  A span's *self time* is ``dur_s``
minus the union of its children's ``[t0, t0 + dur_s]`` intervals.
"""

from __future__ import annotations

import itertools
import re
import sys
import time
from typing import Any, Callable, Optional

from tclb_tpu.telemetry import events

_ids = itertools.count(1)           # span ids, process-wide

#: identifiers a child span takes from its parent unless given its own
INHERITED = ("iteration", "job_id")


def fuse_of(engine: Optional[str]) -> int:
    """Temporal-fusion depth encoded in an engine name (the
    ``,fuse=K`` tag of an engine that runs K steps a kernel call, e.g.
    ``pallas_d3q[d3q19,fuse=3]``); 1 when absent (XLA, unfused engines,
    and ``pallas_resident_generic[...]``, whose one call is a whole
    ``iterate(n)``: its account says the length).  The ``iterate`` span
    records the depth it reads from the tag through this."""
    if not engine:
        return 1
    m = re.search(r"[\[,]fuse=(-?\d+)", engine)
    return int(m.group(1)) if m else 1


class Span:
    """Context manager timing one region; emits a ``span`` event on exit.

    Only constructed when telemetry is enabled (use :func:`span`, which
    returns the shared no-op otherwise), so it may import jax freely."""

    __slots__ = ("name", "fields", "id", "parent", "t0", "_t0",
                 "_wait", "_annotation", "_emit")

    def __init__(self, name: str, fields: dict):
        self.name = name
        self.fields = fields
        self._emit = events.event
        self.id = self.parent = None
        self.t0 = self._t0 = 0.0
        self._wait: Optional[float] = None      # seconds its fences blocked
        self._annotation = None

    def add(self, **fields: Any) -> None:
        """Attach/overwrite fields on the pending span event."""
        self.fields.update(fields)

    def sync(self, x: Any) -> Any:
        """Fence: block until ``x`` (any pytree of jax arrays) is computed
        so the span's wall-time covers the work, not the enqueue.  The
        seconds it blocked go into the span's ``wait_s``."""
        import jax
        t = time.perf_counter()
        x = jax.block_until_ready(x)
        self._wait = (self._wait or 0.0) + time.perf_counter() - t
        return x

    def blocked(self, wait: Callable[[], Any]) -> float:
        """Run ``wait``, which blocks on something other than the device
        (a thread's ``join``): its seconds go into the span's ``wait_s``
        as a fence's do, and are returned."""
        t = time.perf_counter()
        try:
            wait()
        finally:
            dt = time.perf_counter() - t
            self._wait = (self._wait or 0.0) + dt
        return dt

    def mark(self, field: str) -> None:
        """Record the seconds since the span opened under ``field``."""
        self.fields[field] = round(time.perf_counter() - self._t0, 6)

    def inherited(self) -> dict:
        """The identifiers a child would take from this span: what the
        root of the work it hands to another thread has to be given."""
        return {k: self.fields[k] for k in INHERITED if k in self.fields}

    def __enter__(self) -> "Span":
        stack = events.span_stack()
        self.id = next(_ids)
        if stack:
            outer = stack[-1]
            self.parent = outer.id
            for key in INHERITED:
                if key not in self.fields and key in outer.fields:
                    self.fields[key] = outer.fields[key]
        stack.append(self)
        # a span imports no jax of its own: where nothing has yet (the
        # package's import block), no profiler is there to listen
        if "jax" in sys.modules:
            try:
                from jax.profiler import TraceAnnotation
                self._annotation = TraceAnnotation(
                    getattr(events._span_local, "prefix", "") + self.name)
                self._annotation.__enter__()
            except Exception:  # noqa: BLE001 — profiler is optional garnish
                self._annotation = None
        self.t0 = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.perf_counter() - self._t0
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001
                pass
        stack = events.span_stack()
        if self in stack:           # drop it and anything left open inside
            del stack[stack.index(self):]
        fields = self.fields
        if exc is not None:
            fields["ok"] = False
            fields["error"] = repr(exc)
        nodes, iters = fields.get("nodes"), fields.get("iters")
        if nodes and iters and dt > 0:
            # 6 significant digits, not 6 decimals: tiny test domains sit
            # far below 1 MLUPS and must not round to zero
            mlups = float(nodes) * float(iters) / dt / 1e6
            fields["mlups"] = float(f"{mlups:.6g}")
        if self._wait is not None:
            fields["wait_s"] = round(self._wait, 6)
        self._emit("span", name=self.name, id=self.id, parent=self.parent,
                   t0=round(self.t0, 6), dur_s=round(dt, 6), **fields)
        return False


class _NoopSpan:
    """The disabled-mode span: never touches jax, files, or the clock."""

    __slots__ = ()

    def add(self, **fields: Any) -> None:
        pass

    def sync(self, x: Any) -> Any:
        return x

    def blocked(self, wait: Callable[[], Any]) -> float:
        wait()
        return 0.0

    def mark(self, field: str) -> None:
        pass

    def inherited(self) -> dict:
        return {}

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


def span(name: str, **fields: Any):
    """A timing span over a region: ``with span("iterate", niter=n) as sp``.
    Returns the shared no-op (no timing, no sync, no emission) when
    telemetry is disabled."""
    if not events.enabled():
        return NOOP_SPAN
    return Span(name, fields)


def import_span(module: str, boot: bool = False):
    """A ``startup.import`` span over one of the program's own import
    blocks: ``module`` names it, ``preloaded`` says whether ``jax`` was
    in ``sys.modules`` already (as under a caller that imported it
    first).  ``boot``: a block that runs before any sink can exist (the
    package's own): timed whether telemetry is on or not, its event kept
    for the first sink (:func:`events.boot_event`)."""
    if not (boot or events.enabled()):
        return NOOP_SPAN
    sp = Span("startup.import",
              {"module": module, "preloaded": "jax" in sys.modules})
    if boot:
        sp._emit = events.boot_event
    return sp


def off_launch_thread(name: str) -> None:
    """The calling thread launches nothing on the device: from here on
    its spans' profiler annotations are called ``<name>/<span>``.  Their
    events keep the span's name."""
    events._span_local.prefix = name + "/"


def annotate(on: Optional[str] = None, **fields: Any) -> None:
    """Add fields to the innermost span open on this thread (with ``on``:
    the innermost of that name), so that a callee can say what it did
    without a span of its own; nothing when telemetry is disabled or no
    such span is open."""
    if not events.enabled():
        return
    for sp in reversed(events.span_stack()):
        if on is None or sp.name == on:
            sp.add(**fields)
            return
