"""The benchmark's clock: a ``<CallPython>`` handler inside the case.

The generated case calls :func:`tick` last among its handlers, every
``segment`` steps (the greatest common divisor of the handler intervals),
so it splits ``<Solve>``'s loop nowhere new.  A **segment** is one pass
of that loop: one ``Lattice.iterate(segment)`` and the handlers due after
it.  A **period** is the least common multiple of the intervals: every
period does the same work.  The handler

* blocks on the lattice's fields, then reads the clock.  ``<Log>`` reads
  the globals on every pass of every traffic mix here, which has already
  made the loop synchronous, so the block removes no overlap;
* keeps the fields after ``check_segments`` segments (a host copy) for
  the comparison with the plain reference;
* warms up ``warmup_periods`` whole periods after the period that holds
  the first call, **opens the window at that period boundary and closes
  it at the first period boundary at or after ``seconds``**, returning
  ``ITERATION_STOP``.  The window holds whole periods only;
* keeps only the newest VTK file (unlinks the one before);
* in a traced run, starts the profiler at a period boundary in the
  middle of the window and stops it ``trace_periods`` periods later.

``<CallPython>`` can only name a module-level function, so the one
window of a run is installed in this module by :func:`install`.
"""

from __future__ import annotations

import math
import os
import time

ITERATION_STOP = 1          # tclb_tpu.control.solver.ITERATION_STOP

_active = None


def install(window) -> None:
    global _active
    _active = window


def tick(solver) -> int:
    if _active is None:
        raise RuntimeError("benchmark.window.tick called with no window "
                           "installed")
    return _active.tick(solver)


def _default_block(solver) -> None:
    import jax
    jax.block_until_ready(solver.lattice.state.fields)


class Window:
    def __init__(self, handlers: list[dict], seconds: float,
                 warmup_periods: int, check_segments: int = 1,
                 trace_periods: int = 0, profiler=None,
                 clock=time.perf_counter, block=_default_block,
                 after_first_call=None):
        self.intervals = [(h["tag"], int(h["Iterations"])) for h in handlers]
        steps = [n for _, n in self.intervals]
        self.segment = math.gcd(*steps)
        self.period = math.lcm(*steps)
        if warmup_periods < 2:
            raise ValueError("warm up at least two whole periods")
        self.seconds = float(seconds)
        self.open_at = (1 + int(warmup_periods)) * self.period
        self.check_at = int(check_segments) * self.segment
        if self.check_at > self.open_at:
            raise ValueError("the check has to come before the window")
        self.trace_periods = int(trace_periods)
        self.profiler = profiler            # object with start()/stop()
        self.clock, self.block = clock, block
        self.after_first_call = after_first_call
        # what a run leaves behind
        self.t_first = self.t_open = self.t_close = None
        self.t_prev = None
        self.snapshot = None                # host copy of the fields
        self.fields_shape = self.fields_itemsize = None
        self.warmup = []                    # (iteration, seconds, kinds)
        self.segments = []                  # same, inside the window
        self.overhead_s = 0.0               # profiler start/stop, in window
        self.traced = None                  # (first, last) iteration traced
        self.closed = False
        self.newest_vtk = None
        self._trace_from = None
        # observation only: the interpreter's full (generation 2)
        # garbage collections that fall into the window, and their time
        self.gc_full = []                   # (seconds since open, seconds)
        self._gc_t0 = None

    def watch_gc(self, phase: str, info: dict) -> None:
        """A ``gc.callbacks`` entry; the harness installs it, the window
        changes nothing about when the interpreter collects."""
        if info.get("generation") != 2 or self.t_open is None \
                or self.closed:
            return
        if phase == "start":
            self._gc_t0 = self.clock()
        elif self._gc_t0 is not None:
            now = self.clock()
            self.gc_full.append((self._gc_t0 - self.t_open,
                                 now - self._gc_t0))
            self._gc_t0 = None

    def kinds(self, it: int) -> str:
        return "+".join(tag for tag, n in self.intervals if it % n == 0)

    def _keep_newest_vtk(self, solver, it: int) -> None:
        if not any(tag == "VTK" and it % n == 0
                   for tag, n in self.intervals):
            return
        new = [solver.out_path("VTK", ext) for ext in ("vti", "pvti")]
        for old in self.newest_vtk or []:
            try:
                os.unlink(old)
            except FileNotFoundError:
                pass
        self.newest_vtk = new

    def tick(self, solver) -> int:
        self.block(solver)
        now = self.clock()
        it = int(solver.iter)
        boundary = it % self.period == 0
        if self.t_first is None:
            self.t_first = now
            f = solver.lattice.state.fields
            self.fields_shape = tuple(f.shape)
            self.fields_itemsize = f.dtype.itemsize
            if self.after_first_call is not None:
                self.after_first_call()
        if it == self.check_at:
            import numpy as np
            self.snapshot = np.asarray(solver.lattice.state.fields)
            now = self.clock()      # the copy belongs to no segment
        self._keep_newest_vtk(solver, it)
        if it <= self.open_at:
            if self.t_prev is not None:
                self.warmup.append((it, now - self.t_prev, self.kinds(it)))
            if it == self.open_at:
                self.t_open = now
                self._plan_trace()
        else:
            self.segments.append((it, now - self.t_prev, self.kinds(it)))
            if boundary:
                stop = now - self.t_open >= self.seconds
                self._trace_at_boundary(it, stop)
                if stop:
                    self.t_close = now
                    self.closed = True
                    self.t_prev = now
                    return ITERATION_STOP
        self.t_prev = self.clock() if self.profiler else now
        return 0

    # -- the traced run ------------------------------------------------- #

    def _plan_trace(self) -> None:
        """Put the traced periods in the middle of the window, going by
        how long the warm-up's last periods took."""
        if self.profiler is None or self.trace_periods < 1:
            return
        per = self.period // self.segment
        tail = [s for _, s, _ in self.warmup[-per:]]
        period_s = sum(tail) if len(tail) == per else None
        expect = (self.seconds / period_s) if period_s else 0
        first = max(1, int((expect - self.trace_periods) // 2))
        self._trace_from = self.open_at + first * self.period

    def _trace_at_boundary(self, it: int, closing: bool) -> None:
        if self._trace_from is None:
            return
        t0 = self.clock()
        if self.traced is None and it >= self._trace_from and not closing:
            self.profiler.start()
            self.traced = (it, None)
        elif self.traced is not None and self.traced[1] is None and (
                closing or it >= self.traced[0]
                + self.trace_periods * self.period):
            self.profiler.stop()
            self.traced = (self.traced[0], it)
        self.overhead_s += self.clock() - t0

    # -- what the window measured ---------------------------------------- #

    def summary(self, nodes: int) -> dict:
        if not self.closed:
            raise RuntimeError("the window never closed")
        wall = self.t_close - self.t_open
        steps = self.segments[-1][0] - self.open_at
        secs = sorted(s for _, s, _ in self.segments)
        rank = max(1, -(-95 * len(secs) // 100))        # nearest rank
        return {
            "wall_s": wall, "steps": steps,
            "periods": steps // self.period,
            "mlups": nodes * steps / wall / 1e6,
            "segments": len(secs),
            "segment_p95_ms": secs[rank - 1] * 1e3,
            "segment_median_ms": secs[len(secs) // 2] * 1e3,
            "beyond_p95": len(secs) - rank,
        }
