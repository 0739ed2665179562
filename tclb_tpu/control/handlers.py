"""XML handler tree — element name -> behavior.

Parity target: the reference Handlers layer (src/Handlers.{h,cpp.Rt}):
``vHandler`` scheduling with fractional intervals (Now/Next,
src/Handlers.h:46-78), ``GenericAction`` recursive execution + callback
stacking (src/Handlers.cpp.Rt:1418-1454), ``getHandler`` dispatch
(:2989-3119), and the individual handler classes listed in SURVEY.md §2.2.

Handlers run host-side; everything device-bound goes through the Lattice.
"""

from __future__ import annotations

import math
import os
import re
import xml.etree.ElementTree as ET
from typing import Optional

import numpy as np

from tclb_tpu import telemetry
from tclb_tpu.control.solver import ITERATION_STOP, Solver
from tclb_tpu.utils import log


class Handler:
    """Base scheduling unit (reference vHandler, src/Handlers.h:24-78)."""

    kind = "action"   # action | callback | container | design
    # an element whose init holds a loop of segments (<Solve>, <Repeat>)
    # gets no startup.element span of its own: its passes have theirs
    holds_loop = False
    # handlers with mutable numeric run-state must either implement
    # restorable_state()/restore_state() or set this marker (enforced by
    # the hygiene.unrestorable_handler static check)
    checkpoint_exempt = False

    def __init__(self, node: ET.Element, solver: Solver):
        self.node = node
        self.solver = solver
        self.start_iter = 0
        self.every_iter = 0.0
        self.ck_key: Optional[str] = None

    # -- schedule ----------------------------------------------------------- #

    def _parse_interval(self) -> None:
        # deterministic config-order key: the same document always yields
        # the same keys, so checkpointed handler state finds its handler
        # again on a resume replay
        self.ck_key = self.solver.next_ck_key(type(self).__name__)
        self.start_iter = self.solver.iter
        attr = self.node.get("Iterations")
        self.every_iter = self.solver.units.alt(attr) if attr else 0.0
        # a resume restores each recorded handler's schedule anchor before
        # its init body runs (init may immediately start a Solve loop)
        st = self.solver._pending_restore.get(self.ck_key)
        if st is not None and "__start_iter" in st:
            self.start_iter = int(st["__start_iter"])

    def now(self, it: int) -> bool:
        """True when ``it`` is a firing iteration (reference vHandler::Now:
        handles fractional intervals by floor-crossing)."""
        if not self.every_iter:
            return False
        it -= self.start_iter
        return math.floor(it / self.every_iter) > \
            math.floor((it - 1) / self.every_iter)

    def next_it(self, it: int) -> int:
        """Steps until the next firing (reference vHandler::Next)."""
        if not self.every_iter:
            return -1
        it -= self.start_iter
        k = math.floor(it / self.every_iter)
        return int(-math.floor(-(k + 1) * self.every_iter)) - it

    # -- lifecycle ---------------------------------------------------------- #

    def init(self) -> int:
        self._parse_interval()
        if self.node.get("output"):
            self.solver.output_prefix = self.node.get("output")
        return 0

    def do_it(self) -> int:
        return 0

    def finish(self) -> int:
        return 0

    # -- checkpoint protocol ------------------------------------------------- #

    def restorable_state(self) -> dict:
        """Mutable run-state a full-run checkpoint must capture (must be
        JSON-serializable).  The default is stateless; any handler whose
        ``do_it`` mutates numeric attributes overrides this (the
        ``hygiene.unrestorable_handler`` static check enforces it)."""
        return {}

    def restore_state(self, state: dict) -> None:
        """Re-apply a dict previously produced by ``restorable_state``."""


class GenericAction(Handler):
    """Container executing children immediately; periodic children stack
    into ``solver.hands`` until this action completes (reference
    GenericAction::ExecuteInternal/Unstack, src/Handlers.cpp.Rt:1418-1454)."""

    def init(self) -> int:
        super().init()
        return self.execute_internal()

    def execute_internal(self) -> int:
        self._stacked = 0
        for child in self.node:
            h = get_handler(child, self.solver)
            if h is None:
                continue
            with (telemetry.NOOP_SPAN if h.holds_loop else
                  telemetry.span("startup.element", element=child.tag)):
                ret = h.init()
            if ret not in (0, None):
                return ret
            # a pending resume state for this handler (parked by
            # apply_restored_solver_state) lands after init so the init
            # body can't clobber the restored values
            st = self.solver._pending_restore.pop(
                getattr(h, "ck_key", None) or "", None)
            if st is not None:
                h.restore_state({k: v for k, v in st.items()
                                 if not k.startswith("__")})
            if h.every_iter or h.kind == "design":
                self.solver.hands.append(h)
                self._stacked += 1
        return 0

    def unstack(self) -> None:
        for _ in range(getattr(self, "_stacked", 0)):
            h = self.solver.hands.pop()
            h.finish()


class MainContainer(GenericAction):
    """<CLBConfig> root (reference MainContainer,
    src/Handlers.cpp.Rt:1501-1529)."""

    kind = "container"

    def init(self) -> int:
        self.start_iter = self.solver.iter
        self.every_iter = 0.0
        if self.node.get("output"):
            self.solver.output_prefix = self.node.get("output")
        # annotated provenance copy of the config (reference MainContainer
        # dump with version/precision/backend, src/Handlers.cpp.Rt:1504-1522)
        self.solver.dump_config(self.node)
        ret = self.execute_internal()
        self.unstack()
        return ret


class acSolve(GenericAction):
    """<Solve Iterations="N">: the main loop — event-driven batching of
    lattice iterations between due callbacks (reference acSolve,
    src/Handlers.cpp.Rt:1531-1570)."""

    holds_loop = True

    def init(self) -> int:
        Handler.init(self)
        if not self.every_iter:
            raise ValueError("<Solve> needs a positive Iterations attribute")
        ret = self.execute_internal()
        if ret not in (0, None):
            return ret
        s = self.solver
        stop = False
        # visible to checkpoint collection: the running Solve's schedule
        # anchor must be saved so a resume replay completes to the same
        # absolute iteration instead of restarting its count
        s.solve_stack.append(self)
        try:
            # however the loop is left (the count reached, a <Stop>, a
            # Failcheck hit with its rescue children, an exception), what
            # follows <Solve> in the case finds the files whole
            with s.output_drained("solve_end"):
                while True:
                    # one pass of the loop is a segment: its span is the root
                    # of everything the pass does (scheduling, iterate,
                    # progress, the due handlers), so what none of the
                    # children covers is the loop's own time
                    with telemetry.span("segment") as seg:
                        next_it = self.next_it(s.iter)
                        for h in s.hands:
                            it = h.next_it(s.iter)
                            if 0 < it < next_it:
                                next_it = it
                        steps = next_it
                        s.iter += steps
                        # the iteration the segment ends at, as its handlers
                        # carry; iterate gives the one it starts from
                        seg.add(iteration=s.iter, steps=steps)
                        s.update_synthetic_turbulence(steps)
                        s.lattice.iterate(steps)
                        s.progress(steps)
                        for h in s.hands:
                            if h.now(s.iter):
                                # each periodic callback runs under its own
                                # span, so a trace attributes Solve wall-time
                                # between lattice iteration and
                                # VTK/Log/Failcheck/... output work
                                with telemetry.span("handler",
                                                    handler=type(h).__name__,
                                                    iteration=s.iter):
                                    r = h.do_it()
                                if r == ITERATION_STOP:
                                    stop = True
                                elif r not in (0, None):
                                    return r
                        if stop or self.now(s.iter):
                            break
        finally:
            s.solve_stack.pop()
        self.unstack()
        return 0


class acRepeat(GenericAction):
    """<Repeat Times="N">: run children N times (reference acRepeat,
    src/Handlers.cpp.Rt:2191-2212)."""

    holds_loop = True

    def init(self) -> int:
        Handler.init(self)
        times = int(self.node.get("Times", "1"))
        for _ in range(times):
            ret = self.execute_internal()
            if ret not in (0, None):
                return ret
            self.unstack()
        return 0


class acGeometry(Handler):
    """<Geometry>: run the painter and push flags (reference acGeometry,
    src/Handlers.cpp.Rt:2975-2988)."""

    def init(self) -> int:
        super().init()
        s = self.solver
        s.geometry.load(self.node)
        s.lattice.set_flags(s.geometry.result())
        telemetry.annotate(nodes=int(np.prod(s.shape)),
                           zones=len(s.geometry.setting_zones))
        if self.node.get("export") == "vti":
            s.write_geometry_vti()
        return 0


class acModel(GenericAction):
    """<Model>: children (Params) then lattice Init (reference acModel,
    src/Handlers.cpp.Rt:2643-2652)."""

    def init(self) -> int:
        Handler.init(self)
        ret = self.execute_internal()
        if ret not in (0, None):
            return ret
        self.solver.lattice.init()
        self.unstack()
        return 0


class acInit(Handler):
    """<Init/>: re-run the Init action (reference acInit,
    src/Handlers.cpp.Rt:2653-2662)."""

    def init(self) -> int:
        super().init()
        self.solver.lattice.init()
        return 0


class acParams(Handler):
    """<Params name="value" name-zone="value">: set (zonal) settings through
    the units engine; unknown names are ignored with a warning (reference
    acParams, src/Handlers.cpp.Rt:2487-2530)."""

    def init(self) -> int:
        super().init()
        s = self.solver
        m = s.model
        for name, raw in self.node.attrib.items():
            if name in ("Iterations", "output"):
                continue
            zone: Optional[int] = None
            par = name
            if "-" in name:
                par, zname = name.split("-", 1)
                if zname in s.geometry.setting_zones:
                    zone = s.geometry.setting_zones[zname]
                else:
                    log.warning(f"unknown zone {zname!r} "
                          f"(setting {par})")
                    continue
            if par in m.setting_index:
                val = s.units.alt(raw)
                s.lattice.set_setting(par, val, zone=zone)
            else:
                # the reference silently skips unknown names
                # (src/Handlers.cpp.Rt:2512-2525 has no else branch) —
                # a warning is kinder: a typo'd Params otherwise runs a
                # silently different case
                log.warning(f"Params: model {m.name} has no setting "
                            f"{par!r} — ignored")
        return 0


class conControl(Handler):
    """<Control Iterations="N"><CSV file="..." Time="col*1s"/>
    <Params name-zone="col*1m/s+0.5"/></Control>

    Time-dependent zonal settings (reference conControl,
    src/Handlers.cpp.Rt:2213-2452): CSV columns are read through the units
    engine into a context, linearly interpolated onto the iteration grid
    [0, N), and <Params> attribute values are expressions
    ``term + term + ...`` with each term ``variable*scale`` (variable from
    the context) or a units-bearing constant.  The resulting per-iteration
    series land in the lattice's zonal time tables."""

    def init(self) -> int:
        super().init()
        s = self.solver
        horizon = int(round(s.units.alt(self.node.get("Iterations", "0"))))
        if horizon <= 0:
            raise ValueError("<Control> needs a positive Iterations horizon")
        self.horizon = horizon
        context: dict[str, np.ndarray] = {}
        for child in self.node:
            if child.tag == "CSV":
                self._load_csv(child, context)
            elif child.tag == "Params":
                self._params(child, context)
            else:
                raise ValueError(f"unknown element <{child.tag}> in Control")
        # on the element's own span: the table the lattice now holds
        table = s.lattice.params.time_series
        if table is not None:
            telemetry.annotate("startup.element", series=table.shape[0],
                               horizon=table.shape[1],
                               bytes=int(table.nbytes))
        return 0

    def _eval(self, context: dict[str, np.ndarray], expr: str) -> np.ndarray:
        """``var*scale+var2*scale2+const`` -> per-iteration array
        (reference conControl::get, src/Handlers.cpp.Rt:2253-2310).

        Terms are split on top-level ``+``/``-``; a sign directly after
        ``e``/``E`` is a numeric exponent (``1e+5``), not a term boundary,
        and a leading sign negates the first term."""
        s = self.solver
        out = np.zeros(self.horizon)
        # a +/- is an exponent sign only in digit-e contexts ("1e+5", "2.E-3");
        # after an identifier ending in e/E ("rate+flow") it still splits.
        # A sign directly after '*' is a negative factor ("flow*-2"), not a
        # term boundary (tighten spaces around '*' first so "flow * -2"
        # parses the same way).
        expr = re.sub(r"\s*\*\s*", "*", expr)
        parts = re.split(r"(?<![\d.][eE])(?<!\*)([+-])", expr)
        sign = 1.0
        for part in parts:
            part = part.strip()
            if part == "+":
                continue
            if part == "-":
                sign = -sign
                continue
            if not part:
                continue
            factors = part.split("*")
            if factors[0].strip() in context:
                val = context[factors[0].strip()].copy()
                for f in factors[1:]:
                    val = val * s.units.alt(f)
            else:
                v = 1.0
                for f in factors:
                    v *= s.units.alt(f)
                val = v
            out = out + sign * val
            sign = 1.0
        return out

    def _load_csv(self, node: ET.Element, context: dict) -> None:
        """reference conControl::Internal (src/Handlers.cpp.Rt:2311-2452):
        parse, convert through units, interpolate onto the iteration grid."""
        s = self.solver
        fn = node.get("file")
        if not fn:
            raise ValueError("<CSV> in Control needs file=")
        with open(fn) as f:
            header = [h.strip().strip('"') for h in
                      f.readline().strip().split(",")]
            rows = [[s.units.alt(tok) for tok in line.strip().split(",")]
                    for line in f if line.strip()]
        data = {name: np.array([r[i] for r in rows])
                for i, name in enumerate(header)}
        n = len(rows)
        data["_index"] = np.arange(n, dtype=np.float64)
        tattr = node.get("Time")
        if tattr:
            # time expression in iteration units (units.alt maps s -> iters);
            # evaluate over the CSV rows, not the iteration grid
            saved, self.horizon = self.horizon, n
            t = self._eval(data, tattr)
            self.horizon = saved
        else:
            t = data["_index"] * (self.horizon / n)
        # np.interp silently misbehaves on a non-increasing sample grid —
        # sort rows by time and reject duplicates instead
        order = np.argsort(t, kind="stable")
        t = np.asarray(t, dtype=np.float64)[order]
        if (np.diff(t) <= 0).any():
            raise ValueError(f"<CSV {fn}>: Time column has duplicate or "
                             "non-increasing entries after sorting")
        grid = np.arange(self.horizon, dtype=np.float64)
        for name, col in data.items():
            context[name] = np.interp(grid, t, np.asarray(col)[order])
        # the reference also accepts <Params> nested inside <CSV>
        # (conControl::Internal tail, src/Handlers.cpp.Rt:2430-2450)
        for child in node:
            if child.tag == "Params":
                self._params(child, context)

    def _params(self, node: ET.Element, context: dict) -> None:
        s = self.solver
        for name, raw in node.attrib.items():
            par, zones = name, None
            if "-" in name:
                par, zname = name.split("-", 1)
                if zname in s.geometry.setting_zones:
                    zones = [s.geometry.setting_zones[zname]]
                else:
                    log.warning(f"unknown zone {zname!r} (Control "
                          f"setting {par})")
                    continue
            if par not in s.model.setting_index:
                continue
            if zones is None:
                # zone-less: apply to every allocated zone (reference
                # zSet.set with zone -1, src/ZoneSettings.h)
                zones = sorted({0} | set(s.geometry.setting_zones.values()))
            series = self._eval(context, raw)
            for z in zones:
                s.lattice.set_setting_series(par, series, zone=z)


class cbVTK(Handler):
    kind = "callback"

    def _what(self) -> Optional[set]:
        w = self.node.get("what")
        return set(w.split(",")) if w else None

    def do_it(self) -> int:
        compress = (self.node.get("compress", "") or "").lower() \
            in ("1", "true", "yes")
        self.solver.write_vtk(self._what(), compress=compress)
        return 0

    def init(self) -> int:
        super().init()
        if not self.every_iter:
            return self.do_it()
        return 0


class cbTXT(cbVTK):
    def do_it(self) -> int:
        self.solver.write_txt(self._what())
        return 0


class cbBIN(cbVTK):
    def do_it(self) -> int:
        self.solver.write_bin()
        return 0


class cbLog(Handler):
    kind = "callback"

    def do_it(self) -> int:
        self.solver.write_log()
        return 0

    def init(self) -> int:
        super().init()
        if not self.every_iter:
            return self.do_it()
        return 0


class cbDumpSettings(Handler):
    kind = "callback"

    def do_it(self) -> int:
        s = self.solver
        path = s.out_path("Settings", "txt")
        svec = np.asarray(s.lattice.params.settings)
        with open(path, "w") as f:
            for spec in s.model.settings:
                f.write(f"{spec.name} = "
                        f"{svec[s.model.setting_index[spec.name]]!r}\n")
        return 0

    def init(self) -> int:
        super().init()
        if not self.every_iter:
            return self.do_it()
        return 0


class cbStop(Handler):
    """<Stop GlobalChange="eps" Times="k">: stop when every watched Global
    changed less than eps for k consecutive checks (reference cbStop,
    src/Handlers.cpp.Rt:1079-1157)."""

    kind = "callback"

    def init(self) -> int:
        super().init()
        m = self.solver.model
        self.watch: list[tuple[str, float]] = []
        for g in m.globals_:
            a = self.node.get(g.name + "Change")
            if a is not None:
                self.watch.append((g.name, float(a)))
        if not self.watch:
            raise ValueError("No *Change attribute in <Stop>")
        self.times = int(self.node.get("Times", "1"))
        self.old = {n: -12341234.0 for n, _ in self.watch}
        self.score = 0
        return 0

    def do_it(self) -> int:
        g = self.solver.lattice.get_globals()
        any_change = 0
        for name, eps in self.watch:
            if abs(self.old[name] - g[name]) > eps:
                any_change += 1
            self.old[name] = g[name]
        self.score = 0 if any_change else self.score + 1
        if self.score >= self.times:
            self.score = 0
            for name, _ in self.watch:
                self.old[name] = -12341234.0
            return ITERATION_STOP
        return 0

    def restorable_state(self) -> dict:
        return {"old": {k: float(v) for k, v in self.old.items()},
                "score": int(self.score)}

    def restore_state(self, state: dict) -> None:
        for k, v in state.get("old", {}).items():
            if k in self.old:
                self.old[k] = float(v)
        self.score = int(state.get("score", 0))


class cbFailcheck(Handler):
    """<Failcheck Iterations="N" what="...">: look for NaN and infinity in
    the quantities (``what``, default all; adjoint ones skipped), on the
    device: one count a quantity comes to the host
    (``Solver.nonfinite_counts``), never a plane.  On a hit (the first
    quantity, in the model's order, whose count is not zero) warn, emit
    ``telemetry.failcheck``, run the child elements once each (rescue
    dump; these bring planes down) and stop (reference cbFailcheck,
    src/Handlers.cpp.Rt:1175-1277)."""

    kind = "callback"

    def do_it(self) -> int:
        s = self.solver
        what = self.node.get("what")
        wanted = set(what.split(",")) if what else {"all"}
        names = [q.name for q in s.model.quantities if not q.adjoint
                 and ("all" in wanted or q.name in wanted)]
        counts = s.nonfinite_counts(names)
        telemetry.counter("failcheck.device_scans")
        telemetry.annotate(scan="device", quantities=len(names),
                           bytes_to_host=4 * len(names))
        hit = next(((q, n) for q, n in zip(names, counts) if n), None)
        if hit is None:
            return 0
        name, n_bad = hit
        log.warning(f"Failcheck: {name} has {n_bad} non-finite "
                    f"values at iteration {s.iter}")
        telemetry.failcheck(
            iteration=s.iter, quantity=name, n_bad=n_bad,
            engine=getattr(s.lattice, "_fast_name", None) or "xla")
        for child in self.node:
            h = get_handler(child, self.solver)
            if h is not None:
                h.init()
                # a child without Iterations ran in its init, as where
                # it stands in a case; one with them runs now
                if h.every_iter:
                    h.do_it()
        return ITERATION_STOP


class cbSample(Handler):
    """<Sample what="U,Rho" Iterations="N"><Point dx=... dy=.../></Sample>
    (reference cbSample, src/Handlers.cpp.Rt:1278-1337): per-iteration point
    probes flushed on the callback."""

    kind = "callback"

    def init(self) -> int:
        super().init()
        if not self.every_iter:
            raise ValueError("Sampler needs a nonzero Iterations attribute")
        s = self.solver
        what = self.node.get("what")
        quants = ([q.name for q in s.model.quantities if not q.adjoint]
                  if not what or what == "all" else what.split(","))
        pts = []
        for p in self.node:
            if p.tag != "Point":
                raise ValueError(f"unknown element <{p.tag}> in Sampler")
            x = int(round(s.units.alt(p.get("dx", "0"))))
            y = int(round(s.units.alt(p.get("dy", "0"))))
            z = int(round(s.units.alt(p.get("dz", "0"))))
            pts.append((z, y, x)[-s.model.ndim:])
            if any(not 0 <= i < n for i, n in zip(pts[-1], s.shape)):
                raise ValueError(f"Sampler <Point> {pts[-1]} lies outside "
                                 f"the lattice {s.shape}")
        from tclb_tpu.utils.sampler import Sampler
        self.sampler = Sampler(s.model, quants, np.asarray(pts),
                               s.out_path("Sample", "csv", with_iter=False),
                               s.units)
        s.lattice.attach_sampler(self.sampler)
        return 0

    def do_it(self) -> int:
        self.sampler.flush()
        return 0

    def finish(self) -> int:
        self.sampler.flush()
        # through the lattice, so that the engine is selected again
        self.solver.lattice.detach_sampler()
        return 0

    def restorable_state(self) -> dict:
        # flush so no buffered probe rows die with the process; the header
        # flag makes a resumed run append to the CSV instead of rewriting
        self.sampler.flush()
        return {"wrote_header": bool(self.sampler._wrote_header)}

    def restore_state(self, state: dict) -> None:
        if state.get("wrote_header"):
            self.sampler._wrote_header = True


class cbKeep(Handler):
    """<Keep What="..." Above=|Below=|Equal=...>: feedback controller pinning
    a Global by adjusting its InObj weight (reference cbKeep,
    src/Handlers.cpp.Rt:1339-1417)."""

    kind = "callback"

    def init(self) -> int:
        super().init()
        self.gname = self.node.get("What")
        if self.gname not in self.solver.model.global_index:
            raise ValueError(f"Keep: unknown global {self.gname!r}")
        for mode in ("Above", "Below", "Equal"):
            if self.node.get(mode) is not None:
                self.mode = mode
                self.target = self.solver.units.alt(self.node.get(mode))
                break
        else:
            raise ValueError("Keep needs Above=, Below= or Equal=")
        self.rate = float(self.node.get("Rate", "1.0"))
        return 0

    def do_it(self) -> int:
        s = self.solver
        val = s.lattice.get_globals()[self.gname]
        wname = self.gname + "InObj"
        cur = float(np.asarray(s.lattice.params.settings)[
            s.model.setting_index[wname]])
        err = val - self.target
        if (self.mode == "Above" and err < 0) or \
           (self.mode == "Below" and err > 0) or self.mode == "Equal":
            cur -= self.rate * err
            s.lattice.set_setting(wname, cur)
        return 0


class cbSaveBinary(Handler):
    """<SaveBinary [comp=f[i]] [filename=...]>, re-backed onto the
    checkpoint subsystem: path suffixes go through its centralized
    normalization (an exact-extension rule — stems containing dots no
    longer confuse the old ``fn[:-4]`` juggling), every write is atomic,
    and a filename *without* the legacy ``.npz`` suffix saves the new
    manifest-verified checkpoint directory format."""

    kind = "callback"

    def do_it(self) -> int:
        from tclb_tpu import checkpoint as ckpt
        s = self.solver
        comp = self.node.get("comp")
        if comp:
            # per-component dump (reference saveComp,
            # src/Solver.cpp.Rt:480-510: one density -> one .comp file)
            fn = ckpt.with_suffix(self.node.get("filename")
                                  or s.out_path(f"Save_{comp}", "npy"),
                                  ".npy")
            with ckpt.atomic_path(fn) as tmp:
                with open(tmp, "wb") as f:
                    np.save(f, np.asarray(s.lattice.get_density(comp)))
            return 0
        fn = self.node.get("filename") or s.out_path("Save", "npz")
        if fn.endswith(".npz"):
            s.lattice.save(fn)      # legacy single-file format (atomic)
        else:
            ckpt.save_checkpoint(fn, s.lattice,
                                 extra=ckpt.collect_solver_state(s))
        return 0

    def init(self) -> int:
        super().init()
        if not self.every_iter:
            return self.do_it()
        return 0


class acLoadBinary(Handler):
    """<LoadBinary filename=... [comp=f[i]]>: restore a SaveBinary dump —
    either the manifest-verified checkpoint directory format or a legacy
    ``.npz`` — and reconcile the Solver clock with the restored lattice
    iteration so ``every=``-based handlers keep firing on schedule after
    a restart (previously the solver stayed at its old count while the
    lattice jumped, and Control series/Log output went misaligned)."""

    def init(self) -> int:
        super().init()
        fn = self.node.get("filename")
        if not fn:
            raise ValueError("LoadBinary needs filename=")
        from tclb_tpu import checkpoint as ckpt
        comp = self.node.get("comp")
        if comp:
            # per-component restore (reference loadComp,
            # src/Solver.cpp.Rt:512-545); mirror SaveBinary's suffixing
            self.solver.lattice.set_density(
                comp, np.load(ckpt.with_suffix(fn, ".npy")))
            return 0
        man = ckpt.load_any(self.solver.lattice, fn)
        ckpt.apply_restored_solver_state(self.solver, man)
        return 0


class cbSaveCheckpoint(Handler):
    """<SaveCheckpoint Iterations="N" [dir=...] [keep="3"] [mode="async"]
    [compress="zstd"]>: periodic full-run checkpoints through
    :class:`tclb_tpu.checkpoint.CheckpointManager` — atomic, CRC-verified,
    keep-last-N, serialized off-thread (``mode="sync"`` forces blocking
    saves).  ``compress`` codecs the shard files ("zlib"/"zstd"; a zstd
    request without the zstandard package degrades to uncompressed with
    a warning).  Captures lattice state *plus* solver/handler run-state
    (averaging origin, optimizer iteration, every stacked handler's
    ``restorable_state``).

    This handler is also the resume point: when the solver carries a
    ``--resume`` request, its init restores from the requested checkpoint
    (default: the manager's newest *valid* one — corrupted checkpoints
    are skipped) before any <Solve> runs."""

    kind = "callback"

    def init(self) -> int:
        super().init()
        s = self.solver
        from tclb_tpu.checkpoint import CheckpointManager
        root = self.node.get("dir")
        if not root:
            base = s.output_prefix
            if base.endswith("/"):
                os.makedirs(base, exist_ok=True)
                base = os.path.join(base, s.conf_name)
            root = base + "_checkpoint"
        mode = (self.node.get("mode", "async") or "async").lower()
        self.manager = CheckpointManager(
            root, keep_last=int(self.node.get("keep", "3")),
            async_saves=mode != "sync",
            compress=self.node.get("compress"))
        if s.resume_from is not None:
            self._resume()
        return 0

    def _resume(self) -> None:
        s = self.solver
        from tclb_tpu import checkpoint as ckpt
        target, s.resume_from = s.resume_from, None
        if isinstance(target, str) and target not in ("", "latest", "auto"):
            path = target
            if not ckpt.is_checkpoint_dir(path):
                raise ValueError(
                    f"--resume: {path} is not a checkpoint directory")
        else:
            path = self.manager.latest()
        if path is None:
            log.notice("resume requested but no valid checkpoint under "
                       f"{self.manager.root} — starting cold")
            return
        man = ckpt.restore_lattice(s.lattice, path)
        ckpt.apply_restored_solver_state(s, man)
        log.notice(f"resumed from {path} at iteration {s.iter}")

    def do_it(self) -> int:
        s = self.solver
        from tclb_tpu.checkpoint import collect_solver_state
        self.manager.save(s.lattice, step=s.iter,
                          extra=collect_solver_state(s))
        return 0

    def finish(self) -> int:
        self.manager.wait()
        return 0


class acCallPython(Handler):
    """<CallPython module="m" function="f">: call a user function with the
    solver — the reference builds numpy views over staged component buffers
    (cbPythonCall, src/Handlers.cpp.Rt:2774-2970); here the framework *is*
    Python, so the user function receives the live Solver and mutates
    densities via get/set_density."""

    kind = "callback"

    def init(self) -> int:
        super().init()
        import importlib
        mod = self.node.get("module")
        fn = self.node.get("function", "run")
        self._fn = getattr(importlib.import_module(mod), fn)
        if not self.every_iter:
            # run once, where the element stands: an initial field.  Its
            # seconds are set-up, so a trace names them
            with telemetry.span("callpython", module=mod, function=fn) as sp:
                ret = self.do_it()
                sp.sync(self.solver.lattice.state.fields)
            return ret
        return 0

    def do_it(self) -> int:
        ret = self._fn(self.solver)
        return int(ret) if ret else 0


class GenericContainer(GenericAction):
    kind = "container"

    def init(self) -> int:
        Handler.init(self)
        ret = self.execute_internal()
        self.unstack()
        return ret


class acNop(Handler):
    """Elements handled elsewhere (Units is read before the tree runs)."""

    def init(self) -> int:
        return 0


class acSyntheticTurbulence(Handler):
    """<SyntheticTurbulence>: configure the synthetic-inflow turbulence
    generator (reference acSyntheticTurbulence,
    src/Handlers.cpp.Rt:2532-2642).  Wave parameters accept
    <name>WaveLength (inverted), <name>WaveNumber, or <name>WaveFrequency
    (x 2 pi), all unit-converted."""

    def _wave_number(self, name: str):
        u = self.solver.units
        val = None
        a = self.node.get(name + "WaveLength")
        if a is not None:
            val = 1.0 / u.alt(a)
        a = self.node.get(name + "WaveNumber")
        if a is not None:
            val = u.alt(a)
        a = self.node.get(name + "WaveFrequency")
        if a is not None:
            val = u.alt(a) * 2.0 * math.pi
        return val

    def init(self) -> int:
        super().init()
        from tclb_tpu.utils.turbulence import SyntheticTurbulence
        st = SyntheticTurbulence()
        nmodes = int(self.node.get("Modes", 100))
        spec = self.node.get("Spectrum", "Von Karman")
        if spec == "Von Karman":
            main_wn = self._wave_number("Main")
            diff_wn = self._wave_number("Diffusion")
            if main_wn is None or diff_wn is None:
                raise ValueError(
                    "Von Karman spectrum needs MainWaveNumber and "
                    "DiffusionWaveNumber (or WaveLength/Frequency forms)")
            max_wn = self._wave_number("Shortest")
            if max_wn is None:
                max_wn = 2.0 * math.pi / 4.0   # 2 pi over 4 elements
            min_wn = self._wave_number("Longest")
            if min_wn is None:
                min_wn = main_wn / 2.0
            frac = st.set_von_karman(main_wn, diff_wn, min_wn, max_wn,
                                     nmodes)
            if frac < 0.7:
                log.notice(f"synthetic turbulence resolves only "
                           f"{frac:.0%} of the spectrum")
        elif spec == "One Wave":
            wn = self._wave_number("")
            if wn is None:
                raise ValueError("One Wave spectrum needs a WaveNumber")
            st.set_one_wave(wn)
        else:
            raise ValueError(f"unknown spectrum {spec!r}")
        t_wn = self._wave_number("Time")
        if t_wn is None:
            raise ValueError("synthetic turbulence needs TimeWaveNumber "
                             "(iteration correlation scale)")
        st.set_time_scale(t_wn)
        self.solver.synthetic_turbulence = st
        return 0


class cbCatalyst(Handler):
    """<Catalyst what="U,Rho" [slice_axis= slice_index=] [vmin= vmax=]>:
    in-situ frame rendering — the TPU-native equivalent of both the
    ParaView Catalyst co-processor (reference cbCatalyst,
    src/Handlers.cpp.Rt:898-1006) and the GLUT GUI's live Color() view
    (src/gpu_anim.h; see utils/render.py for the redesign rationale).
    Vector quantities render their magnitude; 3D lattices render the
    middle slice of ``slice_axis`` (default z) unless slice_index= is
    given."""

    kind = "callback"

    def do_it(self) -> int:
        from tclb_tpu.utils.render import render_frame
        s = self.solver
        what = (self.node.get("what") or "U").split(",")
        axis = int(self.node.get("slice_axis", "0"))
        vmin = self.node.get("vmin")
        vmax = self.node.get("vmax")
        for q in what:
            q = q.strip()
            a = s.quantity_host(q)
            if a.ndim == len(s.shape) + 1:      # vector -> magnitude
                a = np.sqrt((a ** 2).sum(axis=0))
            if a.ndim == 3:
                idx = int(self.node.get("slice_index",
                                        str(a.shape[axis] // 2)))
                a = np.take(a, idx, axis=axis)
            render_frame(s.out_path(f"frame_{q}", "png"), a,
                         vmin=s.units.alt(vmin) if vmin else None,
                         vmax=s.units.alt(vmax) if vmax else None)
        return 0

    def init(self) -> int:
        super().init()
        if not self.every_iter:
            return self.do_it()
        return 0


class cbAveraging(Handler):
    """<Average>: reset the running averages (average=True densities) and
    restart the sample counter (reference cbAveraging,
    src/Handlers.cpp.Rt:1158-1174 + Lattice::resetAverage,
    src/Lattice.cu.Rt:1193-1201)."""

    kind = "callback"

    def init(self) -> int:
        super().init()
        self.solver.lattice.reset_average()
        return 0

    def do_it(self) -> int:
        self.solver.lattice.reset_average()
        return 0


_HANDLERS = {
    "CLBConfig": MainContainer,
    "SyntheticTurbulence": acSyntheticTurbulence,
    "Average": cbAveraging,
    "Catalyst": cbCatalyst,
    "Solve": acSolve,
    "Repeat": acRepeat,
    "Geometry": acGeometry,
    "Model": acModel,
    "Init": acInit,
    "Params": acParams,
    "Control": conControl,
    "VTK": cbVTK,
    "TXT": cbTXT,
    "BIN": cbBIN,
    "Log": cbLog,
    "Stop": cbStop,
    "Failcheck": cbFailcheck,
    "Sample": cbSample,
    "Keep": cbKeep,
    "SaveBinary": cbSaveBinary,
    "SaveMemoryDump": cbSaveBinary,
    "SaveCheckpoint": cbSaveCheckpoint,
    "LoadBinary": acLoadBinary,
    "LoadMemoryDump": acLoadBinary,
    "DumpSettings": cbDumpSettings,
    "CallPython": acCallPython,
    "Units": acNop,
    "Container": GenericContainer,
    # the reference declares these two with empty Init bodies
    # (src/Handlers.cpp.Rt:2454/2470) — same here: accepted, no-op
    "FieldParameter": acNop,
    "ControlParameter": acNop,
}


def register_handler(name: str, cls) -> None:
    _HANDLERS[name] = cls


def get_handler(node: ET.Element, solver: Solver) -> Optional[Handler]:
    """Element name -> handler instance (reference getHandler,
    src/Handlers.cpp.Rt:2989-3119)."""
    cls = _HANDLERS.get(node.tag)
    if cls is None:
        raise ValueError(f"unknown config element <{node.tag}>")
    return cls(node, solver)


# optimization/adjoint handlers register themselves on import
from tclb_tpu.control import opt_handlers  # noqa: E402,F401  (registration)
