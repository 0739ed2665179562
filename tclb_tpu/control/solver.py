"""Solver: process-level orchestration + config entry points.

Parity target: reference ``Solver`` (src/Solver.h.Rt:57-171,
src/Solver.cpp.Rt) and ``main()`` (src/main.cpp.Rt:172-346): read units and
gauge them, size the lattice from the <Geometry> element, run the handler
tree, fan out VTK/TXT/BIN/Log output, keep the iteration counter and the
stacked periodic callbacks.

The reference's per-rank MPI bookkeeping (MPIDivision, node tables) has no
equivalent here: device parallelism is a ``jax.sharding.Mesh`` handed to the
Lattice, and every host-side array is the *global* lattice (JAX global-view
arrays), so output and geometry code is rank-free by construction.
"""

from __future__ import annotations

import contextlib
import os
import time
import xml.etree.ElementTree as ET
from typing import Any, Iterator, Optional, Sequence

import jax
import numpy as np

from tclb_tpu import telemetry
from tclb_tpu.core.lattice import Lattice
from tclb_tpu.core.registry import Model
from tclb_tpu.utils.geometry import Geometry
from tclb_tpu.utils.units import UnitEnv
from tclb_tpu.utils.vtk import CSVLog

ITERATION_STOP = 1


class OutputError(RuntimeError):
    """A background output write failed; the message names the file."""


def _to_host(name: str, q: jax.Array) -> np.ndarray:
    """A device array on the host, waited for: the copy, or on a mesh
    the gather (``quantity.d2h``)."""
    with telemetry.span("quantity.d2h", quantity=name, bytes=q.nbytes):
        return np.asarray(q)


class Solver:
    """Host orchestration state shared by all handlers."""

    def __init__(self, model: Model, output: str = "output/",
                 mesh: Any = None, dtype: Any = None):
        self.model = model
        self.units = UnitEnv()
        self.output_prefix = output
        self.mesh = mesh
        self.dtype = dtype
        self.lattice: Optional[Lattice] = None
        self.geometry: Optional[Geometry] = None
        self.shape: tuple[int, ...] = ()
        self.iter = 0
        self.iter_type = 0
        self.opt_iter = 0
        self.hands: list = []        # stacked periodic callbacks
        self.designs: list = []      # registered design parameterizations
        self.objective: Optional[float] = None
        self.gradient = None
        self.fd_records: Optional[list] = None
        self.log: Optional[CSVLog] = None
        self.start_walltime = time.time()
        self.conf_name = "run"
        self.stop_flag = False
        self.synthetic_turbulence = None   # set by <SyntheticTurbulence>
        # checkpoint/restart plumbing (tclb_tpu.checkpoint)
        self.resume_from: Optional[str] = None  # --resume target, consumed
        self.solve_stack: list = []    # acSolve handlers currently running
        self._pending_restore: dict = {}   # ck_key -> restored handler state
        self._ck_counts: dict = {}     # class name -> instances seen so far
        # <VTK> output: one background writer, made at the first write,
        # and the piece it is writing (None: nothing in flight)
        self._output = None
        self._output_file: Optional[str] = None

    def next_ck_key(self, cls_name: str) -> str:
        """Deterministic per-handler checkpoint key: Nth instance of a
        handler class in config order gets ``"<Class>#<N>"``.  Stable
        across runs of the same config, which is what lets a checkpoint's
        per-handler state find its owner on resume."""
        n = self._ck_counts.get(cls_name, 0)
        self._ck_counts[cls_name] = n + 1
        return f"{cls_name}#{n}"

    # -- naming (reference Solver::outIterFile/outGlobalFile) --------------- #

    def out_path(self, name: str, ext: str, with_iter: bool = True) -> str:
        base = self.output_prefix
        if base.endswith("/"):
            os.makedirs(base, exist_ok=True)
            base = os.path.join(base, self.conf_name)
        tag = f"_{name}_{self.iter:08d}" if with_iter else f"_{name}"
        return f"{base}{tag}.{ext}"

    @property
    def is_main(self) -> bool:
        """Rank-0 duty filter for file output under --distributed (the
        reference's InitPrint root filter, src/main.cpp.Rt:186): every
        host runs the identical handler tree, only one writes files."""
        import jax
        return jax.process_index() == 0

    # -- setup --------------------------------------------------------------- #

    def set_size(self, shape: tuple[int, ...]) -> None:
        """Allocate lattice + geometry painter (reference Solver::setSize +
        InitAll, src/Solver.cpp.Rt:265-395)."""
        self.shape = tuple(int(s) for s in shape)
        import jax.numpy as jnp
        self.lattice = Lattice(self.model, self.shape,
                               dtype=self.dtype or jnp.float32,
                               mesh=self.mesh)
        self.geometry = Geometry(self.model, self.shape, self.units)

    def set_unit(self, name: str, value: str, gauge: str = "1") -> None:
        self.units.set_unit(name, self.units.read_text(value),
                            float(self.units.si(gauge)))

    def gauge(self) -> None:
        self.units.make_gauge()

    # -- progress/throughput (reference MainCallback live MLBUps/GB/s,
    #    src/main.cpp.Rt:67-156: reports auto-tuned to ~1/s) -------------- #

    def progress(self, steps: int) -> None:
        """Called by <Solve> after each iterate chunk: prints a live
        MLUPS + effective-GB/s line, throttled to ~1 report/s (the
        reference's desired_fps mechanism)."""
        import jax

        from tclb_tpu.utils import log
        now = time.time()
        if not hasattr(self, "_prog_t0"):
            self._prog_t0, self._prog_iters = now, 0
            return
        self._prog_iters += steps
        dt = now - self._prog_t0
        if dt < 1.0:
            return
        # the call that reports, under its span (the others stay two
        # float operations)
        with telemetry.span("progress", iteration=self.iter):
            # force execution so the rate is real (jit dispatch is
            # async); only the elapsed chunk is billed.  The run's own
            # fence, telemetry on or off: it is no Span.sync
            jax.block_until_ready(self.lattice.state.fields)
            dt = time.time() - self._prog_t0
            nodes = float(np.prod(self.shape))
            mlups = nodes * self._prog_iters / dt / 1e6
            bytes_per = (2 * self.model.n_storage
                         * np.dtype(self.lattice.state.fields.dtype).itemsize
                         + 2)
            log.info(f"iter {self.iter}: {mlups:8.1f} MLUPS "
                     f"({mlups * bytes_per / 1e3:6.1f} GB/s eff) "
                     f"[{self._prog_iters} it in {dt:.2f} s]")
            telemetry.event("progress", iteration=self.iter,
                            mlups=round(mlups, 1),
                            gbps=round(mlups * bytes_per / 1e3, 1))
        self._prog_t0, self._prog_iters = time.time(), 0

    # -- config provenance (reference MainContainer dump with version/
    #    precision/backend, src/Handlers.cpp.Rt:1504-1522) ---------------- #

    def dump_config(self, root) -> None:
        import copy as _copy

        import jax
        import jax.numpy as jnp

        from tclb_tpu import __version__
        annotated = _copy.deepcopy(root)
        annotated.set("solver_version", __version__)
        annotated.set("model_name", self.model.name)
        annotated.set("precision",
                      "double" if (self.dtype or jnp.float32) == jnp.float64
                      else "single")
        annotated.set("backend", jax.default_backend())
        path = self.out_path("config", "xml", with_iter=False)
        ET.ElementTree(annotated).write(path)

    # -- synthetic turbulence (reference ST.Generate per iteration,
    #    src/Lattice.cu.Rt:391-397; segment-wise here — utils/turbulence) -- #

    def update_synthetic_turbulence(self, steps: int) -> None:
        """Advance the SynthT* coupling planes by one handler segment of
        ``steps`` iterations with the variance-exact AR(1) update."""
        st = self.synthetic_turbulence
        m = self.model
        if st is None or st.nmodes == 0 or "SynthT" not in m.groups:
            return
        fluct = st.evaluate(self.shape)
        k_aa = st.ar1_factor(steps)
        k_bb = float(np.sqrt(max(0.0, 1.0 - k_aa * k_aa)))
        lat = self.lattice
        idx = list(m.groups["SynthT"])
        # slice on device first: only the SynthT planes cross to the host
        import jax.numpy as jnp
        old = np.asarray(lat.state.fields[jnp.asarray(idx)])
        lat.set_density_planes(
            {m.storage_names[i]: k_aa * old[c] + k_bb * fluct[c]
             for c, i in enumerate(idx)})

    def log_row(self) -> dict[str, float]:
        m = self.model
        lat = self.lattice
        row: dict[str, float] = {
            "Iteration": float(self.iter),
            # 1 s == units.scale[1] lattice iterations (UnitEnv gauge),
            # so SI time of iteration n is n / scale[1]
            "Time_si": float(self.iter) / float(self.units.scale[1]),
            "Walltime": time.time() - self.start_walltime,
            "OptIteration": float(self.opt_iter),
        }
        # the row's three device-to-host copies, in this order
        with telemetry.span("output.log.fetch") as sp:
            svec = np.asarray(lat.params.settings)
            table = (np.asarray(lat.params.zone_table) if self.geometry
                     else None)
            globals_ = lat.get_globals()
            sp.add(copies=2 + (table is not None),
                   bytes=svec.nbytes + lat.state.globals_.nbytes
                   + (0 if table is None else table.nbytes))
        for s in m.settings:
            row[f"{s.name}"] = float(svec[m.setting_index[s.name]])
        if table is not None:
            for s in m.zonal_settings:
                for zname, zid in self.geometry.setting_zones.items():
                    row[f"{s}-{zname}"] = float(table[m.setting_index[s], zid])
        row.update(globals_)
        return row

    def write_log(self) -> None:
        if not self.is_main:
            return
        with telemetry.span("output.log", iteration=self.iter):
            if self.log is None:
                self.log = CSVLog(self.out_path("Log", "csv",
                                                with_iter=False))
            row = self.log_row()
            with telemetry.span("output.log.write") as sp:
                sp.add(bytes=self.log.write(row))

    # -- output fan-out ------------------------------------------------------ #

    def quantity_device(self, name: str) -> jax.Array:
        """One quantity over the lattice, still on the device: its
        compiled program dispatched (``quantity.eval``, fenced when
        traced; ``Lattice.get_quantity`` adds ``program``: ``"built"`` on
        the call that compiled it, ``"reused"`` after).  The output is a
        fresh buffer that no ``iterate`` donates."""
        with telemetry.span("quantity.eval", quantity=name) as sp:
            q = sp.sync(self.lattice.get_quantity(name))
            sp.add(bytes=q.nbytes)
        return q

    def quantity_host(self, name: str) -> np.ndarray:
        """One quantity over the lattice as a host array, on the calling
        thread: :meth:`quantity_device`, then the copy, or on a mesh the
        gather, to the host (``quantity.d2h``), waited for.  For ``<TXT>``
        and ``<Catalyst>``; ``<VTK>`` dispatches here and copies on its
        writer's thread (:meth:`write_vtk`), ``<Failcheck>`` brings no
        plane down (:meth:`nonfinite_counts`)."""
        return _to_host(name, self.quantity_device(name))

    def nonfinite_counts(self, names: Sequence[str]) -> list[int]:
        """How many values of each named quantity are NaN or infinite,
        tested on the device (``Lattice.count_nonfinite``): every count
        program is dispatched before any is waited for (one
        ``quantity.eval`` each, ``reduce="nonfinite"``, fenced only when
        traced), then the counts come to the host in one copy of 4 bytes
        a quantity (``quantity.d2h``)."""
        counts = []
        for name in names:
            with telemetry.span("quantity.eval", quantity=name,
                                reduce="nonfinite") as sp:
                counts.append(sp.sync(self.lattice.count_nonfinite(name)))
        with telemetry.span("quantity.d2h", bytes=4 * len(counts)):
            return [int(c) for c in jax.device_get(counts)]

    def _wanted(self, what: Optional[set[str]]) -> list[str]:
        """The quantities an output handler's ``what`` selects
        (reference vtkWriteLattice quantity loop,
        src/vtkLattice.cpp.Rt:47-66)."""
        return [q.name for q in self.model.quantities if not q.adjoint
                and (not what or q.name in what or "all" in what)]

    def quantity_arrays(self, what: Optional[set[str]] = None
                        ) -> dict[str, np.ndarray]:
        """Evaluate selected quantities -> host arrays."""
        return {name: self.quantity_host(name)
                for name in self._wanted(what)}

    def write_geometry_vti(self) -> str:
        """Write the painted geometry as VTI: raw flags, one 0/1 layer per
        node-type GROUP, and the settings-zone ids (the reference writes
        the geometry's node-type layers through vtkWriteLattice,
        src/vtkLattice.cpp.Rt:33-46)."""
        from tclb_tpu.utils.vtk import write_vti
        m = self.model
        flags = np.asarray(self.lattice.state.flags)
        arrays = {"Flag": flags}
        for group, mask in m.group_masks.items():
            if group in ("ALL", "SETTINGZONE") or mask == 0:
                continue
            arrays[group] = ((flags & mask) != 0).astype(np.uint8)
        arrays["Zone"] = (flags >> m.zone_shift).astype(np.uint16)
        path = self.out_path("geometry", "vti", with_iter=False)
        write_vti(path, arrays)
        return path

    def write_vtk(self, what: Optional[set[str]] = None,
                  compress: bool = False) -> Optional[str]:
        """Hand one ``.vti`` piece and its ``.pvti`` to the output writer
        and return the piece's path; the files are whole once
        :meth:`drain_output` has returned.  On the calling thread
        (``output.vtk``): the dispatch of every selected quantity's
        program, the start of its copy to the host, and the flags (the
        lattice's host copy: ``iterate`` donates the live state).  On the
        writer's thread (``output.vtk.write``, given this write's
        ``iteration``): the copies finished (``quantity.d2h``; on a mesh
        the gather), ``write_vti`` (``output.vtk.encode``,
        ``output.vtk.file``) and ``write_pvti``.  One write is in flight
        at most: a second waits for the first (``output.vtk.drain``)."""
        if not self.is_main:
            return None
        from tclb_tpu.utils.vtk import write_pvti, write_vti
        piece = self.out_path("VTK", "vti")
        master = self.out_path("VTK", "pvti")
        with telemetry.span("output.vtk", iteration=self.iter) as sp:
            pending = {name: self.quantity_device(name)
                       for name in self._wanted(what)}
            for q in pending.values():
                q.copy_to_host_async()
            # node-type group layers (reference writes one flag layer per
            # selected group, src/vtkLattice.cpp.Rt:33-46)
            flags = (self.lattice._flags_host()
                     if not what or "flag" in what else None)
            ids = sp.inherited()
            sp.add(queued_bytes=sum(q.nbytes for q in pending.values())
                   + (0 if flags is None else flags.nbytes))

            def write() -> None:
                with telemetry.span("output.vtk.write", **ids):
                    arrays = {name: _to_host(name, q)
                              for name, q in pending.items()}
                    pending.clear()         # the device's buffers go
                    if flags is not None:
                        arrays["Flag"] = flags
                    write_vti(piece, arrays, compress=compress)
                    write_pvti(master, piece, arrays)

            self.drain_output("next_write")
            if self._output is None:
                from tclb_tpu.checkpoint.writer import AsyncWriter
                self._output = AsyncWriter("tclb-output-writer")
            self._output.submit(write)
            self._output_file = piece
            telemetry.counter("output.vtk.async_writes")
        return piece

    def drain_output(self, reason: str = "run_end") -> None:
        """Wait until the write in flight, if any, has left whole files
        (``output.vtk.drain``: ``reason``, and ``wait_s``, what it
        blocked; counter ``output.vtk.drain_waits`` where that was over a
        millisecond).  A write that failed raises here, naming its file.
        Nothing in flight: returns at once."""
        piece, self._output_file = self._output_file, None
        if piece is None:
            return
        with telemetry.span("output.vtk.drain", reason=reason) as sp:
            try:
                if sp.blocked(self._output.wait) > 1e-3:
                    telemetry.counter("output.vtk.drain_waits")
            except Exception as e:
                raise OutputError(f"writing {piece} failed: {e!r}") from e

    @contextlib.contextmanager
    def output_drained(self, reason: str) -> Iterator[None]:
        """Leave the block with no write in flight, however it is left.
        A failed write fails the run here; where another exception is
        already on its way that one wins and the write's is logged."""
        try:
            yield
        except BaseException:
            try:
                self.drain_output(reason)
            except OutputError as e:
                from tclb_tpu.utils import log
                log.warning(str(e))
            raise
        self.drain_output(reason)

    def write_txt(self, what: Optional[set[str]] = None,
                  gzip_out: bool = True) -> list[str]:
        """Per-quantity text dumps (reference cbTXT/writeTXT gzip path,
        src/Solver.cpp.Rt:228-260)."""
        import gzip

        from tclb_tpu.checkpoint.writer import atomic_path
        if not self.is_main:
            return []
        paths = []
        with telemetry.span("output.txt", iteration=self.iter):
            for name, arr in self.quantity_arrays(what).items():
                p = self.out_path(f"TXT_{name}",
                                  "txt.gz" if gzip_out else "txt")
                a2 = arr.reshape(-1, arr.shape[-1])
                with atomic_path(p) as tmp:
                    if gzip_out:
                        with gzip.open(tmp, "wt") as f:
                            np.savetxt(f, a2)
                    else:
                        np.savetxt(tmp, a2)
                paths.append(p)
        return paths

    def write_bin(self) -> Optional[str]:
        """Raw binary dump of all storage planes (reference cbBIN,
        src/Handlers.cpp.Rt:1011-1027)."""
        if not self.is_main:
            return None
        p = self.out_path("BIN", "npz")
        with telemetry.span("output.bin", iteration=self.iter):
            self.lattice.save(p)
        return p


# --------------------------------------------------------------------------- #
# Config entry points (reference main(), src/main.cpp.Rt:172-346)
# --------------------------------------------------------------------------- #


def _read_units(root: ET.Element, solver: Solver) -> None:
    """<Units><Params Re="100" gauge="1"/>...</Units> (reference readUnits,
    src/main.cpp.Rt:35-62)."""
    units = root.find("Units")
    if units is None:
        return
    for p in units.findall("Params"):
        gauge = p.get("gauge", "1")
        rest = {k: v for k, v in p.attrib.items() if k != "gauge"}
        if len(rest) != 1:
            raise ValueError(
                f"exactly one variable per Units/Params, got {sorted(rest)}")
        (name, value), = rest.items()
        solver.set_unit(name, value, gauge)
    solver.gauge()


def run_config_string(xml_text: str, model: Model, mesh: Any = None,
                      dtype: Any = None, output: Optional[str] = None,
                      conf_name: str = "run",
                      resume: Optional[str] = None) -> Solver:
    root = ET.fromstring(xml_text)
    return _run_root(root, model, mesh, dtype, output, conf_name,
                     resume=resume)


def run_config(path: str, model: Model, mesh: Any = None,
               dtype: Any = None, output: Optional[str] = None,
               resume: Optional[str] = None) -> Solver:
    root = ET.parse(path).getroot()
    name = os.path.splitext(os.path.basename(path))[0]
    return _run_root(root, model, mesh, dtype, output, name, resume=resume)


def _run_root(root: ET.Element, model: Model, mesh, dtype,
              output: Optional[str], conf_name: str,
              resume: Optional[str] = None) -> Solver:
    from tclb_tpu.control.handlers import MainContainer
    if root.tag != "CLBConfig":
        raise ValueError(f"config root must be <CLBConfig>, got <{root.tag}>")
    if output:
        # an explicit prefix (the CLI's --output) wins over the config's
        # own attribute, which <CLBConfig>'s handler would re-apply
        root.set("output", output)
    # the case before its elements: units, sizes, the lattice's arrays
    with telemetry.span("startup.case", model=model.name) as sp:
        solver = Solver(model, output=root.get("output", "output/"),
                        mesh=mesh, dtype=dtype)
        solver.conf_name = conf_name
        solver.resume_from = resume
        _read_units(root, solver)
        geom = root.find("Geometry")
        if geom is None:
            raise ValueError("config must contain a <Geometry> element")
        if model.ndim == 2:
            shape = (int(round(solver.units.alt(geom.get("ny", "1")))),
                     int(round(solver.units.alt(geom.get("nx", "1")))))
        else:
            shape = (int(round(solver.units.alt(geom.get("nz", "1")))),
                     int(round(solver.units.alt(geom.get("ny", "1")))),
                     int(round(solver.units.alt(geom.get("nx", "1")))))
        solver.set_size(shape)
        sp.add(shape=list(shape))
    with solver.output_drained("run_end"):
        MainContainer(root, solver).init()
    if solver.resume_from is not None:
        from tclb_tpu.utils import log
        log.warning("--resume was given but the config has no "
                    "<SaveCheckpoint> handler — nothing was restored")
    return solver
