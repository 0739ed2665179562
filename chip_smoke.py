#!/usr/bin/env python3
"""Prove on the chip that the forward solver starts and computes.

    python chip_smoke.py              one TPU chip, every phase below
    python chip_smoke.py --chips 4    four chips: the sharded paths (2D
                                      in y, 3D in z) and what each is
                                      held against, only
    python chip_smoke.py --rehearse   control-flow rehearsal at tiny
                                      sizes on any backend; can never
                                      print ``"ok": true``
    python chip_smoke.py --only TEXT  the phases whose name holds TEXT

Everything runs in this one process, through the function the ``tclb``
console script runs (``tclb_tpu.__main__``): a chip belongs to one
process at a time, so no child is started.  Each phase fails the script
on an exception, a non-finite or mis-shaped output field, a ``failcheck``
or ``engine_fallback`` event, or an engine family other than the one
expected — a run that finished on the XLA step under a Pallas name is a
failure here, not a result.  One phase turns that round: it plants a NaN
and an infinity and fails unless ``<Failcheck>`` stops the run on them.
``TCLB_FASTPATH`` is never set to ``force``: the engines are whatever
``Lattice`` selects on this backend.

Without an accelerator the script exits non-zero and prints no result.
The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The seconds printed on earlier lines are smoke observations (one
reading, compilation included where it says so), not benchmark numbers.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import struct
import sys
import time
import traceback
import xml.etree.ElementTree as ET
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
EXAMPLE = os.path.join(HERE, "example")
OUT = os.path.join(HERE, "output", "chip_smoke")     # git-ignored

#: Pallas engine vs the XLA step, max |difference| over all populations
#: after AGREE_STEPS steps from one initial state.  f32 (eps 1.2e-7) with
#: populations of order 1: the kernels re-associate the same arithmetic
#: (no barrier inside a compiled kernel), the repo's 10-step interpret
#: tests allow atol 2e-6, parallel/halo.py reports <= 4e-7 for the
#: sharded path.  A wrong kernel is off by 1e-3 or more.
TOL = 2e-5
AGREE_STEPS = 100


# --------------------------------------------------------------------------- #
# case files and outputs
# --------------------------------------------------------------------------- #


def cut_case(src: str, dst: str, solve: int, handlers: bool = True,
             geometry: dict | None = None) -> str:
    """Copy case ``src`` to ``dst`` with ``<Solve>`` cut to ``solve``
    steps; periodic handlers keep their place but fire no later than the
    end (``handlers=False`` drops them).  ``geometry`` overrides size
    attributes (rehearsal only).  Model, parameters and painting stay."""
    tree = ET.parse(src)
    root = tree.getroot()
    for el in list(root):
        if el.tag == "Solve":
            el.set("Iterations", str(solve))
        elif el.tag in ("Log", "VTK", "Failcheck"):
            if not handlers:
                root.remove(el)
            elif int(el.get("Iterations", "0")) > solve:
                el.set("Iterations", str(solve))
        elif el.tag == "Geometry" and geometry:
            for k, v in geometry.items():
                el.set(k, str(v))
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    tree.write(dst)
    return dst


def read_vti(path: str) -> dict:
    """Arrays of a .vti written by tclb_tpu/utils/vtk.py (appended raw
    blocks, plain or vtkZLibDataCompressor), as flat numpy arrays with
    the piece's cell count under ``"__cells__"``."""
    import numpy as np
    raw = open(path, "rb").read()
    head, body = raw.split(b'<AppendedData encoding="raw">\n_', 1)
    body = body.rsplit(b"\n</AppendedData>", 1)[0]
    head = head.decode()
    compressed = "vtkZLibDataCompressor" in head
    ext = [int(v) for v in
           re.search(r'<Piece Extent="([^"]+)"', head).group(1).split()]
    out = {"__cells__": (ext[1] - ext[0]) * (ext[3] - ext[2])
           * (ext[5] - ext[4])}
    types = {"Float32": np.float32, "Float64": np.float64,
             "UInt16": np.uint16, "UInt8": np.uint8, "Int32": np.int32,
             "UInt32": np.uint32}
    for m in re.finditer(r'<DataArray type="(\w+)" Name="([^"]+)" '
                         r'NumberOfComponents="(\d+)" format="appended" '
                         r'offset="(\d+)"/>', head):
        typ, name, ncomp, off = m.group(1), m.group(2), int(m.group(3)), \
            int(m.group(4))
        if compressed:
            nblocks = struct.unpack_from("<I", body, off)[0]
            sizes = struct.unpack_from(f"<{nblocks}I", body, off + 12)
            pos = off + 12 + 4 * nblocks
            chunks = []
            for s in sizes:
                chunks.append(zlib.decompress(body[pos:pos + s]))
                pos += s
            data = b"".join(chunks)
        else:
            n = struct.unpack_from("<I", body, off)[0]
            data = body[off + 4:off + 4 + n]
        a = np.frombuffer(data, dtype=types[typ])
        if a.size != out["__cells__"] * ncomp:
            raise AssertionError(f"{path}: {name} holds {a.size} values, "
                                 f"expected {out['__cells__'] * ncomp}")
        out[name] = a
    return out


#: the planted values of the ``failcheck_fires`` phases: (population,
#: rows from the top edge, column, value); the rows lie in the last shard
PLANTED = ((3, 5, 40, float("nan")), (3, 9, 60, float("inf")))


def spoil(solver) -> int:
    """``<CallPython>`` of the ``failcheck_fires`` phases: PLANTED written
    into the populations where they lie, on one chip or a mesh."""
    import jax
    lat = solver.lattice
    fields, ny = lat.state.fields, lat.shape[-2]
    sharding = fields.sharding
    for plane, up, x, value in PLANTED:
        fields = fields.at[plane, ny - up, x].set(value)
    lat.state = lat.state.replace(fields=jax.device_put(fields, sharding))
    return 0


# --------------------------------------------------------------------------- #
# the smoke
# --------------------------------------------------------------------------- #


#: --rehearse only: each case cut to (steps, size) small enough for the
#: CPU and interpret mode, painted objects still inside the domain
REHEARSAL = {
    "karman.xml": (40, {"nx": 256}),
    "karman_1024.xml": (20, {"nx": 256, "ny": 512}),
    # the probes reach x 920 and y 612: only the rows above them go
    "karman_1024_probes.xml": (12, {"nx": 1024, "ny": 640}),
    "3d_channel.xml": (8, {"nx": 128, "ny": 16, "nz": 8}),
    "3d_channel_512.xml": (8, {"nx": 128, "ny": 16, "nz": 16}),
    "tgv_256.xml": (8, {"nx": 128, "ny": 16, "nz": 8}),
    "tgv_384.xml": (8, {"nx": 128, "ny": 16, "nz": 32}),
    "drop_512.xml": (10, {"nx": 384, "ny": 384}),
    "karman_4096.xml": (12, {"nx": 128, "ny": 256}),
    # rows still wide enough for the raised scoped-VMEM limit
    "karman_8192.xml": (8, {"nx": 2048, "ny": 64}),
}
#: example/karman_8192.xml cut to 256 rows of its 8192 nodes (a plain
#: channel: the obstacle lies below the cut): the bands and the limit
#: are those of the whole case, the state 92 MB
WIDE_ROWS = {"ny": 256}
WIDE_STEPS = 20
#: example/tgv_384.xml cut to a box four chips hold beside the XLA
#: step's own copies (shards of 32 x 256 x 256, 0.29 GB each): the plane
#: is still one no kernel holds whole, so each shard's windows are tiled
ZSPLIT_BOX = {"nx": 256, "ny": 256, "nz": 128}


def case_size(case: str) -> tuple:
    """(number of nodes, ``<Solve>`` steps) that ``case`` asks for."""
    root = ET.parse(case).getroot()
    g = root.find("Geometry")
    nodes = 1
    for k in ("nx", "ny", "nz"):
        nodes *= int(g.get(k, "1"))
    return nodes, int(root.find("Solve").get("Iterations"))


def run_argv(case: str, outdir: str, mesh: str | None = None) -> list:
    """The command line of ``tclb run case`` writing under ``outdir``."""
    a = ["run", case, "--output", outdir + "/"]
    return a + ["--mesh", mesh] if mesh else a


class Smoke:
    def __init__(self, rehearse: bool, only: str = ""):
        from tclb_tpu import telemetry
        self.rehearse = rehearse
        self.only = only
        self.events: list[dict] = []
        self.failed: list[str] = []
        telemetry.subscribe(self.events.append)

    def case(self, name: str, solve: int | None = None,
             handlers: bool = True, geometry: dict | None = None) -> str:
        """The case file a phase runs: ``example/<name>`` itself, or a
        copy under OUT with ``<Solve>`` cut to ``solve`` steps and the
        size attributes of ``geometry`` (and, in a rehearsal, everything
        cut to REHEARSAL's size)."""
        src = os.path.join(EXAMPLE, name)
        if self.rehearse:
            steps, geometry = REHEARSAL[name]
            if handlers:       # a run phase; an agreement keeps its length
                solve = steps
        if solve is None:
            return src
        tag = "cases" if handlers else "cases_bare"
        return cut_case(src, os.path.join(OUT, tag, name), solve,
                        handlers=handlers, geometry=geometry)

    # -- one `tclb run` ---------------------------------------------------- #

    def expect_engine(self, tag: str, engine: tuple) -> None:
        """Under a forced interpret-mode rehearsal the tags are the
        chip's; on a plain CPU rehearsal every engine is "xla": report,
        not fail."""
        forced = os.environ.get("TCLB_FASTPATH") == "force"
        if (not self.rehearse or forced) and not tag.startswith(engine):
            raise AssertionError(f"engine {tag!r}, expected one of "
                                 f"{engine}")

    def check_events(self, ev: list, engine: tuple, steps: int | None
                     ) -> dict:
        """The engine this run selected, after checking that it is of an
        expected family and that nothing fell back or failed a check."""
        sel = [e for e in ev if e.get("kind") == "engine_selected"]
        if len(sel) != 1:
            raise AssertionError(f"{len(sel)} engine_selected events")
        tag = sel[0]["engine"]
        fb = [e for e in ev if e.get("kind") == "engine_fallback"]
        if fb:
            raise AssertionError(
                "engine_fallback: " + "; ".join(
                    f"{e.get('from')} -> {e.get('to')} ({e.get('cause')})"
                    for e in fb))
        fc = [e for e in ev if e.get("kind") == "failcheck"]
        if fc:
            raise AssertionError(f"failcheck fired: {fc[0]}")
        self.expect_engine(tag, engine)
        spans = [e for e in ev if e.get("kind") == "span"
                 and e.get("name") == "iterate"]
        bad = [e for e in spans if e.get("ok") is False]
        if bad:
            raise AssertionError(f"iterate failed: {bad[0].get('error')}")
        done = sum(int(e["iters"]) for e in spans)
        if steps is not None and done != steps:
            raise AssertionError(f"{done} steps ran, expected {steps}")
        first = spans[0]
        rest = spans[1:]
        return {"engine": tag, "steps": done,
                # first call = compile + its steps; the rest are fenced
                # with block_until_ready by the iterate span
                "first_call_s": first["dur_s"],
                "first_call_steps": int(first["iters"]),
                "later_calls_s": round(sum(e["dur_s"] for e in rest), 6),
                "later_calls_steps": sum(int(e["iters"]) for e in rest)}

    def run(self, name: str, case: str, engine: tuple) -> None:
        """`tclb run case` through the console script's main(); checks
        events and the last VTK file it wrote."""
        import numpy as np
        from tclb_tpu.__main__ import main as tclb_main
        nodes, steps = case_size(case)
        outdir = os.path.join(OUT, name)
        shutil.rmtree(outdir, ignore_errors=True)
        mark = len(self.events)
        t0 = time.perf_counter()
        rc = tclb_main(run_argv(case, outdir))
        wall = time.perf_counter() - t0
        if rc != 0:
            raise AssertionError(f"tclb run exited {rc}")
        info = self.check_events(self.events[mark:], engine, steps)
        vtis = sorted(glob.glob(os.path.join(outdir, "*_VTK_*.vti")))
        if not vtis:
            raise AssertionError(f"no VTK output in {outdir}")
        last = vtis[-1]
        if not last.endswith(f"_{steps:08d}.vti"):
            raise AssertionError(f"last VTK is {os.path.basename(last)}, "
                                 f"expected iteration {steps}")
        arrays = read_vti(last)
        cells = arrays.pop("__cells__")
        if cells != nodes:
            raise AssertionError(f"VTK holds {cells} cells, expected "
                                 f"{nodes}")
        for qn, a in arrays.items():
            if a.dtype.kind == "f" and not np.isfinite(a).all():
                raise AssertionError(f"{qn} is not finite in {last}")
        log = glob.glob(os.path.join(outdir, "*_Log.csv"))
        info.update(phase=name, case=os.path.relpath(case, HERE),
                    nodes=nodes, total_s=round(wall, 3),
                    vtk=os.path.basename(last),
                    vtk_arrays=sorted(arrays), log_csv=bool(log))
        print(json.dumps(info), flush=True)

    # -- Pallas vs XLA, and mesh vs one device ------------------------------ #

    def fields_after(self, case: str, outdir: str, engine: tuple,
                     mesh: str | None = None, xla: bool = False):
        """Run ``case`` through the CLI's run_case() and return
        (host copy of the final populations, engine tag, lattice)."""
        import numpy as np
        from tclb_tpu.__main__ import build_parser, run_case
        args = build_parser().parse_args(run_argv(case, outdir, mesh))
        mark = len(self.events)
        before = os.environ.get("TCLB_FASTPATH")
        if xla:
            os.environ["TCLB_FASTPATH"] = "0"    # the plain reference
        try:
            solver = run_case(args)
        finally:
            if xla and before is None:
                del os.environ["TCLB_FASTPATH"]
            elif xla:
                os.environ["TCLB_FASTPATH"] = before
        info = self.check_events(self.events[mark:],
                                 ("xla",) if xla else engine, None)
        if xla and info["engine"] != "xla":
            raise AssertionError(f"reference ran on {info['engine']}")
        lat = solver.lattice
        f = np.asarray(lat.state.fields)
        if not np.isfinite(f).all():
            raise AssertionError("non-finite populations")
        return f, info, lat

    def agree(self, name: str, file: str, engine: tuple,
              steps: int = AGREE_STEPS, geometry: dict | None = None,
              mesh: str | None = None) -> None:
        """``steps`` steps of example ``file`` (at the size ``geometry``
        cuts it to) on the selected Pallas engine and on the XLA step
        (with ``mesh``: both on that device mesh), same initial state;
        max |diff| <= TOL."""
        import numpy as np
        outdir = os.path.join(OUT, name)
        shutil.rmtree(outdir, ignore_errors=True)
        cut = self.case(file, steps, handlers=False, geometry=geometry)
        fp, ip, lat = self.fields_after(cut, outdir, engine, mesh=mesh)
        # the flow has to have moved, or agreement would be vacuous
        umax = float(np.max(np.abs(np.asarray(lat.get_quantity("U")))))
        fx, _, _ = self.fields_after(cut, outdir, engine, mesh=mesh,
                                     xla=True)
        diff = float(np.max(np.abs(fp - fx)))
        print(json.dumps({"phase": name, "engine": ip["engine"],
                          "reference": "xla", "steps": steps,
                          "max_abs_diff": diff, "tolerance": TOL,
                          "max_abs_field": float(np.max(np.abs(fx))),
                          "max_abs_u": umax}), flush=True)
        if not umax > 0.0:
            raise AssertionError("velocity is zero everywhere after "
                                 f"{steps} steps")
        if not diff <= TOL:
            raise AssertionError(f"{ip['engine']} vs XLA: max |diff| "
                                 f"{diff:.3e} > {TOL:.1e}")

    def sharded(self, name: str, file: str, mesh: str) -> None:
        """Example ``file`` on the device mesh and on device 0 alone:
        sharded Pallas engine, one shard per device, agreement within
        TOL."""
        import numpy as np
        outdir = os.path.join(OUT, name)
        shutil.rmtree(outdir, ignore_errors=True)
        cut = self.case(file)
        steps = case_size(cut)[1]
        n = int(np.prod([int(v) for v in mesh.split("x")]))
        fm, im, lat = self.fields_after(cut, outdir, ("pallas_sharded[",),
                                        mesh=mesh)
        shards = lat.state.fields.addressable_shards
        devs = {s.device for s in shards}
        if len(shards) != n or len(devs) != n:
            raise AssertionError(f"{len(shards)} shards on {len(devs)} "
                                 f"devices, expected {n} on {n}")
        rows = sorted((s.index[1].start or 0, s.data.shape[1])
                      for s in shards)
        f1, i1, _ = self.fields_after(
            cut, outdir, ("pallas_2d[", "pallas_resident["))
        diff = float(np.max(np.abs(fm - f1)))
        print(json.dumps({
            "phase": name, "case": os.path.relpath(cut, HERE),
            "mesh": mesh, "engine": im["engine"], "steps": steps,
            "shards": len(shards),
            "shard_devices": sorted(str(d) for d in devs),
            "shard_rows": rows,
            "first_call_s": im["first_call_s"],
            "later_calls_s": im["later_calls_s"],
            "later_calls_steps": im["later_calls_steps"],
            "one_device_engine": i1["engine"],
            "one_device_later_calls_s": i1["later_calls_s"],
            "max_abs_diff": diff, "tolerance": TOL}), flush=True)
        if not diff <= TOL:
            raise AssertionError(f"mesh {mesh} vs one device: max |diff| "
                                 f"{diff:.3e} > {TOL:.1e}")

    # -- the guard itself ---------------------------------------------------- #

    def failcheck_fires(self, name: str, file: str, engine: tuple,
                        mesh: str | None = None) -> None:
        """Example ``file`` with its handlers replaced by ``<Failcheck>``
        every ``step`` steps with a ``<VTK/>`` rescue child, and PLANTED
        written at the second firing: the first passes and brings four
        bytes a quantity to the host, the second names ``Rho``, counts
        two, writes one file and stops the run; every quantity's count
        on the device equals a host scan of its plane."""
        import numpy as np
        from tclb_tpu.__main__ import build_parser, run_case
        step = 4 if self.rehearse else 100
        outdir = os.path.join(OUT, name)
        shutil.rmtree(outdir, ignore_errors=True)
        tree = ET.parse(self.case(file, 3 * step, handlers=False))
        root = tree.getroot()
        at = list(root).index(root.find("Solve"))
        root.insert(at, ET.Element("CallPython", {
            "module": "chip_smoke", "function": "spoil",
            "Iterations": str(2 * step)}))
        guard = ET.Element("Failcheck", {"Iterations": str(step)})
        guard.append(ET.Element("VTK"))
        root.insert(at + 1, guard)
        cut = os.path.join(OUT, "cases_guard", file)
        os.makedirs(os.path.dirname(cut), exist_ok=True)
        tree.write(cut)

        mark = len(self.events)
        solver = run_case(build_parser().parse_args(
            run_argv(cut, outdir, mesh)))
        ev = self.events[mark:]
        tag = next(e["engine"] for e in ev
                   if e.get("kind") == "engine_selected")
        self.expect_engine(tag, engine)
        spans = [e for e in ev if e.get("kind") == "span"]
        done = sum(int(e["iters"]) for e in spans if e["name"] == "iterate")
        if done != 2 * step:
            raise AssertionError(f"{done} steps ran, expected the run to "
                                 f"stop after {2 * step}")
        lat = solver.lattice
        names = [q.name for q in lat.model.quantities if not q.adjoint]
        hits = [(e["iteration"], e["quantity"], e["n_bad"])
                for e in ev if e.get("kind") == "failcheck"]
        if hits != [(2 * step, "Rho", len(PLANTED))]:
            raise AssertionError(f"failcheck events {hits}, expected one "
                                 f"for Rho at {2 * step} counting 2")
        guards = [e for e in spans if e["name"] == "handler"
                  and e.get("handler") == "cbFailcheck"]
        to_host = []
        for g in guards:
            # its own children are count programs and one copy of the
            # counts; planes come down under the rescue's output.vtk only
            kids = [e for e in spans if e.get("parent") == g["id"]]
            evals = [e.get("reduce") for e in kids
                     if e["name"] == "quantity.eval"]
            if evals != ["nonfinite"] * len(names):
                raise AssertionError(f"quantity.eval spans {evals}")
            to_host.append(sum(e["bytes"] for e in kids
                               if e["name"] == "quantity.d2h"))
        if to_host != [4 * len(names)] * 2:
            raise AssertionError(f"bytes to the host {to_host}, expected "
                                 f"{4 * len(names)} at each of 2 firings")
        writes = sum(e["name"] == "output.vtk" for e in spans)
        if writes != 1:
            raise AssertionError(f"{writes} output.vtk spans, expected 1")
        vtis = sorted(glob.glob(os.path.join(outdir, "*_VTK_*.vti")))
        if [os.path.basename(v)[-12:] for v in vtis] \
                != [f"{2 * step:08d}.vti"]:
            raise AssertionError(f"rescue files {vtis}, expected one")
        rho = read_vti(vtis[0])["Rho"]
        if int(rho.size - np.isfinite(rho).sum()) != len(PLANTED):
            raise AssertionError("the rescue file does not hold the "
                                 "planted values")
        counts = {}
        for q in names:
            c = lat.count_nonfinite(q)
            plane = np.asarray(lat.get_quantity(q))
            counts[q] = int(c)
            if counts[q] != int(plane.size - np.isfinite(plane).sum()):
                raise AssertionError(f"{q}: the device counts {counts[q]}"
                                     ", a host scan otherwise")
            if len(c.sharding.device_set) != len(
                    lat.state.fields.sharding.device_set):
                raise AssertionError(f"{q}: count on {c.sharding}")
        ny = lat.shape[-2]
        print(json.dumps({
            "phase": name, "case": os.path.relpath(cut, HERE),
            "mesh": mesh, "engine": tag, "steps": done,
            "failcheck": hits[0], "counts": counts,
            "bytes_to_host": to_host, "rescue": os.path.basename(vtis[0]),
            "planted_rows": [ny - up for _, up, _, _ in PLANTED],
            "devices": len(lat.state.fields.sharding.device_set),
            "failcheck_ms": [round(1e3 * g["dur_s"], 3) for g in guards],
        }), flush=True)

    # -- phase bookkeeping --------------------------------------------------- #

    def phase(self, name: str, fn, *a, **k) -> None:
        if self.only not in name:
            return
        print(f"--- {name}", flush=True)
        try:
            fn(name, *a, **k)
        except Exception:  # noqa: BLE001 — report, go on, fail at the end
            traceback.print_exc()
            sys.stderr.flush()
            print(f"FAILED {name}", flush=True)
            self.failed.append(name)


def one_chip(s: Smoke) -> None:
    # the registry-driven generic engine, band or VMEM-resident flavour
    # (at 512x512 the Lattice picks the resident one)
    generic = ("pallas_generic[d2q9_kuper,fuse=",
               "pallas_resident_generic[d2q9_kuper]")
    cumulant = ("pallas_d3q[d3q27_cumulant,fuse=",)
    s.phase("2d_karman_as_shipped", s.run, s.case("karman.xml"),
            ("pallas_resident[d2q9,", "pallas_2d[d2q9,"))
    s.phase("2d_karman_1024", s.run, s.case("karman_1024.xml"),
            ("pallas_2d[d2q9,fuse=2]",))
    # <Sample>: the sampled run keeps the tuned band, one step a call
    s.phase("2d_karman_1024_probes", s.run, s.case("karman_1024_probes.xml"),
            ("pallas_2d[d2q9,fuse=1]",))
    s.phase("3d_channel", s.run, s.case("3d_channel.xml", 1000), cumulant)
    s.phase("3d_channel_512", s.run, s.case("3d_channel_512.xml"), cumulant)
    # a 256 x 256 plane, which the engine tiles in y; its initial field is
    # set by <CallPython>
    s.phase("3d_tgv_256", s.run, s.case("tgv_256.xml", 500), cumulant)
    s.phase("generic_drop_512", s.run, s.case("drop_512.xml"), generic)
    s.phase("agree_d2q9", s.agree, "karman_1024.xml", ("pallas_2d[d2q9,",))
    # rows of 8192 nodes: bands planned under the raised scoped-VMEM
    # limit, the first call probed; a minute on the chip
    s.phase("agree_d2q9_wide_rows", s.agree, "karman_8192.xml",
            ("pallas_2d[d2q9,fuse=2]",), WIDE_STEPS, WIDE_ROWS)
    s.phase("agree_d3q27_cumulant", s.agree, "3d_channel.xml", cumulant)
    s.phase("agree_d3q27_cumulant_tiled", s.agree, "tgv_256.xml", cumulant)
    s.phase("agree_d2q9_kuper", s.agree, "drop_512.xml", generic)
    s.phase("failcheck_fires", s.failcheck_fires, "karman_1024.xml",
            ("pallas_2d[d2q9,fuse=2]",))


def four_chips(s: Smoke) -> None:
    s.phase("sharded_4x1", s.sharded, "karman_4096.xml", "4x1")
    # a 3D box split in z, its 256 x 256 plane tiled in y on every
    # shard: the fused kernel on the neighbours' exchanged slabs, and the
    # last step on the sharded Pallas tail, against the sharded XLA step
    s.phase("agree_zsplit_4x1x1", s.agree, "tgv_384.xml",
            ("pallas_sharded[{'z': 4, 'y': 1, 'x': 1},fuse=",),
            AGREE_STEPS, ZSPLIT_BOX, "4x1x1")
    s.phase("failcheck_fires_4x1", s.failcheck_fires, "karman_4096.xml",
            ("pallas_sharded[",), mesh="4x1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and what it is "
                    "compared with, on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend, to find wrong paths "
                    "and arguments before a chip run; never prints ok")
    ap.add_argument("--only", default="", metavar="TEXT",
                    help="run only the phases whose name holds TEXT; the "
                    "last line then says so")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no accelerator (JAX reports {device}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"JAX reports {len(devs)}", file=sys.stderr)
        return 2

    import jaxlib
    import numpy as np

    from tclb_tpu import native
    from tclb_tpu.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — not installed as a distribution
        libtpu = None
    print(json.dumps({
        "device": device, "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "numpy": np.__version__, "python": sys.version.split()[0],
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start":
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
        # the compressed VTK of 2d_karman_1024 goes through this library
        # when it built, else through the zlib fallback in Python
        "native_library": "built" if native.available()
        else "absent: Python fallback writes the compressed VTK",
        "rehearsal": args.rehearse}), flush=True)

    t0 = time.perf_counter()
    s = Smoke(args.rehearse, args.only)
    (four_chips if args.chips == 4 else one_chip)(s)
    print(json.dumps({"total_s": round(time.perf_counter() - t0, 1),
                      "failed": s.failed}), flush=True)
    if s.failed:
        return 1
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device,
                      **({"only": args.only} if args.only else {})}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
