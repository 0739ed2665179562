"""VTI (VTK ImageData) + CSV output writers.

Parity target: the reference's parallel VTI writer (reference
src/vtkLattice.cpp.Rt:17-75, src/vtkOutput.cpp) which emits a .pvti master +
per-rank .vti pieces with appended raw binary data, per-Quantity arrays and
node-type-group flag layers, and the CSV ``Log`` fan-out
(src/Solver.cpp.Rt:120-206).

Here quantities are computed on-device over the (sharded) lattice and
fetched once; files are written with the "appended" raw encoding the
reference uses (base64 would bloat; raw is what VTK tools read fastest).
A single .vti plus a .pvti master referencing it keeps tool compatibility
with the reference's output convention.
"""

from __future__ import annotations

import os
import struct
from typing import Iterable

import numpy as np

from tclb_tpu import telemetry


def _vtk_type(a: np.ndarray) -> str:
    return {
        np.dtype(np.float32): "Float32", np.dtype(np.float64): "Float64",
        np.dtype(np.uint16): "UInt16", np.dtype(np.uint8): "UInt8",
        np.dtype(np.int32): "Int32", np.dtype(np.uint32): "UInt32",
    }[a.dtype]


def write_vti(path: str, arrays: dict[str, np.ndarray],
              spacing: float = 1.0, origin=(0.0, 0.0, 0.0),
              compress: bool = False) -> str:
    """Write point-data arrays on a uniform grid to ``path``.vti.

    Every array is (nz, ny, nx) scalar or (3, nz, ny, nx) vector — 2D inputs
    get a unit z axis.  Appended raw-binary encoding (reference vtkOutput's
    appended data blocks, src/vtkOutput.cpp); ``compress=True`` switches the
    blocks to vtkZLibDataCompressor layout (native C++ encoder in
    tclb_tpu/native when available, which compresses the 32 KB blocks in
    parallel on the usable host cores but two; the file does not depend on the
    thread count) — every VTK reader understands it and large fields
    shrink ~3x.  The file appears under its name whole (written beside
    it, then renamed).
    """
    norm: dict[str, np.ndarray] = {}
    shape = None
    for name, a in arrays.items():
        a = np.asarray(a)
        if a.ndim == 2:
            a = a[None]                      # (1, ny, nx)
        elif a.ndim == 3 and a.shape[0] == 3 and len(arrays) and any(
                np.asarray(v).ndim == 2 for v in arrays.values()):
            a = a[:, None]                   # vector on 2D grid
        norm[name] = a
        s = a.shape[-3:]
        if shape is None:
            shape = s
        elif s != shape:
            raise ValueError(f"array {name}: shape {s} != {shape}")
    nz, ny, nx = shape
    extent = f"0 {nx} 0 {ny} 0 {nz}"

    # cell data: VTK extent counts points; our lattice nodes are cells
    comp_attr = ' compressor="vtkZLibDataCompressor"' if compress else ""
    head = [
        '<?xml version="1.0"?>',
        '<VTKFile type="ImageData" version="0.1" '
        f'byte_order="LittleEndian" header_type="UInt32"{comp_attr}>',
        f'<ImageData WholeExtent="{extent}" Origin="{origin[0]} {origin[1]} '
        f'{origin[2]}" Spacing="{spacing} {spacing} {spacing}">',
        f'<Piece Extent="{extent}">',
        "<CellData>",
    ]
    offset = bytes_in = 0
    blocks: list[bytes | memoryview] = []
    encoder: dict = {}               # what zlib_blocks did: the span says
    with telemetry.span("output.vtk.encode", compress=compress) as sp:
        for name, a in norm.items():
            ncomp = a.shape[0] if a.ndim == 4 else 1
            if a.ndim == 4:
                flat = np.ascontiguousarray(np.moveaxis(a, 0, -1))
            else:
                flat = np.ascontiguousarray(a)
            head.append(
                f'<DataArray type="{_vtk_type(a)}" Name="{name}" '
                f'NumberOfComponents="{ncomp}" format="appended" '
                f'offset="{offset}"/>')
            if compress:
                from tclb_tpu.native import zlib_blocks
                blocks.append(zlib_blocks(flat, stats=encoder))
            else:
                blocks.append(struct.pack("<I", flat.nbytes)
                              + flat.tobytes())
            offset += len(blocks[-1])
            bytes_in += flat.nbytes
        sp.add(bytes_in=bytes_in, bytes_out=offset, **encoder)
    head += ["</CellData>", "</Piece>", "</ImageData>",
             '<AppendedData encoding="raw">']
    if not path.endswith(".vti"):
        path += ".vti"
    from tclb_tpu.checkpoint.writer import atomic_path
    with telemetry.span("output.vtk.file") as sp:
        # under a temporary name, then renamed: a reader (or a run that
        # dies in the write) never sees half a file
        with atomic_path(path) as tmp, open(tmp, "wb") as f:
            f.write("\n".join(head).encode())
            f.write(b"\n_")
            for b in blocks:
                f.write(b)
            f.write(b"\n</AppendedData>\n</VTKFile>\n")
            sp.add(bytes=f.tell())
    return path


def write_pvti(path: str, piece: str, arrays: dict[str, np.ndarray],
               spacing: float = 1.0) -> str:
    """Master file referencing the piece (reference rank-0 .pvti,
    src/vtkOutput.cpp)."""
    sample = next(iter(arrays.values()))
    a = np.asarray(sample)
    if a.ndim == 2:
        nz, (ny, nx) = 1, a.shape
    else:
        nz, ny, nx = a.shape[-3:]
    extent = f"0 {nx} 0 {ny} 0 {nz}"
    lines = [
        '<?xml version="1.0"?>',
        '<VTKFile type="PImageData" version="0.1" '
        'byte_order="LittleEndian">',
        f'<PImageData WholeExtent="{extent}" GhostLevel="0" '
        f'Origin="0 0 0" Spacing="{spacing} {spacing} {spacing}">',
        "<PCellData>",
    ]
    for name, arr in arrays.items():
        arr = np.asarray(arr)
        ncomp = 3 if (arr.ndim == 3 and arr.shape[0] == 3 and a.ndim == 2) \
            or arr.ndim == 4 else 1
        lines.append(f'<PDataArray type="{_vtk_type(arr)}" Name="{name}" '
                     f'NumberOfComponents="{ncomp}"/>')
    lines += ["</PCellData>",
              f'<Piece Extent="{extent}" Source="{os.path.basename(piece)}"/>',
              "</PImageData>", "</VTKFile>"]
    if not path.endswith(".pvti"):
        path += ".pvti"
    from tclb_tpu.checkpoint.writer import atomic_write_bytes
    atomic_write_bytes(path, "\n".join(lines).encode())
    return path


class CSVLog:
    """The reference's CSV ``Log``: one row per callback with iteration,
    SI time, walltime, settings (lattice+SI), zonal settings per zone,
    globals (lattice+SI) and unit scales (reference initLog/writeLog,
    src/Solver.cpp.Rt:120-206)."""

    def __init__(self, path: str):
        self.path = path
        self._header: list[str] | None = None
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    def write(self, row: dict[str, float]) -> int:
        """Append ``row``; the characters written (ASCII: the bytes)."""
        n = 0
        if self._header is None:
            self._header = list(row.keys())
            with open(self.path, "w") as f:
                n = f.write(",".join(f'"{h}"' for h in self._header) + "\n")
        with open(self.path, "a") as f:
            return n + f.write(",".join(repr(float(row.get(h, 0.0)))
                                        for h in self._header) + "\n")


def csvdiff(a: str, b: str, tol: float = 1e-10,
            skip: Iterable[str] = ("Walltime",)) -> list[str]:
    """Compare two CSV logs with numeric tolerance, discarding volatile
    columns (the reference's golden-test comparator, tools/csvdiff:40-50).
    Returns a list of mismatch descriptions (empty = match)."""
    import csv

    def load(p):
        with open(p) as f:
            r = list(csv.reader(f))
        return r[0], r[1:]

    ha, ra = load(a)
    hb, rb = load(b)
    errs = []
    if ha != hb:
        errs.append(f"headers differ: {ha} vs {hb}")
        return errs
    if len(ra) != len(rb):
        errs.append(f"row counts differ: {len(ra)} vs {len(rb)}")
    for i, (x, y) in enumerate(zip(ra, rb)):
        for h, u, v in zip(ha, x, y):
            if h in skip:
                continue
            try:
                fu, fv = float(u), float(v)
            except ValueError:
                if u != v:
                    errs.append(f"row {i} col {h}: {u!r} != {v!r}")
                continue
            if abs(fu - fv) > tol * max(1.0, abs(fu), abs(fv)):
                errs.append(f"row {i} col {h}: {fu} != {fv} (tol {tol})")
    return errs
