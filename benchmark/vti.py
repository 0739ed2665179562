"""Reader of the .vti files the program writes (appended raw blocks,
plain or vtkZLibDataCompressor), for the check of the window's output.
A copy of ``chip_smoke.py:read_vti``; part of the yardstick."""

from __future__ import annotations

import re
import struct
import zlib

import numpy as np

TYPES = {"Float32": np.float32, "Float64": np.float64, "UInt16": np.uint16,
         "UInt8": np.uint8, "Int32": np.int32, "UInt32": np.uint32}


def read_vti(path: str) -> tuple[int, dict[str, np.ndarray]]:
    """(number of cells of the piece, arrays by name, flat)."""
    with open(path, "rb") as f:
        raw = f.read()
    head, body = raw.split(b'<AppendedData encoding="raw">\n_', 1)
    body = body.rsplit(b"\n</AppendedData>", 1)[0]
    head = head.decode()
    compressed = "vtkZLibDataCompressor" in head
    ext = [int(v) for v in
           re.search(r'<Piece Extent="([^"]+)"', head).group(1).split()]
    cells = (ext[1] - ext[0]) * (ext[3] - ext[2]) * (ext[5] - ext[4])
    out = {}
    for m in re.finditer(r'<DataArray type="(\w+)" Name="([^"]+)" '
                         r'NumberOfComponents="(\d+)" format="appended" '
                         r'offset="(\d+)"/>', head):
        typ, name, ncomp, off = (m.group(1), m.group(2), int(m.group(3)),
                                 int(m.group(4)))
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
        a = np.frombuffer(data, dtype=TYPES[typ])
        if a.size != cells * ncomp:
            raise ValueError(f"{path}: {name} holds {a.size} values, "
                             f"expected {cells * ncomp}")
        out[name] = a
    return cells, out


def check_file(path: str, nodes: int) -> str | None:
    """None when the file holds finite arrays of ``nodes`` cells, else
    what is wrong with it."""
    try:
        cells, arrays = read_vti(path)
    except (OSError, ValueError, AttributeError, struct.error,
            zlib.error) as e:
        return f"unreadable: {e!r}"
    if cells != nodes:
        return f"{cells} cells, the case has {nodes}"
    if not arrays:
        return "no arrays"
    for name, a in arrays.items():
        if a.dtype.kind == "f" and not np.isfinite(a).all():
            return f"{name} is not finite"
    return None
