"""Native host-side kernels: on-demand g++ build + ctypes bindings.

The reference's host layer is C++ (SURVEY.md §2.1 native-code census);
here the two genuinely hot host loops — STL voxelization
(reference src/Geometry.cpp.Rt:462-577) and VTI appended-data encoding
(reference src/vtkOutput.cpp) — are native C++ (src/tclb_native.cpp),
compiled once per checkout into ``_build/`` and loaded via ctypes.  The
VTI encoder compresses its blocks in parallel, on as many threads as the
process has usable cores (less two, left to the thread that launches the
device's programs and the runtime's own: the encoder runs beside them)
and the array has blocks for; the blocks are independent zlib streams,
so the bytes do not depend on the thread count.

Everything degrades gracefully: no compiler, a failed build, or
``TCLB_NATIVE=0`` fall back to the pure-Python implementations
(tclb_tpu/utils/stl.py, zlib stdlib), which remain the test oracle.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import zlib

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src", "tclb_native.cpp")
_lib: ctypes.CDLL | None = None
_tried = False


def _build_lib() -> str | None:
    """Compile (or reuse) the shared lib; returns its path or None.

    Any OSError — missing .cpp in a stripped install, read-only
    site-packages, no compiler — means "no native lib", never a crash."""
    try:
        with open(_SRC, "rb") as f:
            tag = hashlib.sha256(f.read()).hexdigest()[:16]
        out = os.path.join(_DIR, "_build", f"libtclb_native-{tag}.so")
        if os.path.exists(out):
            return out
        os.makedirs(os.path.dirname(out), exist_ok=True)
        tmp = f"{out}.tmp.{os.getpid()}"  # per-pid: parallel builders
        cmd = ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
               _SRC, "-o", tmp, "-lz"]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)  # atomic publish
        return out
    except (OSError, subprocess.SubprocessError):
        return None


def get_lib() -> ctypes.CDLL | None:
    """The loaded native library, building it on first call (or None)."""
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("TCLB_NATIVE", "1") == "0":
        return None
    path = _build_lib()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    lib.tclb_voxelize.restype = ctypes.c_int
    lib.tclb_voxelize.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, ctypes.POINTER(ctypes.c_uint8)]
    lib.tclb_zlib_blocks.restype = ctypes.c_int64
    lib.tclb_zlib_blocks.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int]
    _lib = lib
    return _lib


def available() -> bool:
    return get_lib() is not None


_SIDES = {"in": 0, "out": 1, "surface": 2}


def voxelize(tri: np.ndarray, shape_xyz: tuple[int, int, int],
             side: str = "in") -> np.ndarray | None:
    """Native ray-parity voxelization; None if the native lib is absent.

    Same contract as tclb_tpu.utils.stl.voxelize: bool array [z, y, x].
    """
    lib = get_lib()
    if lib is None:
        return None
    tri = np.ascontiguousarray(tri, dtype=np.float64)
    nx, ny, nz = shape_xyz
    out = np.zeros((nz, ny, nx), dtype=np.uint8)
    rc = lib.tclb_voxelize(
        tri.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), tri.shape[0],
        nx, ny, nz, _SIDES[side],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if rc != 0:
        return None
    return out.astype(bool)


# a block of 32 KB takes zlib about a millisecond and a half, a thread some
# tens of microseconds to start: below this many blocks a thread is not
# worth having (on the chip's host 4 and 8 read alike, 16 slower: PERF.md)
_MIN_BLOCKS_PER_THREAD = 8


def _usable_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # not Linux
        return os.cpu_count() or 1


# the encoder runs on the output writer's thread while the main thread
# launches the next iterate and the runtime's own thread starts it: with
# all 13 cores of the chip's host encoding, the segment after a write ran
# 84 ms for 62; with one core left free 73, with two 64.6, with three
# 63.3 while the encode grows (PERF.md, PR 38)
_CORES_LEFT_FREE = 2


def _zlib_threads(nblocks: int) -> int:
    """Threads for one array: the usable cores but ``_CORES_LEFT_FREE``,
    as far as the blocks go."""
    return max(1, min(_usable_cores() - _CORES_LEFT_FREE,
                      nblocks // _MIN_BLOCKS_PER_THREAD))


def _native_blocks(lib: ctypes.CDLL, src: np.ndarray, block: int,
                   level: int, threads: int) -> memoryview | None:
    """``src`` (uint8, 1-D, not empty) through the native encoder on
    ``threads`` threads; None if it failed."""
    nblocks = (src.size + block - 1) // block
    cap = 4 * (3 + nblocks) + nblocks * (block + block // 1000 + 64)
    out = np.empty(cap, dtype=np.uint8)
    total = lib.tclb_zlib_blocks(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), src.size,
        block, level,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap, threads)
    return memoryview(out)[:total] if total > 0 else None


def _python_blocks(src: np.ndarray, block: int, level: int) -> bytes:
    """The serial fallback and the tests' oracle.  No data gives the header
    [0, block, 0], zero blocks: [1, block, 0] would declare one FULL
    uncompressed block per VTK convention while the stream decompresses
    to nothing — a strict reader would mis-size."""
    n = src.size
    nblocks = (n + block - 1) // block
    last = n - (nblocks - 1) * block
    chunks = [zlib.compress(src[b * block:(b + 1) * block], level)
              for b in range(nblocks)]
    head = np.array([nblocks, block, 0 if last == block else last]
                    + [len(c) for c in chunks], dtype=np.uint32)
    return head.tobytes() + b"".join(chunks)


def zlib_blocks(data, block: int = 1 << 15, level: int = 6,
                stats: dict | None = None) -> bytes | memoryview:
    """vtkZLibDataCompressor appended block: UInt32 header + zlib streams.

    ``data`` is any C-contiguous buffer (``bytes``, an ndarray of any shape
    and dtype); it is read in place.  Uses the native encoder when
    available, which compresses the blocks in parallel on
    :func:`_zlib_threads` threads, else a serial Python fallback whose
    streams decode to the same bytes (both are zlib at the same level).
    The result does not depend on the thread count.  ``stats``, if given,
    accumulates what was done over the arrays of one file: ``blocks``
    (sum), ``threads`` (largest count used), ``native`` (False once any
    array took the fallback).
    """
    src = np.frombuffer(data, dtype=np.uint8)
    nblocks = (src.size + block - 1) // block
    lib = get_lib()
    out = None
    if lib is not None and src.size:
        threads = _zlib_threads(nblocks)
        out = _native_blocks(lib, src, block, level, threads)
    native = out is not None
    if not native:
        threads, out = 1, _python_blocks(src, block, level)
    if stats is not None:
        stats["blocks"] = stats.get("blocks", 0) + nblocks
        stats["threads"] = max(stats.get("threads", 1), threads)
        stats["native"] = stats.get("native", True) and native
    return out
