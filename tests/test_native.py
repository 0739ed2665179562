"""Native C++ layer: voxelizer and VTI zlib encoder vs the Python oracle.

The pure-Python implementations in utils/stl.py and the stdlib-zlib
fallback in native/__init__.py are the oracles; the native lib must match
them exactly (same algorithm, same rounding — see src/tclb_native.cpp).
"""

import struct
import zlib

import numpy as np
import pytest

from tclb_tpu import native
from tclb_tpu.utils import stl


def make_sphere_tri(r=9.0, center=(15.0, 14.0, 13.0), n=24):
    """Watertight UV-sphere triangle soup (ntri, 3, 3) float64."""
    th = np.linspace(0, np.pi, n)
    ph = np.linspace(0, 2 * np.pi, 2 * n, endpoint=False)
    tris = []
    for i in range(n - 1):
        for j in range(2 * n):
            j2 = (j + 1) % (2 * n)
            p = []
            for t, f in ((i, j), (i + 1, j), (i, j2), (i + 1, j2)):
                x = center[0] + r * np.sin(th[t]) * np.cos(ph[f])
                y = center[1] + r * np.sin(th[t]) * np.sin(ph[f])
                z = center[2] + r * np.cos(th[t])
                p.append((x, y, z))
            tris.append((p[0], p[1], p[2]))
            tris.append((p[2], p[1], p[3]))
    return np.asarray(tris, dtype=np.float64)


needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native lib not built (no g++?)")


@needs_native
@pytest.mark.parametrize("side", ["in", "out", "surface"])
def test_voxelize_matches_python(side):
    tri = make_sphere_tri()
    shape = (30, 29, 28)
    got = native.voxelize(tri, shape, side)
    want = stl.voxelize_py(tri, shape, side)
    assert got.shape == want.shape
    assert (got == want).all()


@needs_native
def test_voxelize_dispatch_is_native():
    # the public voxelize() must route through the native path and still
    # give the oracle's answer
    tri = make_sphere_tri(r=5.0, center=(8, 8, 8), n=10)
    shape = (17, 16, 18)
    assert (stl.voxelize(tri, shape) == stl.voxelize_py(tri, shape)).all()


def _decode_blocks(buf: bytes) -> bytes:
    nblocks, block, last = struct.unpack_from("<III", buf, 0)
    sizes = struct.unpack_from(f"<{nblocks}I", buf, 12)
    off = 12 + 4 * nblocks
    out = b""
    for s in sizes:
        out += zlib.decompress(buf[off:off + s])
        off += s
    assert off == len(buf)
    return out


def _field_bytes(n: int) -> np.ndarray:
    """n bytes that compress like a field does: some blocks nearly
    constant, some noisy, so that blocks differ in size and cost."""
    rng = np.random.default_rng(n)
    a = rng.integers(0, 50, n, dtype=np.uint8)
    a[: n // 3] //= 25
    return a


BLOCK = 1 << 15
SIZES = [0, 1, 100, BLOCK, BLOCK + 1, 200000, 3 * BLOCK * 40 + 12345]


@pytest.mark.parametrize("n", SIZES)
def test_zlib_blocks_roundtrip(n):
    data = _field_bytes(n).tobytes()
    assert _decode_blocks(native.zlib_blocks(data)) == data


@needs_native
@pytest.mark.parametrize("n", SIZES[1:])
@pytest.mark.parametrize("threads", [1, 2, 3, 8, 1000])
def test_zlib_blocks_same_bytes_on_any_thread_count(n, threads):
    """1000 threads are more than any of these sizes has blocks.  The
    bytes are those of one thread, decode to the input, and match the
    Python fallback after decoding."""
    src = _field_bytes(n)
    lib = native.get_lib()
    one = native._native_blocks(lib, src, BLOCK, 6, 1)
    got = native._native_blocks(lib, src, BLOCK, 6, threads)
    assert got == one
    want = native._python_blocks(src, BLOCK, 6)
    assert _decode_blocks(bytes(got)) == _decode_blocks(want) \
        == src.tobytes()
    assert bytes(got[:12]) == want[:12]


@needs_native
@pytest.mark.parametrize("cores,nblocks,want", [
    (13, 0, 1), (13, 1, 1), (13, 15, 1), (13, 64, 8), (13, 128, 11),
    (13, 384, 11), (1, 384, 1), (2, 384, 1), (224, 384, 48)])
def test_zlib_threads_follow_cores_and_blocks(monkeypatch, cores, nblocks,
                                              want):
    monkeypatch.setattr(native, "_usable_cores", lambda: cores)
    assert native._zlib_threads(nblocks) == want


@needs_native
@pytest.mark.parametrize("cores", [1, 5])
def test_zlib_blocks_takes_an_array_in_place(monkeypatch, cores):
    """A C-contiguous array of any shape and dtype goes in as it is and
    gives the bytes of its ``tobytes()``, on one thread or several; what
    cannot be read in place is refused, not copied."""
    monkeypatch.setattr(native, "_usable_cores", lambda: cores)
    rng = np.random.default_rng(3)
    a = np.cumsum(rng.standard_normal((40, 64, 96)), axis=2) \
        .astype(np.float32)
    stats: dict = {}
    got = native.zlib_blocks(a, stats=stats)
    assert stats == {"blocks": 30, "threads": min(cores, 30 // 8),
                     "native": True}
    assert got == native.zlib_blocks(a.tobytes())
    assert _decode_blocks(bytes(got)) == a.tobytes()
    with pytest.raises(ValueError):
        native.zlib_blocks(a.T)


def test_zlib_blocks_stats_accumulate_over_arrays(monkeypatch):
    monkeypatch.setattr(native, "get_lib", lambda: None)
    stats: dict = {}
    native.zlib_blocks(bytes(BLOCK * 40), stats=stats)
    native.zlib_blocks(bytes(5), stats=stats)
    assert stats == {"blocks": 41, "threads": 1, "native": False}


@needs_native
def test_zlib_blocks_native_matches_python(monkeypatch):
    rng = np.random.default_rng(0)
    data = rng.integers(0, 9, 100000, dtype=np.uint8).tobytes()
    got = native.zlib_blocks(data)
    monkeypatch.setattr(native, "get_lib", lambda: None)
    want = native.zlib_blocks(data)
    assert _decode_blocks(got) == _decode_blocks(want) == data


@pytest.mark.parametrize("shape", [(4, 20, 30), (24, 128, 192)])
def test_write_vti_compressed_roundtrip(tmp_path, shape, monkeypatch):
    """A scalar and a 3-component array; the second case is large enough
    (U: 7 MB, 216 blocks) for the encoder to use more than one thread."""
    from tclb_tpu import telemetry
    from tclb_tpu.utils.vtk import write_vti
    monkeypatch.setattr(native, "_usable_cores", lambda: 6)  # two stay free
    rng = np.random.default_rng(1)
    a = rng.standard_normal(shape).astype(np.float32)
    u = np.cumsum(rng.standard_normal((3,) + shape), axis=3) \
        .astype(np.float32)
    seen = []
    telemetry.subscribe(seen.append)
    try:
        p = write_vti(str(tmp_path / "x"), {"A": a, "U": u}, compress=True)
    finally:
        telemetry.unsubscribe(seen.append)
    raw = open(p, "rb").read()
    assert b'compressor="vtkZLibDataCompressor"' in raw
    body = raw.split(b'<AppendedData encoding="raw">\n_', 1)[1]
    body = body.rsplit(b"\n</AppendedData>", 1)[0]
    off_u = int(raw.split(b'Name="U"', 1)[1].split(b'offset="', 1)[1]
                .split(b'"', 1)[0])
    back = np.frombuffer(_decode_blocks(body[:off_u]), dtype=np.float32)
    assert (back.reshape(a.shape) == a).all()
    back = np.frombuffer(_decode_blocks(body[off_u:]), dtype=np.float32)
    assert (back.reshape(shape + (3,)) == np.moveaxis(u, 0, -1)).all()
    encode, = [e for e in seen if e.get("name") == "output.vtk.encode"]
    big = shape[0] > 4
    if native.available():
        assert encode["native"] is True
        assert encode["threads"] == (4 if big else 1)
    assert encode["blocks"] == (72 + 216 if big else 1 + 1)
