"""Atomic file plumbing + the async background writer.

Everything that touches the filesystem on the save path goes through
here: the temp+fsync+rename discipline (no output file can ever be
observed half-written — also adopted by ``Solver.write_txt``/
``write_bin``), the centralized filename-suffix normalization that the
SaveBinary/LoadBinary handlers previously juggled inline (``fn[:-4]``
broke for stems containing a dot), and the one-save-in-flight background
thread that :class:`~tclb_tpu.checkpoint.manager.CheckpointManager`
serializes on.
"""

from __future__ import annotations

import contextlib
import os
import threading
import zlib
from typing import Callable, Iterator, Optional

import numpy as np

from tclb_tpu import faults, telemetry


# -- path normalization ------------------------------------------------------- #
# One place for the ".npz"/".npy" suffix rules: a suffix is only ever the
# exact trailing extension, never "the last 4 characters", so stems with
# dots ("state.v2", "run.best") survive a save/load round trip.


def with_suffix(path: str, ext: str) -> str:
    """``path`` guaranteed to end with ``ext`` (appended when absent)."""
    return path if path.endswith(ext) else path + ext


def strip_suffix(path: str, ext: str) -> str:
    """``path`` with one trailing ``ext`` removed (only if present)."""
    return path[:-len(ext)] if path.endswith(ext) else path


def resolve_npz(path: str) -> str:
    """The on-disk file a legacy ``.npz`` reference points at: the path
    itself when it exists (or already carries the suffix), else the
    suffixed variant ``np.savez`` would have produced."""
    if path.endswith(".npz") or os.path.exists(path):
        return path
    return path + ".npz"


# -- atomic writes ------------------------------------------------------------ #


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass   # some filesystems refuse fsync on directories
    finally:
        os.close(fd)


@contextlib.contextmanager
def atomic_path(path: str) -> Iterator[str]:
    """Yield a temp path; on clean exit fsync it and rename onto ``path``.

    The rename is atomic on POSIX, so readers see either the old file or
    the complete new one — never a torn write.  On error the temp file is
    removed and nothing replaces ``path``.
    """
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        yield tmp
        _fsync_file(tmp)
        os.replace(tmp, path)
        _fsync_dir(d)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def atomic_write_bytes(path: str, data: bytes) -> None:
    with atomic_path(path) as tmp:
        with open(tmp, "wb") as f:
            f.write(data)


# -- shard codecs ------------------------------------------------------------- #
# Optional compression of shard files.  The manifest records the codec
# per array record and the CRC is ALWAYS over the uncompressed .npy
# bytes — so verification proves the payload decodes to exactly what was
# saved, not merely that the compressed envelope is intact, and a
# checkpoint re-written with a different codec keeps the same CRC.

CODEC_SUFFIX = {"zstd": ".zst", "zlib": ".zlib"}


def resolve_codec(codec: Optional[str]) -> str:
    """Normalize + availability-check a codec request.  Unknown names
    raise; a ``zstd`` request without the ``zstandard`` package degrades
    to uncompressed with a warning (a save must never fail because an
    optional dependency is absent)."""
    codec = (codec or "none").lower()
    if codec not in ("none", "zlib", "zstd"):
        raise ValueError(f"unknown checkpoint codec {codec!r} "
                         "(known: none, zlib, zstd)")
    if codec == "zstd":
        try:
            import zstandard  # noqa: F401
        except ImportError:
            from tclb_tpu.utils import log
            log.warning("checkpoint: compress='zstd' requested but the "
                        "zstandard package is not installed — saving "
                        "uncompressed")
            return "none"
    return codec


def compress_bytes(data: bytes, codec: str) -> bytes:
    if codec == "none":
        return data
    if codec == "zlib":
        return zlib.compress(data, level=1)
    if codec == "zstd":
        import zstandard
        return zstandard.ZstdCompressor(level=3).compress(data)
    raise ValueError(f"unknown checkpoint codec {codec!r}")


def decompress_bytes(data: bytes, codec: str) -> bytes:
    if codec == "none":
        return data
    if codec == "zlib":
        return zlib.decompress(data)
    if codec == "zstd":
        try:
            import zstandard
        except ImportError as e:
            raise RuntimeError(
                "this checkpoint's shards are zstd-compressed but the "
                "zstandard package is not installed") from e
        return zstandard.ZstdDecompressor().decompress(data)
    raise ValueError(f"unknown checkpoint codec {codec!r}")


def npy_bytes(arr: np.ndarray) -> bytes:
    """The exact ``.npy`` serialization of ``arr`` (what the CRC covers)."""
    import io
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(arr))
    return buf.getvalue()


def write_npy(path: str, arr: np.ndarray, codec: str = "none") -> dict:
    """Write one shard file and return its manifest record.

    ``codec="none"`` writes a plain ``.npy``; compressed codecs append
    their suffix (``fields.npy.zst``) and store the compressed stream.
    The record's ``crc32`` covers the uncompressed npy bytes either way
    (see CODEC_SUFFIX block comment)."""
    arr = np.ascontiguousarray(arr)
    raw = npy_bytes(arr)
    crc = zlib.crc32(raw) & 0xFFFFFFFF
    if codec != "none":
        path = path + CODEC_SUFFIX[codec]
    payload = compress_bytes(raw, codec)
    # the chaos seam for checkpoint IO: `enospc` raises before the open
    # (disk full), `slow` stalls the fsync path, `torn` truncates the
    # payload so CRC verification downstream must catch it
    mode = faults.fire("checkpoint.write", file=os.path.basename(path))
    if mode == "torn":
        payload = payload[:max(1, len(payload) // 2)]
    with open(path, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    rec = {"file": os.path.basename(path),
           "crc32": crc,
           "dtype": str(arr.dtype),
           "shape": [int(s) for s in arr.shape],
           "nbytes": int(arr.nbytes)}
    if codec != "none":
        rec["codec"] = codec
    return rec


def read_npy(path: str, codec: str = "none") -> np.ndarray:
    """Load one shard file written by :func:`write_npy`."""
    if codec == "none":
        return np.load(path)
    import io
    with open(path, "rb") as f:
        raw = decompress_bytes(f.read(), codec)
    return np.load(io.BytesIO(raw))


def crc32_file(path: str, chunk: int = 1 << 22) -> int:
    """Streaming CRC32 of a file's bytes (what the manifest records and
    verification recomputes)."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(chunk)
            if not buf:
                break
            crc = zlib.crc32(buf, crc)
    return crc & 0xFFFFFFFF


def commit_dir(tmp_dir: str, final_dir: str) -> None:
    """Atomically promote a fully-written temp step directory: fsync its
    contents, rename into place, fsync the parent.

    An existing ``final_dir`` (a re-save of a step the run already
    passed — e.g. after resuming below a corrupted checkpoint) is
    removed first; ``os.replace`` cannot rename onto a non-empty
    directory, so this one case trades the atomic swap for a brief
    window in which the step is absent rather than torn."""
    import shutil
    for name in os.listdir(tmp_dir):
        _fsync_file(os.path.join(tmp_dir, name))
    _fsync_dir(tmp_dir)
    if os.path.isdir(final_dir):
        shutil.rmtree(final_dir)
    os.replace(tmp_dir, final_dir)
    _fsync_dir(os.path.dirname(os.path.abspath(final_dir)))


# -- async serialization ------------------------------------------------------ #


class AsyncWriter:
    """At most one background job in flight, on a thread called ``name``.

    ``submit`` first drains any previous job (so two saves can never
    interleave in one checkpoint root, and at most one write's arrays
    are held on the host), then runs ``fn`` on a daemon thread.  Errors
    are captured and re-raised on the *next* ``wait()`` — a failed
    background write must not kill the solve loop, but it must not stay
    silent either.  The checkpoint manager owns one for its saves, the
    ``Solver`` one for ``<VTK>`` output (``Solver.write_vtk``).  The
    thread launches nothing on the device: its spans are kept apart from
    the launching thread's in a profile (``telemetry.off_launch_thread``).
    """

    def __init__(self, name: str = "tclb-checkpoint-writer") -> None:
        self.name = name
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def submit(self, fn: Callable[[], None]) -> None:
        self.wait()

        def run() -> None:
            telemetry.off_launch_thread(self.name)
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — surfaced on wait()
                # concurrency-ok[unguarded]: single-writer latch — only
                # this worker writes it, and wait() joins the thread
                # before reading (join is the happens-before edge)
                self._error = e

        self._thread = threading.Thread(target=run, daemon=True,
                                        name=self.name)
        self._thread.start()

    def busy(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            # concurrency-ok[unguarded]: read/cleared only after join()
            # above — the writing thread is gone by this line
            err, self._error = self._error, None
            raise err
