"""Native (C++) runtime components for heat_tpu.

The reference delegates all native performance to libtorch kernels and the
MPI C library (SURVEY §2: pure-Python repo).  In the TPU-native rebuild the
compute path is XLA; this package supplies the *runtime* native layer around
it — parallel file parsing and background IO prefetch — compiled from
``src/*.cpp`` with g++ at first use and bound through :mod:`ctypes`.

Every entry point degrades to its pure-Python path when the library is
unavailable (``HEAT_TPU_NO_NATIVE=1`` disables it outright); a build that
was attempted and failed says so in a ``RuntimeWarning`` carrying the
compiler's output. The library is rebuilt when the content of
``src/*.cpp`` changes (a sha256 kept beside it), never by mtime.

Components
----------
- CSV parser (``src/csv.cpp``): mmap + multithreaded ``std::from_chars``,
  replacing the reference's Python byte-range parser
  (reference ``heat/core/io.py:713``).
- IDX reader (``src/idx.cpp``): MNIST-format binary loader
  (reference ``heat/utils/data/mnist.py:16``).
- Prefetch stream (``src/stream.cpp``): background pread(2) ring buffer,
  the native analogue of the reference's ``queue_thread`` slab loader
  (reference ``heat/utils/data/partial_dataset.py:20,224``).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings
from typing import Optional, Tuple

import numpy as np

__all__ = [
    "available",
    "csv_dims",
    "csv_parse",
    "csv_parse_range",
    "idx_read",
    "FileStream",
]

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_LIB_PATH = os.path.join(os.path.dirname(__file__), "_heat_native.so")
_DIGEST_PATH = _LIB_PATH + ".sha256"
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]


def _source_digest(sources) -> str:
    """sha256 over the compile command and every source's name and
    bytes: what the binary is a function of. mtimes are not — a copied
    or freshly checked-out tree scrambles them."""
    h = hashlib.sha256(" ".join(_CXX).encode())
    for src in sources:
        h.update(os.path.basename(src).encode() + b"\0")
        with open(src, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


def _build() -> bool:
    try:
        names = os.listdir(_SRC_DIR)
    except OSError:
        # non-editable installs may ship without src/ — degrade to Python paths
        return os.path.exists(_LIB_PATH)
    sources = sorted(os.path.join(_SRC_DIR, f) for f in names if f.endswith(".cpp"))
    if not sources:
        return False
    digest = _source_digest(sources)
    try:
        with open(_DIGEST_PATH) as fh:
            if fh.read().strip() == digest and os.path.exists(_LIB_PATH):
                return True
    except OSError:
        pass
    # compile to a per-process temp name, then atomically rename: a
    # concurrent process must never dlopen a half-written library. The
    # digest lands after the library, so a crash in between rebuilds.
    tmp = f"{_LIB_PATH}.tmp{os.getpid()}"
    try:
        subprocess.run([*_CXX, *sources, "-o", tmp], check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)
        with open(tmp, "w") as fh:
            fh.write(digest + "\n")
        os.replace(tmp, _DIGEST_PATH)
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        # callers still degrade to their Python paths, but never in
        # silence: a run that must not degrade turns this into an error
        detail = getattr(e, "stderr", b"") or b""
        warnings.warn(
            f"heat_tpu.native: building {_LIB_PATH} failed ({e}); "
            f"{detail.decode(errors='replace')[-2000:]}",
            RuntimeWarning,
            stacklevel=3,
        )
        return False
    return True


def _load() -> Optional[ctypes.CDLL]:
    """Build (if its sources changed) and dlopen the native library;
    None when it is disabled or unavailable."""
    global _lib, _tried
    if _lib is not None:
        return _lib
    if _tried:
        return None
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("HEAT_TPU_NO_NATIVE"):
            return None
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            _bind_symbols(lib)
        except (OSError, AttributeError) as e:
            # a prebuilt library without src/ may be stale or foreign
            warnings.warn(
                f"heat_tpu.native: loading {_LIB_PATH} failed ({e})",
                RuntimeWarning,
                stacklevel=2,
            )
            return None
        _lib = lib
        return _lib


def _bind_symbols(lib: ctypes.CDLL) -> None:
    lib.ht_csv_dims.restype = ctypes.c_int64
    lib.ht_csv_dims.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_char,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.ht_csv_open.restype = ctypes.c_void_p
    lib.ht_csv_open.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_char,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.ht_csv_open_range.restype = ctypes.c_void_p
    lib.ht_csv_open_range.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_char,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.ht_csv_parse_h.restype = ctypes.c_int64
    lib.ht_csv_parse_h.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char,
        ctypes.c_int32,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int32,
    ]
    lib.ht_csv_close.restype = None
    lib.ht_csv_close.argtypes = [ctypes.c_void_p]
    lib.ht_idx_header.restype = ctypes.c_int64
    lib.ht_idx_header.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.ht_idx_read.restype = ctypes.c_int64
    lib.ht_idx_read.argtypes = [ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64]
    lib.ht_stream_open.restype = ctypes.c_void_p
    lib.ht_stream_open.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int32,
    ]
    lib.ht_stream_next.restype = ctypes.c_int64
    lib.ht_stream_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
    lib.ht_stream_close.restype = None
    lib.ht_stream_close.argtypes = [ctypes.c_void_p]


def available() -> bool:
    """True when the native library is (or can be) built and loaded."""
    return _load() is not None


def csv_dims(path: str, header_lines: int = 0, sep: str = ",") -> Optional[Tuple[int, int]]:
    """(rows, cols) of the CSV data region, or None if native is unavailable."""
    lib = _load()
    if lib is None or len(sep) != 1:
        return None
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.ht_csv_dims(
        path.encode(), header_lines, sep.encode(), ctypes.byref(rows), ctypes.byref(cols)
    )
    if rc != 0:
        return None
    return rows.value, cols.value


def _csv_dtype_code(dtype: np.dtype):
    np_dtype = np.dtype(dtype)
    if np_dtype == np.float32:
        return 0, np_dtype, None
    if np_dtype == np.float64:
        return 1, np_dtype, None
    # ints etc.: parse as f64 then cast — matching the reference, which
    # parses every field with Python float() before the dtype cast
    # (reference heat/core/io.py:800-806), including its >2**53
    # rounding behavior
    return 1, np.dtype(np.float64), np_dtype


def _csv_parse_handle(lib, handle, sep, rows, cols, code, np_dtype, cast_to, nthreads):
    try:
        if rows == 0 or cols == 0:
            return np.empty((rows, cols), dtype=cast_to or np_dtype)
        out = np.empty((rows, cols), dtype=np_dtype)
        if nthreads <= 0:
            nthreads = min(16, os.cpu_count() or 1)
        rc = lib.ht_csv_parse_h(
            handle,
            sep.encode(),
            code,
            out.ctypes.data_as(ctypes.c_void_p),
            rows,
            cols,
            nthreads,
        )
    finally:
        lib.ht_csv_close(handle)
    if rc != 0:
        return None
    return out if cast_to is None else out.astype(cast_to)


def csv_parse(
    path: str,
    header_lines: int = 0,
    sep: str = ",",
    dtype: np.dtype = np.float32,
    nthreads: int = 0,
) -> Optional[np.ndarray]:
    """Parse a numeric CSV into a numpy array; None → caller falls back."""
    lib = _load()
    if lib is None or len(sep) != 1:
        return None
    code, np_dtype, cast_to = _csv_dtype_code(dtype)
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    handle = lib.ht_csv_open(
        path.encode(), header_lines, sep.encode(), ctypes.byref(rows), ctypes.byref(cols)
    )
    if not handle:
        return None
    return _csv_parse_handle(
        lib, handle, sep, rows.value, cols.value, code, np_dtype, cast_to, nthreads
    )


def csv_parse_range(
    path: str,
    offset: int,
    length: int,
    header_lines: int = 0,
    sep: str = ",",
    dtype: np.dtype = np.float32,
    nthreads: int = 0,
) -> Optional[np.ndarray]:
    """Parse only the rows OWNED by byte range [offset, offset+length) —
    a row belongs to the range containing its first byte and is parsed to
    its end even across the boundary, so ranges partitioning the file give
    disjoint covering row sets (the reference's per-rank convention,
    ``heat/core/io.py:713-924``). ``length < 0`` means to EOF.
    None → caller falls back to the Python range parser."""
    lib = _load()
    if lib is None or len(sep) != 1:
        return None
    code, np_dtype, cast_to = _csv_dtype_code(dtype)
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    handle = lib.ht_csv_open_range(
        path.encode(), header_lines, sep.encode(), offset, length,
        ctypes.byref(rows), ctypes.byref(cols),
    )
    if not handle:
        return None
    return _csv_parse_handle(
        lib, handle, sep, rows.value, cols.value, code, np_dtype, cast_to, nthreads
    )


_IDX_DTYPES = {
    0x08: np.uint8,
    0x09: np.int8,
    0x0B: np.int16,
    0x0C: np.int32,
    0x0D: np.float32,
    0x0E: np.float64,
}


def idx_read(path: str) -> Optional[np.ndarray]:
    """Read an (uncompressed) IDX file into a numpy array; None → fallback."""
    lib = _load()
    if lib is None:
        return None
    dims = (ctypes.c_int64 * 8)()
    ndims = ctypes.c_int64()
    code = ctypes.c_int32()
    rc = lib.ht_idx_header(path.encode(), dims, ctypes.byref(ndims), ctypes.byref(code))
    if rc != 0 or code.value not in _IDX_DTYPES:
        return None
    shape = tuple(dims[i] for i in range(ndims.value))
    out = np.empty(shape, dtype=_IDX_DTYPES[code.value])
    rc = lib.ht_idx_read(path.encode(), out.ctypes.data_as(ctypes.c_void_p), out.nbytes)
    if rc != 0:
        return None
    return out


class FileStream:
    """Background-prefetched sequential reader over a byte range of a file.

    A native OS thread preads slabs of ``chunk_bytes`` into a ring of
    ``depth`` buffers ahead of the consumer, so disk IO overlaps Python-side
    compute without the GIL (native analogue of reference
    ``heat/utils/data/partial_dataset.py:20`` ``queue_thread``).

    Iterating yields ``numpy.uint8`` arrays of at most ``chunk_bytes``.
    Usable as a context manager.
    """

    def __init__(
        self,
        path: str,
        offset: int = 0,
        length: Optional[int] = None,
        chunk_bytes: int = 1 << 20,
        depth: int = 4,
    ):
        lib = _load()
        if lib is None:
            raise RuntimeError("heat_tpu native library unavailable")
        if length is None:
            length = max(0, os.path.getsize(path) - offset)
        self._lib = lib
        self._chunk = chunk_bytes
        self._handle = lib.ht_stream_open(path.encode(), offset, length, chunk_bytes, depth)
        if not self._handle:
            raise OSError(f"cannot open stream on {path!r}")

    def read_next(self) -> Optional[np.ndarray]:
        """Next slab as a uint8 array, or None at end of stream."""
        if self._handle is None:
            return None
        buf = np.empty(self._chunk, dtype=np.uint8)
        n = self._lib.ht_stream_next(
            self._handle, buf.ctypes.data_as(ctypes.c_void_p), self._chunk
        )
        if n < 0:
            raise OSError(f"native stream read failed (code {n})")
        if n == 0:
            return None
        return buf[:n]

    def __iter__(self):
        while True:
            slab = self.read_next()
            if slab is None:
                return
            yield slab

    def close(self) -> None:
        if self._handle is not None:
            self._lib.ht_stream_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except (OSError, AttributeError):
            # close() only touches the ctypes handle; never mask anything
            # wider (e.g. ResilienceError) from interpreter teardown
            pass
