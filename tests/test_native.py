"""Native (C++) runtime layer tests: CSV parser, IDX reader, prefetch stream.

Oracle strategy mirrors the rest of the suite: native results must equal the
pure-Python/numpy path bit-for-bit (reference parity targets:
``heat/core/io.py:713`` load_csv, ``heat/utils/data/mnist.py:16`` IDX,
``heat/utils/data/partial_dataset.py:20`` background slab loader).
"""
from __future__ import annotations

import os
import struct
import tempfile

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


def test_rebuild_follows_source_content_not_mtime(tmp_path, monkeypatch):
    """A copied checkout scrambles mtimes: the library is rebuilt when the
    bytes of ``src/*.cpp`` change and only then, and a build that fails
    says so instead of degrading in silence."""
    import ctypes

    src = tmp_path / "src"
    src.mkdir()
    cpp = src / "probe.cpp"
    cpp.write_text('extern "C" int ht_probe() { return 1; }\n')
    lib = tmp_path / "_probe.so"
    monkeypatch.setattr(native, "_SRC_DIR", str(src))
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    monkeypatch.setattr(native, "_DIGEST_PATH", str(lib) + ".sha256")
    assert native._build()
    # sources "newer" than the library, same bytes: the library stays
    os.utime(lib, ns=(1, 1))
    far = 4_000_000_000 * 10**9
    os.utime(cpp, ns=(far, far))
    assert native._build()
    assert lib.stat().st_mtime_ns == 1
    # sources "older" than the library, other bytes: rebuilt
    cpp.write_text('extern "C" int ht_probe() { return 2; }\n')
    os.utime(cpp, ns=(0, 0))
    assert native._build()
    assert ctypes.CDLL(str(lib)).ht_probe() == 2
    cpp.write_text("this is not C++\n")
    with pytest.warns(RuntimeWarning, match="building .* failed"):
        assert not native._build()


def _write_csv(path, arr, sep=",", header_lines=0, crlf=False, trailing_newline=True):
    eol = "\r\n" if crlf else "\n"
    with open(path, "w", newline="") as f:
        for h in range(header_lines):
            f.write(f"header {h}{eol}")
        lines = [sep.join(repr(float(v)) for v in row) for row in arr]
        f.write(eol.join(lines))
        if trailing_newline:
            f.write(eol)


class TestNativeCSV:
    def test_dims_and_parse_roundtrip(self):
        rng = np.random.default_rng(3)
        arr = rng.standard_normal((57, 5))
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "a.csv")
            _write_csv(path, arr)
            assert native.csv_dims(path) == (57, 5)
            out = native.csv_parse(path, dtype=np.float64)
            np.testing.assert_array_equal(out, arr)

    def test_header_sep_crlf_no_trailing_newline(self):
        rng = np.random.default_rng(4)
        arr = rng.standard_normal((11, 3))
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "a.csv")
            _write_csv(path, arr, sep=";", header_lines=2, crlf=True, trailing_newline=False)
            assert native.csv_dims(path, header_lines=2, sep=";") == (11, 3)
            out = native.csv_parse(path, header_lines=2, sep=";", dtype=np.float64)
            np.testing.assert_array_equal(out, arr)

    def test_range_ownership_partition(self):
        """Byte ranges that partition the file must yield disjoint,
        covering row sets — a row belongs to the range holding its first
        byte (the reference's per-rank convention, io.py:713-924) — for
        the native parser AND the Python fallback, at several range
        counts, with headers and CRLF."""
        from heat_tpu.core.io import _py_csv_range

        rng = np.random.default_rng(6)
        arr = rng.standard_normal((101, 3))
        for crlf in (False, True):
            with tempfile.TemporaryDirectory() as d:
                path = os.path.join(d, "a.csv")
                _write_csv(path, arr, header_lines=1, crlf=crlf)
                fsize = os.path.getsize(path)
                for nparts in (1, 2, 3, 5, 8):
                    per = -(-fsize // nparts)
                    nat, py = [], []
                    for p in range(nparts):
                        ln = native.csv_parse_range(
                            path, p * per, per, header_lines=1, dtype=np.float64
                        )
                        assert ln is not None
                        if nparts > 1:
                            assert ln.shape[0] < arr.shape[0], (nparts, p)
                        if ln.size:
                            nat.append(ln)
                        lp = _py_csv_range(path, p * per, per, 1, ",", "utf-8")
                        if lp.size:
                            py.append(lp)
                    np.testing.assert_array_equal(np.concatenate(nat), arr)
                    np.testing.assert_array_equal(np.concatenate(py), arr)
        # range past EOF / inside the header -> empty
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "b.csv")
            _write_csv(path, arr[:3], header_lines=2)
            out = native.csv_parse_range(path, 0, 4, header_lines=2, dtype=np.float64)
            assert out is not None and out.shape[0] == 0

    def test_float32_and_int_casts(self):
        arr = np.array([[1.5, -2.25], [3.0, 4.125]])
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "a.csv")
            _write_csv(path, arr)
            out32 = native.csv_parse(path, dtype=np.float32)
            assert out32.dtype == np.float32
            np.testing.assert_array_equal(out32, arr.astype(np.float32))
            outi = native.csv_parse(path, dtype=np.int64)
            np.testing.assert_array_equal(outi, arr.astype(np.int64))

    def test_malformed_returns_none(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "bad.csv")
            with open(path, "w") as f:
                f.write("1.0,2.0\n3.0,not_a_number\n")
            assert native.csv_parse(path, dtype=np.float64) is None
            ragged = os.path.join(d, "ragged.csv")
            with open(ragged, "w") as f:
                f.write("1.0,2.0\n3.0\n")
            assert native.csv_parse(ragged, dtype=np.float64) is None

    def test_load_csv_uses_native_and_matches_reference_shape(self):
        rng = np.random.default_rng(5)
        arr = rng.standard_normal((29, 4)).astype(np.float32)
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "a.csv")
            _write_csv(path, arr)
            for split in (None, 0, 1):
                res = ht.load_csv(path, split=split)
                assert res.shape == (29, 4)
                np.testing.assert_allclose(res.numpy(), arr, rtol=1e-6)

    def test_missing_file(self):
        assert native.csv_dims("/nonexistent/x.csv") is None
        assert native.csv_parse("/nonexistent/x.csv") is None


def _write_idx(path, arr):
    codes = {
        np.dtype(np.uint8): 0x08,
        np.dtype(np.int8): 0x09,
        np.dtype(np.int16): 0x0B,
        np.dtype(np.int32): 0x0C,
        np.dtype(np.float32): 0x0D,
        np.dtype(np.float64): 0x0E,
    }
    with open(path, "wb") as f:
        f.write(struct.pack(">HBB", 0, codes[arr.dtype], arr.ndim))
        for d in arr.shape:
            f.write(struct.pack(">I", d))
        f.write(arr.astype(arr.dtype.newbyteorder(">")).tobytes())


class TestNativeIDX:
    @pytest.mark.parametrize(
        "dtype", [np.uint8, np.int8, np.int16, np.int32, np.float32, np.float64]
    )
    def test_roundtrip_all_dtypes(self, dtype):
        rng = np.random.default_rng(6)
        if np.issubdtype(dtype, np.floating):
            arr = rng.standard_normal((4, 5, 3)).astype(dtype)
        else:
            arr = rng.integers(0, 100, size=(4, 5, 3)).astype(dtype)
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "x.idx")
            _write_idx(path, arr)
            out = native.idx_read(path)
            assert out.dtype == np.dtype(dtype)
            np.testing.assert_array_equal(out, arr)

    def test_bad_magic(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "bad.idx")
            with open(path, "wb") as f:
                f.write(b"\x01\x02\x03\x04garbage")
            assert native.idx_read(path) is None


class TestFileStream:
    def test_stream_reassembles_file(self):
        rng = np.random.default_rng(7)
        payload = rng.integers(0, 256, size=3 * 65536 + 123, dtype=np.uint8).tobytes()
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "blob.bin")
            with open(path, "wb") as f:
                f.write(payload)
            with native.FileStream(path, chunk_bytes=65536, depth=3) as fs:
                got = b"".join(bytes(s) for s in fs)
            assert got == payload

    def test_offset_and_length_window(self):
        payload = bytes(range(256)) * 64
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "blob.bin")
            with open(path, "wb") as f:
                f.write(payload)
            with native.FileStream(path, offset=100, length=1000, chunk_bytes=256) as fs:
                got = b"".join(bytes(s) for s in fs)
            assert got == payload[100:1100]

    def test_empty_window(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "blob.bin")
            with open(path, "wb") as f:
                f.write(b"abc")
            with native.FileStream(path, offset=3, length=0) as fs:
                assert fs.read_next() is None


class TestCSVFallbackConsistency:
    def test_single_column_shape_matches_native(self, monkeypatch):
        arr = np.array([[1.0], [2.0], [3.0]])
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "one.csv")
            _write_csv(path, arr)
            nat = ht.load_csv(path)
            assert nat.shape == (3, 1)
            # force the genfromtxt fallback: it must produce the same 2-D shape
            monkeypatch.setattr(native, "csv_parse", lambda *a, **k: None)
            fb = ht.load_csv(path)
            assert fb.shape == (3, 1)
            np.testing.assert_array_equal(nat.numpy(), fb.numpy())

    def test_single_row_shape_matches_native(self, monkeypatch):
        arr = np.array([[1.0, 2.0, 3.0, 4.0]])
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "row.csv")
            _write_csv(path, arr)
            assert ht.load_csv(path).shape == (1, 4)
            monkeypatch.setattr(native, "csv_parse", lambda *a, **k: None)
            assert ht.load_csv(path).shape == (1, 4)
