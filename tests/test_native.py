"""Native IO runtime tests (deeplearning4j_tpu/native — the TPU build's
analog of the reference's native data path, SURVEY.md §2.9). Skipped
gracefully only if no C++ toolchain exists; in this environment g++ is
guaranteed, so the build must succeed."""
import gzip
import struct

import numpy as np
import pytest

from deeplearning4j_tpu import native


def test_native_builds_and_loads():
    lib = native.load()
    assert lib is not None, "g++ is present in this environment; build must work"
    assert lib.dl4j_io_version() == 1


def test_csv_parse_parity_and_fallback(tmp_path):
    rng = np.random.default_rng(0)
    m = rng.normal(size=(50, 7)).astype(np.float32)
    lines = "\n".join(",".join(f"{v:.6g}" for v in row) for row in m)
    parsed = native.csv_parse(lines.encode())
    assert parsed is not None
    np.testing.assert_allclose(parsed, m, rtol=1e-5)
    # header skipping
    parsed2 = native.csv_parse(("a,b,c,d,e,f,g\n" + lines).encode(),
                               skip_lines=1)
    np.testing.assert_allclose(parsed2, m, rtol=1e-5)
    # quoted / non-numeric content -> None (Python csv fallback)
    assert native.csv_parse(b'1,"two",3\n') is None
    assert native.csv_parse(b"1,2\n3,4,5\n") is None  # ragged


def test_csv_record_reader_uses_native_fast_path(tmp_path):
    from deeplearning4j_tpu.datasets.records.reader import CSVRecordReader
    p = tmp_path / "data.csv"
    p.write_text("1,2,3\n4,5,6\n")
    r = CSVRecordReader().initialize(str(p))
    assert getattr(r, "_native", False) is True
    assert r.next_record() == [1.0, 2.0, 3.0]
    assert r.next_record() == [4.0, 5.0, 6.0]
    # non-numeric file falls back to the general parser, same contract
    p2 = tmp_path / "mixed.csv"
    p2.write_text('x,"y z",3\n')
    r2 = CSVRecordReader().initialize(str(p2))
    assert getattr(r2, "_native", True) is False
    assert r2.next_record() == ["x", "y z", 3.0]


def test_idx_decode_parity(tmp_path):
    rng = np.random.default_rng(1)
    imgs = rng.integers(0, 256, size=(5, 4, 3), dtype=np.uint8)
    buf = struct.pack(">IIII", 2051, 5, 4, 3) + imgs.tobytes()
    out = native.idx_read(buf)
    np.testing.assert_array_equal(out, imgs)
    labels = np.array([1, 2, 3], np.uint8)
    lbuf = struct.pack(">II", 2049, 3) + labels.tobytes()
    np.testing.assert_array_equal(native.idx_read(lbuf), labels)
    assert native.idx_read(b"\x00\x00\x0d\x01" + b"\x00" * 8) is None  # int32 type

    # the MNIST fetcher path consumes these through the native decoder
    from deeplearning4j_tpu.datasets.fetchers.mnist import _read_idx_images
    gz = tmp_path / "imgs.gz"
    with gzip.open(gz, "wb") as f:
        f.write(buf)
    np.testing.assert_array_equal(_read_idx_images(str(gz)), imgs)


def test_gather_normalize_one_hot_parity():
    rng = np.random.default_rng(2)
    src = rng.normal(size=(1000, 17)).astype(np.float32)
    idx = rng.integers(0, 1000, 333)
    np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])
    # multithreaded path
    big_idx = rng.integers(0, 1000, 4096)
    np.testing.assert_array_equal(native.gather_rows(src, big_idx, n_threads=4),
                                  src[big_idx])

    px = rng.integers(0, 256, size=(64, 8), dtype=np.uint8)
    np.testing.assert_allclose(native.normalize_u8(px),
                               px.astype(np.float32) / 255.0, rtol=1e-6)
    np.testing.assert_allclose(native.normalize_u8(px, -1.0, 1.0),
                               px.astype(np.float32) * (2 / 255) - 1.0,
                               rtol=1e-5, atol=1e-6)

    labs = rng.integers(0, 9, 100)
    np.testing.assert_array_equal(native.one_hot(labs, 9),
                                  np.eye(9, dtype=np.float32)[labs])
    with pytest.raises(ValueError):
        native.one_hot([9], 9)


def test_csv_trailing_delimiter_falls_back():
    # '1,2,\n' has an empty trailing field: the Python csv module keeps it,
    # so the native fast path must defer rather than silently drop it
    assert native.csv_parse(b"1,2,\n3,4,\n") is None
    # exact float64 parity with Python float() on a precision-heavy value
    m = native.csv_parse(b"16777217,0.1\n")
    assert m is not None and m.dtype == np.float64
    assert m[0, 0] == float("16777217") and m[0, 1] == float("0.1")


def test_csv_internal_whitespace_falls_back():
    # "1 2" is a string field to the Python parser; native must defer
    assert native.csv_parse(b"1 2\n3 4\n") is None


def test_csv_strict_grammar_defers_nonportable_spellings():
    # strtod would accept all of these, but they are either locale-dependent,
    # spelled differently by Python float(), or rejected by it — the native
    # path must defer to the Python parser (which handles them consistently)
    assert native.csv_parse(b"0x10,2\n") is None          # hex float
    assert native.csv_parse(b"0x1p3,2\n") is None         # hex exponent
    assert native.csv_parse(b"inf,2\n") is None           # float('inf') ok, but defer
    assert native.csv_parse(b"infinity,2\n") is None
    assert native.csv_parse(b"nan,2\n") is None
    assert native.csv_parse(b"NAN(chars),2\n") is None    # strtod-only spelling
    assert native.csv_parse(b"1_0,2\n") is None           # float('1_0')==10.0
    assert native.csv_parse(b" 1.5,2\n") is None          # leading space: strip()ed by Python
    # strict decimal forms all still take the fast path, exact parity
    m = native.csv_parse(b"1.,.5,-3e-2,+4E+1,16777217\n")
    assert m is not None
    assert m.tolist() == [[float("1."), float(".5"), float("-3e-2"),
                           float("+4E+1"), float("16777217")]]


def test_csv_int_looking_fields_take_fast_path_as_floats():
    # documented all-float contract: the Python fallback's _coerce also
    # returns float for int-looking fields, so the paths agree
    from deeplearning4j_tpu.datasets.records.reader import _coerce
    m = native.csv_parse(b"1,2,3\n")
    assert m is not None and m.tolist() == [[1.0, 2.0, 3.0]]
    assert [_coerce(v) for v in "1,2,3".split(",")] == [1.0, 2.0, 3.0]


def test_staleness_is_decided_by_a_source_hash_not_by_file_times():
    """A checkout makes up every mtime, so the library is current exactly
    when the stamp beside it holds the hash of the source it was built
    from — whatever the clocks say."""
    import os
    from deeplearning4j_tpu.native import build as b
    assert b.build() == b.LIB
    digest = b._source_hash()
    with open(b.STAMP) as f:
        assert f.read().strip() == digest
    before = os.stat(b.LIB)
    try:
        os.utime(b.LIB, (0, 0))              # "older than the source"
        assert b._built_from(digest)         # still current: same source
        assert b.build() == b.LIB            # and not rebuilt
        assert os.stat(b.LIB).st_mtime == 0
    finally:
        os.utime(b.LIB, (before.st_atime, before.st_mtime))
    assert not b._built_from("0" * 64)       # another source: stale
