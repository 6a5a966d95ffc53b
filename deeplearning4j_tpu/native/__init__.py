"""Native host-side IO runtime bindings (ctypes over libdl4jtpu_io.so).

Reference analog: SURVEY.md §2.9 — the reference's data/runtime path is
native (libnd4j + DataVec behind JavaCPP); this module is the TPU build's
equivalent seam. The C++ side (src/dl4jtpu_io.cpp) implements the host hot
loops — CSV parse, IDX decode, threaded batch gather, pixel normalize,
one-hot — and the Python data pipeline uses them when the library can be
built, and pure Python otherwise (`load()` returns None when there is no
toolchain, so the framework never hard-requires the build step). Which of the
two a process ended up with is logged once (`native_io_runtime`).
"""
from __future__ import annotations

import ctypes
import os

import numpy as np

_lib = None
_tried = False


def load(build_if_missing=True):
    """Return the loaded CDLL (building it on demand) or None. A failed
    build is reported once and cached — callers with pure-Python fallbacks
    (CSV/IDX readers) must keep working, and the compiler must not be
    re-invoked per parse call."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    from .build import LIB, build
    from ..telemetry.logging import get_logger
    log = get_logger()
    path = None
    if build_if_missing:
        try:
            path = build()      # a no-op when LIB was built from this source
        except RuntimeError as e:
            import warnings
            warnings.warn(f"native IO build failed; using Python fallbacks "
                          f"({e})", stacklevel=2)
            log.warning("native_io_runtime", runtime="python",
                        reason="build failed")
            return None
    elif os.path.exists(LIB):
        path = LIB
    if path is None:
        log.info("native_io_runtime", runtime="python",
                 reason="no C++ toolchain" if build_if_missing
                 else "library not built")
        return None
    log.info("native_io_runtime", runtime="native", library=path)
    lib = ctypes.CDLL(path)
    lib.dl4j_csv_parse.restype = ctypes.c_int
    lib.dl4j_csv_parse.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char, ctypes.c_int64,
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64)]
    lib.dl4j_idx_info.restype = ctypes.c_int
    lib.dl4j_idx_info.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.POINTER(ctypes.c_int32)]
    lib.dl4j_idx_read.restype = ctypes.c_int
    lib.dl4j_idx_read.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                  ctypes.c_void_p, ctypes.c_int64]
    lib.dl4j_gather_rows_f32.restype = None
    lib.dl4j_gather_rows_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int32]
    lib.dl4j_normalize_u8_f32.restype = None
    lib.dl4j_normalize_u8_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_float, ctypes.c_float,
        ctypes.c_void_p]
    lib.dl4j_one_hot_f32.restype = ctypes.c_int
    lib.dl4j_one_hot_f32.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_int64, ctypes.c_void_p]
    lib.dl4j_io_version.restype = ctypes.c_int
    _lib = lib
    return _lib


def available():
    return load(build_if_missing=True) is not None


# ------------------------------------------------------------ wrappers ----

def csv_parse(data: bytes, delimiter=",", skip_lines=0):
    """Parse a numeric CSV byte buffer -> float64 [rows, cols] ndarray
    (float64 so values match the Python float() parser bit-for-bit), or
    None when the native lib is absent or the content needs the general
    (quote-aware / non-numeric) Python parser."""
    lib = load()
    if lib is None or len(delimiter) != 1:
        return None
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.dl4j_csv_parse(data, len(data), delimiter.encode(), skip_lines,
                            None, ctypes.byref(rows), ctypes.byref(cols))
    if rc != 0:
        return None
    out = np.empty((rows.value, cols.value), np.float64)
    rc = lib.dl4j_csv_parse(data, len(data), delimiter.encode(), skip_lines,
                            out.ctypes.data_as(ctypes.c_void_p),
                            ctypes.byref(rows), ctypes.byref(cols))
    if rc != 0:
        return None
    return out


def idx_read(data: bytes):
    """Decode an IDX (MNIST) buffer -> uint8 ndarray, or None if unavailable."""
    lib = load()
    if lib is None:
        return None
    dims = (ctypes.c_int64 * 4)()
    nd = ctypes.c_int32()
    if lib.dl4j_idx_info(data, len(data), dims, ctypes.byref(nd)) != 0:
        return None
    shape = tuple(dims[i] for i in range(nd.value))
    out = np.empty(shape, np.uint8)
    rc = lib.dl4j_idx_read(data, len(data),
                           out.ctypes.data_as(ctypes.c_void_p), out.size)
    return out if rc == 0 else None


def gather_rows(src, indices, n_threads=0):
    """Shuffle-gather rows of a 2-D f32 array into a fresh batch buffer."""
    lib = load()
    src = np.ascontiguousarray(src, np.float32)
    idx = np.ascontiguousarray(indices, np.int64)
    if lib is None:
        return src[idx]
    out = np.empty((len(idx),) + src.shape[1:], np.float32)
    row_elems = int(np.prod(src.shape[1:])) if src.ndim > 1 else 1
    if n_threads <= 0:
        n_threads = min(8, os.cpu_count() or 1)
    lib.dl4j_gather_rows_f32(src.ctypes.data_as(ctypes.c_void_p),
                             idx.ctypes.data_as(ctypes.c_void_p),
                             len(idx), row_elems,
                             out.ctypes.data_as(ctypes.c_void_p), n_threads)
    return out


def normalize_u8(src, min_range=0.0, max_range=1.0):
    lib = load()
    src = np.ascontiguousarray(src, np.uint8)
    if lib is None:
        return src.astype(np.float32) * ((max_range - min_range) / 255.0) \
            + min_range
    out = np.empty(src.shape, np.float32)
    lib.dl4j_normalize_u8_f32(src.ctypes.data_as(ctypes.c_void_p), src.size,
                              min_range, max_range,
                              out.ctypes.data_as(ctypes.c_void_p))
    return out


def one_hot(labels, n_classes):
    lib = load()
    lab = np.ascontiguousarray(labels, np.int64)
    if lib is None:
        return np.eye(n_classes, dtype=np.float32)[lab]
    out = np.empty((len(lab), n_classes), np.float32)
    rc = lib.dl4j_one_hot_f32(lab.ctypes.data_as(ctypes.c_void_p), len(lab),
                              n_classes, out.ctypes.data_as(ctypes.c_void_p))
    if rc != 0:
        raise ValueError("label out of range for one_hot")
    return out
