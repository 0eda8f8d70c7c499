"""ctypes binding for the native libsvm parser.

Loaded opportunistically by :mod:`distlr_tpu.data.libsvm`; any import or
build failure falls back to the pure-Python tokenizer.
"""

from __future__ import annotations

import ctypes
import os
import threading

import numpy as np

from distlr_tpu.utils.native_build import ensure_built

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")
_SO = os.path.join(_DIR, "libdistlr_libsvm.so")
_lock = threading.Lock()
_lib = None


def _load():
    global _lib
    if _lib is None:
        with _lock:
            if _lib is None:
                ensure_built(_DIR, [_SO])
                lib = ctypes.CDLL(_SO)
                lib.libsvm_count.restype = ctypes.c_int
                lib.libsvm_count.argtypes = [
                    ctypes.c_char_p, ctypes.c_int64,
                    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
                ]
                lib.libsvm_parse.restype = ctypes.c_int64
                lib.libsvm_parse.argtypes = [
                    ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ]
                _lib = lib
    return _lib


def parse_libsvm_bytes(data: bytes, multiclass: bool):
    """Returns ``(labels i32, row_ptr i64, cols i32, vals f32)``."""
    lib = _load()
    n = len(data)
    n_rows = ctypes.c_int64()
    n_nnz = ctypes.c_int64()
    lib.libsvm_count(data, n, ctypes.byref(n_rows), ctypes.byref(n_nnz))
    labels = np.empty(n_rows.value, dtype=np.int32)
    row_ptr = np.empty(n_rows.value + 1, dtype=np.int64)
    cols = np.empty(n_nnz.value, dtype=np.int32)
    vals = np.empty(n_nnz.value, dtype=np.float32)
    parsed = lib.libsvm_parse(
        data, n, int(multiclass),
        labels.ctypes.data_as(ctypes.c_void_p),
        row_ptr.ctypes.data_as(ctypes.c_void_p),
        cols.ctypes.data_as(ctypes.c_void_p),
        vals.ctypes.data_as(ctypes.c_void_p),
    )
    if parsed != n_rows.value:
        raise ValueError(f"malformed libsvm input (parsed {parsed} of {n_rows.value} rows)")
    return labels, row_ptr, cols, vals
