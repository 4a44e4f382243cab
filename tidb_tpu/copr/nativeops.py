"""ctypes loader for the native host aggregation primitives.

Reference analog: the reference's aggregation hot loops are compiled Go
(agg_hash_executor.go); ours are C++ (native/hostops.cpp) behind numpy
fallbacks — `count_keys`/`gather_lookup` return None-equivalent behavior
by the caller checking `available()` first.  The library is built from
source on first load (native.ensure_built); a failed build degrades to
the numpy path: the native library is an accelerator, never a
correctness dependency.  `available()` says which one is in use.
"""

from __future__ import annotations

import ctypes
import subprocess
import threading
from typing import Optional

import numpy as np

_lock = threading.Lock()
_lib = None
_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            from ..native import ensure_built
            lib = ctypes.CDLL(ensure_built("libtpuhostops.so",
                                           "hostops.cpp"))
            I64, I32P, I64P = (ctypes.c_int64,
                               ctypes.POINTER(ctypes.c_int32),
                               ctypes.POINTER(ctypes.c_int64))
            lib.hops_count_i32.argtypes = [I32P, I64, I64, I32P]
            lib.hops_count_i64.argtypes = [I64P, I64, I64, I32P]
            lib.hops_gather_i32.argtypes = [I32P, I64, I64, I32P, I64P]
            lib.hops_gather_i64.argtypes = [I64P, I64, I64, I32P, I64P]
            _lib = lib
        except (OSError, subprocess.CalledProcessError, AttributeError):
            # only the expected degradations fall back to numpy: no
            # toolchain / failed build (CalledProcessError), unloadable
            # .so (OSError), stale library missing a symbol
            # (AttributeError).  Anything else is a real bug and raises.
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def count_keys(keys: np.ndarray, lo: int, rng: int) -> Optional[np.ndarray]:
    """Histogram of (keys - lo) over [0, rng) as int32 counts, or None
    when the native library is unavailable / dtype unsupported."""
    lib = _load()
    if lib is None or keys.dtype not in (np.int32, np.int64):
        return None
    keys = np.ascontiguousarray(keys)
    table = np.zeros(rng, np.int32)
    if keys.dtype == np.int32:
        lib.hops_count_i32(_ptr(keys, ctypes.c_int32), len(keys), lo,
                           _ptr(table, ctypes.c_int32))
    else:
        lib.hops_count_i64(_ptr(keys, ctypes.c_int64), len(keys), lo,
                           _ptr(table, ctypes.c_int32))
    return table


def gather_lookup(keys: np.ndarray, lo: int,
                  lookup: np.ndarray) -> Optional[np.ndarray]:
    """inv[i] = lookup[keys[i] - lo] (int64 group ids), or None."""
    lib = _load()
    if lib is None or keys.dtype not in (np.int32, np.int64):
        return None
    keys = np.ascontiguousarray(keys)
    lookup = np.ascontiguousarray(lookup, np.int32)
    inv = np.empty(len(keys), np.int64)
    if keys.dtype == np.int32:
        lib.hops_gather_i32(_ptr(keys, ctypes.c_int32), len(keys), lo,
                            _ptr(lookup, ctypes.c_int32),
                            _ptr(inv, ctypes.c_int64))
    else:
        lib.hops_gather_i64(_ptr(keys, ctypes.c_int64), len(keys), lo,
                            _ptr(lookup, ctypes.c_int32),
                            _ptr(inv, ctypes.c_int64))
    return inv


__all__ = ["available", "count_keys", "gather_lookup"]
