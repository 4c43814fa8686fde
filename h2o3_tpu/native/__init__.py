"""Native runtime components — C++ built on demand, bound via ctypes.

Reference: H2O-3's performance-critical native pieces ship as prebuilt shared
libraries loaded at runtime (``hex/tree/xgboost/XGBoostExtension.java:73-117``
``util/NativeLibrary.java`` loader chain). Same pattern: ``native/*.cpp``
compiles once into a cached ``.so`` next to this package (g++ is in the
image; pybind11 is not, hence the plain C ABI + ctypes). Every native path
has a pure-Python fallback — absence of a toolchain degrades, never breaks.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_FAILED = False

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_PKG_DIR, "..", "..", "native", "csv_parser.cpp")
_SO = os.path.join(_PKG_DIR, "_libh2o3native.so")
#: sha256 of the source the ``.so`` was built from, written beside it: the
#: binary is reused only when this matches, so a stale or foreign ``.so``
#: (git-ignored, but copied with the working tree) is rebuilt
_SO_HASH = _SO + ".sha256"


def _build() -> str | None:
    src = os.path.abspath(_SRC)
    try:
        with open(src, "rb") as f:
            want = hashlib.sha256(f.read()).hexdigest()
    except OSError:
        return None
    try:
        with open(_SO_HASH) as f:
            have = f.read().strip()
    except OSError:
        have = None
    if have == want and os.path.exists(_SO):
        return _SO
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           src, "-o", _SO]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.SubprocessError, FileNotFoundError):
        return None
    with open(_SO_HASH, "w") as f:
        f.write(want + "\n")
    return _SO


def get_lib() -> ctypes.CDLL | None:
    """The native library, compiling on first use; None if unavailable."""
    global _LIB, _FAILED
    if _LIB is not None or _FAILED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _FAILED:
            return _LIB
        so = _build()
        if so is None:
            _FAILED = True
            return None
        lib = ctypes.CDLL(so)
        lib.h2o3_parse_csv.restype = ctypes.c_void_p
        lib.h2o3_parse_csv.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                       ctypes.c_int, ctypes.c_char,
                                       ctypes.c_int]
        lib.h2o3_nrows.restype = ctypes.c_int64
        lib.h2o3_nrows.argtypes = [ctypes.c_void_p]
        lib.h2o3_ncols.restype = ctypes.c_int32
        lib.h2o3_ncols.argtypes = [ctypes.c_void_p]
        lib.h2o3_col_name.restype = ctypes.c_char_p
        lib.h2o3_col_name.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.h2o3_col_type.restype = ctypes.c_int32
        lib.h2o3_col_type.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.h2o3_col_data.restype = ctypes.POINTER(ctypes.c_double)
        lib.h2o3_col_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.h2o3_col_card.restype = ctypes.c_int32
        lib.h2o3_col_card.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.h2o3_col_level.restype = ctypes.c_char_p
        lib.h2o3_col_level.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                       ctypes.c_int]
        lib.h2o3_free.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB


def parse_csv_native(data: bytes, has_header: bool = True, sep: str = ",",
                     nthreads: int | None = None):
    """Parse CSV bytes with the native chunk-parallel parser.

    Returns ``(names, columns)`` where each column is
    ``("num", float64 array)`` or ``("cat", int32 codes, domain tuple)``;
    None when the native library is unavailable (caller falls back).
    """
    import numpy as np

    lib = get_lib()
    if lib is None:
        return None
    if nthreads is None:
        nthreads = min(os.cpu_count() or 4, 16)
    h = lib.h2o3_parse_csv(data, len(data), int(has_header),
                           sep.encode()[0], int(nthreads))
    if not h:
        return None
    try:
        nrows = lib.h2o3_nrows(h)
        ncols = lib.h2o3_ncols(h)
        names, cols = [], []
        for c in range(ncols):
            names.append(lib.h2o3_col_name(h, c).decode())
            ptr = lib.h2o3_col_data(h, c)
            arr = np.ctypeslib.as_array(ptr, shape=(nrows,)).copy()
            if lib.h2o3_col_type(h, c) == 0:
                cols.append(("num", arr))
            else:
                dom = tuple(lib.h2o3_col_level(h, c, i).decode()
                            for i in range(lib.h2o3_col_card(h, c)))
                cols.append(("cat", arr.astype(np.int32), dom))
        return names, cols
    finally:
        lib.h2o3_free(h)
