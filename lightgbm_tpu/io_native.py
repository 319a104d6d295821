"""ctypes binding for the native text parser (native/parser.cpp).

The shared library builds on first use with the baked-in g++ (pybind11 is
not available in this image; the flat C ABI + ctypes mirrors how the
reference's python package binds its C API, basic.py ctypes). The cached
``.so`` is named by the hash of the source and flags that built it, so an
edited source never loads a stale library. Without a compiler io.py and
dataset.py use their Python/numpy paths — with a warning, and
:func:`binning_status` says which one a run got.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional, Tuple

import numpy as np

from .utils.log import Log

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _build_lib(src_name: str = "parser.cpp",
               lib_name: str = "libparser.so",
               extra_flags: tuple = ()) -> Optional[str]:
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "native", src_name)
    # per-user cache dir (a fixed world-writable /tmp path would allow
    # another local user to plant a library) + atomic rename so concurrent
    # builders never dlopen a half-written file
    out_dir = os.environ.get("LIGHTGBM_TPU_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "lightgbm_tpu")
    os.makedirs(out_dir, exist_ok=True)
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + repr(tuple(extra_flags)).encode()).hexdigest()[:16]
    stem, ext = os.path.splitext(lib_name)
    out = os.path.join(out_dir, "%s-%s%s" % (stem, digest, ext))
    if os.path.exists(out):
        return out
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=out_dir)
    os.close(fd)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++14", "-o", tmp, src]
    cmd[1:1] = list(extra_flags)
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        Log.warning("native build of %s unavailable (%s); using the Python "
                    "path", src_name, e)
        return None
    if r.returncode != 0:
        Log.warning("native build of %s failed; using the Python path:\n%s",
                    src_name, r.stderr[-500:])
        os.unlink(tmp)
        return None
    os.replace(tmp, out)
    return out


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    path = _build_lib()
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    lib.count_dims.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
                               ctypes.POINTER(ctypes.c_int64),
                               ctypes.POINTER(ctypes.c_int64)]
    lib.count_dims.restype = ctypes.c_int
    dptr = np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS")
    lib.parse_dense.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                ctypes.c_int64, ctypes.c_int64, dptr]
    lib.parse_dense.restype = ctypes.c_int
    lib.parse_libsvm.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                 ctypes.c_int64, dptr]
    lib.parse_libsvm.restype = ctypes.c_int
    _LIB = lib
    return lib


def parse_file(path: str,
               expect_fmt: Optional[str] = None
               ) -> Optional[Tuple[np.ndarray, str]]:
    """Parse a CSV/TSV/LibSVM file natively.

    Returns (matrix, fmt) where matrix column 0 is the raw first column
    (the caller applies label/ignore-column semantics), fmt in
    {"csv", "tsv", "space", "libsvm"} — or None when the native path is
    unavailable or the detected format differs from ``expect_fmt``
    (caller falls back to Python).
    """
    lib = get_lib()
    if lib is None:
        return None
    sep = ctypes.c_int(0)
    rows = ctypes.c_int64(0)
    cols = ctypes.c_int64(0)
    if lib.count_dims(path.encode(), ctypes.byref(sep), ctypes.byref(rows),
                      ctypes.byref(cols)) != 0:
        return None
    n, c = int(rows.value), int(cols.value)
    if n == 0 or c == 0:
        return None
    detected = "libsvm" if sep.value == -1 else \
        {",": "csv", "\t": "tsv"}.get(chr(sep.value), "space")
    if expect_fmt is not None and detected != expect_fmt:
        return None
    out = np.empty((n, c), dtype=np.float64)
    if sep.value == -1:
        rc = lib.parse_libsvm(path.encode(), n, c, out)
        fmt = "libsvm"
    else:
        rc = lib.parse_dense(path.encode(), sep.value, n, c, out)
        fmt = {",": "csv", "\t": "tsv"}.get(chr(sep.value), "space")
    if rc != 0:
        return None
    return out, fmt


# ---------------------------------------------------------------------------
# Native threaded bin application (native/binning.cpp)
# ---------------------------------------------------------------------------

_BIN_LIB: Optional[ctypes.CDLL] = None
_BIN_TRIED = False


def binning_status() -> str:
    """"native" when the threaded bin applier built and loaded, else
    "numpy" (the build warning says why) — the host-time cliff a run
    should state, not hide."""
    return "native" if get_binning_lib() is not None else "numpy"


def get_binning_lib() -> Optional[ctypes.CDLL]:
    global _BIN_LIB, _BIN_TRIED
    if _BIN_TRIED:
        return _BIN_LIB
    _BIN_TRIED = True
    path = _build_lib("binning.cpp", "libbinning.so", ("-pthread",))
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
        f64p = np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")
        lib.lgbm_apply_bins_u8.argtypes = [
            f64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, i32p,
            f64p, i64p, i32p, i32p, i32p, u8p, ctypes.c_int64, i32p,
            ctypes.c_int32]
        lib.lgbm_apply_bins_u8.restype = None
    except (OSError, AttributeError) as e:
        # a corrupted/stale cached .so must degrade to the numpy path, the
        # same contract as compile failures in _build_lib
        Log.warning("native binning library unusable (%s); using numpy", e)
        return None
    _BIN_LIB = lib
    return lib


def apply_bins_native(Xv: np.ndarray, specs, out: np.ndarray,
                      nthreads: int = 0) -> bool:
    """Bin a batch of numerical features into `out` columns natively.

    specs: list of (x_col, upper_bounds f64 array, missing_type,
    missing_bin, out_col). Returns False when the native library is
    unavailable (caller falls back to numpy searchsorted).
    """
    lib = get_binning_lib()
    if lib is None or not specs:
        return False
    col_idx = np.asarray([s[0] for s in specs], np.int32)
    bounds_cat = np.concatenate([np.asarray(s[1], np.float64) for s in specs])
    off = np.zeros(len(specs), np.int64)
    nb = np.asarray([len(s[1]) for s in specs], np.int32)
    np.cumsum(nb[:-1], out=off[1:])
    mtype = np.asarray([s[2] for s in specs], np.int32)
    mbin = np.asarray([s[3] for s in specs], np.int32)
    ocol = np.asarray([s[4] for s in specs], np.int32)
    lib.lgbm_apply_bins_u8(
        np.ascontiguousarray(Xv), Xv.shape[0], Xv.shape[1],
        np.int32(len(specs)), col_idx, bounds_cat, off, nb, mtype, mbin,
        out, out.shape[1], ocol,
        np.int32(nthreads if nthreads > 0 else (os.cpu_count() or 1)))
    return True
