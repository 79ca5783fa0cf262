"""ctypes bindings for the native (C++) runtime components under native/.

Loads lazily.  A library that is missing, or whose source changed since
it was built, is (re)built with ``make -C native``; one that cannot be
built leaves its callers on their Python implementation — with a
warning, because for the search that is a different engine
(simulator/native_search.py vs simulator/search.py).  ``status()`` says
how each library was obtained.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import warnings
from typing import Dict, Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")

LIBRARIES = ("libffsim.so", "libffdata.so", "libffsearch.so")

_libs: Dict[str, Optional[ctypes.CDLL]] = {}
_status: Dict[str, str] = {}


def _source_digest(path: str) -> str:
    src = os.path.join(os.path.dirname(path),
                       os.path.basename(path)[3:-3] + ".cpp")
    with open(src, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _stale(path: str) -> bool:
    """A prebuilt .so whose source has changed must NOT be loaded: the C
    ABI may have changed and a mismatched call corrupts arguments
    silently (no crash — just wrong numbers).  Judged by the digest of
    the source recorded beside the library at build time, not by mtimes,
    which a copy of the tree may flatten."""
    try:
        with open(path + ".src") as f:
            return f.read().strip() != _source_digest(path)
    except OSError:
        return True


def _load(name: str) -> Optional[ctypes.CDLL]:
    if name in _libs:
        return _libs[name]
    path = os.path.join(_NATIVE_DIR, name)
    how = "prebuilt"
    try:
        if not os.path.exists(path) or _stale(path):
            subprocess.run(["make", "-C", _NATIVE_DIR, "-B", name],
                           check=True, capture_output=True, timeout=120)
            with open(path + ".src", "w") as f:
                f.write(_source_digest(path))
            how = "built in this run"
        lib = ctypes.CDLL(path)
    except (OSError, subprocess.SubprocessError) as e:
        lib, how = None, f"unavailable: {type(e).__name__}: {e}"
        warnings.warn(f"native/{name} {how}; its callers use their Python "
                      f"implementation")
    _libs[name], _status[name] = lib, how
    return lib


def status(load_all: bool = False) -> Dict[str, str]:
    """How each native library this process asked for was obtained:
    "prebuilt", "built in this run" or "unavailable: <why>".
    ``load_all`` asks for every library first."""
    if load_all:
        for name in LIBRARIES:
            _load(name)
    return dict(_status)


def sim_lib() -> Optional[ctypes.CDLL]:
    lib = _load("libffsim.so")
    if lib is not None and not getattr(lib, "_ff_configured", False):
        lib.ffsim_simulate.restype = ctypes.c_double
        lib.ffsim_simulate.argtypes = [
            ctypes.c_int32, ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        lib._ff_configured = True
    return lib


def data_lib() -> Optional[ctypes.CDLL]:
    lib = _load("libffdata.so")
    if lib is not None and not getattr(lib, "_ff_configured", False):
        lib.ffdata_gather_rows.restype = None
        lib.ffdata_gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32]
        lib._ff_configured = True
    return lib


def gather_rows(src, indices, out=None):
    """Multithreaded row gather: out[i] = src[indices[i]].  Falls back to
    numpy fancy indexing when the native lib is unavailable."""
    import numpy as np

    src = np.ascontiguousarray(src)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    lib = data_lib()
    if lib is None or src.ndim < 2:
        return src[indices]
    batch = len(indices)
    if out is None:
        out = np.empty((batch,) + src.shape[1:], dtype=src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:]))
    nthreads = min(8, max(1, os.cpu_count() or 1))
    lib.ffdata_gather_rows(
        src.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        batch, row_bytes, nthreads)
    return out


def simulate_dag(run_times, devices, edge_src, edge_dst) -> Optional[float]:
    """Native event simulation; returns None when the lib is unavailable
    (caller falls back to the Python engine), raises on graph cycles."""
    import numpy as np

    lib = sim_lib()
    if lib is None:
        return None
    rt = np.ascontiguousarray(run_times, dtype=np.float64)
    dv = np.ascontiguousarray(devices, dtype=np.int64)
    es = np.ascontiguousarray(edge_src, dtype=np.int32)
    ed = np.ascontiguousarray(edge_dst, dtype=np.int32)
    res = lib.ffsim_simulate(
        len(rt), rt.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        dv.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(es), es.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ed.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if res < 0:
        raise RuntimeError("cycle in simulated task graph")
    return float(res)
