"""ctypes loader for the native control-plane kernels.

Builds ``libnomad_native.so`` with the in-tree Makefile (g++) the first
time a kernel is requested — whenever the library is missing or older than
its source, so what runs is always what git holds — memoizes the handle,
and degrades to numpy equivalents when no toolchain is available, logging
the failed build; the numpy path is the correctness oracle in tests.
``status()`` says which of the two is running.

API surface (all take/return numpy arrays):
  scatter_add(idx, vals, n_out)  -> [n_out, D] int32 row sums
  fit_check(used, total)         -> (fit bool[N], exhausted_dim int32[N])
  bincount(idx, n_out)           -> int32[n_out]
  available()                    -> bool (native .so loaded)
  status()                       -> {"verifier", "built", "error"}
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger("nomad_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libnomad_native.so")
_SRC = os.path.join(_DIR, "src", "nomad_native.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_built = False
_error: Optional[str] = None


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _stale() -> bool:
    """The library is missing or older than its source (make's own rule,
    checked here so an up-to-date library costs no child process)."""
    try:
        return os.path.getmtime(_SO) < os.path.getmtime(_SRC)
    except OSError:
        return True


def _fail(what: str, detail: str) -> None:
    global _error
    _error = f"{what}: {detail}"
    logger.warning(
        "native kernels unavailable (%s); the numpy verifier runs instead",
        _error)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _built
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _stale():
            try:
                subprocess.run(
                    ["make", "-C", _DIR],
                    capture_output=True, text=True, timeout=120, check=True,
                )
            except subprocess.CalledProcessError as e:
                _fail("make failed", (e.stderr or e.stdout or "").strip()[-400:])
                return None
            except (OSError, subprocess.SubprocessError) as e:
                _fail("make did not run", f"{type(e).__name__}: {e}")
                return None
            _built = True
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            _fail("load failed", str(e))
            return None
        lib.nt_scatter_add_i32.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ]
        lib.nt_fit_check_i32.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
        ]
        lib.nt_bincount_i32.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def status() -> Dict[str, object]:
    """Which plan verifier this process runs: ``native`` (the C++
    library; ``built`` says this process compiled it) or ``numpy``, with
    the build or load error that caused it."""
    native = available()
    return {"verifier": "native" if native else "numpy",
            "built": _built, "error": _error}


def scatter_add(idx: np.ndarray, vals: np.ndarray, n_out: int) -> np.ndarray:
    """Row-sum ``vals`` grouped by ``idx`` into an [n_out, D] matrix."""
    idx = np.ascontiguousarray(idx, dtype=np.int32)
    vals = np.ascontiguousarray(vals, dtype=np.int32)
    n, d = vals.shape
    out = np.zeros((n_out, d), dtype=np.int32)
    lib = _load()
    if lib is not None and n:
        lib.nt_scatter_add_i32(
            _i32p(idx), _i32p(vals), n, d, _i32p(out), n_out
        )
        return out
    # numpy fallback: bincount per dimension (np.add.at is far slower)
    for j in range(d):
        out[:, j] = np.bincount(idx, weights=vals[:, j], minlength=n_out)[
            :n_out
        ].astype(np.int32)
    return out


def fit_check(used: np.ndarray, total: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row superset check (funcs.go:56-71): (fit, first exhausted dim)."""
    used = np.ascontiguousarray(used, dtype=np.int32)
    total = np.ascontiguousarray(total, dtype=np.int32)
    n, d = used.shape
    lib = _load()
    if lib is not None and n:
        fit = np.empty(n, dtype=np.uint8)
        exhausted = np.empty(n, dtype=np.int32)
        lib.nt_fit_check_i32(
            _i32p(used), _i32p(total), n, d, _u8p(fit), _i32p(exhausted)
        )
        return fit.astype(bool), exhausted
    over = used > total
    fit = ~over.any(axis=1)
    exhausted = np.where(fit, -1, over.argmax(axis=1)).astype(np.int32)
    return fit, exhausted


def bincount(idx: np.ndarray, n_out: int) -> np.ndarray:
    idx = np.ascontiguousarray(idx, dtype=np.int32)
    lib = _load()
    if lib is not None and idx.size:
        out = np.zeros(n_out, dtype=np.int32)
        lib.nt_bincount_i32(_i32p(idx), idx.size, _i32p(out), n_out)
        return out
    return np.bincount(idx, minlength=n_out)[:n_out].astype(np.int32)
