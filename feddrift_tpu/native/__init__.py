"""ctypes loader for the native drift-data generator (drift_gen.cpp).

Builds lazily with ``make`` on first use (g++ is in the image); falls back
gracefully — ``available()`` returns False and callers keep the numpy path.
The native path is deterministic per (seed, client, step) cell independent of
thread count, so repeated generation is bitwise-reproducible.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger("feddrift_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libdrift_gen.so")
_SRC = os.path.join(_DIR, "drift_gen.cpp")
_DATASET_IDS = {"sea": 0, "sine": 1, "circle": 2}

_lock = threading.Lock()
_lib = None
_build_failed = False


def _load():
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        # (re)build when the library is missing OR older than its source:
        # a copied tree can carry a stale artefact from another checkout
        if (not os.path.exists(_SO)
                or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            try:
                subprocess.run(["make", "-C", _DIR], check=True,
                               capture_output=True, timeout=120)
            except (subprocess.SubprocessError, FileNotFoundError) as e:
                log.warning("native drift_gen build failed (%s); "
                            "using numpy generator", e)
                _build_failed = True
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            log.warning("could not load %s (%s)", _SO, e)
            _build_failed = True
            return None
        lib.fd_generate.restype = ctypes.c_int
        lib.fd_generate.argtypes = [
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_double, ctypes.c_uint64, ctypes.c_int,
        ]
        lib.fd_feature_dim.restype = ctypes.c_int
        lib.fd_feature_dim.argtypes = [ctypes.c_int]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def generate(name: str, concepts: np.ndarray, sample_num: int,
             noise_prob: float, seed: int,
             n_threads: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Fill [C, T1, N, F] / [C, T1, N] arrays with the native kernel.

    ``concepts``: [T1, C] int matrix (already time-stretch dilated).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native drift generator unavailable")
    if name not in _DATASET_IDS:
        raise KeyError(f"native generator supports {sorted(_DATASET_IDS)}, "
                       f"not {name!r}")
    ds_id = _DATASET_IDS[name]
    T1, C = concepts.shape
    F = int(lib.fd_feature_dim(ds_id))
    x = np.empty((C, T1, sample_num, F), dtype=np.float32)
    y = np.empty((C, T1, sample_num), dtype=np.int32)
    conc = np.ascontiguousarray(concepts, dtype=np.int32)
    rc = lib.fd_generate(
        ds_id,
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        y.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        conc.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        C, T1, sample_num, float(noise_prob), np.uint64(seed), n_threads)
    if rc != 0:
        raise RuntimeError(f"fd_generate returned {rc}")
    return x, y
