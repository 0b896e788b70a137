"""Native host-runtime loader: compile-on-first-use C++ with ctypes bindings.

TPU-native replacement for the reference's NativeLoader (reference:
core/env/NativeLoader.java:28-140 — extract .so from jar resources, then
``System.load``). Here the native source ships with the package; the loader
compiles it once with the system toolchain into a content-addressed cache and
binds the C ABI via ctypes. Everything has a pure-Python fallback, so the
framework degrades gracefully on hosts without a compiler.

API:
- ``get_lib() -> ctypes.CDLL | None`` — the compiled library (cached), or
  None when unavailable.
- ``murmur3_batch(strings, seeds) -> np.uint32[n]`` — batch feature hashing.
- ``bin_batch(X, upper_bounds) -> np.int32[n, F]`` — quantile-bin apply.
- ``csv_read_floats(text, ncols) -> np.float32[rows, ncols]`` — data loader.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import List, Optional, Sequence

import numpy as np

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
# repo layout keeps the C++ at <root>/native/; installed wheels ship a copy
# as package data next to this file (setup.py build_py_with_native)
_SOURCE_CANDIDATES = (
    os.path.join(os.path.dirname(os.path.dirname(_PKG_DIR)), "native",
                 "mmlspark_native.cpp"),
    os.path.join(_PKG_DIR, "mmlspark_native.cpp"),
)
_SOURCE = next((p for p in _SOURCE_CANDIDATES if os.path.exists(p)),
               _SOURCE_CANDIDATES[0])
# wheels built on a host with a toolchain ship the compiled library too
_PREBUILT = os.path.join(_PKG_DIR, "mmlspark_native_prebuilt.so")
# a checkout (the repo-layout source exists) always builds from the source
# git tracks: an untracked prebuilt left in the package dir by some earlier
# build is not what a clean copy of the same commit would run
_IN_CHECKOUT = os.path.exists(_SOURCE_CANDIDATES[0])
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_path: Optional[str] = None
_lib_tried = False


def _cache_dir() -> str:
    # Per-user, mode-0700 cache: a world-writable shared dir would let
    # another local user pre-plant a .so that we'd load into this process.
    d = os.environ.get("MMLSPARK_TPU_NATIVE_CACHE")
    if not d:
        uid = os.getuid() if hasattr(os, "getuid") else "u"
        d = os.path.join(tempfile.gettempdir(), f"mmlspark_tpu_native_{uid}")
    os.makedirs(d, mode=0o700, exist_ok=True)
    st = os.stat(d)
    if hasattr(os, "getuid") and st.st_uid != os.getuid():
        raise PermissionError(f"native cache dir {d} owned by uid {st.st_uid}")
    return d


def _compile() -> Optional[str]:
    if not os.path.exists(_SOURCE):
        return None
    with open(_SOURCE, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_cache_dir(), f"mmlspark_native_{digest}.so")
    if os.path.exists(so_path):
        return so_path
    # unique temp name per process: concurrent cold-cache compiles must not
    # race on one .tmp file (os.replace publishes atomically)
    tmp_path = f"{so_path}.{os.getpid()}.tmp"
    for cxx in (os.environ.get("CXX"), "g++", "c++", "clang++"):
        if not cxx:
            continue
        cmd = [cxx, "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
               _SOURCE, "-o", tmp_path]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp_path, so_path)
            return so_path
        except (OSError, subprocess.SubprocessError):
            continue
    return None


def get_lib() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the native library; None if unavailable."""
    global _lib, _lib_tried
    if _lib_tried:        # lock-free fast path for per-hash callers
        return _lib
    with _lock:
        if _lib_tried:
            return _lib
        try:
            lib = _load()
            if lib is not None:
                _bind(lib)
                _lib = lib
        except Exception:
            # corrupt cached .so, missing symbols, etc.: latch to the
            # Python fallback rather than crashing the first caller
            _lib = None
        finally:
            # published last (the lock-free fast path must never observe
            # _lib_tried=True mid-compile), but always published — a failed
            # attempt latches instead of re-running the compile per call
            _lib_tried = True
        return _lib


# every symbol _bind wires up: a prebuilt .so from an older source tree
# (missing a newer symbol) must fall through to a recompile, not latch the
# whole module to the Python fallback
_EXPECTED_SYMBOLS = ("mm_abi_version", "mm_murmur3_32", "mm_murmur3_batch",
                     "mm_bin_batch", "mm_csv_read_floats", "mm_treeshap")
# behavioral version (mm_abi_version in mmlspark_native.cpp): symbol
# presence alone can't catch a prebuilt whose symbols all exist but whose
# SEMANTICS are stale (e.g. the pre-cycle-guard mm_treeshap); bump both
# on any native behavior change (v4: mm_treeshap rejects out-of-range
# split features, cycles, and trees past the 256 MiB arena budget —
# effective depth cutoff ~3094, with a 4096 structural backstop)
_ABI_VERSION = 4


def _prebuilt_current(lib: ctypes.CDLL) -> bool:
    if not all(hasattr(lib, s) for s in _EXPECTED_SYMBOLS):
        return False
    lib.mm_abi_version.restype = ctypes.c_int64
    lib.mm_abi_version.argtypes = []
    return int(lib.mm_abi_version()) == _ABI_VERSION


def _load() -> Optional[ctypes.CDLL]:
    global _lib_path
    if os.environ.get("MMLSPARK_TPU_DISABLE_NATIVE"):
        return None
    if not _IN_CHECKOUT and os.path.exists(_PREBUILT):
        try:
            lib = ctypes.CDLL(_PREBUILT)
            if _prebuilt_current(lib):
                _lib_path = _PREBUILT
                return lib
            # stale prebuilt (old symbols or old behavior): recompile
        except OSError:
            pass  # wrong arch/ABI for this host: recompile from source
    so = _compile()
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        return None
    _lib_path = so
    return lib


def lib_path() -> Optional[str]:
    """Path of the loaded native library (None: Python fallbacks in use)."""
    return _lib_path if get_lib() is not None else None


def _bind(lib: ctypes.CDLL) -> None:
    lib.mm_murmur3_32.restype = ctypes.c_uint32
    lib.mm_murmur3_32.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                  ctypes.c_uint32]
    lib.mm_murmur3_batch.restype = None
    lib.mm_murmur3_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint32)]
    lib.mm_bin_batch.restype = None
    lib.mm_bin_batch.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32)]
    lib.mm_csv_read_floats.restype = ctypes.c_int64
    lib.mm_csv_read_floats.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    lib.mm_treeshap.restype = ctypes.c_int64
    lib.mm_treeshap.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_double)]


def native_available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# High-level wrappers (with pure-Python fallbacks)
# ---------------------------------------------------------------------------


def murmur3_batch(strings: Sequence[str],
                  seeds: Sequence[int]) -> np.ndarray:
    """Hash n utf-8 strings with per-string seeds -> uint32[n]."""
    lib = get_lib()
    if lib is None:
        from ..ops.murmur import murmur3_32
        return np.asarray([murmur3_32(s, int(seed)) for s, seed
                           in zip(strings, seeds)], dtype=np.uint32)
    encoded: List[bytes] = [s.encode("utf-8") for s in strings]
    offsets = np.zeros(len(encoded) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in encoded], out=offsets[1:])
    buf = b"".join(encoded)
    seeds_arr = np.asarray(seeds, dtype=np.uint32)
    out = np.empty(len(encoded), dtype=np.uint32)
    lib.mm_murmur3_batch(
        buf, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        seeds_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        len(encoded), out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return out


def bin_batch(X: np.ndarray, upper_bounds: np.ndarray) -> np.ndarray:
    """Apply per-feature quantile bins: [n, F] floats -> [n, F] int32 bins."""
    X = np.ascontiguousarray(X, dtype=np.float32)
    ub = np.ascontiguousarray(upper_bounds, dtype=np.float32)
    n, F = X.shape
    lib = get_lib()
    if lib is None:
        out = np.empty((n, F), dtype=np.int32)
        for f in range(F):
            out[:, f] = np.searchsorted(ub[f], X[:, f], side="left")
        out[np.isnan(X)] = 0
        return out
    out = np.empty((n, F), dtype=np.int32)
    lib.mm_bin_batch(
        X.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, F,
        ub.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), ub.shape[1],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out


def csv_read_floats(text: str, ncols: int,
                    max_rows: Optional[int] = None) -> np.ndarray:
    """Parse numeric CSV text -> float32[rows, ncols]; raises on ragged rows."""
    data = text.encode("utf-8") if isinstance(text, str) else text
    lib = get_lib()
    if max_rows is None:
        max_rows = data.count(b"\n") + 1
    if lib is None:
        def parse(p: str) -> float:
            p = p.strip()
            if not p:
                return np.nan
            try:
                return float(p)
            except ValueError:
                return np.nan      # same as the native parser: bad field=NaN

        rows = []
        for line in data.decode("utf-8").splitlines():
            if not line.strip():
                continue
            parts = line.split(",")
            if len(parts) != ncols:
                raise ValueError(f"expected {ncols} columns, got {len(parts)}")
            rows.append([parse(p) for p in parts])
            if len(rows) >= max_rows:
                break
        if not rows:
            # keep the native path's [0, ncols] shape so callers can
            # concatenate empty and non-empty parses
            return np.zeros((0, ncols), dtype=np.float32)
        return np.asarray(rows, dtype=np.float32)
    out = np.empty((max_rows, ncols), dtype=np.float32)
    n = lib.mm_csv_read_floats(
        data, len(data), ncols,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), max_rows)
    if n < 0:
        raise ValueError(f"CSV shape mismatch: expected {ncols} columns")
    return out[:n]


def treeshap_tree(feat: np.ndarray, left: np.ndarray, right: np.ndarray,
                  is_leaf: np.ndarray, cover: np.ndarray,
                  values: np.ndarray, go_left: np.ndarray,
                  n_features: int,
                  n_threads: int = 0) -> Optional[np.ndarray]:
    """Exact TreeSHAP for one tree, all instances: -> float64[n, F].

    ``go_left`` is the [M, n] per-node routing matrix the caller
    precomputes (thresholds / categorical bitsets / NaN policy stay in
    models/gbdt/treeshap.py, the single source of split semantics).
    Returns None when the native library is unavailable — the caller
    falls back to the vectorized numpy recursion; there is deliberately
    no Python fallback here because that numpy engine IS the fallback.
    ``n_threads=0`` uses the hardware concurrency.
    """
    lib = get_lib()
    if lib is None:
        return None
    feat = np.ascontiguousarray(feat, dtype=np.int32)
    left = np.ascontiguousarray(left, dtype=np.int32)
    right = np.ascontiguousarray(right, dtype=np.int32)
    is_leaf = np.ascontiguousarray(is_leaf, dtype=np.uint8)
    cover = np.ascontiguousarray(cover, dtype=np.float64)
    values = np.ascontiguousarray(values, dtype=np.float64)
    go_left = np.ascontiguousarray(go_left, dtype=np.uint8)
    M, n = go_left.shape
    phi = np.zeros((n, int(n_features)), dtype=np.float64)
    rc = lib.mm_treeshap(
        feat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        left.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        right.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        is_leaf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        cover.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        go_left.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        M, n, int(n_features), int(n_threads),
        phi.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if rc != 0:
        # malformed/degenerate tree (bad child or feature index, cycle,
        # depth past the native arena budget): route to the Python engine
        # — shap_values pre-validates split features, bad child indices
        # raise a meaningful IndexError there, and legitimately deep
        # chains run on its heap-based stack instead of C recursion
        return None
    return phi
