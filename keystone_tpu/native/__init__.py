"""ctypes bindings for the native kernel library.

Replaces the reference's JNI wrappers `utils.external.{VLFeat, EncEval}`
(SURVEY.md §2.3) [unverified]. The library is built on demand from the
in-tree C++ (`make` in this directory); when the toolchain is unavailable
the callers gate on `available()` — mirroring the reference's
"skip if the native lib is missing" test pattern (SURVEY.md §4).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libkeystone_native.so")
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None
_has_jpeg: bool = False


def _f32(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.float32)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    if _lib is not None:
        return _lib
    # Always invoke make: it no-ops when up to date and rebuilds after source
    # edits; binaries are gitignored so a foreign-machine .so never ships.
    # A failed make means unavailable — a .so left on disk by an earlier
    # build is not what the sources say, and every entry point below raises
    # with the build error rather than run it.
    try:
        subprocess.run(
            ["make", "-s"],
            cwd=_DIR,
            check=True,
            capture_output=True,
            text=True,
        )
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        _build_error = getattr(e, "stderr", str(e)) or str(e)
        return None
    # Binding/ABI failures (missing optional symbols) must degrade to
    # unavailable(), never raise — the auto ingest backend depends on a
    # clean False to fall back to PIL.
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.ks_abi_version.restype = ctypes.c_int
        if lib.ks_abi_version() != 2:
            _build_error = "native ABI mismatch — run make clean"
            return None
        lib.ks_sift_num_keypoints.restype = ctypes.c_int
        lib.ks_sift_num_keypoints.argtypes = [ctypes.c_int] * 4
        lib.ks_dense_sift.restype = ctypes.c_int
        lib.ks_dense_sift.argtypes = [f32p] + [ctypes.c_int] * 5 + [f32p]
        lib.ks_gmm_fit.restype = ctypes.c_int
        lib.ks_gmm_fit.argtypes = (
            [f32p] + [ctypes.c_int] * 4 + [ctypes.c_uint64, f32p, f32p, f32p]
        )
        lib.ks_fisher_vector.restype = ctypes.c_int
        lib.ks_fisher_vector.argtypes = (
            [f32p, ctypes.c_int, ctypes.c_int, f32p, f32p, f32p, ctypes.c_int, f32p]
        )
        # Optional: compiled out when the host lacks libjpeg (Makefile gate).
        global _has_jpeg
        _has_jpeg = hasattr(lib, "ks_decode_jpeg_batch")
        if _has_jpeg:
            lib.ks_decode_jpeg_batch.restype = ctypes.c_int
            lib.ks_decode_jpeg_batch.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_int,
                ctypes.c_int,
                f32p,
            ]
    except Exception as e:  # lint: broad-ok ctypes probe: any load/signature failure means 'no native backend'
        _build_error = f"native binding failed: {e}"
        return None
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def jpeg_available() -> bool:
    """True when the library was built against libjpeg."""
    return _load() is not None and _has_jpeg


def build_error() -> Optional[str]:
    _load()
    return _build_error


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def sift_num_keypoints(h: int, w: int, step: int, bin_size: int) -> int:
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    n = lib.ks_sift_num_keypoints(h, w, step, bin_size)
    if n < 0:
        raise ValueError("bad SIFT grid parameters")
    return n


def dense_sift(
    images: np.ndarray, step: int = 4, bin_size: int = 4
) -> np.ndarray:
    """(n, h, w) grayscale in [0,1] → (n, num_keypoints, 128) float32."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    images = _f32(images)
    n, h, w = images.shape
    nkp = sift_num_keypoints(h, w, step, bin_size)
    out = np.empty((n, nkp, 128), dtype=np.float32)
    rc = lib.ks_dense_sift(_ptr(images), n, h, w, step, bin_size, _ptr(out))
    if rc != 0:
        raise RuntimeError(f"ks_dense_sift failed ({rc})")
    return out


def gmm_fit(
    X: np.ndarray, k: int, iters: int = 25, seed: int = 0
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(n, d) → (weights (k,), means (k, d), vars (k, d))."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    X = _f32(X)
    n, d = X.shape
    weights = np.empty(k, dtype=np.float32)
    means = np.empty((k, d), dtype=np.float32)
    variances = np.empty((k, d), dtype=np.float32)
    rc = lib.ks_gmm_fit(
        _ptr(X), n, d, k, iters, seed, _ptr(weights), _ptr(means), _ptr(variances)
    )
    if rc != 0:
        raise RuntimeError(f"ks_gmm_fit failed ({rc})")
    return weights, means, variances


def decode_jpeg_batch(bufs, size: int) -> np.ndarray:
    """list of jpeg byte strings → (n, size, size, 3) float32 NHWC in [0,1].

    libjpeg DCT-scaled decode + bilinear resize, OpenMP across images —
    the native replacement for the PIL thread pool on the ingest path.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    if not _has_jpeg:
        raise RuntimeError("native library was built without libjpeg")
    n = len(bufs)
    if n == 0:
        return np.empty((0, size, size, 3), dtype=np.float32)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    for i, b in enumerate(bufs):
        offsets[i + 1] = offsets[i] + len(b)
    data = np.frombuffer(b"".join(bufs), dtype=np.uint8)
    out = np.empty((n, size, size, 3), dtype=np.float32)
    rc = lib.ks_decode_jpeg_batch(
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n,
        size,
        _ptr(out),
    )
    if rc != 0:
        raise ValueError(f"jpeg decode failed at image {-rc - 1}")
    return out


def fisher_vector(
    X: np.ndarray,
    weights: np.ndarray,
    means: np.ndarray,
    variances: np.ndarray,
) -> np.ndarray:
    """Descriptor set (n, d) against a GMM (k) → raw FV (2·k·d,)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    X = _f32(X)
    weights = _f32(weights)
    means = _f32(means)
    variances = _f32(variances)
    n, d = X.shape
    k = weights.shape[0]
    out = np.empty(2 * k * d, dtype=np.float32)
    rc = lib.ks_fisher_vector(
        _ptr(X), n, d, _ptr(weights), _ptr(means), _ptr(variances), k, _ptr(out)
    )
    if rc != 0:
        raise RuntimeError(f"ks_fisher_vector failed ({rc})")
    return out
