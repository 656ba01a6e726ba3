"""Native host runtime: ctypes bindings to libmmlspark_native.so.

Reference: the four external C++ engines bridged via JNI/SWIG (SURVEY §2.9)
and their `NativeLoader` (extract .so + System.load).  Here the native lib is
built from mmlspark_tpu/native/src/native.cpp on first use (g++ is part of
the toolchain) and loaded with ctypes; every entry point has a NumPy
fallback so the framework stays functional without a compiler.

Surface:
  available()                 -> bool (lib built + loaded)
  murmur3_batch(strs, seed)   -> uint32 hashes (VW murmur parity)
  histogram(bins, g, h, node) -> GBDT gradient/hessian histograms
  load_csv_numeric(path)      -> float64 matrix (fast columnar ingestion)
  decode_jpeg_bgr(bytes)      -> HWC uint8 BGR array (libjpeg fast path,
                                 DCT-domain 1/2..1/8 scale_denom decodes)
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Union

import numpy as np

__all__ = ["available", "build", "murmur3_batch", "histogram",
           "load_csv_numeric", "decode_jpeg_bgr", "decode_jpeg_bgr_into",
           "jpeg_probe", "jpeg_available"]

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libmmlspark_native.so")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

# same default ceiling as PIL's DecompressionBombError threshold
MAX_JPEG_PIXELS = 178_956_970


def build(force: bool = False) -> bool:
    """Compile the shared lib (make -C mmlspark_tpu/native).  Always runs
    make (a no-op when fresh) so a stale .so picks up new entry points;
    `force` rebuilds from src/native.cpp unconditionally.  True only when
    make itself succeeded: a failed build never passes off whatever
    untracked .so happens to lie on disk as the result."""
    try:
        subprocess.run(
            ["make", "-C", _DIR] + (["-B"] if force else []),
            check=True, capture_output=True, timeout=120,
        )
    except (subprocess.SubprocessError, FileNotFoundError):
        return False
    return os.path.exists(_SO)


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if not build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.murmur3_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_uint32, ctypes.c_void_p,
        ]
        lib.histogram_f64.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.csv_count.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ]
        lib.csv_count.restype = ctypes.c_int64
        lib.csv_parse.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.csv_parse.restype = ctypes.c_int64
        for fn in ("mml_jpeg_probe", "mml_jpeg_decode_bgr"):
            if hasattr(lib, fn):
                getattr(lib, fn).restype = ctypes.c_int32
        if hasattr(lib, "mml_jpeg_probe"):
            lib.mml_jpeg_probe.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
            lib.mml_jpeg_decode_bgr.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
        _LIB = lib
        return _LIB


def available() -> bool:
    return _load() is not None


def murmur3_batch(strings: Sequence[Union[str, bytes]],
                  seed: int = 0) -> np.ndarray:
    """Hash a batch of strings; bit-exact with online.hashing.murmurhash3_32."""
    blobs = [s.encode("utf-8") if isinstance(s, str) else bytes(s)
             for s in strings]
    lib = _load()
    if lib is None:  # NumPy-free Python fallback
        from ..online.hashing import murmurhash3_32

        return np.array([murmurhash3_32(b, seed) for b in blobs], np.uint32)
    data = b"".join(blobs)
    offsets = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=offsets[1:])
    out = np.zeros(len(blobs), np.uint32)
    buf = np.frombuffer(data, np.uint8) if data else np.zeros(1, np.uint8)
    lib.murmur3_batch(
        buf.ctypes.data, offsets.ctypes.data, len(blobs),
        ctypes.c_uint32(seed & 0xFFFFFFFF), out.ctypes.data,
    )
    return out


def histogram(bins: np.ndarray, grad: np.ndarray, hess: np.ndarray,
              node_idx: np.ndarray, n_nodes: int,
              n_bins: int = 256) -> np.ndarray:
    """(n_nodes, n_features, n_bins, 2) gradient/hessian histograms.

    bins: (n, f) uint8; node_idx: (n,) int32, -1 = skip row.
    """
    bins = np.ascontiguousarray(bins, np.uint8)
    grad = np.ascontiguousarray(grad, np.float32)
    hess = np.ascontiguousarray(hess, np.float32)
    node_idx = np.ascontiguousarray(node_idx, np.int32)
    n, f = bins.shape
    out = np.zeros((n_nodes, f, n_bins, 2), np.float64)
    lib = _load()
    if lib is None:
        for node in range(n_nodes):
            mask = node_idx == node
            for j in range(f):
                np.add.at(out[node, j, :, 0], bins[mask, j], grad[mask])
                np.add.at(out[node, j, :, 1], bins[mask, j], hess[mask])
        return out
    lib.histogram_f64(
        bins.ctypes.data, grad.ctypes.data, hess.ctypes.data,
        node_idx.ctypes.data, n, f, n_bins, n_nodes, out.ctypes.data,
    )
    return out


def load_csv_numeric(path: str, has_header: bool = True) -> np.ndarray:
    """Parse a numeric CSV into a float64 (rows, cols) matrix."""
    lib = _load()
    if lib is None:
        return np.loadtxt(path, delimiter=",", dtype=np.float64,
                          skiprows=1 if has_header else 0, ndmin=2)
    n_rows = ctypes.c_int64()
    n_cols = ctypes.c_int64()
    rc = lib.csv_count(path.encode(), ctypes.byref(n_rows),
                       ctypes.byref(n_cols), int(has_header))
    if rc == -1:
        raise FileNotFoundError(path)
    if rc < 0:
        raise ValueError(f"ragged CSV: {path}")
    r, c = n_rows.value, n_cols.value
    out = np.zeros(r * c, np.float64)
    written = lib.csv_parse(path.encode(), int(has_header),
                            out.ctypes.data, r * c)
    if written == -4:
        raise ValueError(f"non-numeric cell in CSV: {path}")
    if written != r * c:
        raise ValueError(f"CSV parse mismatch: {written} != {r * c}")
    return out.reshape(r, c)


def jpeg_available() -> bool:
    """True when the lib was built against libjpeg (probe returns != -2)."""
    lib = _load()
    if lib is None or not hasattr(lib, "mml_jpeg_probe"):
        return False
    h = ctypes.c_int32()
    w = ctypes.c_int32()
    c = ctypes.c_int32()
    # 2-byte garbage: -1 (bad stream) means jpeg code is compiled in; -2 not
    buf = np.frombuffer(b"xx", np.uint8)
    rc = lib.mml_jpeg_probe(buf.ctypes.data, 2, 1, ctypes.byref(h),
                            ctypes.byref(w), ctypes.byref(c))
    return rc != -2


def decode_jpeg_bgr(data: bytes, scale_denom: int = 1) -> Optional[np.ndarray]:
    """Decode JPEG bytes to an HWC uint8 array in BGR order (gray: 1
    channel); None when the native path is unavailable or the stream is
    invalid.  `scale_denom` in {1,2,4,8} decodes at reduced resolution in
    the DCT domain — the cheap path when the target size is far below the
    source (ImageTransformer decode modes, SURVEY §2.6).

    The GIL is released during the C call, so a ThreadPoolExecutor over
    this function scales decode across host cores.
    """
    lib = _load()
    if lib is None or not hasattr(lib, "mml_jpeg_decode_bgr"):
        return None
    buf = np.frombuffer(data, np.uint8)
    if len(buf) == 0:
        return None
    h = ctypes.c_int32()
    w = ctypes.c_int32()
    c = ctypes.c_int32()
    rc = lib.mml_jpeg_probe(buf.ctypes.data, len(buf), int(scale_denom),
                            ctypes.byref(h), ctypes.byref(w), ctypes.byref(c))
    if rc != 0:
        return None
    # decompression-bomb guard (PIL's Image.MAX_IMAGE_PIXELS analog): the
    # dims come from an untrusted header; don't allocate gigabytes for them
    if h.value * w.value > MAX_JPEG_PIXELS:
        return None
    out = np.empty(h.value * w.value * c.value, np.uint8)
    rc = lib.mml_jpeg_decode_bgr(buf.ctypes.data, len(buf), int(scale_denom),
                                 out.ctypes.data, out.nbytes,
                                 ctypes.byref(h), ctypes.byref(w),
                                 ctypes.byref(c))
    if rc != 0:
        return None
    return out.reshape(h.value, w.value, c.value)


def jpeg_probe(data: bytes, scale_denom: int = 1):
    """Header-only (h, w, c) of a JPEG stream (~µs, no pixel decode) — lets
    callers group rows by output shape and preallocate batch buffers before
    any decode.  None when unavailable/invalid/bomb-sized."""
    lib = _load()
    if lib is None or not hasattr(lib, "mml_jpeg_probe"):
        return None
    buf = np.frombuffer(data, np.uint8)
    if len(buf) == 0:
        return None
    h = ctypes.c_int32()
    w = ctypes.c_int32()
    c = ctypes.c_int32()
    rc = lib.mml_jpeg_probe(buf.ctypes.data, len(buf), int(scale_denom),
                            ctypes.byref(h), ctypes.byref(w), ctypes.byref(c))
    if rc != 0 or h.value * w.value > MAX_JPEG_PIXELS:
        return None
    return (h.value, w.value, c.value)


def decode_jpeg_bgr_into(data: bytes, out: np.ndarray,
                         scale_denom: int = 1) -> bool:
    """Decode JPEG bytes directly into a preallocated HWC uint8 view (e.g.
    one image slot of a [N,H,W,C] batch buffer) — no intermediate array, no
    stack copy.  `out` must be C-contiguous and exactly match the decoded
    (h, w, c).  Returns False on any mismatch or decode failure (caller
    falls back / drops the row)."""
    lib = _load()
    if lib is None or not hasattr(lib, "mml_jpeg_decode_bgr"):
        return False
    if not out.flags["C_CONTIGUOUS"] or out.dtype != np.uint8:
        raise ValueError("decode_jpeg_bgr_into: need C-contiguous uint8 out")
    buf = np.frombuffer(data, np.uint8)
    if len(buf) == 0:
        return False
    h = ctypes.c_int32()
    w = ctypes.c_int32()
    c = ctypes.c_int32()
    rc = lib.mml_jpeg_decode_bgr(buf.ctypes.data, len(buf), int(scale_denom),
                                 out.ctypes.data, out.nbytes,
                                 ctypes.byref(h), ctypes.byref(w),
                                 ctypes.byref(c))
    return rc == 0 and out.shape == (h.value, w.value, c.value)
