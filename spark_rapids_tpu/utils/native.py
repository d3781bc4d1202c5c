"""ctypes bindings for the native host library (native/src/rtpu_native.cpp).

Builds the .so on first use (g++ is in the image; pybind11 is not, hence
the plain C ABI). Every entry point has a pure-Python/numpy fallback so the
engine still works if a build is impossible — the native path is the fast
path, not a hard dependency (mirrors how the reference degrades from UCX to
the default shuffle when the native transport is unavailable).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
#: why the library is not loaded (build or dlopen failure); the engine then
#: runs its Python paths, and ``load_error()`` is how anyone finds out
_ERROR: Optional[str] = None
_LOCK = threading.Lock()

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
_SO = os.path.join(_ROOT, "native", "librtpu_native.so")
_STAMP = _SO + ".srchash"


def _source_hash() -> str:
    import hashlib
    h = hashlib.sha256()
    src_dir = os.path.join(_ROOT, "native", "src")
    for name in sorted(os.listdir(src_dir)):
        with open(os.path.join(src_dir, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _needs_build() -> bool:
    """Rebuild when the .so is missing OR the C++ source changed since the
    last build (the build is keyed on a source hash so a stale binary is
    never silently loaded)."""
    if not os.path.exists(_SO):
        return True
    try:
        with open(_STAMP) as f:
            return f.read().strip() != _source_hash()
    except OSError:
        return True


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED, _ERROR
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        try:
            if _needs_build():
                subprocess.run(["sh", os.path.join(_ROOT, "native",
                                                   "build.sh")],
                               check=True, capture_output=True, timeout=120)
                with open(_STAMP, "w") as f:
                    f.write(_source_hash())
            lib = ctypes.CDLL(_SO)
            lib.rtpu_lz4_compress.restype = ctypes.c_int64
            lib.rtpu_lz4_compress.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64]
            lib.rtpu_lz4_decompress.restype = ctypes.c_int64
            lib.rtpu_lz4_decompress.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64]
            lib.rtpu_zstd_compress.restype = ctypes.c_int64
            lib.rtpu_zstd_compress.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64]
            lib.rtpu_zstd_decompress.restype = ctypes.c_int64
            lib.rtpu_zstd_decompress.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64]
            lib.rtpu_strings_to_matrix.restype = ctypes.c_int32
            lib.rtpu_strings_to_matrix.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p]
            lib.rtpu_matrix_to_strings.restype = None
            lib.rtpu_matrix_to_strings.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p]
            _LIB = lib
        except Exception as e:
            _LIB = None
            stderr = getattr(e, "stderr", None)
            _ERROR = f"{type(e).__name__}: {e}" + (
                f"\n{stderr.decode(errors='replace')[-2000:]}"
                if stderr else "")
        return _LIB


def available() -> bool:
    return _load() is not None


def load_error() -> Optional[str]:
    """None when the native library is loaded; otherwise what stopped its
    build (native/build.sh) or load."""
    _load()
    return _ERROR


# ---------------------------------------------------------------------------
# Compression codecs: lz4 (in-tree block codec) and zstd (system libzstd),
# matching the reference's nvcomp LZ4 + ZSTD pair
# (TableCompressionCodec.scala). Fallback: zlib level 1.
# ---------------------------------------------------------------------------

CODECS = ("none", "lz4", "zstd")

#: process default, set from spark.rapids.tpu.shuffle.compression.codec by
#: the shuffle manager; serializers use it when no codec is passed
_DEFAULT_CODEC = "lz4"


def validate_codec(name: str) -> None:
    if name not in CODECS:
        raise ValueError(
            f"unsupported compression codec {name!r}; pick one of "
            f"{CODECS}")
    if name == "zstd" and _load() is None:
        raise ValueError(
            "codec 'zstd' needs the native library, which failed to "
            "build on this host")


def set_default_codec(name: str) -> None:
    """Process default for paths without a per-exchange codec (spill
    tier); shuffle exchanges carry their session's codec explicitly."""
    global _DEFAULT_CODEC
    validate_codec(name)
    _DEFAULT_CODEC = name


def default_codec() -> str:
    return _DEFAULT_CODEC


def compress(data: bytes, codec: Optional[str] = None) -> Tuple[bytes, str]:
    """Returns (payload, codec_tag)."""
    codec = codec or _DEFAULT_CODEC
    if codec == "none":
        return data, "none"
    lib = _load()
    if lib is None:
        import zlib
        return zlib.compress(data, 1), "zlib"
    src = np.frombuffer(data, np.uint8)
    cap = len(data) + len(data) // 4 + 256
    dst = np.empty(cap, np.uint8)
    if codec == "zstd":
        n = lib.rtpu_zstd_compress(src.ctypes.data, len(data),
                                   dst.ctypes.data, cap)
        if n >= 0:
            return dst[:n].tobytes(), "zstd"
        return data, "none"    # zstd worst case exceeded cap: store raw
    n = lib.rtpu_lz4_compress(src.ctypes.data, len(data),
                              dst.ctypes.data, cap)
    if n < 0:
        import zlib
        return zlib.compress(data, 1), "zlib"
    return dst[:n].tobytes(), "lz4"


def decompress(payload: bytes, codec: str, out_size: int) -> bytes:
    if codec == "zlib":
        import zlib
        return zlib.decompress(payload)
    if codec == "none":
        return payload
    lib = _load()
    if lib is None:
        raise RuntimeError(f"{codec} payload but native library unavailable")
    src = np.frombuffer(payload, np.uint8)
    dst = np.empty(out_size, np.uint8)
    if codec == "zstd":
        n = lib.rtpu_zstd_decompress(src.ctypes.data, len(payload),
                                     dst.ctypes.data, out_size)
    else:
        n = lib.rtpu_lz4_decompress(src.ctypes.data, len(payload),
                                    dst.ctypes.data, out_size)
    if n != out_size:
        raise ValueError(f"{codec} decompress: got {n}, want {out_size}")
    return dst.tobytes()


# ---------------------------------------------------------------------------
# String layout conversion (fallback: numpy vectorized)
# ---------------------------------------------------------------------------

def strings_to_matrix(offsets: np.ndarray, data: np.ndarray, max_len: int
                      ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Arrow offsets[n+1] + data bytes -> (matrix[n, max_len], lengths[n]).
    Returns None when a string exceeds max_len (caller handles overflow)."""
    n = len(offsets) - 1
    lib = _load()
    if lib is None or n == 0:
        return None   # caller falls back to the numpy path
    offsets = np.ascontiguousarray(offsets, np.int32)
    data = np.ascontiguousarray(data, np.uint8)
    matrix = np.empty((n, max_len), np.uint8)
    lengths = np.empty(n, np.int32)
    rc = lib.rtpu_strings_to_matrix(offsets.ctypes.data, data.ctypes.data,
                                    n, max_len, matrix.ctypes.data,
                                    lengths.ctypes.data)
    if rc != 0:
        return None
    return matrix, lengths


def matrix_to_strings(matrix: np.ndarray, lengths: np.ndarray
                      ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    n, max_len = matrix.shape
    lib = _load()
    if lib is None or n == 0:
        return None
    matrix = np.ascontiguousarray(matrix, np.uint8)
    lengths = np.ascontiguousarray(lengths, np.int32)
    total = int(lengths.sum())
    out = np.empty(total, np.uint8)
    offsets = np.empty(n + 1, np.int32)
    lib.rtpu_matrix_to_strings(matrix.ctypes.data, lengths.ctypes.data,
                               n, max_len, out.ctypes.data,
                               offsets.ctypes.data)
    return out, offsets
