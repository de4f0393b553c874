"""Build + load the native qcodec library (C++ via ctypes).

The reference leans on pip-native compression (lz4/zfpy C bindings,
``/root/reference/README.md:19``); our native piece is first-party:
``native/qcodec.cpp``, an LZ77 byte codec compiled on first use with g++
into ``native/build/`` (git-ignored: a checkout carries the source, never
a binary built elsewhere) and loaded through ctypes. Falls back to zlib
(stdlib) if no toolchain is available, keeping the codec API usable
everywhere.
"""

from __future__ import annotations

import ctypes
import pathlib
import subprocess
import threading

from adapt_tpu.utils.logging import get_logger
from adapt_tpu.utils.metrics import global_metrics
from adapt_tpu.utils.tracing import global_flight_recorder

log = get_logger("native")

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
_SRC = _REPO_ROOT / "native" / "qcodec.cpp"
_SO = _REPO_ROOT / "native" / "build" / "libqcodec.so"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build() -> bool:
    _SO.parent.mkdir(parents=True, exist_ok=True)
    cmd = [
        "g++",
        "-O3",
        "-shared",
        "-fPIC",
        "-std=c++17",
        str(_SRC),
        "-o",
        str(_SO),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except (subprocess.SubprocessError, FileNotFoundError) as e:
        log.warning("qcodec build failed (%s); falling back to zlib", e)
        return False


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not _SO.exists() or _SO.stat().st_mtime < _SRC.stat().st_mtime:
            if not _build():
                # Fallback visibility: a zlib-serving pool looks healthy
                # but pays different codec CPU — surface the downgrade on
                # /metrics and in the flight recorder, not just a log
                # line at import time.
                global_metrics().inc("native.qcodec_fallback")
                global_flight_recorder().record(
                    "native_codec", built=False, fallback="zlib"
                )
                return None
        try:
            lib = ctypes.CDLL(str(_SO))
        except OSError as e:
            log.warning("qcodec load failed: %s", e)
            global_metrics().inc("native.qcodec_fallback")
            global_flight_recorder().record(
                "native_codec", built=True, loaded=False, fallback="zlib"
            )
            return None
        lib.qz_bound.restype = ctypes.c_size_t
        lib.qz_bound.argtypes = [ctypes.c_size_t]
        lib.qz_compress.restype = ctypes.c_size_t
        lib.qz_compress.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        lib.qz_decompress.restype = ctypes.c_size_t
        lib.qz_decompress.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        _lib = lib
        global_metrics().inc("native.qcodec_loaded")
        return _lib


def _c_src(buf):
    """ctypes-passable view of any bytes-like object WITHOUT copying when
    possible: bytes pass through (c_char_p accepts them) and writable
    buffers (ndarray.data, bytearray) wrap via from_buffer; only
    read-only non-bytes views pay a materializing copy."""
    if isinstance(buf, bytes):
        return buf, len(buf)
    mv = buf if isinstance(buf, memoryview) else memoryview(buf)
    if mv.format != "B" or mv.ndim != 1:
        mv = mv.cast("B")
    if mv.readonly:
        b = bytes(mv)
        return b, len(b)
    return (ctypes.c_char * mv.nbytes).from_buffer(mv), mv.nbytes


def compress(data) -> bytes:
    """LZ-compress any bytes-like object (bytes, bytearray, memoryview,
    ndarray buffer) — buffer inputs avoid a staging ``tobytes`` copy."""
    lib = load()
    if lib is None:
        import zlib

        return b"Z" + zlib.compress(data, 1)
    src, n_src = _c_src(data)
    bound = lib.qz_bound(n_src)
    dst = ctypes.create_string_buffer(bound)
    n = lib.qz_compress(src, n_src, dst, bound)
    if n == 0:
        raise RuntimeError("qz_compress failed")
    return b"Q" + dst.raw[:n]


def decompress(blob, raw_len: int) -> bytes:
    mv = blob if isinstance(blob, memoryview) else memoryview(blob)
    tag, body = bytes(mv[:1]), mv[1:]
    if tag == b"Z":
        import zlib

        return zlib.decompress(body)
    if tag != b"Q":
        raise ValueError(f"unknown qcodec tag {tag!r}")
    if raw_len == 0:
        return b""  # qz_decompress uses 0 for errors; disambiguate here
    lib = load()
    if lib is None:
        raise RuntimeError("native qcodec unavailable for 'Q' blob")
    src, n_src = _c_src(body)
    dst = ctypes.create_string_buffer(raw_len)
    n = lib.qz_decompress(src, n_src, dst, raw_len)
    if n == 0:
        raise ValueError("qz_decompress: malformed input")
    return dst.raw[:n]
