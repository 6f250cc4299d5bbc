"""ctypes bindings to the native C++ library (native/*.cpp).

The library is built on demand (make -C native) and provides:
  * HighwayHash-64/256 (single-shot, batched, streaming) — the CPU bitrot
    engine (reference behavior: cmd/bitrot.go algorithms).
  * gf_matmul — GFNI/AVX-512 (or portable) GF(2^8) coding matmul — the CPU
    fallback codec and bench baseline.

Everything degrades gracefully: if the shared library is missing and make
fails, `available()` returns False and pure-Python/numpy fallbacks take
over (slower, same bytes).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libminio_tpu_native.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, "-s"],
                       check=True, capture_output=True, timeout=300)
        return True
    except Exception:
        return False


def get_lib() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # Always invoke make: it is a timestamp-based no-op when current,
        # and rebuilds the .so after source edits (a pre-existing stale
        # binary would otherwise be loaded silently forever).
        if not _build() and not os.path.exists(_LIB_PATH):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.hh64.restype = ctypes.c_uint64
        lib.hh64.argtypes = [u8p, u8p, ctypes.c_size_t]
        lib.hh256.restype = None
        lib.hh256.argtypes = [u8p, u8p, ctypes.c_size_t, u8p]
        lib.hh256_batch.restype = None
        lib.hh256_batch.argtypes = [u8p, u8p, ctypes.c_size_t,
                                    ctypes.c_size_t, ctypes.c_size_t, u8p]
        # absent in a stale .so that make could not rebuild: the
        # callers then take a row at a time
        vpp = ctypes.POINTER(ctypes.c_void_p)
        try:
            lib.hh256_rows.restype = None
            lib.hh256_rows.argtypes = [u8p, vpp, ctypes.c_size_t,
                                       ctypes.c_size_t, u8p]
            lib.gather_rows.restype = None
            lib.gather_rows.argtypes = [vpp, ctypes.c_size_t,
                                        ctypes.c_size_t, u8p]
            lib.rows_ok = True
        except AttributeError:
            lib.rows_ok = False
        lib.hh_init.restype = None
        lib.hh_init.argtypes = [u8p, u8p]
        lib.hh_update_packets.restype = None
        lib.hh_update_packets.argtypes = [u8p, u8p, ctypes.c_size_t]
        lib.hh_final256.restype = None
        lib.hh_final256.argtypes = [u8p, u8p, ctypes.c_size_t, u8p]
        lib.gf_matmul.restype = None
        lib.gf_matmul.argtypes = [u8p, ctypes.c_size_t, ctypes.c_size_t,
                                  u8p, ctypes.c_size_t,
                                  u8p, ctypes.c_size_t, ctypes.c_size_t,
                                  ctypes.c_int]
        lib.gf_has_gfni.restype = ctypes.c_int
        lib.gf_has_gfni.argtypes = []
        # snappy/S2 codec (absent in a stale pre-r5 .so: make rebuilds,
        # but guard the lookup so an unwritable tree degrades cleanly)
        try:
            lib.snappy_crc32c.restype = ctypes.c_uint32
            lib.snappy_crc32c.argtypes = [u8p, ctypes.c_size_t]
            lib.snappy_max_compressed_length.restype = ctypes.c_size_t
            lib.snappy_max_compressed_length.argtypes = [ctypes.c_size_t]
            lib.snappy_compress_block.restype = ctypes.c_int
            lib.snappy_compress_block.argtypes = [
                u8p, ctypes.c_size_t, u8p,
                ctypes.POINTER(ctypes.c_size_t)]
            lib.snappy_uncompressed_length.restype = ctypes.c_int64
            lib.snappy_uncompressed_length.argtypes = [u8p,
                                                       ctypes.c_size_t]
            lib.snappy_uncompress_block.restype = ctypes.c_int64
            lib.snappy_uncompress_block.argtypes = [
                u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]
            lib.snappy_ok = True
        except AttributeError:
            lib.snappy_ok = False
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


def _u8p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def hh64(key: bytes, data: bytes | np.ndarray) -> int:
    lib = get_lib()
    assert lib is not None
    k = np.frombuffer(key, dtype=np.uint8)
    d = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray)) else np.ascontiguousarray(data, np.uint8)
    return int(lib.hh64(_u8p(k), _u8p(d), d.size))


def hh256(key: bytes, data: bytes | np.ndarray) -> bytes:
    lib = get_lib()
    assert lib is not None
    k = np.frombuffer(key, dtype=np.uint8)
    d = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray)) else np.ascontiguousarray(data, np.uint8)
    out = np.zeros(32, dtype=np.uint8)
    lib.hh256(_u8p(k), _u8p(d), d.size, _u8p(out))
    return out.tobytes()


def hh256_batch(key: bytes, shards: np.ndarray) -> np.ndarray:
    """Hash each row of a contiguous (n, L) uint8 array -> (n, 32)."""
    lib = get_lib()
    assert lib is not None
    shards = np.ascontiguousarray(shards, np.uint8)
    n, length = shards.shape
    k = np.frombuffer(key, dtype=np.uint8)
    out = np.zeros((n, 32), dtype=np.uint8)
    lib.hh256_batch(_u8p(k), _u8p(shards), n, length, shards.strides[0],
                    _u8p(out))
    return out


def rows_available() -> bool:
    """hh256_rows and gather_rows are in the loaded library."""
    lib = get_lib()
    return bool(lib and getattr(lib, "rows_ok", False))


def _row_pointers(rows: list, size: int):
    """The addresses of n equal-length 1-D C-contiguous uint8 arrays
    (read-only `bytes`-backed views included), for one native call."""
    for r in rows:
        if r.dtype != np.uint8 or r.ndim != 1 or r.size != size \
                or not r.flags.c_contiguous:
            raise ValueError("rows must be 1-D contiguous uint8 of one "
                             "length")
    return (ctypes.c_void_p * max(len(rows), 1))(
        *(r.ctypes.data for r in rows))


def hh256_rows(key: bytes, rows: list) -> np.ndarray:
    """Hash n equal-length rows, each where it lies -> (n, 32); one
    native call, the interpreter lock released once for all of them."""
    lib = get_lib()
    assert lib is not None and lib.rows_ok
    n = len(rows)
    size = rows[0].size if n else 0
    ptrs = _row_pointers(rows, size)
    k = np.frombuffer(key, dtype=np.uint8)
    out = np.zeros((n, 32), dtype=np.uint8)
    if n:
        lib.hh256_rows(_u8p(k), ptrs, n, size, _u8p(out))
    return out


def gather_rows(rows: list, out: np.ndarray) -> None:
    """Copy n equal-length rows, each from where it lies, into the
    consecutive rows of the C-contiguous uint8 array `out` (n·size
    bytes); one native call, the interpreter lock released once."""
    lib = get_lib()
    assert lib is not None and lib.rows_ok
    n = len(rows)
    size = rows[0].size if n else 0
    if out.dtype != np.uint8 or not out.flags.c_contiguous \
            or not out.flags.writeable or out.size != n * size:
        raise ValueError("out must be a writable contiguous uint8 array "
                         "of n·size bytes")
    ptrs = _row_pointers(rows, size)
    if n:
        lib.gather_rows(ptrs, n, size, _u8p(out))


def gf_matmul(matrix: np.ndarray, data: np.ndarray,
              force_path: int = 0) -> np.ndarray:
    """out(r,L) = matrix(r,k) (x) data(k,L) over GF(2^8), native speed."""
    lib = get_lib()
    assert lib is not None
    matrix = np.ascontiguousarray(matrix, np.uint8)
    data = np.ascontiguousarray(data, np.uint8)
    r, k = matrix.shape
    k2, length = data.shape
    assert k == k2
    out = np.zeros((r, length), dtype=np.uint8)
    lib.gf_matmul(_u8p(matrix), r, k, _u8p(data), data.strides[0],
                  _u8p(out), out.strides[0], length, force_path)
    return out


def has_gfni() -> bool:
    lib = get_lib()
    return bool(lib and lib.gf_has_gfni())


# ---------------------------------------------------------------------------
# snappy/S2 block codec + CRC32C
# ---------------------------------------------------------------------------

def snappy_available() -> bool:
    lib = get_lib()
    return bool(lib and getattr(lib, "snappy_ok", False))


def crc32c(data: bytes | memoryview) -> int:
    lib = get_lib()
    assert lib is not None and lib.snappy_ok
    d = np.frombuffer(data, dtype=np.uint8)
    return int(lib.snappy_crc32c(_u8p(d), d.size))


def snappy_compress_block(data: bytes | memoryview) -> bytes:
    """One snappy block (<= 65536 bytes — the framing chunk limit; the
    C hash table stores 16-bit positions)."""
    lib = get_lib()
    assert lib is not None and lib.snappy_ok
    d = np.frombuffer(data, dtype=np.uint8)
    assert d.size <= 65536
    out = np.empty(int(lib.snappy_max_compressed_length(d.size)),
                   dtype=np.uint8)
    n = ctypes.c_size_t(0)
    lib.snappy_compress_block(_u8p(d), d.size, _u8p(out),
                              ctypes.byref(n))
    return out[:n.value].tobytes()


def snappy_uncompress_block(data: bytes | memoryview,
                            max_out: int = 1 << 24) -> bytes:
    """Decode one snappy/S2 block; raises ValueError on corrupt input
    and NotImplementedError on S2 encodings outside the subset."""
    lib = get_lib()
    assert lib is not None and lib.snappy_ok
    d = np.frombuffer(data, dtype=np.uint8)
    want = int(lib.snappy_uncompressed_length(_u8p(d), d.size))
    if want < 0 or want > max_out:
        raise ValueError("corrupt snappy block header")
    out = np.empty(want, dtype=np.uint8)
    got = int(lib.snappy_uncompress_block(_u8p(d), d.size, _u8p(out),
                                          want))
    if got == -2:
        raise NotImplementedError(
            "S2 extended repeat encoding outside the decoded subset")
    if got != want:
        raise ValueError("corrupt snappy block")
    return out.tobytes()
