"""ctypes bindings for the native tensorwire library (libnnstw.so).

The native layer mirrors the reference's C hot paths (ORC transform
kernels, converter stride memcpy, sparse codec — see
native/tensorwire/tensorwire.cc for the file-level mapping).  Every entry
point has a numpy fallback so the framework works without the toolchain;
``available()`` reports which path is active.

The library is built on demand (``make -C native``, about a second) in
the FOREGROUND the first time it is requested, then cached: a process
runs either the native path or the fallback from its first frame to its
last, never a mix that depends on when a build happened to finish.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libnnstw.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

# dtype kind codes shared with tensorwire.cc
_KIND = {"float32": 8, "float64": 9}


def _build() -> None:
    """Build to a process-unique name, then atomically rename into place:
    concurrent builders (pytest -n, parallel pipelines) each produce a
    whole .so and the last rename wins — never a torn file.  A failed
    build leaves whatever .so was there (or none) for the caller's load
    to decide."""
    tmp = f"libnnstw.so.tmp.{os.getpid()}"
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, f"TARGET={tmp}"],
                       check=True, capture_output=True, timeout=120)
        os.replace(os.path.join(_NATIVE_DIR, tmp), _SO_PATH)
    except (subprocess.SubprocessError, OSError):
        try:
            os.unlink(os.path.join(_NATIVE_DIR, tmp))
        except OSError:
            pass


def _load() -> Optional[ctypes.CDLL]:
    """Load libnnstw.so, building it first when it is absent or older
    than its sources; ``None`` (the numpy fallback, for the life of the
    process) when it cannot be built or loaded."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        src_dir = os.path.join(_NATIVE_DIR, "tensorwire")
        try:
            so_m = os.path.getmtime(_SO_PATH)
            stale = any(os.path.getmtime(os.path.join(src_dir, f)) > so_m
                        for f in os.listdir(src_dir))
        except OSError:
            stale = True   # no .so (or unreadable sources): try a build
        if stale:
            _build()
        _tried = True
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            return None
        if lib.tw_abi_version() not in (1, 2):
            # unknown future ABI: fall back rather than call with wrong
            # signatures (1 = original kernels, 2 = +reader)
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.tw_sparse_count.restype = ctypes.c_size_t
        lib.tw_sparse_count.argtypes = [u8p, ctypes.c_size_t,
                                        ctypes.c_size_t, ctypes.c_int]
        lib.tw_sparse_gather.restype = ctypes.c_size_t
        lib.tw_sparse_gather.argtypes = [u8p, ctypes.c_size_t,
                                         ctypes.c_size_t, ctypes.c_int,
                                         u8p, u32p]
        lib.tw_sparse_scatter.argtypes = [u8p, u32p, ctypes.c_size_t,
                                          ctypes.c_size_t, u8p,
                                          ctypes.c_size_t]
        lib.tw_unstride.argtypes = [u8p, ctypes.c_size_t, u8p,
                                    ctypes.c_size_t, ctypes.c_size_t]
        lib.tw_bgrx_to_rgb.argtypes = [u8p, u8p, ctypes.c_size_t]
        lib.tw_gray_to_rgb.argtypes = [u8p, u8p, ctypes.c_size_t]
        lib.tw_crc32c.restype = ctypes.c_uint32
        lib.tw_crc32c.argtypes = [u8p, ctypes.c_size_t, ctypes.c_uint32]
        _lib = lib
        return _lib


def available() -> bool:
    """Whether the native path is the active one (builds on first call)."""
    return _load() is not None


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def sparse_gather(arr: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Return (values, uint32 flat indices) of nonzero elements."""
    flat = np.ascontiguousarray(arr).reshape(-1)
    lib = _load()
    if lib is None:
        idx = np.flatnonzero(flat).astype(np.uint32)
        return flat[idx], idx
    kind = _KIND.get(flat.dtype.name, 0)
    esz = flat.dtype.itemsize
    nnz = lib.tw_sparse_count(_u8(flat.view(np.uint8)), flat.size, esz, kind)
    values = np.empty(nnz, dtype=flat.dtype)
    indices = np.empty(nnz, dtype=np.uint32)
    lib.tw_sparse_gather(_u8(flat.view(np.uint8)), flat.size, esz, kind,
                         _u8(values.view(np.uint8)),
                         indices.ctypes.data_as(
                             ctypes.POINTER(ctypes.c_uint32)))
    return values, indices


def sparse_scatter(values: np.ndarray, indices: np.ndarray,
                   n_elems: int) -> np.ndarray:
    """Dense flat array from (values, indices)."""
    lib = _load()
    dense = np.zeros(n_elems, dtype=values.dtype)
    if lib is None:
        dense[indices] = values
        return dense
    lib.tw_sparse_scatter(_u8(np.ascontiguousarray(values).view(np.uint8)),
                          np.ascontiguousarray(indices).ctypes.data_as(
                              ctypes.POINTER(ctypes.c_uint32)),
                          len(values), values.dtype.itemsize,
                          _u8(dense.view(np.uint8)), n_elems)
    return dense


def bgrx_to_rgb(frame: np.ndarray) -> np.ndarray:
    """(H, W, 4) BGRx → (H, W, 3) RGB."""
    h, w = frame.shape[:2]
    lib = _load()
    if lib is None:
        return frame[..., [2, 1, 0]].copy()
    src = np.ascontiguousarray(frame)
    dst = np.empty((h, w, 3), np.uint8)
    lib.tw_bgrx_to_rgb(_u8(src), _u8(dst), h * w)
    return dst


def gray_to_rgb(frame: np.ndarray) -> np.ndarray:
    """(H, W, 1) GRAY8 → (H, W, 3) RGB."""
    h, w = frame.shape[:2]
    lib = _load()
    src = np.ascontiguousarray(frame)
    if lib is None:
        return np.repeat(src.reshape(h, w, 1), 3, axis=2)
    dst = np.empty((h, w, 3), np.uint8)
    lib.tw_gray_to_rgb(_u8(src), _u8(dst), h * w)
    return dst


def unstride(src: np.ndarray, src_stride: int, row_bytes: int,
             rows: int) -> np.ndarray:
    """Drop per-row padding from a strided image buffer."""
    flat = np.ascontiguousarray(src).reshape(-1).view(np.uint8)
    lib = _load()
    if lib is None:
        out = np.empty(rows * row_bytes, np.uint8)
        for r in range(rows):
            out[r * row_bytes:(r + 1) * row_bytes] = \
                flat[r * src_stride:r * src_stride + row_bytes]
        return out
    dst = np.empty(rows * row_bytes, np.uint8)
    lib.tw_unstride(_u8(flat), src_stride, _u8(dst), row_bytes, rows)
    return dst


_CRC32C_TABLE: Optional[np.ndarray] = None


def _crc32c_table() -> np.ndarray:
    global _CRC32C_TABLE
    if _CRC32C_TABLE is None:
        table = np.empty(256, np.uint32)
        for i in range(256):
            c = i
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if (c & 1) else (c >> 1)
            table[i] = c
        _CRC32C_TABLE = table
    return _CRC32C_TABLE


def crc32c_fn():
    """Return a lock-free CRC-32C callable bound to the loaded native lib,
    or None when the lib is unavailable (callers fall back / skip).  The
    per-call path touches no module locks — resolve once, use per frame."""
    lib = _load()
    if lib is None:
        return None

    def _fn(data: bytes, seed: int = 0) -> int:
        arr = np.frombuffer(data, np.uint8)
        return int(lib.tw_crc32c(_u8(arr), len(data), seed))

    return _fn


def crc32c(data: bytes, seed: int = 0) -> int:
    """CRC-32C (Castagnoli) — the SAME polynomial on both paths so mixed
    native/fallback hosts agree on checksums."""
    lib = _load()
    if lib is None:
        table = _crc32c_table()
        c = ~seed & 0xFFFFFFFF
        for b in data:
            c = int(table[(c ^ b) & 0xFF]) ^ (c >> 8)
        return (~c) & 0xFFFFFFFF
    arr = np.frombuffer(data, np.uint8)
    return int(lib.tw_crc32c(_u8(arr), len(data), seed))


# ---------------------------------------------------------------------------
# Native dataset reader (data-loader role: gstdatareposrc.c reimplemented as
# a native IO engine — background pread prefetch ring, bounded memory).
# Python mmap fallback keeps behavior identical without the .so.
# ---------------------------------------------------------------------------

class RepoReader:
    """Sequential frame reader over a binary dataset file.

    ``next_frame()`` returns (global_frame_index, bytes) — the index keeps
    counting across epochs when ``wrap`` — or None at the end of a
    non-wrapping stream.
    """

    def __init__(self, path: str, frame_bytes: int, capacity: int = 8,
                 wrap: bool = False) -> None:
        self.frame_bytes = frame_bytes
        self._native = None
        self._mm = None
        self._served = 0
        self._wrap = wrap
        lib = _load()
        if lib is not None and hasattr(lib, "tw_reader_open"):
            lib.tw_reader_open.restype = ctypes.c_void_p
            lib.tw_reader_open.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                           ctypes.c_int, ctypes.c_int]
            lib.tw_reader_frames.restype = ctypes.c_long
            lib.tw_reader_frames.argtypes = [ctypes.c_void_p]
            lib.tw_reader_next.restype = ctypes.c_long
            lib.tw_reader_next.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8)]
            lib.tw_reader_close.argtypes = [ctypes.c_void_p]
            h = lib.tw_reader_open(path.encode(), frame_bytes,
                                   int(capacity), int(wrap))
            if h:
                self._native = (lib, h)
                self.num_frames = int(lib.tw_reader_frames(h))
                return
        # fallback: mmap (bounded memory too, readahead by the kernel)
        import mmap

        f = open(path, "rb")
        try:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except ValueError:          # zero-byte file cannot be mapped
            f.close()
            raise ValueError(f"{path}: smaller than one frame") from None
        self._mm = (f, mm)
        self.num_frames = len(mm) // frame_bytes
        if self.num_frames == 0:
            self.close()
            raise ValueError(f"{path}: smaller than one frame")

    @property
    def is_native(self) -> bool:
        return self._native is not None

    def next_frame(self):
        """(global_frame_index, uint8 ndarray) — exactly one copy out of
        the ring/page cache per frame on either path."""
        if self._native is not None:
            lib, h = self._native
            dst = np.empty(self.frame_bytes, np.uint8)
            idx = lib.tw_reader_next(h, _u8(dst))
            if idx == -2:
                raise IOError(f"native reader: IO error at frame "
                              f"{self._served}")
            if idx < 0:
                return None
            self._served += 1
            return int(idx), dst
        if not self._wrap and self._served >= self.num_frames:
            return None
        idx = self._served
        pos = (idx % self.num_frames) * self.frame_bytes
        self._served += 1
        # mm[a:b] copies out of the page cache; frombuffer wraps it
        # zero-copy (a view of the mmap itself would block mm.close())
        return idx, np.frombuffer(self._mm[1][pos:pos + self.frame_bytes],
                                  np.uint8)

    def close(self) -> None:
        if self._native is not None:
            lib, h = self._native
            lib.tw_reader_close(h)
            self._native = None
        if self._mm is not None:
            self._mm[1].close()
            self._mm[0].close()
            self._mm = None
