"""Native (C++) runtime components, loaded via ctypes.

Reference parity (SURVEY.md §2.10): the reference's native layer is
external C++ (RMM arena, pinned staging, nvcomp, UCX).  Here the native
host arena backs the HOST spill tier; it is built on first use with g++
and cached next to the source.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_DIR, "spill_arena.cpp"),
         os.path.join(_DIR, "block_codec.cpp")]
_SO = os.path.join(_DIR, "libspark_rapids_tpu_native.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _build() -> str:
    if os.path.exists(_SO) and all(
            os.path.getmtime(_SO) >= os.path.getmtime(s) for s in _SRCS):
        return _SO
    # per-process temp name: two processes building at once (a spawned
    # executor child next to its parent) must not interleave one file
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", *_SRCS, "-o",
           tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native library build failed ({' '.join(cmd)}):\n"
            f"{e.stderr[-2000:]}") from e
    os.replace(tmp, _SO)
    return _SO


def load() -> ctypes.CDLL:
    """Build (if needed) and load the native library."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(_build())
        lib.arena_create.restype = ctypes.c_void_p
        lib.arena_create.argtypes = [ctypes.c_int64]
        lib.arena_alloc.restype = ctypes.c_int64
        lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.arena_base.restype = ctypes.c_void_p
        lib.arena_base.argtypes = [ctypes.c_void_p]
        lib.arena_used.restype = ctypes.c_int64
        lib.arena_used.argtypes = [ctypes.c_void_p]
        lib.arena_capacity.restype = ctypes.c_int64
        lib.arena_capacity.argtypes = [ctypes.c_void_p]
        lib.arena_num_free_blocks.restype = ctypes.c_int64
        lib.arena_num_free_blocks.argtypes = [ctypes.c_void_p]
        lib.arena_write_file.restype = ctypes.c_int
        lib.arena_write_file.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_char_p]
        lib.arena_read_file.restype = ctypes.c_int
        lib.arena_read_file.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_int64, ctypes.c_char_p]
        lib.arena_destroy.argtypes = [ctypes.c_void_p]
        lib.tplz_max_compressed_size.restype = ctypes.c_size_t
        lib.tplz_max_compressed_size.argtypes = [ctypes.c_size_t]
        lib.tplz_compress.restype = ctypes.c_size_t
        lib.tplz_compress.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                      ctypes.c_void_p, ctypes.c_size_t]
        lib.tplz_decompress.restype = ctypes.c_size_t
        lib.tplz_decompress.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                        ctypes.c_void_p, ctypes.c_size_t]
        _lib = lib
        return lib


def tplz_compress(data: bytes) -> bytes:
    """Native LZ block compression (the nvcomp-LZ4 role)."""
    lib = load()
    cap = lib.tplz_max_compressed_size(len(data))
    out = ctypes.create_string_buffer(cap)
    n = lib.tplz_compress(data, len(data), out, cap)
    if n == 0 and len(data):
        raise RuntimeError("tplz compression failed")
    return out.raw[:n]


def tplz_decompress(data: bytes, uncompressed_size: int) -> bytes:
    lib = load()
    out = ctypes.create_string_buffer(max(uncompressed_size, 1))
    n = lib.tplz_decompress(data, len(data), out, uncompressed_size)
    if n != uncompressed_size:
        raise RuntimeError(
            f"tplz decompression produced {n} bytes, "
            f"expected {uncompressed_size}")
    return out.raw[:n]


class HostArena:
    """Python wrapper over the native slab arena.

    Buffers are exposed as zero-copy numpy views into the slab, so
    device->host staging is a single jax device_get into arena memory.
    """

    def __init__(self, capacity: int):
        import numpy as np
        self._lib = load()
        self._h = self._lib.arena_create(capacity)
        if not self._h:
            raise MemoryError(f"cannot create {capacity}-byte host arena")
        base = self._lib.arena_base(self._h)
        self._np = np
        self._view = (ctypes.c_uint8 * self.capacity).from_address(base)
        # every numpy view handed out by view() has this ctypes array as
        # its base, and the array does not own the slab: tie the arena's
        # lifetime to it, so the slab is freed only after the last view
        # (or anything aliasing one) is gone — a session re-init used to
        # free it under a pipeline worker still holding a view (flaky
        # segfault in the test suite)
        self._view._owner = self

    @property
    def capacity(self) -> int:
        return self._lib.arena_capacity(self._h)

    @property
    def used(self) -> int:
        return self._lib.arena_used(self._h)

    @property
    def num_free_blocks(self) -> int:
        return self._lib.arena_num_free_blocks(self._h)

    def alloc(self, nbytes: int) -> int:
        off = self._lib.arena_alloc(self._h, nbytes)
        if off < 0:
            raise MemoryError(
                f"host arena exhausted ({self.used}/{self.capacity})")
        return off

    def free(self, offset: int):
        self._lib.arena_free(self._h, offset)

    def view(self, offset: int, nbytes: int):
        """Zero-copy numpy uint8 view of [offset, offset+nbytes)."""
        arr = self._np.frombuffer(self._view, dtype=self._np.uint8,
                                  count=nbytes, offset=offset)
        return arr

    def write_file(self, offset: int, nbytes: int, path: str):
        rc = self._lib.arena_write_file(self._h, offset, nbytes,
                                       path.encode())
        if rc != 0:
            raise OSError(rc, f"spill write failed: {path}")

    def read_file(self, offset: int, nbytes: int, path: str):
        rc = self._lib.arena_read_file(self._h, offset, nbytes,
                                      path.encode())
        if rc != 0:
            raise OSError(rc, f"spill read failed: {path}")

    def close(self):
        if self._h:
            self._lib.arena_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
