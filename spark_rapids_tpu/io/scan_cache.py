"""Device-resident scan cache.

Reference analogue: ParquetCachedBatchSerializer (the reference caches
columnar batches so repeat reads skip decode) — applied here at the
scan, and kept ON DEVICE: re-decoding and re-uploading the same
immutable file data every query dominates short queries.  Batches
are immutable (functional JAX arrays), so sharing them across queries
is safe.

Eviction: LRU past ``spark.rapids.tpu.io.deviceScanCache.bytes``; the
whole cache is dropped when the real device allocator reports OOM
(memory/pressure.py) — cached scans are always recomputable.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import List, Optional, Tuple

from ..obs import trace as _trace


class DeviceScanCache:
    _instance: Optional["DeviceScanCache"] = None
    _lock = threading.Lock()

    def __init__(self):
        self._store: "OrderedDict[tuple, Tuple[list, int]]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0

    @classmethod
    def get(cls) -> "DeviceScanCache":
        with cls._lock:
            if cls._instance is None:
                cls._instance = DeviceScanCache()
            return cls._instance

    def lookup(self, key: tuple) -> Optional[List[list]]:
        with self._lock:
            hit = self._store.get(key)
            if hit is None:
                self.misses += 1
                return None
            self._store.move_to_end(key)
            self.hits += 1
            return hit[0]

    def insert(self, key: tuple, parts: List[list], cap_bytes: int):
        nbytes = sum(b.nbytes() for part in parts for b in part)
        if nbytes > cap_bytes:
            return
        with self._lock:
            old = self._store.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._store[key] = (parts, nbytes)
            self._bytes += nbytes
            evicted = 0
            while self._bytes > cap_bytes and len(self._store) > 1:
                _, (_, nb) = self._store.popitem(last=False)
                self._bytes -= nb
                evicted += 1
        if evicted:
            _trace.count("scan.cache.evicted", evicted)

    def clear(self):
        with self._lock:
            self._store.clear()
            self._bytes = 0

    @property
    def nbytes(self) -> int:
        return self._bytes


def clear_on_pressure():
    """Drop every cached scan (device-OOM hook; all entries are
    recomputable from their files)."""
    if DeviceScanCache._instance is not None:
        DeviceScanCache._instance.clear()
