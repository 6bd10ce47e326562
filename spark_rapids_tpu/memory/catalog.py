"""Tiered buffer catalog: DEVICE -> HOST -> DISK spill framework.

Reference: RapidsBufferCatalog.scala:40 + RapidsBufferStore.scala:41 +
StorageTier (RapidsBuffer.scala:53), SpillPriorities.scala, and the
DeviceMemoryEventHandler alloc-failure -> synchronous-spill contract
(DeviceMemoryEventHandler.scala:33).

TPU adaptation: XLA owns physical HBM, so the device tier tracks *logical*
bytes of live device buffers and the memory budget is enforced by the
arena (memory/arena.py) calling ``spill_to_fit`` before admitting new
batches — the same synchronous-spill-on-pressure contract, with jax
device_get/device_put as the tier movers.
"""
from __future__ import annotations

import dataclasses
import enum
import os
import pickle
import threading
import time
import uuid
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs import flight as _flight
from ..obs import memplane as _memplane
from ..obs import trace as _trace


class StorageTier(enum.IntEnum):
    DEVICE = 0
    HOST = 1
    DISK = 2


# Spill priorities (reference: SpillPriorities.scala): lower value spills
# first.  Shuffle output spills before active working buffers.
SHUFFLE_OUTPUT_PRIORITY = -100
ACTIVE_BATCH_PRIORITY = 0
ACTIVE_ON_DECK_PRIORITY = 100


@dataclasses.dataclass
class BufferEntry:
    buffer_id: str
    tier: StorageTier
    nbytes: int
    priority: int
    # DEVICE tier: the live object (ColumnarBatch); HOST: host_payload;
    # DISK: file path
    device_obj: object = None
    host_payload: object = None
    disk_path: Optional[str] = None
    refcount: int = 0
    # decompressed .raw cache for repeated acquire_slice over a
    # compressed DISK entry (cleared on any tier change)
    raw_cache: Optional[bytes] = None
    # allocation provenance (obs/memplane.py): the query that owned the
    # registration, the operator class and site it came from, and the
    # registration call-site tag the leak report prints
    owner_query: Optional[str] = None
    owner_op: str = ""
    owner_site: str = _memplane.SITE_OTHER
    owner_tag: str = ""


class BufferCatalog:
    """Process-wide registry of spillable buffers."""

    _instance: Optional["BufferCatalog"] = None

    def __init__(self, spill_dir: str = "/tmp/spark_rapids_tpu_spill",
                 device_limit: int = 28 << 30,
                 host_limit: int = 8 << 30,
                 use_native_arena: bool = True,
                 compression: str = "none"):
        self._entries: Dict[str, BufferEntry] = {}
        self._lock = threading.RLock()
        self.spill_dir = spill_dir
        self.device_limit = device_limit
        self.host_limit = host_limit
        self.device_bytes = 0
        self.device_peak_bytes = 0
        self.host_bytes = 0
        self.disk_bytes = 0
        self.spilled_device_to_host = 0
        self.spilled_host_to_disk = 0
        self.raw_cache_bytes = 0
        from ..shuffle.compression import get_codec
        self.codec = get_codec(compression)
        # native host slab arena for the HOST tier (pinned-pool role).
        # A failed g++ build or load raises: python-heap payloads are
        # what use_native_arena=False asks for, not a silent downgrade
        self.arena = None
        if use_native_arena:
            from ..native import HostArena
            self.arena = HostArena(min(host_limit, 2 << 30))

    @classmethod
    def get(cls) -> "BufferCatalog":
        if cls._instance is None:
            cls._instance = BufferCatalog()
        return cls._instance

    @classmethod
    def reset(cls, **kwargs) -> "BufferCatalog":
        cls._instance = BufferCatalog(**kwargs)
        # the plane's incremental decomposition mirrors THIS catalog's
        # entries; a new epoch starts both from zero (otherwise stale
        # owner bytes would survive the reset and the per-site gauges
        # would stop summing to device_bytes)
        _memplane.reset()
        return cls._instance

    # ------------------------------------------------------------------
    def register(self, device_obj, nbytes: int,
                 priority: int = ACTIVE_BATCH_PRIORITY,
                 op: str = "", site: str = _memplane.SITE_OTHER) -> str:
        buffer_id = uuid.uuid4().hex
        # attribute the buffer to the active query (if any) so a
        # cancelled/failed query's leftover registrations can be
        # unwound by the service (unregister of an already-released id
        # is a no-op, so double-accounting is harmless) — and so the
        # memory plane can decompose live bytes per owner
        from ..service.cancellation import current_token
        tok = current_token()
        owner_query = tok.query_id if tok is not None else None
        tag = _memplane.call_tag()
        with self._lock:
            if buffer_id in self._entries:
                raise ValueError(f"duplicate buffer {buffer_id}")
            self._entries[buffer_id] = BufferEntry(
                buffer_id, StorageTier.DEVICE, nbytes, priority,
                device_obj=device_obj, owner_query=owner_query,
                owner_op=op, owner_site=site, owner_tag=tag)
            self.device_bytes += nbytes
            if self.device_bytes > self.device_peak_bytes:
                self.device_peak_bytes = self.device_bytes
            _memplane.note_register(nbytes, owner_query, site, op,
                                    self.device_bytes)
        if tok is not None:
            tok.own_buffer(buffer_id)
        return buffer_id

    def unregister(self, buffer_id: str):
        with self._lock:
            e = self._entries.pop(buffer_id, None)
            if e is None:
                return
            if e.tier == StorageTier.DEVICE:
                self.device_bytes -= e.nbytes
                _memplane.note_unregister(e.nbytes, e.owner_query,
                                          e.owner_site, e.owner_op,
                                          self.device_bytes)
            elif e.tier == StorageTier.HOST:
                self.host_bytes -= e.nbytes
                p = e.host_payload
                if isinstance(p, tuple) and p and p[0] == "arena" and \
                        self.arena is not None:
                    self.arena.free(p[5])
            else:
                self.disk_bytes -= e.nbytes
                if e.raw_cache is not None:
                    self.raw_cache_bytes -= len(e.raw_cache)
                    e.raw_cache = None
                if e.disk_path and os.path.exists(e.disk_path):
                    os.unlink(e.disk_path)
                if e.disk_path and os.path.exists(e.disk_path + ".raw"):
                    os.unlink(e.disk_path + ".raw")

    def demote(self, buffer_id: str):
        """Serialize a DEVICE-tier entry down to the HOST tier (used by
        the out-of-core sort after sampling a materialized run), then
        cascade host->disk while over host_limit so sampling runs that
        lived on DISK do not silently blow the host budget."""
        with self._lock:
            e = self._entries.get(buffer_id)
            if e is not None and e.tier == StorageTier.DEVICE:
                self._spill_entry_to_host(e)
            while self.host_bytes > self.host_limit:
                host_entries = sorted(
                    (x for x in self._entries.values()
                     if x.tier == StorageTier.HOST),
                    key=lambda x: x.priority)
                if not host_entries:
                    break
                self._spill_entry_to_disk(host_entries[0])

    # -- acquire (may unspill, like RapidsBufferCatalog.acquireBuffer) -----
    def acquire(self, buffer_id: str):
        with self._lock:
            e = self._entries[buffer_id]
            if e.tier == StorageTier.DEVICE:
                return e.device_obj
            if e.tier == StorageTier.HOST:
                obj = self._unspill_host(e)
            else:
                obj = self._unspill_disk(e)
            return obj

    # ------------------------------------------------------------------
    def _serialize(self, device_obj):
        """ColumnarBatch -> host payload (schema, num_rows, numpy buffers)."""
        from ..columnar.batch import ColumnarBatch
        from ..analysis import residency  # lazy: avoids import cycle
        assert isinstance(device_obj, ColumnarBatch)
        with residency.declared_transfer(site="spill_d2h"):
            bufs = [np.asarray(a) for a in device_obj.device_buffers()]
        from ..columnar.column import StringColumn

        def kind(c):
            # gather views serialize in materialized StringColumn layout
            if isinstance(c, StringColumn):
                return "StringColumn"
            return type(c).__name__
        return (device_obj.schema, device_obj.num_rows,
                [kind(c) for c in device_obj.columns], bufs)

    def _deserialize(self, payload):
        import jax.numpy as jnp
        from ..columnar.batch import ColumnarBatch
        from ..columnar.column import Column, StringColumn
        from ..columnar.binary64 import Binary64Column
        schema, num_rows, kinds, bufs = payload
        cols = []
        i = 0
        for f, kind in zip(schema, kinds):
            if kind == "StringColumn":
                offsets, data, validity = bufs[i], bufs[i + 1], bufs[i + 2]
                max_b = int(np.diff(
                    np.asarray(offsets)[:num_rows + 1]).max()) \
                    if num_rows else 0
                cols.append(StringColumn(jnp.asarray(offsets),
                                         jnp.asarray(data),
                                         jnp.asarray(validity),
                                         max_bytes=max_b))
                i += 3
            elif kind == "Binary64Column":
                # exact-double mode: data is int64 bit patterns, NOT a
                # float payload — restoring as a plain Column would
                # reinterpret bits as f64 values downstream
                data, validity = bufs[i], bufs[i + 1]
                cols.append(Binary64Column(jnp.asarray(data),
                                           jnp.asarray(validity)))
                i += 2
            else:
                data, validity = bufs[i], bufs[i + 1]
                cols.append(Column(f.dtype, jnp.asarray(data),
                                   jnp.asarray(validity)))
                i += 2
        return ColumnarBatch(schema, cols, num_rows)

    def acquire_slice(self, buffer_id: str, lo: int, hi: int):
        """Materialize ONLY rows [lo, hi) of a spilled batch.

        The out-of-core sort merge (GpuSortExec.scala:219 role) walks
        spilled sorted runs in bounded chunks; bringing a whole run back
        to the device tier per chunk would defeat the spill.  DEVICE-tier
        entries slice on device; HOST/DISK entries slice the host numpy
        payload and upload just the slice."""
        with self._lock:
            e = self._entries[buffer_id]
            if e.tier == StorageTier.DEVICE:
                return e.device_obj.slice(lo, hi - lo)
            if e.tier == StorageTier.HOST:
                schema, num_rows, kinds, fetch = self._host_fetcher(e)
            else:
                schema, num_rows, kinds, fetch = self._disk_fetcher(e)
            from .pressure import oom_retry
            return oom_retry(_slice_from_fetch, schema, num_rows, kinds,
                             fetch, lo, hi)

    @staticmethod
    def _meta_fetcher(metas, read_bytes):
        """fetch(buf_idx, elem_lo, elem_hi) over a flat byte region
        described by ``metas`` [(dtype_str, shape)], reading ONLY the
        requested element range via ``read_bytes(byte_off, nbytes)``."""
        starts = []
        pos = 0
        infos = []
        for dtype_str, shape in metas:
            dt = np.dtype(dtype_str)
            count = int(np.prod(shape)) if shape else 1
            starts.append(pos)
            infos.append((dt, shape, count))
            pos += count * dt.itemsize

        def fetch(i, elem_lo, elem_hi):
            dt, shape, count = infos[i]
            if len(shape) != 1:   # nested layouts: read whole buffer
                raw = read_bytes(starts[i], count * dt.itemsize)
                return np.frombuffer(raw, dt).reshape(shape)
            elem_lo = max(0, min(elem_lo, count))
            elem_hi = max(elem_lo, min(elem_hi, count))
            raw = read_bytes(starts[i] + elem_lo * dt.itemsize,
                             (elem_hi - elem_lo) * dt.itemsize)
            return np.frombuffer(raw, dt)
        return fetch

    def _host_fetcher(self, e: BufferEntry):
        """(schema, num_rows, kinds, fetch) for a HOST-tier entry without
        freeing its arena slab (the destructive reader is
        _unpack_payload, used by full unspills)."""
        p = e.host_payload
        if isinstance(p, tuple) and p and p[0] == "arena":
            _, schema, num_rows, kinds, metas, off, total = p

            def read_bytes(boff, nb):
                return bytes(self.arena.view(off + boff, nb)) if nb \
                    else b""
            return schema, num_rows, kinds, \
                self._meta_fetcher(metas, read_bytes)
        schema, num_rows, kinds, bufs = p

        def fetch(i, elem_lo, elem_hi):
            b = bufs[i]
            if b.ndim != 1:
                return b
            return b[elem_lo:elem_hi]
        return schema, num_rows, kinds, fetch

    def _disk_fetcher(self, e: BufferEntry):
        """(schema, num_rows, kinds, fetch) for a DISK-tier entry without
        changing its tier.  Uncompressed raw files are read by seek/read
        of just the requested ranges; compressed files decompress once
        and cache under a host budget.  The pickle header caches on the
        entry so repeated slices skip re-deserializing it — note the
        non-arena payload pickles the FULL buffers, so slicing that path
        still loads the whole run."""
        payload = getattr(e, "_pickle_cache", None)
        if payload is None:
            with open(e.disk_path, "rb") as f:
                payload = pickle.load(f)
            if isinstance(payload, tuple) and payload and \
                    payload[0] == "arena_file":
                e._pickle_cache = payload
        if not (isinstance(payload, tuple) and payload
                and payload[0] == "arena_file"):
            schema, num_rows, kinds, bufs = payload

            def fetch(i, elem_lo, elem_hi):
                b = bufs[i]
                if b.ndim != 1:
                    return b
                return b[elem_lo:elem_hi]
            return schema, num_rows, kinds, fetch
        _, schema, num_rows, kinds, metas, total, codec_name = payload
        if codec_name != "none":
            raw = e.raw_cache
            if raw is None:
                from ..shuffle.compression import get_codec
                with open(e.disk_path + ".raw", "rb") as f:
                    raw = get_codec(codec_name).decompress(f.read(),
                                                           max(total, 1))
                # bounded cache: pinning every decompressed run would
                # grow host RAM by the dataset size in exactly the
                # memory-constrained case the OOC merge targets
                if self.raw_cache_bytes + len(raw) <= \
                        self.host_limit // 4:
                    e.raw_cache = raw
                    self.raw_cache_bytes += len(raw)

            def read_bytes(boff, nb):
                return raw[boff:boff + nb]
        else:
            path = e.disk_path + ".raw"

            def read_bytes(boff, nb):
                if not nb:
                    return b""
                with open(path, "rb") as f:
                    f.seek(boff)
                    return f.read(nb)
        return schema, num_rows, kinds, \
            self._meta_fetcher(metas, read_bytes)

    def _spill_entry_to_host(self, e: BufferEntry, rank: int = 0):
        _flight.record(_flight.EV_SPILL, "device_to_host", a=e.nbytes)
        t0 = time.perf_counter_ns()
        with _trace.span("srt.spill.device_to_host", "memory", True,
                         bytes=e.nbytes):
            payload = self._serialize(e.device_obj)
            if self.arena is not None:
                payload = self._pack_into_arena(payload)
            e.host_payload = payload
            e.device_obj = None
            e.tier = StorageTier.HOST
            self.device_bytes -= e.nbytes
            self.host_bytes += e.nbytes
            self.spilled_device_to_host += e.nbytes
        _memplane.note_spill(
            _memplane.DIR_DEVICE_TO_HOST, e.buffer_id, e.owner_query,
            e.owner_site, e.owner_op, e.nbytes,
            _memplane.current_reason(), rank,
            time.perf_counter_ns() - t0, self.device_bytes)

    # -- native-arena packing (host staging slab; SURVEY.md §2.10.2) -------
    def _pack_into_arena(self, payload):
        schema, num_rows, kinds, bufs = payload
        metas = [(b.dtype.str, b.shape) for b in bufs]
        total = sum(int(b.nbytes) for b in bufs)
        try:
            off = self.arena.alloc(max(total, 1))
        except MemoryError:
            return payload  # arena full: keep python-heap payload
        pos = off
        for b in bufs:
            nb = int(b.nbytes)
            if nb:
                self.arena.view(pos, nb)[:] = b.reshape(-1).view(np.uint8)
            pos += nb
        return ("arena", schema, num_rows, kinds, metas, off, total)

    def _unpack_payload(self, payload):
        if not (isinstance(payload, tuple) and payload
                and payload[0] == "arena"):
            return payload, None
        _, schema, num_rows, kinds, metas, off, total = payload
        bufs = []
        pos = off
        for dtype_str, shape in metas:
            dt = np.dtype(dtype_str)
            count = int(np.prod(shape)) if shape else 1
            nb = count * dt.itemsize
            arr = np.empty(shape, dtype=dt)
            if nb:
                arr.reshape(-1).view(np.uint8)[:] = self.arena.view(pos, nb)
            bufs.append(arr)
            pos += nb
        self.arena.free(off)
        return (schema, num_rows, kinds, bufs), (off, total)

    def _spill_entry_to_disk(self, e: BufferEntry, rank: int = 0):
        _flight.record(_flight.EV_SPILL, "host_to_disk", a=e.nbytes)
        t0 = time.perf_counter_ns()
        with _trace.span("srt.spill.host_to_disk", "memory", True,
                         bytes=e.nbytes):
            self._spill_entry_to_disk_inner(e)
        _memplane.note_spill(
            _memplane.DIR_HOST_TO_DISK, e.buffer_id, e.owner_query,
            e.owner_site, e.owner_op, e.nbytes,
            _memplane.current_reason(), rank,
            time.perf_counter_ns() - t0, self.device_bytes)

    def _spill_entry_to_disk_inner(self, e: BufferEntry):
        os.makedirs(self.spill_dir, exist_ok=True)
        path = os.path.join(self.spill_dir, f"{e.buffer_id}.spill")
        payload = e.host_payload
        compressed = self.codec.name != "none"
        if isinstance(payload, tuple) and payload and payload[0] == "arena":
            _, schema, num_rows, kinds, metas, off, total = payload
            if compressed:
                raw = bytes(self.arena.view(off, max(total, 1)))
                with open(path + ".raw", "wb") as f:
                    f.write(self.codec.compress(raw))
            else:
                # stream the slab region straight to the file (native path)
                self.arena.write_file(off, max(total, 1), path + ".raw")
            self.arena.free(off)
            with open(path, "wb") as f:
                pickle.dump(("arena_file", schema, num_rows, kinds, metas,
                             total, self.codec.name if compressed
                             else "none"), f, protocol=4)
        else:
            with open(path, "wb") as f:
                pickle.dump(payload, f, protocol=4)
        e.host_payload = None
        e.disk_path = path
        e.tier = StorageTier.DISK
        self.host_bytes -= e.nbytes
        self.disk_bytes += e.nbytes
        self.spilled_host_to_disk += e.nbytes

    def _unspill_host(self, e: BufferEntry, extra_ns: int = 0):
        from .pressure import oom_retry
        _flight.record(_flight.EV_UNSPILL, "host_to_device", a=e.nbytes)
        t0 = time.perf_counter_ns()
        with _trace.span("srt.unspill.host_to_device", "memory", True,
                         bytes=e.nbytes):
            payload, _ = self._unpack_payload(e.host_payload)
            # the device put can hit the REAL allocator's
            # RESOURCE_EXHAUSTED even under the logical budget
            # (fragmentation, temporaries): spill-everything-and-retry
            # (DeviceMemoryEventHandler contract)
            obj = oom_retry(self._deserialize, payload)
            e.host_payload = None
            e.device_obj = obj
            e.tier = StorageTier.DEVICE
            self.host_bytes -= e.nbytes
            self.device_bytes += e.nbytes
            if self.device_bytes > self.device_peak_bytes:
                self.device_peak_bytes = self.device_bytes
        # one ledger record per unspill covering the whole read-back
        # path (extra_ns carries the disk->host hop when there was one)
        _memplane.note_spill(
            _memplane.DIR_UNSPILL, e.buffer_id, e.owner_query,
            e.owner_site, e.owner_op, e.nbytes,
            _memplane.current_reason(), 0,
            time.perf_counter_ns() - t0 + extra_ns, self.device_bytes)
        return obj

    def _unspill_disk(self, e: BufferEntry):
        _flight.record(_flight.EV_UNSPILL, "disk_to_host", a=e.nbytes)
        t0 = time.perf_counter_ns()
        with _trace.span("srt.unspill.disk_to_host", "memory", True,
                         bytes=e.nbytes):
            self._unspill_disk_inner(e)
        return self._unspill_host(e,
                                  extra_ns=time.perf_counter_ns() - t0)

    def _unspill_disk_inner(self, e: BufferEntry):
        with open(e.disk_path, "rb") as f:
            payload = pickle.load(f)
        if isinstance(payload, tuple) and payload and \
                payload[0] == "arena_file":
            _, schema, num_rows, kinds, metas, total, codec_name = payload
            off = self.arena.alloc(max(total, 1))
            if codec_name != "none":
                from ..shuffle.compression import get_codec
                with open(e.disk_path + ".raw", "rb") as f:
                    raw = get_codec(codec_name).decompress(
                        f.read(), max(total, 1))
                self.arena.view(off, max(total, 1))[:] = \
                    np.frombuffer(raw, np.uint8)
            else:
                self.arena.read_file(off, max(total, 1),
                                     e.disk_path + ".raw")
            os.unlink(e.disk_path + ".raw")
            payload = ("arena", schema, num_rows, kinds, metas, off, total)
        os.unlink(e.disk_path)
        e.disk_path = None
        if e.raw_cache is not None:
            self.raw_cache_bytes -= len(e.raw_cache)
        e.raw_cache = None
        if hasattr(e, "_pickle_cache"):
            del e._pickle_cache
        e.host_payload = payload
        e.tier = StorageTier.HOST
        self.disk_bytes -= e.nbytes
        self.host_bytes += e.nbytes

    # -- synchronous spill (DeviceMemoryEventHandler.onAllocFailure role) --
    def spill_device_to_fit(self, needed_bytes: int,
                            reason: Optional[str] = None) -> int:
        """Spill device-tier entries (lowest priority first) until at least

        ``needed_bytes`` are free under device_limit.  Returns bytes spilled.

        ``reason`` names the trigger for the spill ledger (budget /
        pressure / explicit); omitted, the thread's active
        ``memplane.spill_reason`` scope (or ``explicit``) applies.
        When the walk exhausts its candidates with the target still
        unmet — only pinned (refcount>0) entries remain — the
        shortfall is signalled (tpu_mem_spill_skipped_total + an
        EV_MEM flight event) instead of silently short-returning."""
        if reason is None:
            reason = _memplane.current_reason()
        spilled = 0
        with self._lock, _memplane.spill_reason(reason):
            target = self.device_limit - needed_bytes
            candidates = sorted(
                (e for e in self._entries.values()
                 if e.tier == StorageTier.DEVICE and e.refcount == 0),
                key=lambda e: e.priority)
            rank = 0
            for e in candidates:
                if self.device_bytes <= target:
                    break
                self._spill_entry_to_host(e, rank=rank)
                rank += 1
                spilled += e.nbytes
            if self.device_bytes > max(target, 0):
                pinned_count = 0
                pinned_bytes = 0
                for e in self._entries.values():
                    if e.tier == StorageTier.DEVICE and e.refcount > 0:
                        pinned_count += 1
                        pinned_bytes += e.nbytes
                if pinned_count:
                    _memplane.note_spill_skipped(
                        _memplane.REASON_PINNED, pinned_count,
                        pinned_bytes)
            # cascade host -> disk if host is over budget
            if self.host_bytes > self.host_limit:
                host_candidates = sorted(
                    (e for e in self._entries.values()
                     if e.tier == StorageTier.HOST and e.refcount == 0),
                    key=lambda e: e.priority)
                rank = 0
                for e in host_candidates:
                    if self.host_bytes <= self.host_limit:
                        break
                    self._spill_entry_to_disk(e, rank=rank)
                    rank += 1
        return spilled

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return dict(device_bytes=self.device_bytes,
                        device_peak_bytes=self.device_peak_bytes,
                        host_bytes=self.host_bytes,
                        disk_bytes=self.disk_bytes,
                        num_buffers=len(self._entries),
                        spilled_device_to_host=self.spilled_device_to_host,
                        spilled_host_to_disk=self.spilled_host_to_disk,
                        oom_retries=getattr(self, "oom_retries", 0))


def _slice_from_fetch(schema, num_rows, kinds, fetch, lo: int, hi: int):
    """Rows [lo, hi) of a serialized batch as a device batch, reading
    only the slice's elements via ``fetch(buf_idx, elem_lo, elem_hi)``.

    Only the slice's bytes cross to the device (the out-of-core merge
    contract).  Strings rebase offsets onto a sliced byte buffer."""
    import jax.numpy as jnp
    from ..columnar.batch import ColumnarBatch
    from ..columnar.column import (Column, StringColumn, bucket_capacity,
                                   _pad_np)
    from ..columnar.binary64 import Binary64Column
    lo = max(0, min(lo, num_rows))
    hi = max(lo, min(hi, num_rows))
    n = hi - lo
    cap = bucket_capacity(max(n, 1))
    cols = []
    i = 0
    for f, kind in zip(schema, kinds):
        if kind == "StringColumn":
            offs = np.asarray(fetch(i, lo, hi + 1))
            base, end = int(offs[0]), int(offs[n])
            sub = np.zeros(cap + 1, np.int32)
            sub[:n + 1] = offs[:n + 1] - base
            sub[n + 1:] = sub[n]
            byte_cap = bucket_capacity(max(end - base, 1))
            buf = np.zeros(byte_cap, np.uint8)
            buf[:end - base] = np.asarray(fetch(i + 1, base, end))
            validity = np.asarray(fetch(i + 2, lo, hi))
            mb = int(np.diff(offs[:n + 1]).max()) if n else 0
            cols.append(StringColumn(
                jnp.asarray(sub), jnp.asarray(buf),
                jnp.asarray(_pad_np(validity, cap, fill=False)),
                max_bytes=mb))
            i += 3
            continue
        d = jnp.asarray(_pad_np(np.asarray(fetch(i, lo, hi)), cap))
        v = jnp.asarray(_pad_np(np.asarray(fetch(i + 1, lo, hi)), cap,
                                fill=False))
        i += 2
        if kind == "Binary64Column":
            cols.append(Binary64Column(d, v))
        else:
            cols.append(Column(f.dtype, d, v))
    return ColumnarBatch(schema, cols, n)
