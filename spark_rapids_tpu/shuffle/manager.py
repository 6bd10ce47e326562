"""Shuffle manager: catalog-backed map-output storage + transport SPI.

Reference architecture (SURVEY.md §2.7): RapidsShuffleInternalManagerBase
keeps map output **in device memory** (RapidsCachingWriter -> catalog) and
serves reduce-side reads either locally (RapidsCachingReader) or over a
pluggable transport (RapidsShuffleTransport SPI -> UCX).  Here:

- ShuffleWriteSupport stores per-(shuffle, map, reduce) batches in a
  process-wide catalog whose entries are spillable via the memory layer.
- ShuffleTransport is the SPI; LocalTransport serves in-process reads
  (the single-host case) and the executor-to-executor transports live in
  transport.py / inprocess.py / tcp.py (the UCX role for the DCN edge);
  mesh-collective exchanges ride exec/tpu_mesh_aggregate.py over ICI.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..columnar.batch import ColumnarBatch
from ..obs import netplane as _netplane
from ..obs import trace as _trace
from ..obs.registry import SHUFFLE_READ_BYTES, SHUFFLE_WRITE_BYTES


@dataclasses.dataclass(frozen=True)
class ShuffleBlockId:
    shuffle_id: int
    map_id: int
    reduce_id: int


class ShuffleTransport:
    """Transport SPI (reference: shuffle/RapidsShuffleTransport.scala:338)."""

    def fetch(self, blocks: List[ShuffleBlockId]) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    def close(self):
        pass


# every live ShuffleCatalog (manager singleton + per-executor contexts):
# the memory plane's end-of-query leak check treats batches still held by
# ANY of them as expected survivors, not leaks (a peer query's reducer may
# still fetch them)
_ALL_CATALOGS: List["weakref.ref[ShuffleCatalog]"] = []
_ALL_CATALOGS_LOCK = threading.Lock()


def live_spill_buffer_ids() -> Set[int]:
    """Buffer ids of every shuffle batch still materialized in a live
    catalog (survivor set for ``obs.memplane.leak_check``)."""
    with _ALL_CATALOGS_LOCK:
        cats = [r() for r in _ALL_CATALOGS]
        if any(c is None for c in cats):
            _ALL_CATALOGS[:] = [r for r in _ALL_CATALOGS
                                if r() is not None]
    out: Set[int] = set()
    for c in cats:
        if c is None:
            continue
        with c._lock:
            for es in c._store.values():
                for e in es:
                    bid = getattr(e, "buffer_id", None)
                    if bid is not None:
                        out.add(bid)
    return out


class ShuffleCatalog:
    """In-memory map-output catalog (ShuffleBufferCatalog role).

    Batches are registered with the memory manager's spill framework when
    available so device pressure can push them host-side.
    """

    def __init__(self):
        self._store: Dict[ShuffleBlockId, List] = {}
        self._lock = threading.Lock()
        with _ALL_CATALOGS_LOCK:
            _ALL_CATALOGS.append(weakref.ref(self))

    def put(self, block: ShuffleBlockId, batches: List[ColumnarBatch]):
        # residency-audited: registering a block serializes nothing by
        # itself — SpillableBatch pulls device buffers only on a spill
        # or transport serialize, and both pull paths run inside
        # declared regions (spill_d2h in memory/catalog.py,
        # shuffle_serialize in shuffle/meta.py)
        from ..memory.spillable import SpillableBatch
        t0 = time.perf_counter_ns()
        with _trace.span("srt.shuffle.write", "shuffle", True):
            entries = [SpillableBatch(b, op="TpuShuffleExchange",
                                      site="exchange") for b in batches]
        nbytes = sum(e.nbytes for e in entries)
        SHUFFLE_WRITE_BYTES.inc(nbytes)
        _netplane.note_serialize(block.shuffle_id, block.map_id,
                                 block.reduce_id,
                                 sum(e.num_rows for e in entries), nbytes,
                                 time.perf_counter_ns() - t0)
        with self._lock:
            self._store[block] = entries

    def append(self, block: ShuffleBlockId, batches: List[ColumnarBatch]):
        """Incremental put: extend a block's batch list (map-side
        streaming writes register pieces as they finalize so they
        become spillable immediately)."""
        from ..memory.spillable import SpillableBatch
        t0 = time.perf_counter_ns()
        with _trace.span("srt.shuffle.write", "shuffle", True):
            entries = [SpillableBatch(b, op="TpuShuffleExchange",
                                      site="exchange") for b in batches]
        nbytes = sum(e.nbytes for e in entries)
        SHUFFLE_WRITE_BYTES.inc(nbytes)
        _netplane.note_serialize(block.shuffle_id, block.map_id,
                                 block.reduce_id,
                                 sum(e.num_rows for e in entries), nbytes,
                                 time.perf_counter_ns() - t0)
        with self._lock:
            self._store.setdefault(block, []).extend(entries)

    def get(self, block: ShuffleBlockId) -> List[ColumnarBatch]:
        with self._lock:
            entries = self._store.get(block, [])
        nbytes = sum(e.nbytes for e in entries)
        SHUFFLE_READ_BYTES.inc(nbytes)
        t0 = time.perf_counter_ns()
        with _trace.span("srt.shuffle.read", "shuffle", True):
            out = [e.materialize() for e in entries]
        if entries:
            _netplane.note_deserialize(block.shuffle_id, block.map_id,
                                       block.reduce_id, nbytes,
                                       time.perf_counter_ns() - t0)
        return out

    def stats_for_block(self, block: ShuffleBlockId):
        """(bytes, rows) without materializing (stays spilled —
        SpillableBatch caches both; the MapOutputStatistics role)."""
        with self._lock:
            entries = self._store.get(block, [])
            return (sum(e.nbytes for e in entries),
                    sum(e.num_rows for e in entries))

    def blocks_for_reduce(self, shuffle_id: int,
                          reduce_id: int) -> List[ShuffleBlockId]:
        with self._lock:
            return sorted(
                (b for b in self._store
                 if b.shuffle_id == shuffle_id and b.reduce_id == reduce_id),
                key=lambda b: b.map_id)

    def remove_shuffle(self, shuffle_id: int):
        with self._lock:
            for b in [b for b in self._store if b.shuffle_id == shuffle_id]:
                for e in self._store[b]:
                    e.close()           # release the catalog entry
                del self._store[b]

    def clear(self):
        with self._lock:
            for es in self._store.values():
                for e in es:
                    e.close()
            self._store.clear()

    def nbytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for es in self._store.values() for e in es)


class LocalTransport(ShuffleTransport):
    def __init__(self, catalog: ShuffleCatalog):
        self.catalog = catalog

    def fetch(self, blocks):
        for b in blocks:
            for batch in self.catalog.get(b):
                yield batch


class ShuffleManager:
    """Process-wide shuffle coordination (RapidsShuffleInternalManagerBase)."""

    _instance: Optional["ShuffleManager"] = None

    def __init__(self):
        self.catalog = ShuffleCatalog()
        self.transport: ShuffleTransport = LocalTransport(self.catalog)
        self._next_shuffle = 0
        self._lock = threading.Lock()

    @classmethod
    def get(cls) -> "ShuffleManager":
        if cls._instance is None:
            cls._instance = ShuffleManager()
        return cls._instance

    def new_shuffle_id(self) -> int:
        with self._lock:
            sid = self._next_shuffle
            self._next_shuffle += 1
        # attribute the shuffle to the active query (if any): concurrent
        # queries through the service clean up per-shuffle-id instead of
        # clear_all(), which would drop map outputs a peer query is
        # still draining
        from ..service.cancellation import current_token
        tok = current_token()
        if tok is not None:
            tok.own_shuffle(sid)
        return sid

    def clear_all(self):
        """Drop every shuffle's map output (the ContextCleaner role:
        shuffle blocks are per-query artifacts; without an end-of-query
        release a long sweep accumulates them until the REAL device
        allocator exhausts — the TPC-DS 99-query RESOURCE_EXHAUSTED
        failure mode)."""
        self.catalog.clear()

    # -- write side (RapidsCachingWriter role) -----------------------------
    def write_map_output(self, shuffle_id: int, map_id: int,
                         per_reduce: Dict[int, List[ColumnarBatch]]):
        for reduce_id, batches in per_reduce.items():
            if batches:
                self.catalog.put(
                    ShuffleBlockId(shuffle_id, map_id, reduce_id), batches)

    def append_map_output(self, shuffle_id: int, map_id: int,
                          per_reduce: Dict[int, List[ColumnarBatch]]):
        """Streaming variant of write_map_output: pieces land in the
        (spillable) catalog as they finalize, so a byte-budgeted map
        stage releases device memory mid-partition."""
        for reduce_id, batches in per_reduce.items():
            if batches:
                self.catalog.append(
                    ShuffleBlockId(shuffle_id, map_id, reduce_id), batches)

    # -- read side (RapidsCachingReader / RapidsShuffleIterator role) ------
    def read_partition(self, shuffle_id: int,
                       reduce_id: int) -> Iterator[ColumnarBatch]:
        blocks = self.catalog.blocks_for_reduce(shuffle_id, reduce_id)
        return self.transport.fetch(blocks)

    def cleanup(self, shuffle_id: int):
        self.catalog.remove_shuffle(shuffle_id)


# ---------------------------------------------------------------------------
# multi-executor mode: map-output tracking + transport-backed reads
# ---------------------------------------------------------------------------

class MapOutputTracker:
    """Driver-side block -> owning-executor registry.

    Reference: the MapStatus/MapOutputTracker round trip — the caching
    writer advertises a BlockManagerId (with the transport port folded
    into the topology string, RapidsShuffleInternalManagerBase:164-186)
    and reducers group fetches by owner.
    """

    def __init__(self):
        self._owner: Dict[Tuple[int, int], str] = {}   # (shuffle,map)->exec
        self._lock = threading.Lock()

    def register_map_output(self, shuffle_id: int, map_id: int,
                            executor_id: str):
        with self._lock:
            self._owner[(shuffle_id, map_id)] = executor_id

    def owner_of(self, shuffle_id: int, map_id: int) -> Optional[str]:
        with self._lock:
            return self._owner.get((shuffle_id, map_id))

    def map_ids(self, shuffle_id: int) -> List[int]:
        with self._lock:
            return sorted(m for s, m in self._owner if s == shuffle_id)

    def outputs_for_shuffle(self, shuffle_id: int) -> Dict[int, str]:
        """Atomic {map_id: owner} snapshot (one lock acquisition, so a
        concurrent unregister can't yield a map id with a None owner)."""
        with self._lock:
            return {m: o for (s, m), o in self._owner.items()
                    if s == shuffle_id}

    def unregister_shuffle(self, shuffle_id: int):
        with self._lock:
            for k in [k for k in self._owner if k[0] == shuffle_id]:
                del self._owner[k]


class ShuffleExecutorContext:
    """One executor's shuffle endpoint: catalog + transport + server.

    Bundles the pieces a real deployment wires at executor-plugin init
    (§3.4): the caching-writer catalog, the transport, the serving side
    (ShuffleServer over a CatalogRequestHandler) and heartbeat
    registration.  Used by tests and by the multi-process runner.
    """

    def __init__(self, executor_id: str, transport,
                 tracker: MapOutputTracker,
                 heartbeat_manager=None,
                 bounce_buffer_size: int = 1 << 20,
                 num_bounce_buffers: int = 4):
        from .heartbeat import PeerInfo, RapidsShuffleHeartbeatEndpoint
        from .server import CatalogRequestHandler, ShuffleServer
        self.executor_id = executor_id
        self.transport = transport
        self.tracker = tracker
        self.catalog = ShuffleCatalog()
        self.server = ShuffleServer(
            transport, CatalogRequestHandler(self.catalog),
            bounce_buffer_size=bounce_buffer_size,
            num_bounce_buffers=num_bounce_buffers)
        self.server.start()
        self.heartbeat = None
        if heartbeat_manager is not None:
            self.heartbeat = RapidsShuffleHeartbeatEndpoint(
                heartbeat_manager, transport, PeerInfo(executor_id))

    # -- write side (RapidsCachingWriter role) -----------------------------
    def write_map_output(self, shuffle_id: int, map_id: int,
                         per_reduce: Dict[int, List[ColumnarBatch]]):
        for reduce_id, batches in per_reduce.items():
            if batches:
                self.catalog.put(
                    ShuffleBlockId(shuffle_id, map_id, reduce_id), batches)
        self.tracker.register_map_output(shuffle_id, map_id,
                                         self.executor_id)

    def append_map_output(self, shuffle_id: int, map_id: int,
                          per_reduce: Dict[int, List[ColumnarBatch]]):
        """Streaming write: pieces append to this executor's catalog as
        they finalize, then the map registers with the tracker (the
        RapidsCachingWriter + MapStatus pairing in ONE place)."""
        for reduce_id, batches in per_reduce.items():
            if batches:
                self.catalog.append(
                    ShuffleBlockId(shuffle_id, map_id, reduce_id),
                    batches)
        self.tracker.register_map_output(shuffle_id, map_id,
                                         self.executor_id)

    # -- read side (RapidsCachingReader + RapidsShuffleIterator) -----------
    def read_partition(self, shuffle_id: int, reduce_id: int,
                       timeout_s: float = 30.0):
        from .iterator import RapidsShuffleIterator
        from .transport import BlockIdSpec
        local: List[ColumnarBatch] = []
        remote: Dict[str, List[BlockIdSpec]] = {}
        for map_id, owner in sorted(
                self.tracker.outputs_for_shuffle(shuffle_id).items()):
            if owner == self.executor_id:
                local.extend(self.catalog.get(
                    ShuffleBlockId(shuffle_id, map_id, reduce_id)))
            else:
                remote.setdefault(owner, []).append(
                    BlockIdSpec(shuffle_id, map_id, reduce_id))
        return RapidsShuffleIterator(self.transport, local, remote,
                                     timeout_s=timeout_s)
