"""Exchange operators: shuffle + broadcast.

Reference: GpuShuffleExchangeExecBase (org/.../GpuShuffleExchangeExec.scala:98,
prepareBatchShuffleDependency :176) and GpuBroadcastExchangeExec.

Execution model: the map side runs eagerly when the reduce side first
pulls (a stage barrier, like Spark), splitting every batch with a device
partitioner and registering the slices in the shuffle catalog; reduce
partitions then stream from the catalog through the transport SPI.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

from ..columnar.batch import ColumnarBatch, concat_batches
from ..obs import flight as _flight
from ..obs import netplane as _netplane
from ..obs import trace as _trace
from ..shuffle.manager import ShuffleManager
from ..shuffle.partitioners import Partitioner, RangePartitioner
from .base import PhysicalPlan, PARTITION_TIME, NUM_OUTPUT_ROWS, timed
from .pipeline import drain_parallel
from .tpu_basic import TpuExec


class _DistWriter:
    """ShuffleManager facade writing into a ShuffleExecutorContext: map
    output lands in the executor's own catalog + its map registration in
    the (driver) tracker, so reducers in OTHER processes fetch it over
    the transport (RapidsCachingWriter + MapStatus round trip)."""

    def __init__(self, ctx, shuffle_id: int):
        self.ctx = ctx
        self.shuffle_id = shuffle_id

    def new_shuffle_id(self) -> int:
        return self.shuffle_id

    def append_map_output(self, shuffle_id, map_id, per_reduce):
        self.ctx.append_map_output(shuffle_id, map_id, per_reduce)


class TpuShuffleExchange(TpuExec):
    def __init__(self, child: PhysicalPlan, partitioner: Partitioner):
        super().__init__(child)
        self.partitioner = partitioner
        self._shuffle_id: Optional[int] = None
        # parallel reduce pulls (pipelined drains) race to trigger the
        # map stage; the barrier must run exactly once
        self._mat_lock = threading.Lock()
        self._materialized = False
        # distributed mode (executor-process split): set by
        # attach_distributed; None = in-process ShuffleManager
        self._dist_ctx = None
        self._dist_shuffle_id: Optional[int] = None
        self._dist_run_map = True

    def attach_distributed(self, ctx, shuffle_id: int, run_map: bool):
        """Split this exchange across OS processes: ``run_map=True``
        executes the map side into ``ctx``'s catalog (an executor
        serving fetches); ``run_map=False`` skips the local map stage
        (it ran in another process) and reduces via ``ctx``'s
        transport-aware read path."""
        self._dist_ctx = ctx
        self._dist_shuffle_id = shuffle_id
        self._dist_run_map = run_map

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def num_partitions_hint(self):
        return self.partitioner.num_partitions

    def _node_string(self):
        return (f"TpuShuffleExchange[{type(self.partitioner).__name__}"
                f"({self.partitioner.num_partitions})]")

    def _materialize_map_side(self):
        t_map0 = time.perf_counter_ns()
        # netplane snapshot: attributes this exchange's serialize volume
        # in the map-side trace span (best-effort under concurrent
        # exchanges — the global matrix stays exact either way)
        np_marker = _netplane.begin_query()
        from ..columnar import pending
        from ..columnar.batch import resolve_speculative
        mgr = ShuffleManager.get() if self._dist_ctx is None else \
            _DistWriter(self._dist_ctx, self._dist_shuffle_id)
        self._shuffle_id = mgr.new_shuffle_id()
        in_parts = self.children[0].execute()
        # range partitioner needs bounds from a sample pass first
        if isinstance(self.partitioner, RangePartitioner) and \
                self.partitioner.bound_words is None:
            all_batches = [[b for b in p] for p in in_parts]
            sample = [b for part in all_batches for b in part]
            self.partitioner.fit(sample)
            in_parts = [iter(p) for p in all_batches]
        # Phase 1 (device-only): drain map partitions, staging the split
        # sort + boundary counts per batch — nothing pulls yet.
        # Phase 2: ONE fused flush resolves every count and every
        # speculative fit flag (columnar/pending.py); the rare batch
        # whose table-path assumptions failed is recomputed exactly here,
        # at the stage barrier, before any result is exposed.
        # Staging is BOUNDED: past mapStagingBytes of staged device data
        # (input + sorted copy) the exchange flushes, finalizes what is
        # staged, and APPENDS the pieces straight into the (spillable)
        # catalog — including the in-progress map partition — so device
        # memory held between flushes never exceeds the budget and hash
        # shuffles larger than device memory still stream.  (Range
        # exchanges materialized everything above for bound sampling;
        # the budget does not cover that path.)
        from ..config import get_active, SHUFFLE_MAP_STAGING_BYTES
        from ..obs import profile
        from ..obs import stats as obs_stats
        conf = get_active()
        budget = int(conf.get(SHUFFLE_MAP_STAGING_BYTES))
        n_red = self.partitioner.num_partitions
        stats_on = obs_stats.enabled(conf)
        if stats_on:
            acc = obs_stats.exchange_acc(
                self, n_red, obs_stats.sketch_registers(conf),
                obs_stats._row_width(self.output_schema), "shuffle",
                type(self.partitioner).__name__,
                obs_stats.sample_every(conf))
        # flushes forced at this barrier belong to the producing stage:
        # attribute to the fused superstage feeding the exchange when
        # there is one, else to the exchange itself (obs/profile.py)
        child = self.children[0]
        attrib_target = child if getattr(child, "lowering", None) \
            is not None else self
        staged = []        # (map_id, batch, (sorted_batch, counts), st)
        staged_bytes = 0

        def finalize_staged():
            nonlocal staged_bytes
            with profile.attrib_scope(attrib_target):
                # residency-audited: the map-side count pull rides this
                # one declared pending_flush region (RES001-clean) —
                # every per-batch split count resolves through the
                # fused pool, never an inline np.asarray
                pending.flush()
                per_reduce_by_map = {}
                crossed = 0
                for map_id, batch, (sorted_batch, counts), st in staged:
                    checked = resolve_speculative(batch)
                    if checked is not batch:
                        with timed(self.metrics[PARTITION_TIME], self):
                            sorted_batch, counts = \
                                self.partitioner.split_staged(checked)
                        if stats_on:
                            # the staged sketch saw the failed
                            # speculative batch; re-stage from the exact
                            # one BEFORE finalize_split forces the redo
                            # flush, which then resolves it for free.
                            # force only when a sketch was actually
                            # staged — a sampling-skipped batch stays
                            # skipped, keeping acc.sketched consistent
                            if st is not None:
                                st = obs_stats.stage_exchange_batch(
                                    self.partitioner, checked, acc.m,
                                    acc, force=True)
                    split = self.partitioner.finalize_split(sorted_batch,
                                                            counts)
                    if stats_on:
                        acc.absorb(split.offsets, st)
                    crossed += int(split.offsets[-1])
                    if split.offsets[-1] == 0:
                        continue
                    per_reduce = per_reduce_by_map.setdefault(map_id, {})
                    for pid in range(n_red):
                        piece = split.partition_slice(pid)
                        if piece is not None:
                            per_reduce.setdefault(pid, []).append(piece)
                # the offsets are on the host here: map batches split and
                # the rows that crossed, into the draining query's table
                if staged:
                    _trace.count("exchange.batches", len(staged))
                if crossed:
                    _trace.count("exchange.rows", crossed)
                staged.clear()
                staged_bytes = 0
                for map_id, per_reduce in per_reduce_by_map.items():
                    mgr.append_map_output(self._shuffle_id, map_id,
                                          per_reduce)

        def split_one(batch):
            # runs on pipeline producers (under the DeviceSemaphore):
            # the split's device dispatch + host prep for one map batch
            # overlaps the splits of other partitions in flight; the
            # stats sketch is enqueued in the SAME dispatch window so
            # it rides the finalize flush (zero extra round trips)
            with timed(self.metrics[PARTITION_TIME], self), \
                    profile.dispatch(profile.SITE_SPLIT):
                split = self.partitioner.split_staged(batch)
                st = obs_stats.stage_exchange_batch(
                    self.partitioner, batch, acc.m,
                    acc) if stats_on else None
                return batch, split, st

        # morsel-parallel map drain (exec/pipeline.py): partitions are
        # pulled + split concurrently, but arrive here in deterministic
        # (map_id, batch) order, so staging/flush boundaries — and the
        # map output — are identical to the serial drain's
        for map_id, (batch, split, st) in drain_parallel(
                in_parts, sink=split_one, label="shuffle_map"):
            staged.append((map_id, batch, split, st))
            staged_bytes += 2 * batch.nbytes()
            if staged_bytes > budget:
                finalize_staged()
        finalize_staged()
        if stats_on:
            obs_stats.finish_exchange(self, conf)
        _flight.record(_flight.EV_NET, "map_side", n_red)
        if _trace._ENABLED:
            net = _netplane.query_summary(np_marker)
            _trace.emit("exchange_map_side", "shuffle", t_map0,
                        time.perf_counter_ns() - t_map0,
                        shuffle_id=self._shuffle_id, partitions=n_red,
                        staged_bytes=net["staged_bytes"],
                        serialize_ms=net["phases_ms"]["serialize"])

    def ensure_materialized(self):
        """Run the map side once (the AQE stage-materialization barrier).

        Double-checked lock: concurrent reduce pulls (the pipelined
        collect drains partitions in parallel) must not double-run the
        map stage; losers block until the winner's outputs are fully
        registered.  ``_materialized`` is set only after the drain
        completes — ``_shuffle_id`` alone is assigned early inside
        ``_materialize_map_side`` and would leak a half-built stage.

        The whole barrier runs with the calling thread's device permits
        dropped (``sem.released()``): a reduce pull reaches here from
        inside a pipeline producer's permit-held dispatch region, and
        pinning that permit while a loser parks on ``_mat_lock`` — or
        while the winner runs the entire map-side drain, which acquires
        permits of its own — starves concurrent queries and can
        deadlock the nested drain's pool workers behind it.  Permits
        are reacquired to the same depth before returning to the pull."""
        if self._materialized:
            return
        from ..memory.arena import DeviceManager
        with DeviceManager.get().semaphore.released():
            with self._mat_lock:
                if self._materialized:
                    return
                if self._dist_ctx is not None and not self._dist_run_map:
                    # the map stage ran in another executor process; its
                    # outputs are registered in the shared tracker
                    self._shuffle_id = self._dist_shuffle_id
                else:
                    # the flush inside the map-side drain is the POINT
                    # of this barrier: stage outputs must be on device
                    # before any reduce pull proceeds, losers are
                    # SUPPOSED to park until then, and device permits
                    # are dropped for the whole region (above) so the
                    # wait cannot deadlock the dispatch pool
                    with _trace.span("srt.exchange.map", coarse=True):
                        # lint: allow(LOCK003)
                        self._materialize_map_side()
                self._materialized = True

    def partition_stats(self):
        """Per-reduce-partition (bytes, rows) from the materialized map
        output — the MapOutputStatistics role AQE re-plans from."""
        self.ensure_materialized()
        # distributed mode: only THIS executor's blocks are visible
        # (remote stats would need a tracker protocol extension); AQE
        # then sees zeros for remote-only partitions and keeps the
        # static plan, which is correct if conservative
        cat = self._dist_ctx.catalog if self._dist_ctx is not None \
            else ShuffleManager.get().catalog
        stats = []
        for pid in range(self.partitioner.num_partitions):
            nbytes = rows = 0
            for block in cat.blocks_for_reduce(self._shuffle_id, pid):
                nb, nr = cat.stats_for_block(block)
                nbytes += nb
                rows += nr
            stats.append((nbytes, rows))
        return stats

    def stream_reduce(self, reduce_id: int):
        """Stream one reduce partition batch-by-batch (batches unspill
        one at a time — the memory-bounded path)."""
        self.ensure_materialized()
        _flight.record(_flight.EV_NET, "reduce_stream", reduce_id)
        if self._dist_ctx is not None:
            # transport-aware read: local blocks from this executor's
            # catalog, remote ones fetched over the wire
            for b in self._dist_ctx.read_partition(self._shuffle_id,
                                                   reduce_id):
                self.metrics[NUM_OUTPUT_ROWS] += b.rows_lazy
                yield b
            return
        mgr = ShuffleManager.get()
        for b in mgr.read_partition(self._shuffle_id, reduce_id):
            self.metrics[NUM_OUTPUT_ROWS] += b.rows_lazy
            yield b

    def read_reduce(self, reduce_id: int):
        """All batches of one reduce partition as a list — for AQE
        callers that re-group/slice partitions; plain execution streams
        via stream_reduce instead."""
        return list(self.stream_reduce(reduce_id))

    def execute(self):
        schema = self.output_schema

        def reduce_iter(reduce_id):
            got = False
            for b in self.stream_reduce(reduce_id):
                got = True
                yield b
            if not got:
                yield ColumnarBatch.empty(schema)
        return [reduce_iter(i)
                for i in range(self.partitioner.num_partitions)]


class TpuBroadcastExchange(TpuExec):
    """Concat the whole input into one batch, replicated to consumers.

    Reference: GpuBroadcastExchangeExec.scala:48."""

    def __init__(self, child: PhysicalPlan):
        super().__init__(child)
        self._result: Optional[ColumnarBatch] = None
        # concurrent probes (pipelined drains pull both join sides in
        # parallel) must build once; losers block until the winner
        # publishes — the double-checked lock below
        self._build_lock = threading.Lock()

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def num_partitions_hint(self):
        return 1

    def broadcast_batch(self) -> ColumnarBatch:
        from ..columnar.batch import resolve_speculative
        from ..memory.arena import DeviceManager
        from ..service.cancellation import cancel_checkpoint
        if self._result is not None:
            return self._result
        # probes reach this barrier from inside a pipeline producer's
        # permit-held pull region; the build (and the loser park on
        # _build_lock) runs with those permits dropped — same deadlock/
        # starvation rationale as ensure_materialized — and reacquires
        # them before the probe resumes
        with DeviceManager.get().semaphore.released():
            with self._build_lock:
                if self._result is not None:
                    return self._result
                # the build side materializes in full before the first
                # probe batch: checkpoint per pulled batch so
                # cancellation can unwind the drain; the pull itself is
                # a (possibly nested) morsel-parallel drain
                raw = []
                for _pid, b in drain_parallel(self.children[0].execute(),
                                              label="broadcast_build"):
                    cancel_checkpoint()
                    raw.append(b)
                if len(raw) == 1:
                    # single-batch build side (the dominant dimension-
                    # table shape): pass through WITHOUT forcing the
                    # host count — consumers key off device counts
                    # (canon rank words mask dead rows) and resolve any
                    # speculative flag at their own flush barrier, so
                    # the broadcast costs zero round trips here
                    self._result = raw[0]
                else:
                    from ..obs import profile
                    child = self.children[0]
                    target = child if getattr(child, "lowering", None) \
                        is not None else self
                    with profile.attrib_scope(target):
                        batches = [resolve_speculative(b) for b in raw]
                        batches = [b for b in batches if b.num_rows > 0]
                    self._result = concat_batches(batches) if batches \
                        else ColumnarBatch.empty(self.output_schema)
                from ..obs import stats as obs_stats
                # unconditional: a bare attribute store, so no conf
                # lookup on this helper thread (ambient-conf fallback
                # is unreliable off the session/pipeline threads); the
                # session's own conf gates everything at profile-build
                # time, and rows read lazily there — the single-batch
                # path stays zero-round-trip
                obs_stats.note_broadcast(self, self._result)
        return self._result

    def execute(self):
        return [iter([self.broadcast_batch()])]


class TpuCoalescePartitions(TpuExec):
    """N partitions -> 1 without reordering (single partitioning exchange)."""

    def __init__(self, child: PhysicalPlan):
        super().__init__(child)

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def num_partitions_hint(self):
        return 1

    def execute(self):
        parts = self.children[0].execute()

        def run():
            for p in parts:
                for b in p:
                    yield b
        return [run()]
