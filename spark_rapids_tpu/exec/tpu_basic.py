"""TPU physical operators: scan/project/filter/limit/union/transitions.

Reference analogues: GpuProjectExec/GpuFilterExec/GpuLocalLimitExec/
GpuUnionExec (basicPhysicalOperators.scala, limit.scala),
GpuRowToColumnarExec/GpuColumnarToRowExec (transitions),
GpuCoalesceBatches (GpuCoalesceBatches.scala:195).
"""
from __future__ import annotations

import threading
from typing import Iterator, List, Optional

import numpy as np
import jax.numpy as jnp
import pyarrow as pa

from ..columnar import dtypes as T
from ..columnar.schema import Field, Schema
from ..columnar.column import Column, bucket_capacity
from ..columnar.batch import ColumnarBatch, LazyCount, concat_batches
from ..columnar.arrow import from_arrow, to_arrow, schema_to_arrow
from ..expr import core as ec
from ..kernels import basic as bk
from .base import (PhysicalPlan, NUM_OUTPUT_ROWS, NUM_OUTPUT_BATCHES,
                   OP_TIME, CONCAT_TIME, timed)


class TpuExec(PhysicalPlan):
    columnar = True


class TpuLocalScan(TpuExec):
    def __init__(self, table: pa.Table, num_partitions: int = 1,
                 batch_rows: int = 1 << 20):
        super().__init__()
        self.table = table
        self.num_partitions = max(1, num_partitions)
        self.batch_rows = batch_rows

    @property
    def output_schema(self):
        from ..columnar.arrow import schema_from_arrow
        return schema_from_arrow(self.table.schema)

    def num_partitions_hint(self):
        return self.num_partitions

    # host->device uploads dominate repeated queries over the same local
    # table, so uploaded batches are kept device-resident per source table —
    # a small LRU so HBM stays bounded.
    _DEVICE_CACHE: "OrderedDict" = None
    # concurrent scans (pipelined drains + concurrent service queries)
    # mutate the class-level LRU; all get/move_to_end/set/evict steps
    # run under this lock.  The upload loop below stays OUTSIDE it:
    # from_arrow only dispatches (lazy device upload, no blocking), but
    # serializing uploads under a class-wide lock would still defeat
    # the pipeline's overlap — only the dict ops need the lock.
    _DEVICE_CACHE_LOCK = threading.Lock()
    # key -> (table, Event) while a miss is uploading: concurrent
    # misses on the same key wait for the first builder instead of each
    # uploading the full partition set (transient double HBM residency
    # for large tables, last-write-wins churn).  A builder that fails
    # pops its sentinel in the finally, so waiters retry and one of
    # them becomes the next builder.
    _DEVICE_CACHE_BUILDING: dict = {}

    def _cached_batches(self):
        from collections import OrderedDict
        from ..service.cancellation import cancel_checkpoint
        cls = TpuLocalScan
        key = (id(self.table), self.num_partitions, self.batch_rows)
        while True:
            with cls._DEVICE_CACHE_LOCK:
                if cls._DEVICE_CACHE is None:
                    cls._DEVICE_CACHE = OrderedDict()
                hit = cls._DEVICE_CACHE.get(key)
                if hit is not None and hit[0] is self.table:
                    cls._DEVICE_CACHE.move_to_end(key)
                    return hit[1]
                building = cls._DEVICE_CACHE_BUILDING.get(key)
                if building is None:
                    done = threading.Event()
                    cls._DEVICE_CACHE_BUILDING[key] = (self.table, done)
                    break
                done = building[1]
            # a peer is uploading this key (ours, or — after id reuse —
            # another table's): park OUTSIDE the lock, checkpointed so
            # cancellation unwinds a waiter, then re-check from the top
            while not done.wait(0.05):
                cancel_checkpoint()
        try:
            n = self.table.num_rows
            per = -(-n // self.num_partitions) if n else 0
            parts = []
            for i in range(self.num_partitions):
                lo = min(i * per, n)
                hi = min(lo + per, n)
                batches = []
                pos = lo
                while pos < hi:
                    k = min(self.batch_rows, hi - pos)
                    batches.append(from_arrow(self.table.slice(pos, k)))
                    pos += k
                if lo == hi and lo == 0 and self.num_partitions == 1:
                    batches.append(from_arrow(self.table.slice(0, 0)))
                parts.append(batches)
            with cls._DEVICE_CACHE_LOCK:
                cls._DEVICE_CACHE[key] = (self.table, parts)
                while len(cls._DEVICE_CACHE) > 8:
                    cls._DEVICE_CACHE.popitem(last=False)
        finally:
            with cls._DEVICE_CACHE_LOCK:
                cls._DEVICE_CACHE_BUILDING.pop(key, None)
            done.set()
        return parts

    def execute(self):
        from ..obs import stats as obs_stats
        if obs_stats.enabled():
            # exact per-partition sizes from the slicing arithmetic —
            # zero device work (stats plane, obs/stats.py)
            n = self.table.num_rows
            per = -(-n // self.num_partitions) if n else 0
            obs_stats.note_scan(self, [
                min(i * per + per, n) - min(i * per, n)
                for i in range(self.num_partitions)])
        return [iter(batches) for batches in self._cached_batches()]


class TpuRange(TpuExec):
    """Reference: GpuRangeExec (basicPhysicalOperators.scala:245)."""

    def __init__(self, start, end, step, num_partitions,
                 batch_rows: int = 1 << 20):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.num_partitions = max(1, num_partitions)
        self.batch_rows = batch_rows

    @property
    def output_schema(self):
        return Schema([Field("id", T.INT64, False)])

    def num_partitions_hint(self):
        return self.num_partitions

    def execute(self):
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self.num_partitions) if total else 0
        from ..obs import stats as obs_stats
        if obs_stats.enabled():
            obs_stats.note_scan(self, [
                max(0, min((i + 1) * per, total) - i * per)
                for i in range(self.num_partitions)])
        parts = []
        for i in range(self.num_partitions):
            lo, hi = i * per, min((i + 1) * per, total)

            def gen(lo=lo, hi=hi):
                pos = lo
                while pos < hi:
                    k = min(self.batch_rows, hi - pos)
                    cap = bucket_capacity(k)
                    ids = (self.start +
                           (jnp.arange(cap, dtype=jnp.int64) + pos) *
                           self.step)
                    col = Column(T.INT64, ids, jnp.arange(cap) < k)
                    yield ColumnarBatch(self.output_schema, [col], k)
                    pos += k
                if hi <= lo:
                    yield ColumnarBatch.empty(self.output_schema)
            parts.append(gen())
        return parts


class TpuProject(TpuExec):
    """Reference: GpuProjectExec (basicPhysicalOperators.scala:83)."""

    def __init__(self, exprs: List[ec.Expression], child: PhysicalPlan):
        super().__init__(child)
        self.exprs = exprs
        self._bound: Optional[List[ec.Expression]] = None

    @property
    def output_schema(self):
        return Schema([Field(ec.output_name(e), e.dtype(), e.nullable)
                       for e in self.exprs])

    def execute(self):
        from .fused import FusedEval
        child_schema = self.children[0].output_schema
        bound = [e.bind(child_schema) for e in self.exprs]
        out_schema = self.output_schema
        fused = FusedEval(bound, child_schema)

        def project_one(batch):
            cols = fused(batch)
            if cols is None:
                cols = [ec.eval_as_column(b, batch) for b in bound]
            return ColumnarBatch(out_schema, cols, batch.rows_lazy)

        def run(part):
            from ..columnar.batch import chain_speculative
            for batch in part:
                with timed(self.metrics[OP_TIME], self):
                    # chain, don't drop, a speculative input's fit flags:
                    # projection preserves row identity, so the consumer's
                    # barrier can vouch for input + output together
                    out = chain_speculative(project_one(batch), batch,
                                            project_one)
                self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield out
        return [run(p) for p in self.children[0].execute()]

    def _node_string(self):
        return f"TpuProject[{', '.join(ec.output_name(e) for e in self.exprs)}]"


class TpuFilter(TpuExec):
    """Reference: GpuFilterExec — boolean mask + compaction gather."""

    def __init__(self, condition: ec.Expression, child: PhysicalPlan):
        super().__init__(child)
        self.condition = condition

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def execute(self):
        from .fused import FusedEval
        child_schema = self.children[0].output_schema
        bound = self.condition.bind(child_schema)
        fused = FusedEval([bound], child_schema)

        def filter_one(batch):
            fcols = fused(batch)
            pred = fcols[0] if fcols is not None else \
                ec.eval_as_column(bound, batch)
            keep = pred.data.astype(bool) & pred.validity
            idx, cnt = bk.filter_compact_indices(keep, batch.rows_dev)
            # keep the count on device: pulling it per batch
            # costs a full dispatch-queue sync (LazyCount doc)
            n = LazyCount(cnt)
            mask = jnp.arange(batch.capacity) < cnt
            return batch.gather(idx, n, live=mask, unique=True)

        def run(part):
            from ..columnar.batch import chain_speculative
            for batch in part:
                with timed(self.metrics[OP_TIME], self):
                    out = chain_speculative(filter_one(batch), batch,
                                            filter_one)
                self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield out
        return [run(p) for p in self.children[0].execute()]

    def _node_string(self):
        return f"TpuFilter[{self.condition!r}]"


class TpuCoalesceBatches(TpuExec):
    """Concat small batches up to a rows/bytes goal.

    Reference: GpuCoalesceBatches + AbstractGpuCoalesceIterator
    (GpuCoalesceBatches.scala:195,402).
    """

    def __init__(self, child: PhysicalPlan, target_rows: int = 1 << 20,
                 target_bytes: int = 512 << 20):
        super().__init__(child)
        self.target_rows = target_rows
        self.target_bytes = target_bytes

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def execute(self):
        def run(part):
            from ..columnar.batch import resolve_speculative
            pending: List[ColumnarBatch] = []
            rows = 0
            nbytes = 0
            for batch in part:
                # the count reads below are a forcing point: verify a
                # speculative input first (forcing an unverified count
                # would bake a wrong value into the limit bookkeeping)
                batch = resolve_speculative(batch)
                if batch.num_rows == 0 and pending:
                    continue
                pending.append(batch)
                rows += batch.num_rows
                nbytes += batch.nbytes()
                if rows >= self.target_rows or nbytes >= self.target_bytes:
                    # the region ends before the yield: a span held
                    # open across it would time the consumer too
                    with timed(self.metrics[CONCAT_TIME], self):
                        out = concat_batches(pending)
                    yield out
                    pending, rows, nbytes = [], 0, 0
            if pending:
                with timed(self.metrics[CONCAT_TIME], self):
                    out = concat_batches(pending)
                yield out
        return [run(p) for p in self.children[0].execute()]


def _limit_head_lazy(batch: ColumnarBatch, n: int):
    """head-n entirely on device counts — no host pull, propagating any
    speculative flag (superstage path: the collect/exchange barrier then
    resolves limit + sort + agg + join fits in ONE fused flush)."""
    from ..columnar.batch import LazyCount, chain_speculative
    from ..columnar.column import bucket_capacity
    cap = min(bucket_capacity(max(n, 1)), batch.capacity)
    out_n = jnp.minimum(batch.rows_dev, jnp.int32(n))
    take = jnp.arange(cap)
    out = batch.gather(take, LazyCount(out_n), live=take < out_n)

    def redo(fixed):
        return fixed if fixed.num_rows <= n else fixed.slice(0, n)
    return chain_speculative(out, batch, redo)


def _limit_lazy_ok(batch: ColumnarBatch) -> bool:
    """A lazy head pays off (and is needed for correctness ordering)
    only when the count is still device-resident or the batch carries
    unverified fit flags."""
    return not isinstance(batch.rows_lazy, int) or \
        getattr(batch, "_speculative", None) is not None


class TpuLocalLimit(TpuExec):
    def __init__(self, n: int, child: PhysicalPlan):
        super().__init__(child)
        self.n = n

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def execute(self):
        def run(part):
            from ..columnar.batch import resolve_speculative
            it = iter(part)
            first = next(it, None)
            if first is None:
                return
            second = next(it, None)
            if second is None and _limit_lazy_ok(first):
                # single device-counted batch: take the head without a
                # host round trip
                yield _limit_head_lazy(first, self.n)
                return
            remaining = self.n
            for batch in [b for b in (first, second)
                          if b is not None] + list(it):
                if remaining <= 0:
                    break
                batch = resolve_speculative(batch)
                if batch.num_rows <= remaining:
                    remaining -= batch.num_rows
                    yield batch
                else:
                    yield batch.slice(0, remaining)
                    remaining = 0
        return [run(p) for p in self.children[0].execute()]


class TpuGlobalLimit(TpuExec):
    """Single-partition global limit with offset."""

    def __init__(self, n: int, child: PhysicalPlan, offset: int = 0):
        super().__init__(child)
        self.n = n
        self.offset = offset

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def num_partitions_hint(self):
        return 1

    def execute(self):
        parts = self.children[0].execute()

        def run():
            from ..columnar.batch import resolve_speculative
            if len(parts) == 1 and self.offset == 0:
                it = iter(parts[0])
                first = next(it, None)
                if first is None:
                    return
                second = next(it, None)
                if second is None and _limit_lazy_ok(first):
                    yield _limit_head_lazy(first, self.n)
                    return
                parts[0] = [b for b in (first, second)
                            if b is not None] + list(it)
            skip = self.offset
            remaining = self.n
            for p in parts:
                for batch in p:
                    if remaining <= 0:
                        return
                    batch = resolve_speculative(batch)
                    if skip >= batch.num_rows:
                        skip -= batch.num_rows
                        continue
                    if skip > 0:
                        batch = batch.slice(skip, batch.num_rows - skip)
                        skip = 0
                    if batch.num_rows > remaining:
                        batch = batch.slice(0, remaining)
                    remaining -= batch.num_rows
                    yield batch
        return [run()]


class TpuUnion(TpuExec):
    def __init__(self, *children):
        super().__init__(*children)

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def num_partitions_hint(self):
        return sum(c.num_partitions_hint() for c in self.children)

    def execute(self):
        target = self.output_schema
        parts = []
        for c in self.children:
            for p in c.execute():
                def conv(p=p, src=c.output_schema):
                    for b in p:
                        yield _align_schema(b, target)
                parts.append(conv())
        return parts


def _align_schema(batch: ColumnarBatch, target: Schema) -> ColumnarBatch:
    if batch.schema == target:
        return batch
    from ..expr.cast import Cast
    from ..expr.core import BoundReference
    cols = []
    for i, f in enumerate(target):
        src_f = batch.schema[i]
        if src_f.dtype == f.dtype:
            cols.append(batch.columns[i])
        else:
            e = Cast(BoundReference(i, src_f.dtype), f.dtype)
            cols.append(ec.eval_as_column(e, batch))
    return ColumnarBatch(target, cols, batch.num_rows)


class RowToColumnar(TpuExec):
    """CPU pa.Table partitions -> device batches.

    Reference: GpuRowToColumnarExec (GpuRowToColumnarExec.scala:788).
    """

    def __init__(self, child: PhysicalPlan):
        super().__init__(child)
        assert not child.columnar

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def execute(self):
        def run(part):
            for t in part:
                with timed(self.metrics[OP_TIME], self):
                    b = from_arrow(t)
                yield b
        return [run(p) for p in self.children[0].execute()]


class ColumnarToRow(PhysicalPlan):
    """Device batches -> CPU pa.Table partitions.

    Reference: GpuColumnarToRowExec (GpuColumnarToRowExec.scala:341).
    """
    columnar = False

    def __init__(self, child: PhysicalPlan):
        super().__init__(child)
        assert child.columnar

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def execute(self):
        def run(part):
            for b in part:
                with timed(self.metrics[OP_TIME], self):
                    t = to_arrow(b)
                yield t
        return [run(p) for p in self.children[0].execute()]
