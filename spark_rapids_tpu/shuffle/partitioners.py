"""Device partitioners — reference: GpuHashPartitioning.scala,

GpuRangePartitioner.scala + SamplingUtils.scala, GpuRoundRobinPartitioning,
GpuSinglePartitioning, all slicing via contiguous split
(GpuPartitioning.scala:31-73).

TPU-first: partition ids are computed on device (hash of canonical key
words / binary search against range bounds); the "contiguous split" is a
stable sort by partition id + host-visible bincount boundaries, after
which per-partition slices are plain device gathers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch, LazyArray
from ..columnar.column import Column, StringColumn, bucket_capacity
from ..expr import core as ec
from ..kernels import basic as bk
from ..kernels import canon
from ..kernels.sort import sort_permutation, stable_sort_rows
from ..obs import trace as _obs_trace


@dataclasses.dataclass
class SplitBatch:
    """A batch sorted by partition id + per-partition row ranges."""
    batch: ColumnarBatch
    offsets: np.ndarray  # [num_parts + 1] host row offsets

    def partition_slice(self, pid: int) -> Optional[ColumnarBatch]:
        lo, hi = int(self.offsets[pid]), int(self.offsets[pid + 1])
        if hi <= lo:
            return None
        return self.batch.slice(lo, hi - lo)


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnums=(2,))
def partition_sort_counts(pids, num_rows, num_partitions: int):
    """One program: stable u32 sort by partition id (rows past num_rows
    to the end) + per-partition counts via searchsorted boundaries."""
    cap = pids.shape[0]
    in_range = jnp.arange(cap) < num_rows
    sort_key = jnp.where(in_range, pids, jnp.uint32(num_partitions))
    sk, perm = stable_sort_rows(sort_key)
    bounds = jnp.searchsorted(
        sk, jnp.arange(num_partitions + 1, dtype=jnp.uint32), side="left")
    return perm, jnp.diff(bounds)


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnums=(1,))
def partition_hash_ids(word_lists, num_partitions: int):
    """murmur-mix + mod over the key words -> partition id per row, as
    one program (XLA fuses the elementwise chain; Mosaic does not lower
    the 64-bit lanes a hand-written kernel would need)."""
    return bk.hash_to_partition(bk.hash_words(list(word_lists)),
                                num_partitions)


class Partitioner:
    num_partitions: int = 1

    def partition_ids(self, batch: ColumnarBatch) -> jnp.ndarray:
        raise NotImplementedError

    def split_staged(self, batch: ColumnarBatch):
        """Device half of the split: sort by partition id + boundary
        counts.  No host sync — callers stage many batches, then
        finalize them together so one queue drain covers all.

        TPU notes: partition ids always fit u32, so the pair sort runs
        the cheap 32-bit kernel, and counts come from binary search over
        the sorted ids instead of a scatter (TPU scatters are ~15x the
        cost of a searchsorted at shuffle sizes)."""
        pids = self.partition_ids(batch)
        perm, counts = partition_sort_counts(
            pids.astype(jnp.uint32), batch.rows_dev, self.num_partitions)
        sorted_batch = batch.gather(perm, batch.rows_lazy)
        return sorted_batch, LazyArray(counts)

    @staticmethod
    def finalize_split(sorted_batch: ColumnarBatch, counts) -> SplitBatch:
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="shuffle_fit"):
            counts = counts.np if isinstance(counts, LazyArray) \
                else np.asarray(counts)
        offsets = np.zeros(len(counts) + 1, dtype=np.int64)
        offsets[1:] = np.cumsum(counts)
        return SplitBatch(sorted_batch, offsets)

    def split(self, batch: ColumnarBatch) -> SplitBatch:
        """Stable-sort the batch by partition id; contiguous-split analogue."""
        return self.finalize_split(*self.split_staged(batch))


class SinglePartitioner(Partitioner):
    num_partitions = 1

    def partition_ids(self, batch):
        return jnp.zeros(batch.capacity, jnp.int32)


class HashPartitioner(Partitioner):
    """murmur-style hash of key columns mod n (GpuHashPartitioning role)."""

    _SPLIT_JIT: dict = {}

    def __init__(self, key_exprs: List[ec.Expression], num_partitions: int,
                 schema=None):
        self.key_exprs = key_exprs
        self.num_partitions = num_partitions
        self._schema = schema

    def partition_ids(self, batch):
        word_lists = []
        for e in self.key_exprs:
            bound = e.bind(batch.schema)
            col = ec.eval_as_column(bound, batch)
            nr = batch.num_rows if isinstance(col, StringColumn) \
                else batch.rows_dev
            for w in canon.value_words(col, nr):
                word_lists.append(jnp.where(col.validity, w,
                                            jnp.uint64(0x9E3779B97F4A7C15)))
        return partition_hash_ids(tuple(word_lists), self.num_partitions)

    def split_staged(self, batch: ColumnarBatch):
        """Whole split (key eval + hash + sort + counts + gather of every
        column) as ONE jitted program for plain fixed-width batches:
        one dispatch instead of one per eager op."""
        from ..exec.fused import _TracedBatch, _tree_fusable, expr_signature
        if not batch.columns or \
                not all(type(c) is Column for c in batch.columns):
            return super().split_staged(batch)
        try:
            bound = [e.bind(batch.schema) for e in self.key_exprs]
        except KeyError:
            return super().split_staged(batch)
        if not all(_tree_fusable(e) for e in bound):
            return super().split_staged(batch)
        sigs = tuple(expr_signature(e) for e in bound)
        if any(s is None for s in sigs):
            return super().split_staged(batch)
        key = (sigs, tuple(f.dtype.name for f in batch.schema),
               self.num_partitions)
        fn = HashPartitioner._SPLIT_JIT.get(key)
        if fn is None:
            schema = batch.schema
            nparts = self.num_partitions

            def _prog(datas, valids, num_rows):
                cap = datas[0].shape[0]
                cols = [Column(f.dtype, d, v)
                        for f, d, v in zip(schema, datas, valids)]
                b = _TracedBatch(schema, cols, num_rows, cap)
                word_lists = []
                for e in bound:
                    col = ec.eval_as_column(e, b)
                    for w in canon.value_words(col, num_rows):
                        word_lists.append(jnp.where(
                            col.validity, w,
                            jnp.uint64(0x9E3779B97F4A7C15)))
                # plain jnp mixing chain: inside this jit XLA fuses it
                # with the sort-key build
                h = bk.hash_words(word_lists)
                pids = (h % jnp.uint64(nparts)).astype(jnp.int32)
                in_range = jnp.arange(cap) < num_rows
                sort_key = jnp.where(in_range, pids.astype(jnp.uint32),
                                     jnp.uint32(nparts))
                sk, perm = stable_sort_rows(sort_key)
                bounds = jnp.searchsorted(
                    sk, jnp.arange(nparts + 1, dtype=jnp.uint32),
                    side="left")
                pairs = [(jnp.take(d, perm, axis=0, mode="clip"),
                          jnp.take(v, perm, axis=0, mode="clip"))
                         for d, v in zip(datas, valids)]
                return pairs, jnp.diff(bounds)
            from ..obs import compile_watch as _cw
            fn = _cw.wrap_miss("partition_split",
                               _cw.jit(_prog, "partition_split"), key)
            if len(HashPartitioner._SPLIT_JIT) < 4096:
                HashPartitioner._SPLIT_JIT[key] = fn
        # a failure here is a compile or device error to fix, not a
        # mode: it propagates instead of pinning the eager path
        pairs, counts = fn(tuple(c.data for c in batch.columns),
                           tuple(c.validity for c in batch.columns),
                           batch.rows_dev)
        cols = [Column(c.dtype, d, v)
                for c, (d, v) in zip(batch.columns, pairs)]
        sorted_batch = ColumnarBatch(batch.schema, cols, batch.rows_lazy)
        return sorted_batch, LazyArray(counts)


class RoundRobinPartitioner(Partitioner):
    def __init__(self, num_partitions: int, start: int = 0):
        self.num_partitions = num_partitions
        self.start = start

    def partition_ids(self, batch):
        return ((jnp.arange(batch.capacity, dtype=jnp.int64) + self.start)
                % self.num_partitions).astype(jnp.int32)


class RangePartitioner(Partitioner):
    """Sample-based range partitioning for global sort.

    Reference: GpuRangePartitioner.scala + SamplingUtils.scala — sample
    rows, sort the sample, pick n-1 bound rows, then binary-search each
    row against the bounds.  Bounds here are canonical key words.
    """

    def __init__(self, orders, num_partitions: int):
        self.orders = orders
        self.num_partitions = num_partitions
        self.bound_words: Optional[List[np.ndarray]] = None

    def _order_words(self, batch: ColumnarBatch, str_words=None):
        cols = [ec.eval_as_column(o.expr.bind(batch.schema), batch)
                for o in self.orders]
        sw = str_words or [None] * len(cols)
        return canon.batch_key_words(
            cols, batch.num_rows,
            descending=[not o.ascending for o in self.orders],
            nulls_last=[not o.effective_nulls_first for o in self.orders],
            str_words=sw), cols

    def fit(self, sample_batches: Sequence[ColumnarBatch],
            sample_limit: int = 1 << 16):
        """Compute partition bounds from sample batches (host-side pick)."""
        all_words: Optional[List[np.ndarray]] = None
        rows = 0
        # unify string widths across samples
        from ..kernels import strings as skern
        ncols = len(self.orders)
        self._str_words = [None] * ncols
        col_sets = []
        for b in sample_batches:
            cols = [ec.eval_as_column(o.expr.bind(b.schema), b)
                    for o in self.orders]
            col_sets.append((b, cols))
            for i, c in enumerate(cols):
                if isinstance(c, StringColumn):
                    w = skern.needed_key_words(c, b.num_rows)
                    self._str_words[i] = max(self._str_words[i] or 1, w)
        from ..analysis import residency  # lazy: avoids import cycle
        acc: List[List[np.ndarray]] = []
        with residency.declared_transfer(site="shuffle_fit"):
            for b, cols in col_sets:
                words = canon.batch_key_words(
                    cols, b.num_rows,
                    descending=[not o.ascending for o in self.orders],
                    nulls_last=[not o.effective_nulls_first
                                for o in self.orders],
                    str_words=self._str_words)
                acc.append([np.asarray(w)[:b.num_rows] for w in words])
                rows += b.num_rows
        if rows == 0:
            self.bound_words = None
            return
        merged = [np.concatenate([a[i] for a in acc])
                  for i in range(len(acc[0]))]
        if rows > sample_limit:
            sel = np.random.RandomState(0).choice(rows, sample_limit,
                                                  replace=False)
            merged = [m[sel] for m in merged]
            rows = sample_limit
        order = np.lexsort(tuple(reversed(merged)))
        qpos = [int(rows * (i + 1) / self.num_partitions)
                for i in range(self.num_partitions - 1)]
        qpos = [min(q, rows - 1) for q in qpos]
        self.bound_words = [m[order][qpos] for m in merged]

    def partition_ids(self, batch):
        if self.bound_words is None:
            return jnp.zeros(batch.capacity, jnp.int32)
        words, _ = self._order_words(batch, getattr(self, "_str_words", None))
        bounds = [jnp.asarray(b) for b in self.bound_words]
        # partition id = count of bounds <= row  (vectorized lexicographic)
        pid = jnp.zeros(batch.capacity, jnp.int32)
        for bi in range(self.num_partitions - 1):
            idx_b = jnp.full(batch.capacity, bi)
            # bound < row  => row goes to a later partition
            blt = canon.words_less(bounds, idx_b, words,
                                   jnp.arange(batch.capacity))
            beq = ~blt & ~canon.words_less(words, jnp.arange(batch.capacity),
                                           bounds, idx_b)
            pid = pid + (blt | beq).astype(jnp.int32)
        return jnp.clip(pid, 0, self.num_partitions - 1)
