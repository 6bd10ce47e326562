"""Aggregate function expressions — reference analogue: AggregateFunctions.scala

(GpuMin/GpuMax/GpuSum/GpuCount/GpuAverage/GpuFirst/GpuLast/CollectList/
CollectSet/PivotFirst) with the partial/merge/final projection model of
GpuHashAggregateExec (aggregate.scala:240).

Each AggregateFunction declares:
- update: how a partial value is computed from input rows within a batch
  (via the sort+segment kernels)
- merge: how partials combine across batches/partitions
- final dtype and finalization (e.g. Average = sum/count)
The exec layer (exec/aggregate.py) drives these against GroupPlan segments.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column
from ..kernels import aggregate as agg_k
from .core import Expression


class AggregateFunction(Expression):
    """Base for aggregate expressions. children[0] is the input (if any)."""

    def __init__(self, child: Optional[Expression] = None):
        self.children = [child] if child is not None else []

    def with_children(self, c):
        clone = type(self)(c[0]) if c else type(self)()
        if getattr(self, "_distinct", False):
            # DISTINCT marker set by the API layer (functions.count_distinct)
            clone._distinct = True
        return clone

    # number of internal buffer columns for partial aggregation
    @property
    def num_buffers(self) -> int:
        return 1

    def buffer_dtypes(self) -> List[T.DType]:
        return [self.dtype()]

    def update(self, plan: agg_k.GroupPlan, cols: List[Column]):
        """Compute partial buffers from input columns (one per child)."""
        raise NotImplementedError

    def merge(self, plan: agg_k.GroupPlan, buffers: List[Column]):
        """Merge partial buffers grouped by the same keys."""
        raise NotImplementedError

    def finalize(self, buffers: List[Column]) -> Column:
        return buffers[0]

    def float_sum_cols(self, cols: List[Column]) -> List[Column]:
        """The inputs (``update``) or buffers (``merge``) this function
        reduces with a float64 ``agg_k.seg_sum``: the fused cores sum
        them all in one pass (``agg_k.stack_float_sums``)."""
        return []

    def columnar_eval(self, batch):
        raise AssertionError(
            f"{self.name} must be evaluated by an aggregate exec")


def _col_of(data, valid, dt):
    return Column(dt, data.astype(dt.np_dtype), valid)


def _is_b64(c) -> bool:
    from ..columnar.binary64 import Binary64Column
    return isinstance(c, Binary64Column)


def _b64_seg_sum(plan, c):
    """Exact DOUBLE segment sum: windowed integer superaccumulator over
    the plan's sorted order (kernels/binary64.segmented_sum)."""
    from ..kernels import binary64 as b64
    from ..columnar.binary64 import Binary64Column
    v, ok = agg_k._sorted_vals(plan, c.data, c.validity)
    s = b64.segmented_sum(v, ok, plan.seg_id, plan.num_slots,
                          head_pos=plan.head_pos, last_pos=plan.last_pos,
                          num_groups=plan.num_groups)
    cnt = agg_k.seg_count(plan, c.validity)
    return Binary64Column(s, cnt > 0), cnt


def _b64_seg_minmax(plan, c, want_max: bool):
    """Exact DOUBLE min/max via the total-order word (Spark order: NaN
    greatest, -0.0 == 0.0); reduced with two 32-bit scatter passes
    (agg_k.seg_minmax_u64 — no slow 64-bit scatter)."""
    from ..kernels import binary64 as b64
    from ..columnar.binary64 import Binary64Column
    v, ok = agg_k._sorted_vals(plan, c.data, c.validity)
    w = b64.order_word(v)
    m = agg_k.seg_minmax_u64(plan, w, ok, want_max=want_max)
    cnt = agg_k.seg_count(plan, c.validity)
    return Binary64Column(b64.word_to_bits(m), cnt > 0), cnt


class Sum(AggregateFunction):
    def dtype(self):
        ct = self.children[0].dtype()
        if ct.is_integral:
            return T.INT64
        if isinstance(ct, T.DecimalType):
            return T.DecimalType(min(ct.precision + 10, 18), ct.scale)
        return T.FLOAT64

    def float_sum_cols(self, cols):
        return cols[:1] if self.dtype() == T.FLOAT64 else []

    def update(self, plan, cols):
        c = cols[0]
        if _is_b64(c):
            col, _cnt = _b64_seg_sum(plan, c)
            return [col]
        out_t = self.dtype()
        s = agg_k.seg_sum(plan, c.data, c.validity,
                          out_dtype=out_t.np_dtype)
        cnt = agg_k.seg_count(plan, c.validity)
        return [_col_of(s, cnt > 0, out_t)]

    def merge(self, plan, buffers):
        b = buffers[0]
        if _is_b64(b):
            col, _cnt = _b64_seg_sum(plan, b)
            return [col]
        s = agg_k.seg_sum(plan, b.data, b.validity)
        cnt = agg_k.seg_count(plan, b.validity)
        return [_col_of(s, cnt > 0, self.dtype())]


class Count(AggregateFunction):
    """count(expr) or count(*) when child is None."""

    @property
    def nullable(self):
        return False

    def dtype(self):
        return T.INT64

    def update(self, plan, cols):
        if not self.children or cols[0] is None:
            cnt = agg_k.seg_count_all(plan)
        else:
            cnt = agg_k.seg_count(plan, cols[0].validity)
        ones = jnp.ones_like(cnt, dtype=bool)
        return [Column(T.INT64, cnt, ones)]

    def merge(self, plan, buffers):
        b = buffers[0]
        s = agg_k.seg_sum(plan, b.data, b.validity)
        return [Column(T.INT64, s, jnp.ones_like(s, dtype=bool))]


class Min(AggregateFunction):
    def dtype(self):
        return self.children[0].dtype()

    def update(self, plan, cols):
        c = cols[0]
        if _is_b64(c):
            col, _cnt = _b64_seg_minmax(plan, c, want_max=False)
            return [col]
        if c.dtype == T.STRING:
            idx, has = agg_k.seg_first_index_by_order(plan, c, want_min=True)
            return [c.gather(idx).mask_validity(has)]
        m = agg_k.seg_min(plan, c.data, c.validity)
        cnt = agg_k.seg_count(plan, c.validity)
        return [_col_of(m, cnt > 0, self.dtype())]

    merge = update


class Max(AggregateFunction):
    def dtype(self):
        return self.children[0].dtype()

    def update(self, plan, cols):
        c = cols[0]
        if _is_b64(c):
            col, _cnt = _b64_seg_minmax(plan, c, want_max=True)
            return [col]
        if c.dtype == T.STRING:
            idx, has = agg_k.seg_first_index_by_order(plan, c, want_min=False)
            return [c.gather(idx).mask_validity(has)]
        m = agg_k.seg_max(plan, c.data, c.validity)
        cnt = agg_k.seg_count(plan, c.validity)
        return [_col_of(m, cnt > 0, self.dtype())]

    merge = update


class Average(AggregateFunction):
    def dtype(self):
        return T.FLOAT64

    @property
    def num_buffers(self):
        return 2

    def buffer_dtypes(self):
        return [T.FLOAT64, T.INT64]

    def float_sum_cols(self, cols):
        return cols[:1]

    def update(self, plan, cols):
        c = cols[0]
        if _is_b64(c):
            from ..columnar.binary64 import Binary64Column
            col, cnt = _b64_seg_sum(plan, c)
            always = jnp.ones_like(cnt, dtype=bool)
            return [Binary64Column(col.data, always),
                    Column(T.INT64, cnt, always)]
        s = agg_k.seg_sum(plan, c.data, c.validity, out_dtype=jnp.float64)
        cnt = agg_k.seg_count(plan, c.validity)
        always = jnp.ones_like(cnt, dtype=bool)
        return [Column(T.FLOAT64, s, always), Column(T.INT64, cnt, always)]

    def merge(self, plan, buffers):
        if _is_b64(buffers[0]):
            from ..columnar.binary64 import Binary64Column
            col, _ = _b64_seg_sum(plan, buffers[0])
            cnt = agg_k.seg_sum(plan, buffers[1].data, buffers[1].validity)
            always = jnp.ones_like(cnt, dtype=bool)
            return [Binary64Column(col.data, always),
                    Column(T.INT64, cnt, always)]
        s = agg_k.seg_sum(plan, buffers[0].data, buffers[0].validity)
        cnt = agg_k.seg_sum(plan, buffers[1].data, buffers[1].validity)
        always = jnp.ones_like(cnt, dtype=bool)
        return [Column(T.FLOAT64, s, always), Column(T.INT64, cnt, always)]

    def finalize(self, buffers):
        if _is_b64(buffers[0]):
            from ..kernels import binary64 as b64
            from ..columnar.binary64 import Binary64Column
            cnt = buffers[1].data
            ok = cnt > 0
            avg = b64.div(buffers[0].data,
                          b64.from_i64(jnp.where(ok, cnt, 1)))
            return Binary64Column(avg, ok & buffers[0].validity)
        s, cnt = buffers[0].data, buffers[1].data
        ok = cnt > 0
        avg = s / jnp.where(ok, cnt, 1).astype(jnp.float64)
        return Column(T.FLOAT64, avg, ok & buffers[0].validity)


class First(AggregateFunction):
    def __init__(self, child=None, ignore_nulls: bool = True):
        super().__init__(child)
        self.ignore_nulls = ignore_nulls

    def with_children(self, c):
        return First(c[0], self.ignore_nulls)

    def dtype(self):
        return self.children[0].dtype()

    def update(self, plan, cols):
        c = cols[0]
        idx, has = agg_k.seg_first_index(plan, c.validity, self.ignore_nulls)
        out = c.gather(idx.astype(jnp.int32))
        return [out.mask_validity(has)]

    merge = update


class Last(AggregateFunction):
    def __init__(self, child=None, ignore_nulls: bool = True):
        super().__init__(child)
        self.ignore_nulls = ignore_nulls

    def with_children(self, c):
        return Last(c[0], self.ignore_nulls)

    def dtype(self):
        return self.children[0].dtype()

    def update(self, plan, cols):
        c = cols[0]
        idx, has = agg_k.seg_last_index(plan, c.validity, self.ignore_nulls)
        out = c.gather(idx.astype(jnp.int32))
        return [out.mask_validity(has)]

    merge = update


class CentralMoment(AggregateFunction):
    """Shared base for variance/stddev (sample + population).

    Reference: AggregateFunctions.scala GpuStddevSamp/GpuStddevPop/
    GpuVarianceSamp/GpuVariancePop (the M2 family).  Buffers are
    (count, mean, M2) — Welford form, NOT sum/sum-of-squares, because
    the naive sumsq - sum^2/n recovery is catastrophically
    cancellative (variance of [1e8+1, 1e8+2, 1e8+3] comes out 0.0 in
    f64; on the chip's ~48-bit emulated f64 the breakdown starts at
    means around 1e4).  update is a stable two-pass over the plan's
    segments (mean, then squared deltas); merge combines partials with
    the delta formula M2 = sum(M2_i) + sum(n_i * (mean_i - mean)^2).
    """

    #: ddof: 1 for sample, 0 for population
    ddof = 1
    #: take sqrt at finalize (stddev) or not (variance)
    sqrt = False

    def dtype(self):
        return T.FLOAT64

    @property
    def num_buffers(self):
        return 3

    def buffer_dtypes(self):
        return [T.INT64, T.FLOAT64, T.FLOAT64]

    def update(self, plan, cols):
        c = cols[0]
        x, ok = agg_k._sorted_vals(plan, c.data, c.validity)
        x = x.astype(jnp.float64)
        cnt = agg_k.seg_reduce(plan, ok.astype(jnp.int64), "sum")
        s = agg_k.seg_reduce(plan, jnp.where(ok, x, 0.0), "sum")
        mean = s / jnp.maximum(cnt, 1).astype(jnp.float64)
        delta = x - agg_k.seg_spread(plan, mean)
        m2 = agg_k.seg_reduce(plan, jnp.where(ok, delta * delta, 0.0),
                              "sum")
        always = jnp.ones_like(cnt, dtype=bool)
        return [Column(T.INT64, cnt, always),
                Column(T.FLOAT64, mean, always),
                Column(T.FLOAT64, m2, always)]

    def merge(self, plan, buffers):
        cap = plan.num_slots
        n_i, ok = agg_k._sorted_vals(plan, buffers[0].data,
                                     buffers[0].validity)
        n_i = n_i.astype(jnp.float64)
        mean_i, _ = agg_k._sorted_vals(plan, buffers[1].data,
                                       buffers[1].validity)
        m2_i, _ = agg_k._sorted_vals(plan, buffers[2].data,
                                     buffers[2].validity)
        n_i = jnp.where(ok, n_i, 0.0)
        n = agg_k.seg_reduce(plan, n_i, "sum")
        wsum = agg_k.seg_reduce(plan, n_i * mean_i, "sum")
        mean = wsum / jnp.maximum(n, 1.0)
        delta = mean_i - agg_k.seg_spread(plan, mean)
        m2 = agg_k.seg_reduce(
            plan, jnp.where(ok, m2_i + n_i * delta * delta, 0.0), "sum")
        always = jnp.ones(cap, dtype=bool)
        return [Column(T.INT64, n.astype(jnp.int64), always),
                Column(T.FLOAT64, mean, always),
                Column(T.FLOAT64, m2, always)]

    def finalize(self, buffers):
        n = buffers[0].data.astype(jnp.float64)
        m2 = buffers[2].data
        ok = n > self.ddof
        denom = jnp.where(ok, n - self.ddof, 1.0)
        v = jnp.maximum(m2, 0.0) / denom
        if self.sqrt:
            v = jnp.sqrt(v)
        return Column(T.FLOAT64, v, ok)


class VarianceSamp(CentralMoment):
    ddof, sqrt = 1, False


class VariancePop(CentralMoment):
    ddof, sqrt = 0, False


class StddevSamp(CentralMoment):
    ddof, sqrt = 1, True


class StddevPop(CentralMoment):
    ddof, sqrt = 0, True


class PivotFirst(AggregateFunction):
    """Internal pivot aggregate (reference: PivotFirst in
    AggregateFunctions.scala) — the API layer lowers
    ``group_by().pivot(col, values).agg(f(x))`` to one conditional
    aggregate per pivot value (``f(when(col == v, x))``), so this class
    exists for the rule registry/docs; the rewrite path never
    instantiates it on device."""

    def __init__(self, pivot: Optional[Expression] = None,
                 value: Optional[Expression] = None,
                 pivot_values: Optional[list] = None):
        self.children = [e for e in (pivot, value) if e is not None]
        self.pivot_values = list(pivot_values or [])

    def with_children(self, c):
        return PivotFirst(c[0] if c else None,
                          c[1] if len(c) > 1 else None,
                          self.pivot_values)

    def dtype(self):
        return self.children[1].dtype() if len(self.children) > 1 \
            else T.NULL


def _collect_update(plan, c):
    """Device collect_list core: the group plan's stable key sort makes
    each group's rows CONTIGUOUS in sorted order, so the list column is
    just (compacted sorted values, per-group count offsets) — no
    per-group loop, all static shapes.  Nulls drop (Spark collect_list
    semantics); within-group order is input order (stable sort)."""
    from ..columnar.column import ListColumn
    from ..kernels.basic import filter_compact_indices
    cap = c.capacity
    keep = jnp.take(c.validity, plan.perm) & plan.live_sorted
    order2, _n = filter_compact_indices(keep, cap)
    take2 = jnp.take(plan.perm, order2)
    elems = c.gather(take2).mask_validity(jnp.take(keep, order2))
    cnt = jax.ops.segment_sum(keep.astype(jnp.int32), plan.seg_id,
                              num_segments=cap)
    ends = jnp.cumsum(cnt)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               ends.astype(jnp.int32)])
    valid = jnp.arange(cap) < plan.num_groups
    return ListColumn(T.ArrayType(c.dtype), offsets, elems, valid)


def _collect_merge(plan, b):
    """Merge partial lists: gather partial rows into group-sorted order
    (elements re-concatenate contiguously), then per-group offsets are
    segment sums of row lengths."""
    from ..columnar.column import ListColumn
    cap = b.capacity
    g = b.gather(plan.perm)          # contiguous rebuild, invalid len 0
    mask = plan.live_sorted & jnp.take(b.validity, plan.perm)
    lens = (g.offsets[1:] - g.offsets[:-1]).astype(jnp.int32)
    lens = jnp.where(mask, lens, 0)
    cnt = jax.ops.segment_sum(lens, plan.seg_id, num_segments=cap)
    ends = jnp.cumsum(cnt)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               ends.astype(jnp.int32)])
    valid = jnp.arange(cap) < plan.num_groups
    return ListColumn(b.dtype, offsets, g.elements, valid)


class CollectList(AggregateFunction):
    """collect_list on device (reference: GpuCollectList,
    AggregateFunctions.scala) — the sort+segment plan gives group
    contiguity for free, so lists assemble with pure gathers/cumsums
    (strings included via StringColumn.gather; nested elements keep
    the CPU engine)."""

    def dtype(self):
        return T.ArrayType(self.children[0].dtype())

    def update(self, plan, cols):
        return [_collect_update(plan, cols[0])]

    def merge(self, plan, buffers):
        return [_collect_merge(plan, buffers[0])]


class CollectSet(AggregateFunction):
    """collect_set on device: collect_list plus per-group value dedupe
    via canonical value words (fixed-width single-word elements; others
    stay on the CPU engine)."""

    def dtype(self):
        return T.ArrayType(self.children[0].dtype())

    def _dedupe(self, plan, lst):
        from ..columnar.column import ListColumn
        from ..kernels import canon
        cap = lst.capacity
        ecap = lst.elements.capacity
        # element -> group id via offsets
        pos = jnp.arange(ecap)
        grp = jnp.clip(
            jnp.searchsorted(lst.offsets[1:cap + 1], pos, side="right"),
            0, cap - 1).astype(jnp.int32)
        live = pos < lst.offsets[cap]
        words = canon.value_words(lst.elements, ecap)[0]
        # VALUE equality, not ordering equality: the canonical order
        # word conflates -0.0 with 0.0 (Spark total order), but
        # collect_set's java-equality semantics keep them distinct, so
        # a zero-sign word disambiguates for fractional elements
        if lst.dtype.element_type.is_fractional:
            zsign = (jnp.signbit(lst.elements.data) &
                     (lst.elements.data == 0)).astype(jnp.uint64)
        else:
            zsign = jnp.zeros(ecap, jnp.uint64)
        # sort by (live desc, group, value) then mark first-of-run
        rank = jnp.where(live, jnp.uint64(0), jnp.uint64(1))
        _, _, _, _, perm = jax.lax.sort(
            (rank, grp.astype(jnp.uint64), words, zsign,
             pos.astype(jnp.int32)), num_keys=4, is_stable=True)
        sg = jnp.take(grp, perm)
        sw = jnp.take(words, perm)
        sz = jnp.take(zsign, perm)
        slive = jnp.take(live, perm)
        first = jnp.concatenate([
            jnp.ones(1, bool),
            (sg[1:] != sg[:-1]) | (sw[1:] != sw[:-1]) |
            (sz[1:] != sz[:-1])]) & slive
        # compact kept elements
        from ..kernels.basic import filter_compact_indices
        korder, _n = filter_compact_indices(first, first.shape[0])
        ktake = jnp.take(perm, korder)
        elems = lst.elements.gather(ktake).mask_validity(
            jnp.take(first, korder))
        cnt = jax.ops.segment_sum(first.astype(jnp.int32), sg,
                                  num_segments=cap)
        ends = jnp.cumsum(cnt)
        offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   ends.astype(jnp.int32)])
        return ListColumn(lst.dtype, offsets, elems, lst.validity)

    def update(self, plan, cols):
        return [self._dedupe(plan, _collect_update(plan, cols[0]))]

    def merge(self, plan, buffers):
        return [self._dedupe(plan, _collect_merge(plan, buffers[0]))]
