"""TPU window operator.

Reference: GpuWindowExec.scala:338 + GpuWindowExpression.scala (cuDF
rolling/scan windows, running-window optimization for row_number etc.).

TPU-first: one sort by (partition keys, order keys) per spec, shared by
every function over that spec, then each function is a scan over the
sorted runs (``kernels/window.py``).  The device work runs as named
programs, one a role, keyed by capacities, dtypes and the spec's static
shape, never by a row count:

  window_plan         key words -> sort -> partition and peer runs,
                      their first and last positions, the inverse
                      permutation (one scatter)
  window_rank         row_number / rank / dense_rank / ntile /
                      percent_rank / cume_dist
  window_part_agg     sum / count / avg / min / max over the whole
                      partition, broadcast back
  window_frame        the same over a ROWS or RANGE frame (prefix sums
                      with partition clamping, segmented running
                      min/max, a sparse table for bounded min/max)
  window_frame_bounds a RANGE frame's first and last positions
  window_shift        lead / lag: the source row of each row
  window_collect_plan collect_list: each row's element span

Results come back in the original row order (a gather by the inverse
permutation), so row identity is preserved for downstream operators.
What stays outside a program: evaluating the spec's expressions (plain
column references launch nothing), packing a STRING key's words
(``jit_str_pack_words``: the byte bound is host-known), lead / lag's
final gather of the source column (a string column gathers lazily) and
collect_list's element expansion, which is sized by a host pull.
"""
from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.schema import Field, Schema
from ..columnar.column import Column
from ..columnar.batch import (ColumnarBatch, concat_batches,
                              resolve_speculative)
from ..expr import core as ec
from ..expr import aggregates as eagg
from ..expr import window_funcs as wfn
from ..kernels import canon
from ..kernels import window as wk
from ..kernels.basic import prefix_sum
from ..obs import compile_watch as _compile_watch
from ..obs import trace as _trace
from ..obs.registry import compile_cache_event
from ..plan.logical import Window, WindowFunc
from .base import PhysicalPlan, OP_TIME, NUM_OUTPUT_ROWS, timed
from .tpu_basic import TpuExec

_RANK_KINDS = {wfn.RowNumber: "row_number", wfn.Rank: "rank",
               wfn.DenseRank: "dense_rank", wfn.NTile: "ntile",
               wfn.PercentRank: "percent_rank", wfn.CumeDist: "cume_dist"}
_AGG_KINDS = {eagg.Sum: "sum", eagg.Count: "count", eagg.Average: "avg",
              eagg.Min: "min", eagg.Max: "max"}


class _Spec:
    """One (partition, order) sort of one batch: the key columns as
    ``window_plan`` takes them (a STRING key as its packed words) and
    the sorted partitions every function over the spec shares.
    ``nbytes``: the device bytes of the key columns, from capacities and
    dtypes alone (``Column.nbytes``: data and validity; a string column
    by its byte buffer and offsets; a lazy string gather by its index
    map and the source column it reads)."""

    def __init__(self, batch: ColumnarBatch, spec):
        from .tpu_aggregate import _pack_string_key
        schema = batch.schema
        self.pcols = [ec.eval_as_column(e.bind(schema), batch)
                      for e in spec.partition_by]
        self.ocols = [ec.eval_as_column(o.expr.bind(schema), batch)
                      for o in spec.order_by]
        self.descending = tuple(not o.ascending for o in spec.order_by)
        self.nulls_last = tuple(not o.effective_nulls_first
                                for o in spec.order_by)
        arrays, dts = [], []
        for c in self.pcols + self.ocols:
            if c.dtype == T.STRING:
                packed, bound = _pack_string_key(c, batch.rows_dev)
                arrays.append(packed)
                dts.append((c.dtype, bound))
            else:
                if type(c) is not Column:
                    raise NotImplementedError(
                        f"window key of type {type(c).__name__}")
                arrays.append((c.data, c.validity))
                dts.append(c.dtype)
        self.key_arrays = tuple(arrays)
        self.key_dts = tuple(dts)
        self.nbytes = sum(c.nbytes() for c in self.pcols + self.ocols)
        self.parts: Optional[wk.SortedPartitions] = None


class TpuWindow(TpuExec):
    # class-level jit cache, keyed by everything a traced closure
    # captures: plans are rebuilt per query, the programs outlive them
    _PROGRAMS: dict = {}

    def __init__(self, logical: Window, child: PhysicalPlan):
        super().__init__(child)
        self.logical = logical

    @property
    def output_schema(self):
        return self.logical.schema

    def _node_string(self):
        return f"TpuWindow[{[w.alias for w in self.logical.window_funcs]}]"

    def execute(self):
        def run(part):
            batches = [resolve_speculative(b) for b in part]
            if not batches:
                return
            batch = concat_batches(batches) if len(batches) > 1 else \
                batches[0]
            _trace.count("window.batches")
            _trace.count("window.rows", batch.capacity)
            with timed(self.metrics[OP_TIME], self):
                out = self._apply(batch)
            self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
            yield out
        return [run(p) for p in self.children[0].execute()]

    # ------------------------------------------------------------------
    @staticmethod
    def _program(key, build):
        """The jitted program for ``key`` (role first), built on a miss
        behind ``wrap_miss``."""
        cache = TpuWindow._PROGRAMS
        fn = cache.get(key)
        compile_cache_event("window", fn is not None)
        if fn is None:
            fn = cache[key] = _compile_watch.wrap_miss(
                "window", build(), str(key))
        return fn

    def _apply(self, batch: ColumnarBatch) -> ColumnarBatch:
        from .fused import expr_signature
        new_cols: List[Column] = list(batch.columns)
        fields = list(batch.schema.fields)
        specs = {}
        for wf in self.logical.window_funcs:
            s = wf.spec
            sig = (tuple(expr_signature(e) for e in s.partition_by),
                   tuple((expr_signature(o.expr), o.ascending,
                          o.effective_nulls_first) for o in s.order_by))
            if any(x is None for x in sig[0]) or \
                    any(x[0] is None for x in sig[1]):
                sig = id(wf)            # opaque expressions: no sharing
            spec = specs.get(sig)
            if spec is None:
                spec = specs[sig] = _Spec(batch, s)
                self._sort(spec, batch)
            _trace.count("window.funcs")
            col = self._eval_window(batch, wf, spec)
            _trace.count("window.bytes", col.nbytes())
            new_cols.append(col)
            fields.append(Field(wf.alias, col.dtype, True))
        return ColumnarBatch(Schema(fields), new_cols, batch.rows_lazy)

    def _sort(self, spec: _Spec, batch: ColumnarBatch) -> None:
        key_dts, npart = spec.key_dts, len(spec.pcols)
        descending, nulls_last = spec.descending, spec.nulls_last
        # a spec with no key at all has nothing to read its capacity from
        cap = None if key_dts else batch.capacity

        def _plan(key_arrays, num_rows):
            kcols = [canon.PackedStringKey(d, v, dt[1])
                     if isinstance(dt, tuple) else Column(dt, d, v)
                     for dt, (d, v) in zip(key_dts, key_arrays)]
            return wk.sorted_partitions(
                kcols[:npart], kcols[npart:], num_rows, list(descending),
                list(nulls_last),
                cap if cap is not None else kcols[0].capacity)

        fn = self._program(
            ("plan", key_dts, npart, descending, nulls_last, cap),
            lambda: _compile_watch.jit(_plan, "window_plan"))
        _trace.count("window.specs")
        _trace.count("window.bytes", spec.nbytes)
        spec.parts = fn(spec.key_arrays, batch.rows_dev)

    # ------------------------------------------------------------------
    def _eval_window(self, batch: ColumnarBatch, wf: WindowFunc,
                     spec: _Spec) -> Column:
        func = wf.func
        kind = _RANK_KINDS.get(type(func))
        if kind is not None:
            return self._rank(batch, func, kind, spec)
        if isinstance(func, (wfn.Lead, wfn.Lag)):
            return self._shift(batch, func, spec)
        if isinstance(func, eagg.CollectList):
            return self._collect(batch, func, wf.spec, spec)
        if isinstance(func, eagg.AggregateFunction):
            return self._aggregate(batch, func, wf.spec, spec)
        raise NotImplementedError(f"window function {func.name}")

    def _rank(self, batch, func, kind: str, spec: _Spec) -> Column:
        out_dtype = func.dtype()
        buckets = func.n if kind == "ntile" else 0
        np_dtype = out_dtype.np_dtype

        def _rank(p, num_rows):
            vals = wk.ranking(p, kind, buckets)
            return (jnp.take(vals, p.inv).astype(np_dtype),
                    jnp.arange(p.inv.shape[0]) < num_rows)

        fn = self._program(("rank", kind, buckets, out_dtype.name),
                           lambda: _compile_watch.jit(_rank, "window_rank"))
        return Column(out_dtype, *fn(spec.parts, batch.rows_dev))

    def _shift(self, batch, func, spec: _Spec) -> Column:
        src = ec.eval_as_column(func.children[0].bind(batch.schema), batch)
        _trace.count("window.bytes", src.nbytes())
        off = func.offset if isinstance(func, wfn.Lead) else -func.offset

        def _shift(p, num_rows):
            cap = p.perm.shape[0]
            pos = jnp.arange(cap, dtype=jnp.int32)
            at = pos + off
            sp = jnp.clip(at, 0, cap - 1)
            same = (at >= 0) & (at < cap) & p.live & jnp.take(p.live, sp) \
                & (jnp.take(p.seg_start, sp) == p.seg_start)
            return (jnp.take(jnp.take(p.perm, sp), p.inv),
                    jnp.take(same, p.inv) & (pos < num_rows))

        fn = self._program(("shift", off),
                           lambda: _compile_watch.jit(_shift, "window_shift"))
        idx, ok = fn(spec.parts, batch.rows_dev)
        out = src.gather(idx)
        return out.mask_validity(ok)

    # ------------------------------------------------------------------
    def _aggregate(self, batch, func, wspec, spec: _Spec) -> Column:
        kind = _AGG_KINDS.get(type(func))
        if kind is None:
            raise NotImplementedError(f"window aggregate {func.name}")
        child = func.children[0] if func.children else None
        if child is not None:
            src = ec.eval_as_column(child.bind(batch.schema), batch)
            if type(src) is not Column:
                raise NotImplementedError("string window aggregates")
            _trace.count("window.bytes", src.nbytes())
            value = (src.data, src.validity)
        else:
            value = None                    # count(*): every live row
        frame_kind, frame_lo, frame_hi = wspec.frame
        out_dtype = func.dtype()
        np_dtype = out_dtype.np_dtype
        fractional = bool(out_dtype.is_fractional)

        def sorted_value(p, value):
            if value is None:
                return jnp.ones(p.perm.shape[0], jnp.int64), p.live
            data, valid = value
            return jnp.take(data, p.perm), jnp.take(valid, p.perm) & p.live

        if (frame_lo is None and frame_hi is None) or not wspec.order_by:
            def _part_agg(p, value, num_rows):
                sv, sok = sorted_value(p, value)
                vals, ok = wk.partition_aggregate(p, kind, sv, sok,
                                                  fractional)
                # each row reads its partition's last sorted row
                at = jnp.take(p.seg_end, p.inv)
                return (jnp.take(vals, at).astype(np_dtype),
                        jnp.take(ok, at)
                        & (jnp.arange(at.shape[0]) < num_rows))

            fn = self._program(
                ("part_agg", kind, fractional, out_dtype.name),
                lambda: _compile_watch.jit(_part_agg, "window_part_agg"))
            return Column(out_dtype, *fn(spec.parts, value, batch.rows_dev))

        bounds, levels = None, 0
        if frame_kind == "range":
            frame_lo, frame_hi, bounds = self._range_bounds(
                batch, wspec, spec, frame_lo, frame_hi)
            if kind in ("min", "max") and frame_lo is not None \
                    and frame_hi is not None:
                # a bounded RANGE frame's widest window sizes the sparse
                # table: one host pull
                from ..analysis import residency  # lazy: import cycle
                with residency.declared_transfer(site="size_probe"):
                    widest = int(jnp.max(bounds[1] - bounds[0] + 1))
                levels = max(widest, 1)
        elif kind in ("min", "max") and frame_lo is not None \
                and frame_hi is not None:
            levels = max(frame_hi - frame_lo + 1, 1)
        # the sparse table's depth, not the width, shapes the program
        levels = max(levels - 1, 0).bit_length()
        lo_unb, hi_unb = frame_lo is None, frame_hi is None
        explicit = bounds is not None
        lo = None if explicit else frame_lo
        hi = None if explicit else frame_hi

        def _frame(p, value, bounds, num_rows):
            sv, sok = sorted_value(p, value)
            vals, ok = _frame_aggregate(p, kind, sv, sok, lo, hi, bounds,
                                        lo_unb, hi_unb, levels)
            return (jnp.take(vals, p.inv).astype(np_dtype),
                    jnp.take(ok & p.live, p.inv)
                    & (jnp.arange(p.inv.shape[0]) < num_rows))

        fn = self._program(
            ("frame", kind, lo, hi, explicit, lo_unb, hi_unb, levels,
             out_dtype.name),
            lambda: _compile_watch.jit(_frame, "window_frame"))
        return Column(out_dtype,
                      *fn(spec.parts, value, bounds, batch.rows_dev))

    def _range_bounds(self, batch, wspec, spec: _Spec, frame_lo, frame_hi):
        """A RANGE frame's offsets (scaled for a decimal order key) and
        its (first, last) sorted positions per sorted row."""
        order = wspec.order_by[0]
        odt = order.expr.dtype()
        if isinstance(odt, T.DecimalType):
            # decimal order key: data is unscaled int64, so literal
            # frame offsets scale by 10^scale (exact when the offset
            # has no more fractional digits than the key's scale)
            sf = 10 ** odt.scale
            frame_lo = None if frame_lo is None else \
                int(round(frame_lo * sf))
            frame_hi = None if frame_hi is None else \
                int(round(frame_hi * sf))
        ocol = spec.ocols[0]
        ascending, nulls_first = order.ascending, \
            order.effective_nulls_first

        def _bounds(p, odata, ovalid):
            return _range_positions(p, odata, ovalid, ascending,
                                    nulls_first, frame_lo, frame_hi)

        fn = self._program(
            ("frame_bounds", ascending, nulls_first, frame_lo, frame_hi),
            lambda: _compile_watch.jit(_bounds, "window_frame_bounds"))
        return frame_lo, frame_hi, fn(spec.parts, ocol.data, ocol.validity)

    # ------------------------------------------------------------------
    def _collect(self, batch, func, wspec, spec: _Spec) -> Column:
        """collect_list over a window frame -> ListColumn.

        Elements come from the globally valid-compacted sorted rows:
        row i's list is vpos[c_lo_i .. c_hi_i) where cnt is the prefix
        count of valid sorted rows: one running sum and one expand, no
        per-row loops (GpuWindowExpression collect_list role).  The
        spans are one program; the expansion is sized by a host pull."""
        from ..columnar.column import ListColumn, bucket_capacity
        from ..kernels import basic as bk
        from ..kernels import join as join_k
        cap = batch.capacity
        src = ec.eval_as_column(func.children[0].bind(batch.schema),
                                batch)
        _trace.count("window.bytes", src.nbytes())
        frame_kind, frame_lo, frame_hi = wspec.frame
        bounds = None
        if (frame_lo is None and frame_hi is None) or not wspec.order_by:
            frame_lo = frame_hi = None
        elif frame_kind == "range":
            frame_lo, frame_hi, bounds = self._range_bounds(
                batch, wspec, spec, frame_lo, frame_hi)
        explicit = bounds is not None

        def _collect_plan(p, valid, bounds, num_rows):
            cap = p.perm.shape[0]
            pos = jnp.arange(cap, dtype=jnp.int64)
            start, end = p.seg_start.astype(jnp.int64), \
                p.seg_end.astype(jnp.int64)
            if explicit:
                lo_pos, hi_pos = bounds
            else:
                lo_pos = start if frame_lo is None else \
                    jnp.maximum(pos + frame_lo, start)
                hi_pos = end if frame_hi is None else \
                    jnp.minimum(pos + frame_hi, end)
            ok = jnp.take(valid, p.perm) & p.live
            cnt = prefix_sum(ok.astype(jnp.int64))
            hi_c = jnp.clip(hi_pos, 0, cap - 1).astype(jnp.int32)
            lo_c = jnp.clip(lo_pos - 1, -1, cap - 1)
            c_hi = jnp.take(cnt, hi_c)
            c_lo = jnp.where(lo_c < 0, 0,
                             jnp.take(cnt, jnp.maximum(lo_c, 0)))
            m_sorted = jnp.where(hi_pos < lo_pos, 0, c_hi - c_lo)
            m_orig = jnp.where(jnp.arange(cap) < num_rows,
                               jnp.take(m_sorted, p.inv), 0)
            offsets = jnp.concatenate(
                [jnp.zeros(1, jnp.int64),
                 prefix_sum(m_orig)]).astype(jnp.int32)
            return (ok, jnp.take(c_lo, p.inv).astype(jnp.int32),
                    m_orig.astype(jnp.int32), offsets, jnp.sum(m_orig))

        fn = self._program(
            ("collect_plan", frame_lo, frame_hi, explicit),
            lambda: _compile_watch.jit(_collect_plan,
                                       "window_collect_plan"))
        ok, c_lo, m_orig, offsets, total = fn(
            spec.parts, src.validity, bounds, batch.rows_dev)
        sorted_src = src.gather(spec.parts.perm)
        vpos, _ = bk.filter_compact_indices(ok, cap)
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="size_probe"):
            total = int(total)
        out_cap = bucket_capacity(max(total, 1))
        _, elem_pos, live_e, _ = join_k.join_expand_matches(
            c_lo, m_orig, vpos.astype(jnp.int32), out_cap)
        elements = sorted_src.gather(elem_pos)
        elements = elements.mask_validity(live_e)
        return ListColumn(T.ArrayType(src.dtype), offsets, elements,
                          jnp.arange(cap) < batch.rows_dev)


# ---------------------------------------------------------------------------
# traced bodies of the frame programs
# ---------------------------------------------------------------------------

def _range_positions(p: wk.SortedPartitions, odata, ovalid, ascending: bool,
                     nulls_first: bool, frame_lo, frame_hi):
    """RANGE frame bounds as sorted-row positions via rank search.

    Reference: cuDF range-window support behind GpuWindowExec.  For
    each row with order value v the frame covers rows of its
    partition with value in [v+lo, v+hi] (direction-corrected for
    DESC).  Computed without per-row loops: encode values as
    order-preserving uint64 words, rank every row's word in the
    batch-wide sorted word array, and binary-search composite
    (segment, rank) keys — all vectorized searchsorted.
    """
    live = p.live
    vals_sorted = jnp.take(odata, p.perm).astype(jnp.int64)
    ovalid = jnp.take(ovalid, p.perm) & live

    def enc(x):
        w = canon._ints_to_words(x, 64)
        return ~w if not ascending else w

    words = jnp.where(ovalid, enc(vals_sorted),
                      jnp.uint64(0xFFFFFFFFFFFFFFFF))
    v_sorted = jnp.sort(words)
    lo_off = jnp.int64(0 if frame_lo is None else frame_lo)
    hi_off = jnp.int64(0 if frame_hi is None else frame_hi)
    if ascending:
        t1, t2 = vals_sorted + lo_off, vals_sorted + hi_off
    else:
        # DESC: "preceding" rows hold LARGER values, so the value
        # interval flips to [v - hi, v - lo] (Spark range semantics)
        t1, t2 = vals_sorted - hi_off, vals_sorted - lo_off
    e1 = enc(t1)
    e2 = enc(t2)
    wlo = jnp.minimum(e1, e2)
    whi = jnp.maximum(e1, e2)
    r_lo = jnp.searchsorted(v_sorted, wlo, side="left")
    r_hi = jnp.searchsorted(v_sorted, whi, side="right")
    # composite (seg, rank) keys: valid rows at 1+rank, null-order
    # rows pinned to the null end of their segment
    BIG = jnp.int64(1) << jnp.int64(33)
    null_slot = jnp.int64(0) if nulls_first else BIG - 1
    rank_row = jnp.where(
        ovalid,
        1 + jnp.searchsorted(v_sorted, words, side="left"), null_slot)
    # a partition's number: any value that grows with the partition
    seg64 = p.seg_start.astype(jnp.int64)
    C = seg64 * BIG + rank_row.astype(jnp.int64)
    # padding rows past num_rows sort AFTER every live row: pin their
    # composite to +inf or the searchsorted precondition breaks
    C = jnp.where(live, C, jnp.int64(2 ** 62))
    t_lo = jnp.where(ovalid, seg64 * BIG + 1 + r_lo,
                     seg64 * BIG + null_slot)
    t_hi = jnp.where(ovalid, seg64 * BIG + 1 + r_hi,
                     seg64 * BIG + null_slot + 1)
    lo_pos = jnp.searchsorted(C, t_lo, side="left")
    hi_pos = jnp.searchsorted(C, t_hi, side="left") - 1
    # unbounded ends widen to the partition
    start, end = p.seg_start.astype(jnp.int64), p.seg_end.astype(jnp.int64)
    if frame_lo is None:
        lo_pos = start
    if frame_hi is None:
        hi_pos = end
    return jnp.maximum(lo_pos, start), jnp.minimum(hi_pos, end)


def _frame_aggregate(p: wk.SortedPartitions, kind: str, sv, sok,
                     lo: Optional[int], hi: Optional[int], bounds,
                     lo_unbounded: bool, hi_unbounded: bool, levels: int):
    """An aggregate over the frame [lo, hi] row offsets, or over the
    explicit positions ``bounds`` of a RANGE frame, per sorted row."""
    cap = sv.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int64)
    start, end = p.seg_start.astype(jnp.int64), p.seg_end.astype(jnp.int64)
    if bounds is not None:
        lo_pos, hi_pos = bounds
    else:
        lo_pos = start if lo is None else jnp.maximum(pos + lo, start)
        hi_pos = end if hi is None else jnp.minimum(pos + hi, end)
    hi_c = jnp.clip(hi_pos, 0, cap - 1).astype(jnp.int32)
    lo_c = jnp.clip(lo_pos - 1, -1, cap - 1)
    empty = hi_pos < lo_pos

    def between(running):
        """running[hi] - running[lo - 1] of an inclusive running sum."""
        return jnp.take(running, hi_c) - jnp.where(
            lo_c < 0, 0, jnp.take(running, jnp.maximum(lo_c, 0)))

    c = between(prefix_sum(sok.astype(jnp.int64)))
    if kind == "count":
        return jnp.where(empty, 0, c), jnp.ones(cap, bool)
    if kind in ("sum", "avg"):
        s = between(prefix_sum(jnp.where(sok, sv.astype(jnp.float64), 0.0)))
        if kind == "avg":
            s = s / jnp.maximum(c, 1)
        return s, (c > 0) & ~empty
    want_max = kind == "max"
    ident = wk.extreme_of(sv.dtype, want_max)
    comb = jnp.maximum if want_max else jnp.minimum
    x = jnp.where(sok, sv, ident)
    if lo_unbounded or hi_unbounded:
        # half-unbounded frame (a running min/max among them): one
        # segmented scan from the partition's open end and a gather
        if lo_unbounded:
            scanned = wk.seg_scan(x, p.seg_first, comb)
            vals = jnp.take(scanned, hi_c)
        else:
            is_last = pos == end
            scanned = wk.seg_scan(x, is_last, comb, reverse=True)
            vals = jnp.take(scanned, jnp.clip(lo_pos, 0, cap - 1))
    else:
        # general bounded frame: log-doubling range-min/max table;
        # range [l, r] = combine of the two overlapping 2^k blocks at
        # its ends (sparse-table RMQ), ``levels`` deep
        tables = [x]
        step = 1
        for _ in range(levels):
            prev = tables[-1]
            shifted = jnp.concatenate(
                [prev[step:], jnp.full(step, ident, prev.dtype)])
            tables.append(comb(prev, shifted))
            step *= 2
        rmq = jnp.stack(tables)            # [levels + 1, cap]
        length = jnp.maximum(hi_pos - lo_pos + 1, 0)
        # k = floor(log2(length)) via static comparisons (no float
        # log on the emulated-f64 chip); 2^k <= length
        k = jnp.zeros(cap, jnp.int32)
        for j in range(1, len(tables)):
            k = jnp.where(length >= (1 << j), j, k)
        two_k = jnp.left_shift(jnp.int64(1), k.astype(jnp.int64))
        a_idx = jnp.clip(lo_pos, 0, cap - 1)
        b_idx = jnp.clip(hi_pos - two_k + 1, 0, cap - 1)
        flat = rmq.reshape(-1)
        a = jnp.take(flat, k.astype(jnp.int64) * cap + a_idx)
        b = jnp.take(flat, k.astype(jnp.int64) * cap + b_idx)
        vals = comb(a, b)
    return vals, (c > 0) & ~empty
