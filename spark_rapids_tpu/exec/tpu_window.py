"""TPU window operator.

Reference: GpuWindowExec.scala:338 + GpuWindowExpression.scala (cuDF
rolling/scan windows, running-window optimization for row_number etc.).

TPU-first: one sort by (partition keys, order keys) per spec, then every
window function is a segmented scan/reduce over the sorted order:
  row_number        position - segment_start
  rank / dense_rank run boundaries + segment-min of run ids
  lead / lag        shifted gather with same-segment mask
  agg (whole part.) segment reduce broadcast back through seg ids
  agg (running/rows frame) prefix sums with segment clamping
Results are scattered back to the original row order (inverse perm), so
row identity is preserved for downstream operators.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.schema import Field, Schema
from ..columnar.column import Column
from ..columnar.batch import ColumnarBatch, concat_batches
from ..expr import core as ec
from ..expr import aggregates as eagg
from ..expr import window_funcs as wfn
from ..kernels import canon
from ..kernels.sort import sorted_words
from ..plan.logical import Window, WindowFunc
from .base import PhysicalPlan, OP_TIME, NUM_OUTPUT_ROWS, timed
from .tpu_basic import TpuExec


class TpuWindow(TpuExec):
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
            batches = [b for b in part]
            if not batches:
                return
            batch = concat_batches(batches) if len(batches) > 1 else \
                batches[0]
            with timed(self.metrics[OP_TIME], self):
                out = self._apply(batch)
            self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
            yield out
        return [run(p) for p in self.children[0].execute()]

    # ------------------------------------------------------------------
    def _apply(self, batch: ColumnarBatch) -> ColumnarBatch:
        schema = batch.schema
        new_cols: List[Column] = list(batch.columns)
        fields = list(schema.fields)
        for wf in self.logical.window_funcs:
            col = self._eval_window(batch, wf)
            new_cols.append(col)
            fields.append(Field(wf.alias, col.dtype, True))
        return ColumnarBatch(Schema(fields), new_cols, batch.num_rows)

    def _eval_window(self, batch: ColumnarBatch, wf: WindowFunc) -> Column:
        spec = wf.spec
        cap = batch.capacity
        n = batch.num_rows
        pcols = [ec.eval_as_column(e.bind(batch.schema), batch)
                 for e in spec.partition_by]
        ocols = [ec.eval_as_column(o.expr.bind(batch.schema), batch)
                 for o in spec.order_by]

        pwords = canon.batch_key_words(pcols, n) if pcols else \
            [jnp.where(jnp.arange(cap) < n, jnp.uint64(1), jnp.uint64(2))]
        owords = canon.batch_key_words(
            ocols, n,
            descending=[not o.ascending for o in spec.order_by],
            nulls_last=[not o.effective_nulls_first
                        for o in spec.order_by]) if ocols else []

        all_words = pwords + owords
        sorted_ws, perm = sorted_words(all_words)
        live = sorted_ws[0] != jnp.uint64(2)

        npw = len(pwords)
        seg_boundary = canon.words_equal_adjacent(sorted_ws[:npw]) & live
        seg = jnp.maximum(jnp.cumsum(seg_boundary.astype(jnp.int32)) - 1, 0)
        pos = jnp.arange(cap, dtype=jnp.int64)
        # position of segment start, broadcast per row
        seg_start = jax.ops.segment_min(
            jnp.where(live, pos, jnp.int64(cap)), seg, num_segments=cap)
        row_in_seg = pos - jnp.take(seg_start, seg)

        func = wf.func
        if isinstance(func, wfn.RowNumber):
            vals = (row_in_seg + 1).astype(jnp.int64)
            out_valid = live
            out_dtype = T.INT64
        elif isinstance(func, (wfn.Rank, wfn.DenseRank)):
            run_boundary = canon.words_equal_adjacent(sorted_ws) & live
            run_id = jnp.maximum(
                jnp.cumsum(run_boundary.astype(jnp.int32)) - 1, 0)
            if isinstance(func, wfn.Rank):
                run_first = jax.ops.segment_min(
                    jnp.where(live, pos, jnp.int64(cap)), run_id,
                    num_segments=cap)
                vals = (jnp.take(run_first, run_id) -
                        jnp.take(seg_start, seg) + 1).astype(jnp.int64)
            else:
                seg_first_run = jax.ops.segment_min(
                    jnp.where(live, run_id.astype(jnp.int64),
                              jnp.int64(cap)), seg, num_segments=cap)
                vals = (run_id - jnp.take(seg_first_run, seg) + 1
                        ).astype(jnp.int64)
            out_valid = live
            out_dtype = T.INT64
        elif isinstance(func, (wfn.NTile, wfn.PercentRank, wfn.CumeDist)):
            seg_len = jax.ops.segment_sum(
                jnp.where(live, jnp.int64(1), jnp.int64(0)), seg,
                num_segments=cap)
            L = jnp.take(seg_len, seg)
            if isinstance(func, wfn.NTile):
                # Spark NTile: first (L % n) buckets hold ceil(L/n) rows
                nb = jnp.int64(func.n)
                base = L // nb
                rem = L % nb
                cut = rem * (base + 1)
                vals = jnp.where(
                    row_in_seg < cut,
                    row_in_seg // jnp.maximum(base + 1, 1),
                    rem + (row_in_seg - cut) // jnp.maximum(base, 1)) + 1
                out_valid = live
                out_dtype = T.INT64
            else:
                run_boundary = canon.words_equal_adjacent(sorted_ws) & live
                run_id = jnp.maximum(
                    jnp.cumsum(run_boundary.astype(jnp.int32)) - 1, 0)
                if isinstance(func, wfn.PercentRank):
                    run_first = jax.ops.segment_min(
                        jnp.where(live, pos, jnp.int64(cap)), run_id,
                        num_segments=cap)
                    rank = (jnp.take(run_first, run_id) -
                            jnp.take(seg_start, seg) + 1)
                    vals = jnp.where(
                        L > 1,
                        (rank - 1).astype(jnp.float64) /
                        jnp.maximum(L - 1, 1).astype(jnp.float64), 0.0)
                else:   # CumeDist: rows <= current / partition rows
                    run_last = jax.ops.segment_max(
                        jnp.where(live, pos, jnp.int64(-1)), run_id,
                        num_segments=cap)
                    vals = (jnp.take(run_last, run_id) -
                            jnp.take(seg_start, seg) + 1).astype(
                        jnp.float64) / jnp.maximum(L, 1).astype(
                        jnp.float64)
                out_valid = live
                out_dtype = T.FLOAT64
        elif isinstance(func, (wfn.Lead, wfn.Lag)):
            src = ec.eval_as_column(func.children[0].bind(batch.schema),
                                    batch)
            off = func.offset if isinstance(func, wfn.Lead) else -func.offset
            shifted_pos = pos + off
            inb = (shifted_pos >= 0) & (shifted_pos < cap)
            sp = jnp.clip(shifted_pos, 0, cap - 1).astype(jnp.int32)
            same_seg = inb & (jnp.take(seg, sp) == seg) & \
                jnp.take(live, sp) & live
            src_sorted_idx = jnp.take(perm, sp)
            sorted_vals = src.gather(src_sorted_idx)
            valid = sorted_vals.validity & same_seg
            # scatter back to original order
            inv = jnp.argsort(perm)
            out = sorted_vals.gather(inv)
            return out.mask_validity(jnp.take(valid, inv) &
                                     (jnp.arange(cap) < n))
        elif isinstance(func, eagg.AggregateFunction):
            return self._window_agg(batch, func, spec, perm, seg, live,
                                    row_in_seg, seg_start, n)
        else:
            raise NotImplementedError(f"window function {func.name}")

        inv = jnp.argsort(perm)
        vals_orig = jnp.take(vals, inv)
        valid_orig = jnp.take(out_valid, inv) & (jnp.arange(cap) < n)
        return Column(out_dtype, vals_orig.astype(out_dtype.np_dtype),
                      valid_orig)

    # ------------------------------------------------------------------
    def _window_agg(self, batch, func, spec, perm, seg, live, row_in_seg,
                    seg_start, n) -> Column:
        cap = batch.capacity
        if isinstance(func, eagg.CollectList):
            return self._window_collect(batch, func, spec, perm, seg,
                                        live, row_in_seg, seg_start, n)
        child = func.children[0] if func.children else None
        if child is not None:
            src = ec.eval_as_column(child.bind(batch.schema), batch)
            sv = jnp.take(src.data, perm) if not hasattr(src, "offsets") \
                else None
            if sv is None:
                raise NotImplementedError("string window aggregates")
            sok = jnp.take(src.validity, perm) & live
        else:
            sv = jnp.ones(cap, jnp.int64)
            sok = live

        kind, frame_lo, frame_hi = spec.frame
        unbounded = frame_lo is None and frame_hi is None
        out_dtype = func.dtype()

        if unbounded or not spec.order_by:
            # whole-partition aggregate broadcast back
            vals, ok = self._seg_reduce(func, sv, sok, seg, cap)
            vals = jnp.take(vals, seg)
            ok = jnp.take(ok, seg) & live
        elif kind == "range":
            lo_pos, hi_pos = self._range_positions(
                batch, spec, perm, seg, seg_start, live, cap,
                frame_lo, frame_hi)
            vals, ok = self._frame_agg(func, sv, sok, seg, row_in_seg,
                                       seg_start, cap, None, None,
                                       lo_pos=lo_pos, hi_pos=hi_pos,
                                       lo_unbounded=frame_lo is None,
                                       hi_unbounded=frame_hi is None)
            ok = ok & live
        else:
            lo = frame_lo  # None = unbounded preceding
            hi = frame_hi if frame_hi is not None else None
            vals, ok = self._frame_agg(func, sv, sok, seg, row_in_seg,
                                       seg_start, cap, lo, hi)
            ok = ok & live
        inv = jnp.argsort(perm)
        vals_orig = jnp.take(vals, inv)
        ok_orig = jnp.take(ok, inv) & (jnp.arange(cap) < n)
        return Column(out_dtype, vals_orig.astype(out_dtype.np_dtype),
                      ok_orig)

    def _window_collect(self, batch, func, spec, perm, seg, live,
                        row_in_seg, seg_start, n) -> Column:
        """collect_list over a window frame -> ListColumn.

        Elements come from the globally valid-compacted sorted rows:
        row i's list is vpos[c_lo_i .. c_hi_i) where cnt is the prefix
        count of valid sorted rows — one cumsum + one expand, no
        per-row loops (GpuWindowExpression collect_list role)."""
        from ..columnar.column import ListColumn, bucket_capacity
        from ..kernels import basic as bk
        from ..kernels import join as join_k
        cap = batch.capacity
        src = ec.eval_as_column(func.children[0].bind(batch.schema),
                                batch)
        sorted_src = src.gather(perm)
        valid = sorted_src.validity & live
        kind, frame_lo, frame_hi = spec.frame
        seg_start_pos, seg_end_pos = self._seg_extents(seg, seg_start,
                                                       cap)
        pos = jnp.arange(cap, dtype=jnp.int64)
        if (frame_lo is None and frame_hi is None) or not spec.order_by:
            lo_pos, hi_pos = seg_start_pos, seg_end_pos
        elif kind == "range":
            lo_pos, hi_pos = self._range_positions(
                batch, spec, perm, seg, seg_start, live, cap,
                frame_lo, frame_hi)
        else:
            lo_pos = seg_start_pos if frame_lo is None else \
                jnp.maximum(pos + frame_lo, seg_start_pos)
            hi_pos = seg_end_pos if frame_hi is None else \
                jnp.minimum(pos + frame_hi, seg_end_pos)
        cnt = jnp.cumsum(valid.astype(jnp.int64))
        hi_c = jnp.clip(hi_pos, 0, cap - 1).astype(jnp.int32)
        lo_c = jnp.clip(lo_pos - 1, -1, cap - 1)
        c_hi = jnp.take(cnt, hi_c)
        c_lo = jnp.where(lo_c < 0, 0, jnp.take(cnt, jnp.maximum(lo_c, 0)))
        m_sorted = jnp.where(hi_pos < lo_pos, 0, c_hi - c_lo)
        vpos, _ = bk.filter_compact_indices(valid, cap)
        inv = jnp.argsort(perm)
        m_orig = jnp.where(jnp.arange(cap) < n,
                           jnp.take(m_sorted, inv), 0)
        c_lo_orig = jnp.take(c_lo, inv)
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="size_probe"):
            total = int(jnp.sum(m_orig))
        out_cap = bucket_capacity(max(total, 1))
        _, elem_pos, live_e, _ = join_k.join_expand_matches(
            c_lo_orig.astype(jnp.int32), m_orig.astype(jnp.int32),
            vpos.astype(jnp.int32), out_cap)
        elements = sorted_src.gather(elem_pos)
        elements = elements.mask_validity(live_e)
        offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int64),
             jnp.cumsum(m_orig)]).astype(jnp.int32)
        out_valid = jnp.arange(cap) < n
        return ListColumn(T.ArrayType(src.dtype), offsets, elements,
                          out_valid)

    @staticmethod
    def _seg_extents(seg, seg_start, cap):
        """(per-row segment start position, per-row segment end
        position) — shared by every frame kind."""
        seg_start_pos = jnp.take(seg_start, seg)
        seg_len = jax.ops.segment_sum(
            jnp.ones(cap, jnp.int64), seg, num_segments=cap)
        seg_end_pos = seg_start_pos + jnp.take(seg_len, seg) - 1
        return seg_start_pos, seg_end_pos

    @staticmethod
    def _minmax_ident(is_min: bool, dtype):
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(jnp.inf if is_min else -jnp.inf, dtype)
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.max if is_min else info.min, dtype)

    def _seg_reduce(self, func, sv, sok, seg, cap):
        contrib_ok = sok
        if isinstance(func, eagg.Sum):
            vals = jax.ops.segment_sum(
                jnp.where(contrib_ok, sv.astype(jnp.float64)
                          if func.dtype().is_fractional else
                          sv.astype(jnp.int64), 0), seg, num_segments=cap)
            cnt = jax.ops.segment_sum(contrib_ok.astype(jnp.int64), seg,
                                      num_segments=cap)
            return vals, cnt > 0
        if isinstance(func, eagg.Count):
            vals = jax.ops.segment_sum(contrib_ok.astype(jnp.int64), seg,
                                       num_segments=cap)
            return vals, jnp.ones_like(vals, bool)
        if isinstance(func, eagg.Average):
            s = jax.ops.segment_sum(
                jnp.where(contrib_ok, sv.astype(jnp.float64), 0.0), seg,
                num_segments=cap)
            c = jax.ops.segment_sum(contrib_ok.astype(jnp.int64), seg,
                                    num_segments=cap)
            return s / jnp.maximum(c, 1), c > 0
        if isinstance(func, eagg.Min):
            big = jnp.asarray(jnp.inf if jnp.issubdtype(sv.dtype,
                                                        jnp.floating)
                              else jnp.iinfo(sv.dtype).max, sv.dtype)
            vals = jax.ops.segment_min(jnp.where(contrib_ok, sv, big), seg,
                                       num_segments=cap)
            cnt = jax.ops.segment_sum(contrib_ok.astype(jnp.int64), seg,
                                      num_segments=cap)
            return vals, cnt > 0
        if isinstance(func, eagg.Max):
            small = jnp.asarray(-jnp.inf if jnp.issubdtype(sv.dtype,
                                                           jnp.floating)
                                else jnp.iinfo(sv.dtype).min, sv.dtype)
            vals = jax.ops.segment_max(jnp.where(contrib_ok, sv, small), seg,
                                       num_segments=cap)
            cnt = jax.ops.segment_sum(contrib_ok.astype(jnp.int64), seg,
                                      num_segments=cap)
            return vals, cnt > 0
        raise NotImplementedError(f"window aggregate {func.name}")

    def _range_positions(self, batch, spec, perm, seg, seg_start, live,
                         cap, frame_lo, frame_hi):
        """RANGE frame bounds as sorted-row positions via rank search.

        Reference: cuDF range-window support behind GpuWindowExec.  For
        each row with order value v the frame covers rows of its
        partition with value in [v+lo, v+hi] (direction-corrected for
        DESC).  Computed without per-row loops: encode values as
        order-preserving uint64 words, rank every row's word in the
        batch-wide sorted word array, and binary-search composite
        (segment, rank) keys — all vectorized searchsorted.
        """
        order = spec.order_by[0]
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
        ocol = ec.eval_as_column(order.expr.bind(batch.schema), batch)
        vals_sorted = jnp.take(ocol.data, perm).astype(jnp.int64)
        ovalid = jnp.take(ocol.validity, perm) & live

        def enc(x):
            w = canon._ints_to_words(x, 64)
            return ~w if not order.ascending else w

        words = jnp.where(ovalid, enc(vals_sorted),
                          jnp.uint64(0xFFFFFFFFFFFFFFFF))
        v_sorted = jnp.sort(words)
        lo_off = jnp.int64(0 if frame_lo is None else frame_lo)
        hi_off = jnp.int64(0 if frame_hi is None else frame_hi)
        if order.ascending:
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
        nulls_first = order.effective_nulls_first
        null_slot = jnp.int64(0) if nulls_first else BIG - 1
        rank_row = jnp.where(
            ovalid,
            1 + jnp.searchsorted(v_sorted, words, side="left"), null_slot)
        C = seg.astype(jnp.int64) * BIG + rank_row.astype(jnp.int64)
        # padding rows past num_rows sort AFTER every live row: pin their
        # composite to +inf or the searchsorted precondition breaks
        C = jnp.where(live, C, jnp.int64(2 ** 62))
        seg64 = seg.astype(jnp.int64)
        t_lo = jnp.where(ovalid, seg64 * BIG + 1 + r_lo,
                         seg64 * BIG + null_slot)
        t_hi = jnp.where(ovalid, seg64 * BIG + 1 + r_hi,
                         seg64 * BIG + null_slot + 1)
        lo_pos = jnp.searchsorted(C, t_lo, side="left")
        hi_pos = jnp.searchsorted(C, t_hi, side="left") - 1
        # unbounded ends widen to the partition
        seg_start_pos = jnp.take(seg_start, seg)
        seg_len = jax.ops.segment_sum(
            jnp.ones(cap, jnp.int64), seg, num_segments=cap)
        seg_end_pos = seg_start_pos + jnp.take(seg_len, seg) - 1
        if frame_lo is None:
            lo_pos = seg_start_pos
        if frame_hi is None:
            hi_pos = seg_end_pos
        lo_pos = jnp.maximum(lo_pos, seg_start_pos)
        hi_pos = jnp.minimum(hi_pos, seg_end_pos)
        return lo_pos, hi_pos

    def _frame_agg(self, func, sv, sok, seg, row_in_seg, seg_start, cap,
                   lo: Optional[int], hi: Optional[int],
                   lo_pos=None, hi_pos=None,
                   lo_unbounded: bool = False,
                   hi_unbounded: bool = False):
        """Frame [lo, hi] row offsets, or explicit positions
        (lo_pos/hi_pos from a RANGE frame)."""
        pos = jnp.arange(cap, dtype=jnp.int64)
        explicit = lo_pos is not None
        if isinstance(func, (eagg.Sum, eagg.Count, eagg.Average)):
            acc_dtype = jnp.float64 if not isinstance(func, eagg.Count) \
                else jnp.int64
            contrib = jnp.where(sok, sv.astype(acc_dtype)
                                if not isinstance(func, eagg.Count)
                                else jnp.ones(cap, jnp.int64),
                                jnp.zeros(cap, acc_dtype))
            ps = jnp.cumsum(contrib)          # inclusive prefix sum
            cnt = jnp.cumsum(sok.astype(jnp.int64))
            seg_start_pos, seg_end_pos = self._seg_extents(
                seg, seg_start, cap)
            if not explicit:
                lo_pos = seg_start_pos if lo is None else \
                    jnp.maximum(pos + lo, seg_start_pos)
                hi_pos = seg_end_pos if hi is None else \
                    jnp.minimum(pos + hi, seg_end_pos)
            hi_c = jnp.clip(hi_pos, 0, cap - 1).astype(jnp.int32)
            lo_c = jnp.clip(lo_pos - 1, -1, cap - 1)
            ps_hi = jnp.take(ps, hi_c)
            ps_lo = jnp.where(lo_c < 0, 0,
                              jnp.take(ps, jnp.maximum(lo_c, 0)))
            cnt_hi = jnp.take(cnt, hi_c)
            cnt_lo = jnp.where(lo_c < 0, 0,
                               jnp.take(cnt, jnp.maximum(lo_c, 0)))
            s = ps_hi - ps_lo
            c = cnt_hi - cnt_lo
            empty = hi_pos < lo_pos
            if isinstance(func, eagg.Count):
                return jnp.where(empty, 0, c), jnp.ones(cap, bool)
            if isinstance(func, eagg.Average):
                return s / jnp.maximum(c, 1), (c > 0) & ~empty
            return s, (c > 0) & ~empty
        if isinstance(func, (eagg.Min, eagg.Max)) and lo is None and \
                hi == 0:
            # running min/max: segmented inclusive scan
            is_min = isinstance(func, eagg.Min)
            ident = self._minmax_ident(is_min, sv.dtype)
            x = jnp.where(sok, sv, ident)
            reset = row_in_seg == 0

            def combine(a, b):
                av, ar = a
                bv, br = b
                merged = jnp.where(br, bv,
                                   jnp.minimum(av, bv) if is_min
                                   else jnp.maximum(av, bv))
                return merged, ar | br
            scanned, _ = jax.lax.associative_scan(combine, (x, reset))
            cnt = jnp.cumsum(sok.astype(jnp.int64))
            seg_start_pos = jnp.take(seg_start, seg)
            cnt_before = jnp.where(
                seg_start_pos > 0,
                jnp.take(cnt, jnp.clip(seg_start_pos - 1, 0, cap - 1)), 0)
            has = (cnt - cnt_before) > 0
            return scanned, has
        if isinstance(func, (eagg.Min, eagg.Max)):
            is_min = isinstance(func, eagg.Min)
            ident = self._minmax_ident(is_min, sv.dtype)
            seg_start_pos, seg_end_pos = self._seg_extents(
                seg, seg_start, cap)
            x = jnp.where(sok, sv, ident)
            comb = jnp.minimum if is_min else jnp.maximum

            def seg_scan(values, reverse=False):
                reset = (row_in_seg == 0) if not reverse else \
                    (pos == seg_end_pos)
                v = values[::-1] if reverse else values
                r = reset[::-1] if reverse else reset

                def combine(a, b):
                    av, ar = a
                    bv, br = b
                    return jnp.where(br, bv, comb(av, bv)), ar | br
                scanned, _ = jax.lax.associative_scan(combine, (v, r))
                return scanned[::-1] if reverse else scanned
            if not explicit:
                lo_pos = seg_start_pos if lo is None else \
                    jnp.maximum(pos + lo, seg_start_pos)
                hi_pos = seg_end_pos if hi is None else \
                    jnp.minimum(pos + hi, seg_end_pos)
            if (not explicit and (lo is None or hi is None)) or \
                    (explicit and (lo_unbounded or hi_unbounded)):
                # half-unbounded frame (ROWS offsets or RANGE with one
                # unbounded side): one segmented scan + a gather,
                # O(cap) memory, no host sync (no sparse table needed)
                if lo is None if not explicit else lo_unbounded:
                    scanned = seg_scan(x)            # prefix from start
                    vals = jnp.take(scanned,
                                    jnp.clip(hi_pos, 0, cap - 1))
                else:
                    scanned = seg_scan(x, reverse=True)  # suffix to end
                    vals = jnp.take(scanned,
                                    jnp.clip(lo_pos, 0, cap - 1))
            else:
                # general bounded frame: log-doubling range-min/max
                # table; range [l, r] = combine of the two overlapping
                # 2^k blocks at its ends (sparse-table RMQ).  Levels
                # stop at the widest frame actually present.
                if not explicit:
                    max_window = max(hi - lo + 1, 1)
                else:
                    # RANGE frame: one host sync learns the widest window
                    from ..analysis import residency  # lazy import
                    with residency.declared_transfer(site="size_probe"):
                        max_window = max(
                            int(jnp.max(hi_pos - lo_pos + 1)), 1)
                tables = [x]
                step = 1
                while step < max_window:
                    prev = tables[-1]
                    shifted = jnp.concatenate(
                        [prev[step:], jnp.full(step, ident, prev.dtype)])
                    tables.append(comb(prev, shifted))
                    step *= 2
                rmq = jnp.stack(tables)            # [levels, cap]
                length = jnp.maximum(hi_pos - lo_pos + 1, 0)
                # k = floor(log2(length)) via static comparisons (no
                # float log on the emulated-f64 chip); 2^k <= length
                k = jnp.zeros(cap, jnp.int32)
                for j in range(1, len(tables)):
                    k = jnp.where(length >= (1 << j), j, k)
                k = jnp.minimum(k, len(tables) - 1)
                two_k = jnp.left_shift(jnp.int64(1),
                                       k.astype(jnp.int64))
                a_idx = jnp.clip(lo_pos, 0, cap - 1)
                b_idx = jnp.clip(hi_pos - two_k + 1, 0, cap - 1)
                flat = rmq.reshape(-1)
                a = jnp.take(flat, k.astype(jnp.int64) * cap + a_idx)
                b = jnp.take(flat, k.astype(jnp.int64) * cap + b_idx)
                vals = comb(a, b)
            cnt = jnp.cumsum(sok.astype(jnp.int64))
            hi_c = jnp.clip(hi_pos, 0, cap - 1).astype(jnp.int32)
            lo_c = jnp.clip(lo_pos - 1, -1, cap - 1)
            cnt_hi = jnp.take(cnt, hi_c)
            cnt_lo = jnp.where(lo_c < 0, 0,
                               jnp.take(cnt, jnp.maximum(lo_c, 0)))
            has = (cnt_hi - cnt_lo) > 0
            empty = hi_pos < lo_pos
            return vals, has & ~empty
        raise NotImplementedError(
            f"window frame ({lo},{hi}) for {func.name}")
