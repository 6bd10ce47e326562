"""TPU hash-aggregate operator.

Reference: GpuHashAggregateExec (aggregate.scala:240,282-460): per-batch
update aggregation, then concat+merge of partials, with partial/final/
complete modes driven by the planner around exchanges.

TPU-first: grouping is the sort+segmented-reduce kernel
(kernels/aggregate.py) — no hash tables; one compiled program per
(schema, capacity) bucket.
"""
from __future__ import annotations

import logging
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.schema import Field, Schema
from ..columnar.column import Column, bucket_capacity
from ..columnar.gather import gather_columns
from ..columnar.batch import (ColumnarBatch, LazyCount, SpeculativeResult,
                              concat_batches, resolve_speculative)
from ..expr import aggregates as ea
from ..expr import core as ec
from ..compile import aot as _aot
from ..kernels import canon, aggregate as agg_k
from ..obs import compile_watch as _compile_watch
from ..obs import costplane as _costplane
from ..obs import trace as _obs_trace
from ..obs.registry import compile_cache_event
from ..plan.logical import AggExpr
from .base import PhysicalPlan, AGG_TIME, NUM_OUTPUT_ROWS, timed
from .tpu_basic import TpuExec

PARTIAL, FINAL, COMPLETE = "partial", "final", "complete"


def _assemble_group_output(plan, key_cols, aggs, agg_buffers,
                           emit_buffers: bool):
    """Traced output assembly: keys + agg buffers at rows 0..G-1 of the
    plan's ``num_slots`` (the core's output capacity).

    Runs INSIDE the fused cores — eager per-column gathers/masks after the
    jitted plan pay one dispatch each, which dominated the reduce side.
    The buffers come from the segment kernels at ``num_slots`` already;
    only the keys are gathered, at their groups' representative rows.  A
    STRING key (``canon.PackedStringKey``: the core holds its words, not
    its bytes) yields (representative row, validity) for the caller's
    lazy gather of the source column (``_output_columns``)."""
    ng = plan.num_groups
    slots = plan.num_slots
    live = jnp.arange(slots) < ng
    take = jnp.where(live, plan.rep_indices, 0).astype(jnp.int32)
    outs = []
    for c in key_cols:
        if type(c) is canon.PackedStringKey:
            outs.append((take, jnp.take(c.validity, take) & live))
            continue
        g = c.gather(take, live=live, unique=True).mask_validity(live)
        outs.append((g.data, g.validity))
    for a, bufs in zip(aggs, agg_buffers):
        cols_out = bufs if emit_buffers else [a.func.finalize(bufs)]
        for o in cols_out:
            assert o.capacity == slots, (o.capacity, slots)
            outs.append((o.data, o.validity & live))
    return ng, outs


#: core cache key -> 64-bit key words the core sorts (one LSD pass each)
_KEY_WORDS: dict = {}


def _group_reduce(key_cols, live, num_rows, aggs, agg_cols,
                  update_mode: bool, out_cap: Optional[int],
                  emit_buffers: bool, cache_key=None):
    """The traced body both grouped cores share: merged key words ->
    sort -> ONE row gather of every array the aggregates read in sorted
    order -> update / merge (the DOUBLE sums as one stacked pass) ->
    output assembly, all per-group work at the output capacity.
    ``live`` (or None) marks the rows a folded-in filter kept: a dead
    row gets rank 2 in the first key field and sorts past every group,
    so nothing is compacted.  Returns (num_groups, fit, output pairs in
    schema order).  ``cache_key``: the core's, under which the number
    of merged key words it sorts is left for ``agg.key_words`` (known
    when the core is traced, and the same at every capacity)."""
    cap = key_cols[0].capacity
    rows = jnp.arange(cap) < num_rows
    live = rows if live is None else live & rows
    with jax.named_scope("key_words"):
        words = canon.group_key_words(key_cols, num_rows, live)
    _KEY_WORDS[cache_key] = len(words)
    carried = []                # what the aggregates read in sorted order
    for a, cols in zip(aggs, agg_cols):
        for c in cols:
            if c is None:
                continue
            carried.append(c.validity)
            # first / last read one row a group, by index
            if not isinstance(a.func, (ea.First, ea.Last)):
                carried.append(c.data)
    plan = agg_k.groupby_plan(words, num_slots=out_cap, inputs=carried,
                              live=live)
    with jax.named_scope("segment_reduce"):
        agg_k.stack_float_sums(
            plan, [c for a, cols in zip(aggs, agg_cols)
                   for c in a.func.float_sum_cols(cols)])
        agg_buffers = [a.func.update(plan, cols) if update_mode
                       else a.func.merge(plan, cols)
                       for a, cols in zip(aggs, agg_cols)]
    fit = (plan.num_groups <= plan.num_slots).astype(jnp.int32) \
        if out_cap and out_cap < cap else jnp.int32(1)
    with jax.named_scope("emit"):
        ng, outs = _assemble_group_output(plan, key_cols, aggs,
                                          agg_buffers, emit_buffers)
    return ng, fit, outs


def _global_reduce(aggs, agg_cols, live, update_mode: bool,
                   emit_buffers: bool):
    """The traced body of the global core (no group keys): every
    ``live`` row is one group (``agg_k.single_group_plan``), so nothing
    is sorted or moved; the DOUBLE sums are one stacked tree-ordered
    reduce and every other kernel reduces the masked rows.  Returns the
    output pairs in schema order, one row at ``bucket_capacity(1)``:
    over no live row a count reads 0 and every other result NULL."""
    plan = agg_k.single_group_plan(live)
    out_cap = bucket_capacity(1)
    one = jnp.arange(out_cap) < 1
    has_rows = jnp.any(live)
    with jax.named_scope("segment_reduce"):
        agg_k.stack_float_sums(
            plan, [c for a, cols in zip(aggs, agg_cols)
                   for c in a.func.float_sum_cols(cols) if type(c) is Column])
        agg_buffers = [a.func.update(plan, cols) if update_mode
                       else a.func.merge(plan, cols)
                       for a, cols in zip(aggs, agg_cols)]
    outs = []
    for a, bufs in zip(aggs, agg_buffers):
        for o in (bufs if emit_buffers else [a.func.finalize(bufs)]):
            c = o.gather(jnp.zeros(out_cap, jnp.int32))
            if isinstance(a.func, ea.Count):
                # counts are valid even over empty input (0)
                c = Column(T.INT64,
                           jnp.where(one, c.data.astype(jnp.int64), 0), one)
            else:
                c = c.mask_validity(one & has_rows)
            outs.append((c.data, c.validity))
    return outs


def _masked_chain(src_schema, pre_ops, bounds, bound_keys, bound_inputs,
                  datas, valids, num_rows):
    """The traced front of the whole-stage and folded global cores: the
    source columns (a STRING key source as its packed words, of
    ``bounds``' byte bound; any other STRING column, which nothing
    reads, as None), the filter / project chain folded into the row
    mask (``staged.apply_ops_masked``), then the keys and the
    aggregates' inputs, keyed by bound expression: sum(x) and avg(x)
    read ONE evaluated column.  Returns (live, keys, inputs)."""
    from .fused import _TracedBatch, expr_signature
    from .staged import apply_ops_masked
    cap = next(v for v in valids if v is not None).shape[0]
    byte_bound = dict(bounds)
    cols = [None if v is None else
            canon.PackedStringKey(d, v, byte_bound[i])
            if f.dtype == T.STRING else Column(f.dtype, d, v)
            for i, (f, d, v) in enumerate(zip(src_schema, datas, valids))]
    b = _TracedBatch(src_schema, cols, num_rows, cap)
    with jax.named_scope("pre_ops"):
        b, live = apply_ops_masked(pre_ops, b, jnp.arange(cap) < num_rows)
    kcols = [ec.eval_as_column(e, b) for e in bound_keys]
    evaluated = {}
    agg_cols = []
    for bs in bound_inputs:
        for e in bs:
            sig = expr_signature(e)
            if sig not in evaluated:
                evaluated[sig] = ec.eval_as_column(e, b)
        agg_cols.append([evaluated[expr_signature(e)] for e in bs] or [None])
    return live, kcols, agg_cols


def _pack_string_key(col, num_rows, at_least: int = 0):
    """(value words, validity) of a STRING key column for a core's
    ``canon.PackedStringKey``, and the byte bound that sized the words:
    ``jit_str_pack_words`` on the source column (a lazy gather view
    gathers its source's words), outside the core because the bound is
    host-known, not traced.  The bound is rounded up to a power of two:
    it is part of the core's cache key.  ``at_least``: the widest bound
    an earlier batch of the same key had; the words are sized by the
    wider of the two, so the batches share one program.  A column whose
    own bound is 0 holds only NULLs and empty strings (a key a grouping
    set rolled up): its words are zero at any width and nothing is
    launched for them."""
    from ..kernels import strings as skern
    own = skern.key_byte_bound(col, num_rows)
    bound = max(1 << max(0, own - 1).bit_length(), at_least)
    num_words = skern.bucket_words(bound)
    if own == 0:
        zero = jnp.zeros(col.capacity, jnp.uint64)
        return ((zero,) * (num_words + 1), col.validity), bound
    words = canon.value_words(col, num_rows, str_words=num_words,
                              str_bound=bound)
    return (tuple(words), col.validity), bound


def _output_columns(schema, pairs, string_keys) -> list:
    """Columns of a core's output pairs; ``string_keys`` maps a key's
    position to the StringColumn its (row, validity) pair gathers from,
    lazily (``GatheredStringColumn``: the bytes of at most ``out_cap``
    representatives are copied when something reads them)."""
    from ..columnar.column import GatheredStringColumn
    cols = []
    for i, (f, (d, v)) in enumerate(zip(schema, pairs)):
        src = string_keys.get(i)
        if src is None:
            cols.append(Column(f.dtype, d, v))
        else:
            with _obs_trace.launch("string_gather", 1, d.shape[0]):
                cols.append(GatheredStringColumn(src, d, v, unique=True))
    return cols


# -- 32-bit device helpers for exact-float table aggregation ----------------
# The chip's 64-bit scatters cost ~5x 32-bit ones, so exact FLOAT64 table
# aggregation works entirely in 32-bit lanes: a value's two native f32
# components decompose into signed 8-bit integer chunks (sums) or flip-
# ordered u32 words (min/max).

CH_B = 8          # bits per chunk lane
CH_LANES = 15     # window = 120 bits
CH_W0 = 88        # max chunk position (top term bit 88+23 < 120)


def _flip32(f):
    """f32 -> u32 whose unsigned order equals the float total order
    (-0.0 handled by callers; NaNs must be masked out)."""
    u = jax.lax.bitcast_convert_type(f, jnp.uint32)
    neg = (u >> jnp.uint32(31)) != jnp.uint32(0)
    return jnp.where(neg, ~u, u | jnp.uint32(0x80000000))


def _unflip32(w):
    neg = (w & jnp.uint32(0x80000000)) == jnp.uint32(0)
    u = jnp.where(neg, ~w, w & jnp.uint32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _pow2f(k):
    """2^k as f32 from a traced i32 scalar, k in [-126, 127]."""
    return jax.lax.bitcast_convert_type(
        ((k + 127).astype(jnp.uint32) << jnp.uint32(23)), jnp.float32)


def _f32_exp(f):
    """(biased exponent clamped >=1, 24-bit significand, negative) of an
    f32 array."""
    u = jax.lax.bitcast_convert_type(f, jnp.uint32)
    neg = (u >> jnp.uint32(31)) != jnp.uint32(0)
    e = ((u >> jnp.uint32(23)) & jnp.uint32(0xFF)).astype(jnp.int32)
    m = u & jnp.uint32(0x7FFFFF)
    sig = jnp.where(e > 0, m | jnp.uint32(1 << 23), m)
    return jnp.maximum(e, 1), sig, neg


def _part_chunk_rows(f, ok, emax):
    """One f32 component -> CH_LANES signed i32 chunk rows on the
    window anchored at ``emax`` (value = sig * 2^(e-150); window bit 0
    weighs 2^(emax-150-CH_W0)).  Exact for terms within the window;
    the caller's fit flag excludes batches with wider spread."""
    ee, sig, neg = _f32_exp(f)
    p = jnp.int32(CH_W0) - (emax - ee)
    keep = ok & (p >= 0) & (sig != jnp.uint32(0))
    off = (jnp.maximum(p, 0) & jnp.int32(7)).astype(jnp.uint32)
    q = jnp.maximum(p, 0) >> jnp.int32(3)
    l32 = sig << off                       # <= 2^31: stays in u32
    sgn = jnp.where(neg, jnp.int32(-1), jnp.int32(1))
    z = jnp.int32(0)
    cks = [jnp.where(keep, ((l32 >> jnp.uint32(CH_B * k)) &
                            jnp.uint32(0xFF)).astype(jnp.int32) * sgn, z)
           for k in range(4)]
    rows = []
    qmax = CH_W0 >> 3
    for L in range(CH_LANES):
        r = z
        for k in range(4):
            if 0 <= L - k <= qmax:
                r = r + jnp.where(q == L - k, cks[k], z)
        rows.append(r)
    return rows


def _chunk_recombine(lanes_f64, emax):
    """[table, CH_LANES] per-bucket lane sums (as f64) + batch emax
    -> per-bucket f64 totals.  Scale split into two in-range f32
    powers of two."""
    out = jnp.zeros(lanes_f64.shape[0], jnp.float64)
    for L in range(CH_LANES):
        k = jnp.int32(CH_B * L) + emax - jnp.int32(CH_W0 + 150)
        k1 = k // 2
        s1 = _pow2f(k1).astype(jnp.float64)
        s2 = _pow2f(k - k1).astype(jnp.float64)
        out = out + (lanes_f64[:, L] * s1) * s2
    return out


# the cores' sort, row gather and scan are sized by the batch: past this
# many slots a batch takes the eager fallback
_CORE_MAX_CAPACITY = 1 << 22


def _agg_signature(aggs) -> tuple:
    """The aggregate functions as a core's cache key names them."""
    return tuple((type(a.func).__name__, repr(a.func),
                  getattr(a.func, "ignore_nulls", None)) for a in aggs)


def buffer_schema(group_exprs, aggs: List[AggExpr]) -> Schema:
    """Schema of partial-aggregation output: keys + flattened buffers."""
    fields = [Field(ec.output_name(e), e.dtype(), True) for e in group_exprs]
    for a in aggs:
        for bi, bt in enumerate(a.func.buffer_dtypes()):
            fields.append(Field(f"__{a.alias}__buf{bi}", bt, True))
    return Schema(fields)


class TpuHashAggregate(TpuExec):
    def __init__(self, group_exprs: List[ec.Expression], aggs: List[AggExpr],
                 child: PhysicalPlan, mode: str = COMPLETE):
        super().__init__(child)
        self.group_exprs = group_exprs
        self.aggs = aggs
        self.mode = mode
        # whole-stage fusion: a leading filter/project chain folded in by
        # the planner post-pass (exec/staged.py) — applied before keys
        self.pre_ops = None
        # per-exec memo: whole-stage guards / signatures by source
        # dtypes, the eager pre_ops programs, the compaction's state
        self._ws_memo = {}

    @property
    def output_schema(self):
        if self.mode == PARTIAL:
            return buffer_schema(self.group_exprs, self.aggs)
        fields = [Field(ec.output_name(e), e.dtype(), True)
                  for e in self.group_exprs]
        fields += [Field(a.alias, a.func.dtype(), a.func.nullable)
                   for a in self.aggs]
        return Schema(fields)

    def _node_string(self):
        ws = f", staged={len(self.pre_ops)} ops" if self.pre_ops else ""
        return f"TpuHashAggregate[{self.mode}{ws}]"

    def execute(self):
        child_schema = self.children[0].output_schema
        nkeys = len(self.group_exprs)

        def run(part):
            # per-batch update aggregation, then concat+merge of partials —
            # the reference's iterative model (aggregate.scala:366-390)
            # keeps memory bounded by partial size, not input size.
            partials = []
            with timed(self.metrics[AGG_TIME], self):
                batches = list(part)
                if self.mode == FINAL:
                    # FINAL inputs are post-shuffle slices with host-known
                    # counts: concat them up front (one jitted program)
                    # and run ONE merge core instead of one per piece —
                    # per-piece cores dominated the reduce side.  Falls
                    # back to the iterative path when sizes are unknown
                    # or the coalesced batch would be huge.
                    if len(batches) > 1 and all(
                            isinstance(b.rows_lazy, int) for b in batches) \
                            and sum(b.num_rows for b in batches) <= (1 << 21):
                        batches = [concat_batches(batches)]
                for batch in batches:
                    # only skip empties whose count is already host-known
                    # (checking a lazy count would force a sync per batch)
                    if isinstance(batch.rows_lazy, int) and \
                            batch.num_rows == 0 and partials:
                        continue
                    in_spec = getattr(batch, "_speculative", None)
                    p = self._update_batch(batch)
                    if in_spec is not None:
                        # the update ran on a speculative input (e.g. a
                        # superstage's sync-free join): carry the input
                        # fits so the barrier that checks this partial
                        # also vouches for the rows it aggregated, and
                        # redo the update on the exactly-recomputed input
                        own = getattr(p, "_speculative", None)

                        def _redo_update(batch=batch):
                            return self._update_batch(
                                resolve_speculative(batch))
                        p._speculative = SpeculativeResult(
                            list(in_spec.fits) +
                            (list(own.fits) if own is not None else []),
                            _redo_update)
                    partials.append(p)
                if not partials:
                    partials = [self._update_batch(
                        ColumnarBatch.empty(child_schema))]
                # A single PARTIAL passes through unverified/uncompacted
                # (zero syncs); the exchange downstream holds the flush
                # barrier that verifies speculatively compacted batches
                # and slices them.  Any path that merges/finalizes here
                # must verify first (the merge would bake garbage in) —
                # EXCEPT the single-partial deferred path below, which
                # re-attaches the unverified flag to its own output so
                # the next consumer's flush barrier (join phase A, the
                # exchange, or to_arrow) performs the verification and
                # the redo closure recomputes the whole chain exactly.
                def _lazy_unresolved(v):
                    st = getattr(v, "_staged", None)
                    return st is not None and not st.resolved and \
                        getattr(v, "_val", None) is None
                spec = getattr(partials[0], "_speculative", None) \
                    if len(partials) == 1 else None
                spec_unresolved = spec is not None and any(
                    _lazy_unresolved(f) for f in spec.fits)
                count_unresolved = len(partials) == 1 and \
                    _lazy_unresolved(partials[0]._rows)
                # Deferring EITHER forcing point (the speculative fit
                # flag, or the host count the compaction slice needs)
                # saves a full device round trip — legal only when this
                # node's consumer provably holds its own flush barrier.
                defer = (self.mode != PARTIAL and len(partials) == 1 and
                         getattr(self, "allow_deferred_verify", False) and
                         (spec_unresolved or count_unresolved))
                if not defer and (len(partials) > 1 or
                                  self.mode != PARTIAL):
                    partials = [resolve_speculative(p) for p in partials]
                    partials = [self._compact_partial(p) for p in partials]
                merged = concat_batches(partials) if len(partials) > 1 \
                    else partials[0]
                out = self._merge_finalize(merged,
                                           multiple=len(partials) > 1)
                # slots of group state carried into a merge and yielded
                _obs_trace.count(
                    "agg.groups_capacity", out.capacity +
                    (merged.capacity if len(partials) > 1 else 0))
                if defer and spec is not None:
                    out_spec = getattr(out, "_speculative", None)

                    def redo_chain(spec=spec):
                        fixed = resolve_speculative(spec.redo())
                        fixed = self._compact_partial(fixed)
                        return resolve_speculative(
                            self._merge_finalize(fixed, multiple=False))
                    fits = list(spec.fits) + (
                        list(out_spec.fits) if out_spec is not None else [])
                    out._speculative = SpeculativeResult(fits, redo_chain)
                elif self.mode != PARTIAL and not getattr(
                        self, "allow_deferred_verify", False):
                    # the merge itself may have attached a compaction
                    # fit flag; an unmarked consumer (e.g. a Project)
                    # would silently DROP it and consume a truncated
                    # batch, so verify here (PARTIAL outputs flow to
                    # the exchange, which always verifies)
                    out = resolve_speculative(out)
            self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
            yield out
        return [run(p) for p in self.children[0].execute()]

    @staticmethod
    def _compact_partial(b: ColumnarBatch) -> ColumnarBatch:
        """Shrink a group-compact batch (rows 0..G-1 live) to its bucket
        capacity once the group count is host-visible."""
        n = b.num_rows
        cap = bucket_capacity(max(n, 1))
        if cap >= b.capacity:
            return b
        return b.slice(0, max(n, 1))

    def _update_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        """One input batch -> buffer batch: the update of a PARTIAL or
        COMPLETE aggregate; a FINAL one's input is buffer-shaped already
        and merges within the batch."""
        if self.mode == FINAL:
            return self._aggregate_batch(batch, FINAL, emit_buffers=True)
        return self._aggregate_batch(batch, PARTIAL)

    def _merge_finalize(self, merged: ColumnarBatch,
                        multiple: bool) -> ColumnarBatch:
        if self.mode == PARTIAL:
            if not multiple:
                return merged
            # merge duplicate keys across partials, stay in buffer form
            return self._aggregate_batch(merged, FINAL, emit_buffers=True)
        return self._aggregate_batch(merged, FINAL)

    def _out_schema(self, emit_buffers: bool) -> Schema:
        """What one ``_aggregate_batch`` step yields: buffers, or this
        node's finalized columns (a PARTIAL node only emits buffers)."""
        return buffer_schema(self.group_exprs, self.aggs) \
            if emit_buffers else self.output_schema

    # -- the one-program cores ---------------------------------------------
    # what a core's update / merge can trace (``collect_*`` cannot)
    _FUSABLE_FUNCS = (ea.Sum, ea.Count, ea.Min, ea.Max, ea.Average,
                      ea.First, ea.Last, ea.CentralMoment)
    # class-level jit cache, keyed by everything the traced closure
    # captures: plans are rebuilt per query, the programs outlive them
    _CORE_CACHE = {}

    def _run_core(self, cache_key, build, args, batch: ColumnarBatch,
                  what: str, warmer=None):
        """The tail the three cores share: look ``cache_key`` up in
        ``_CORE_CACHE`` (a key whose core failed before stays failed),
        on a miss ``build()`` the jitted program behind ``wrap_miss``
        and register ``warmer`` = (AOT program name, ``warm(core,
        bucket)``) for it, note the demand at the batch's capacity and
        call.  A core that raises is logged, cached as ``False`` and
        answered with None: the caller falls back."""
        cache = TpuHashAggregate._CORE_CACHE
        core = cache.get(cache_key)
        compile_cache_event("hash_aggregate", core is not None)
        if core is False:
            return None
        if core is None:
            core = cache[cache_key] = _compile_watch.wrap_miss(
                "hash_aggregate", build(), str(cache_key))
            if warmer is not None:
                program, warm = warmer
                _aot.register_warmer(
                    program, lambda bucket, core=core: warm(core, bucket),
                    str(hash(cache_key)))
        _aot.note_demand("hash_aggregate", batch.capacity,
                         _costplane.rows_if_resolved(batch))
        try:
            out = core(*args)
            if cache_key in _KEY_WORDS:         # a grouped core
                _obs_trace.count("agg.key_words", _KEY_WORDS[cache_key])
            return out
        except Exception:  # noqa: BLE001 - fall back, but loudly
            logging.getLogger("spark_rapids_tpu.exec.aggregate").warning(
                "%s aggregate core failed; falling back", what,
                exc_info=True)
            cache[cache_key] = False
            return None

    def _fused_agg_core(self, key_cols, input_cols, update_mode: bool,
                        batch: ColumnarBatch, emit_buffers: bool,
                        out_cap: Optional[int] = None):
        """keys->words->plan->update/merge->output assembly as ONE jitted
        computation (``_group_reduce``), returning (num_groups, fit,
        output columns in schema order) (``out_cap``/``fit``: speculative
        device-side compaction, see _fused_whole_stage_core).

        Takes every grouped update whose pre_ops ran eagerly and every
        merge (``update_mode=False``): fixed-width keys as (data,
        validity), STRING keys as their packed words
        (``_pack_string_key``), fixed-width inputs.  What stays on the
        eager path of ``_aggregate_batch``: STRING or nested aggregate
        inputs, functions outside ``_FUSABLE_FUNCS`` (``collect_*``),
        ``exactDouble``, capacities over 2^22.

        The whole grouping pipeline is device-pure (the only host sync is
        the group count, pulled after); fusing it collapses the eager
        path's launches (for TPC-H Q1 99 capacity-sized takes and 7
        float64 scatters a batch, PERF.md section 4) into one — the same
        rationale as exec/fused.py, applied to the aggregate hot loop
        (aggregate.scala:366 computeAggregate role).
        """
        from ..columnar.binary64 import exact_double_enabled
        from ..columnar.column import StringColumn
        if exact_double_enabled():
            # traced reassembly would strip Binary64Columns
            return None
        if batch.capacity > _CORE_MAX_CAPACITY:
            return None
        if not all(type(c) is Column or isinstance(c, StringColumn)
                   for c in key_cols):
            return None
        for cols in input_cols:
            if not all(c is None or type(c) is Column for c in cols):
                return None
        if not all(isinstance(a.func, self._FUSABLE_FUNCS)
                   for a in self.aggs):
            return None
        in_dts = tuple(tuple(None if c is None else c.dtype for c in cols)
                       for cols in input_cols)
        aggs = self.aggs
        # one program for every batch of this exec: a key's words are
        # sized by the widest bound a batch of it has had (under a
        # ROLLUP each grouping set NULLs other keys, and a bound a
        # projection would be nine update programs of 30-45 s each to
        # compile where the finest set's serves all nine)
        widest = self._ws_memo.setdefault(("key_bounds", update_mode), {})
        packed = {}
        for i, c in enumerate(key_cols):
            if c.dtype == T.STRING:
                packed[i] = _pack_string_key(c, batch.rows_dev,
                                             widest.get(i, 0))
                widest[i] = packed[i][1]
        # a STRING key's dtype stands with its byte bound in the key
        key_dts = tuple((c.dtype, packed[i][1]) if i in packed else c.dtype
                        for i, c in enumerate(key_cols))
        cache_key = (update_mode, emit_buffers, key_dts, in_dts, out_cap,
                     _agg_signature(aggs))

        def _core(key_arrays, in_arrays, num_rows):
            kcols = [canon.PackedStringKey(d, v, dt[1])
                     if isinstance(dt, tuple) else Column(dt, d, v)
                     for dt, (d, v) in zip(key_dts, key_arrays)]
            it = iter(in_arrays)
            agg_cols = [[None if dt is None else Column(dt, *next(it))
                         for dt in dts] or [None] for dts in in_dts]
            return _group_reduce(kcols, None, num_rows, aggs, agg_cols,
                                 update_mode, out_cap, emit_buffers,
                                 cache_key)

        key_nps = tuple(None if isinstance(dt, tuple) else dt.np_dtype
                        for dt in key_dts)
        in_nps = tuple(dt.np_dtype for dts in in_dts for dt in dts
                       if dt is not None)

        def warm(core, bucket: int) -> None:
            ka = tuple((jnp.zeros(bucket, d), jnp.zeros(bucket, jnp.bool_))
                       for d in key_nps)
            ia = tuple((jnp.zeros(bucket, d), jnp.zeros(bucket, jnp.bool_))
                       for d in in_nps)
            core(ka, ia, jnp.int32(0))
        # flat arg list, None inputs omitted (the dtypes tuple encodes
        # which are None — no placeholder transfers)
        in_arrays = tuple(
            (c.data, c.validity)
            for cols in input_cols for c in cols if c is not None)
        key_arrays = tuple(
            packed[i][0] if i in packed else (c.data, c.validity)
            for i, c in enumerate(key_cols))
        out = self._run_core(
            cache_key,
            lambda: _compile_watch.jit(_core, "agg_grouped_core"),
            (key_arrays, in_arrays, batch.rows_dev), batch, "grouped",
            warmer=None if None in key_nps + in_nps
            else ("hash_aggregate_grouped", warm))
        if out is None:
            return None
        ng, fit, pairs = out
        return ng, fit, _output_columns(
            self._out_schema(emit_buffers), pairs,
            {i: c for i, c in enumerate(key_cols) if c.dtype == T.STRING})

    # -- sort-free bucket-table path ---------------------------------------
    # (kernels/aggregate.py table_bucket / table_reduce; the cuDF-hash-
    # groupby role done the TPU way: mixed-radix bucket ids + small-output
    # scatters, no sort, speculative dispatch verified by a device-side
    # fit flag.)  Taken by what it admits, not by an option: integer-
    # family keys over plain columns at a capacity of at least
    # ``sql.agg.tableSize``.  On the chip it is 4.5-9x ahead of the
    # grouped core on such a key at 2^20 slots (PERF.md section 5).

    @staticmethod
    def _table_key_ok(dt) -> bool:
        return (dt.is_integral or dt == T.BOOL or
                dt in (T.DATE, T.TIMESTAMP) or
                isinstance(dt, T.DecimalType))

    def _table_prepare(self, src_schema):
        """Guards + lowering descriptors for the table path; False when
        this (pre_ops, schema, aggs) can never use it."""
        from ..config import get_active, VARIABLE_FLOAT_AGG
        from ..expr import aggregates as ea
        from .fused import _tree_fusable, expr_signature
        from .staged import ops_fusable, ops_signature
        fast_float = get_active().get(VARIABLE_FLOAT_AGG)
        if self.pre_ops:
            if not ops_fusable(self.pre_ops):
                return False
            osig = ops_signature(self.pre_ops)
            if osig is None:
                return False
            post_schema = self.pre_ops[-1][2]
        else:
            osig = ""
            post_schema = src_schema
        try:
            bound_keys = [e.bind(post_schema) for e in self.group_exprs]
            bound_inputs = [[c.bind(post_schema) for c in a.func.children]
                            for a in self.aggs]
        except KeyError:
            return False
        if not bound_keys:
            return False
        if not all(_tree_fusable(e) and self._table_key_ok(e.dtype())
                   for e in bound_keys):
            return False
        for bs in bound_inputs:
            if not all(_tree_fusable(e) for e in bs):
                return False
        # per-agg lowering descriptor
        descs = []
        for a, bs in zip(self.aggs, bound_inputs):
            f = a.func
            cdt = bs[0].dtype() if bs else None
            if isinstance(f, ea.Count):
                descs.append(("count",))
            elif isinstance(f, ea.Sum):
                if cdt is None or not cdt.is_fractional:
                    return False    # exact int/decimal sums: sort path
                # exact (default) float mode: accumulate the row in the
                # device's full f64 representation — a 64-bit scatter
                # lane beside the f32 reduce rows, no f32 narrowing,
                # no overflow fit constraint
                descs.append(("fsum",) if fast_float else ("fsum64",))
            elif isinstance(f, ea.Average):
                if cdt is None or not cdt.is_fractional:
                    return False
                descs.append(("avg",) if fast_float else ("favg64",))
            elif isinstance(f, (ea.Min, ea.Max)):
                want_max = isinstance(f, ea.Max)
                if cdt == T.FLOAT32:
                    descs.append(("fminmax", want_max))
                elif cdt is not None and cdt.is_fractional:
                    descs.append(("fminmax", want_max) if fast_float
                                 else ("fminmax64", want_max))
                elif cdt is not None and self._table_key_ok(cdt):
                    descs.append(("iminmax", want_max))
                else:
                    return False
            elif isinstance(f, (ea.First, ea.Last)):
                if cdt is None or cdt == T.STRING or cdt.is_nested:
                    return False
                descs.append(("firstlast", isinstance(f, ea.Last),
                              getattr(f, "ignore_nulls", True)))
            else:
                return False
        ksigs = [expr_signature(e) for e in bound_keys]
        isigs = [tuple(expr_signature(e) for e in bs)
                 for bs in bound_inputs]
        if any(s is None for s in ksigs) or \
                any(s is None for t in isigs for s in t):
            return False
        cache_key = ("table", osig, tuple(ksigs),
                     tuple(x for t in isigs for x in t),
                     tuple(f.dtype.name for f in src_schema),
                     tuple(descs), fast_float)
        return cache_key, bound_keys, bound_inputs, descs

    def _fused_table_core(self, batch: ColumnarBatch):
        """pre_ops + key eval + bucket-table aggregation as ONE program.

        Returns a buffer-schema ColumnarBatch (capacity = table size)
        carrying a SpeculativeResult, or None to use the general path."""
        from ..config import get_active, AGG_TABLE_SIZE
        from ..columnar.binary64 import exact_double_enabled
        if exact_double_enabled():
            return None
        table = int(get_active().get(AGG_TABLE_SIZE))
        # capacity cap is 2^24: all reduce rows are f32, so per-group
        # counts and first/last positions are exact only up to 2^24
        # (f32 integer-exact range); a larger batch could silently
        # saturate Count or round a First/Last position
        if batch.capacity < table or batch.capacity > (1 << 24) or \
                not batch.columns:
            return None
        if not all(type(c) is Column for c in batch.columns):
            return None
        if self._ws_memo.get("table_state") == "off":
            return None
        mkey = ("tprep", tuple(f.dtype.name for f in batch.schema))
        prep = self._ws_memo.get(mkey)
        if prep is None:
            prep = self._table_prepare(batch.schema)
            self._ws_memo[mkey] = prep
        if prep is False:
            return None
        cache_key, bound_keys, bound_inputs, descs = prep
        # i32 chunk-lane sums are exact only while a bucket's lane sum
        # stays under 2^31: |hr+lr| <= 510/row/lane -> max 2^22 rows
        if batch.capacity > (1 << 22) and \
                any(d[0] in ("fsum64", "favg64") for d in descs):
            return None
        datas = tuple(c.data for c in batch.columns)
        valids = tuple(c.validity for c in batch.columns)
        out = self._run_core(
            (cache_key, table),
            lambda: _compile_watch.jit(self._build_table_core(
                batch.schema, bound_keys, bound_inputs, descs, table),
                "agg_table_core"),
            (datas, valids, batch.rows_dev), batch, "table")
        if out is None:
            return None
        fit, ng, key_pairs, buf_groups = out
        out_cols = [Column(e.dtype(), d, v)
                    for e, (d, v) in zip(bound_keys, key_pairs)]
        for a, pairs in zip(self.aggs, buf_groups):
            dts = a.func.buffer_dtypes()
            out_cols.extend(Column(dt, d, v)
                            for dt, (d, v) in zip(dts, pairs))
        out = ColumnarBatch(buffer_schema(self.group_exprs, self.aggs),
                            out_cols, LazyCount(ng))

        def redo():
            # the table did not fit: this batch is computed a second
            # time on the sort path, and later ones skip the table
            _obs_trace.count("agg.table.misfit")
            self._ws_memo["table_state"] = "off"
            return self._aggregate_batch(batch, PARTIAL, no_table=True)
        out._speculative = SpeculativeResult([LazyCount(fit)], redo)
        return out

    def _build_table_core(self, src_schema, bound_keys, bound_inputs,
                          descs, table: int):
        """Build the traced table-aggregation program.

        One pass: mixed-radix bucket ids (kernels/aggregate.table_bucket),
        then ONE table reduce (kernels/aggregate.table_reduce) covering
        every sum/count row (one stacked scatter-add) and every min/max
        row (a scatter-max each; mins ride negated).  Exact float mode
        adds 64-bit lanes (fsum64/favg64/fminmax64) reduced by direct
        small-output scatters in the device's full f64 representation.
        All f32 reduce rows; integer min/max and first/last positions are exact
        because the fit flag restricts them to the f32-exact integer
        range (2^24) — non-fitting batches re-run on the sort path."""
        from .fused import _TracedBatch
        from .staged import apply_ops_masked
        pre_ops = self.pre_ops
        SIGN = 0x8000000000000000

        def decode_word(dtype, word):
            if dtype == T.BOOL:
                return word != 0
            v = (word ^ jnp.uint64(SIGN)).astype(jnp.int64)
            return v.astype(dtype.np_dtype)

        def _core(datas, valids, num_rows):
            # made under the trace: a device array closed over from
            # outside is pulled to the host when the program lowers,
            # which the residency guard refuses (so the core failed and
            # fell back on every guarded query before PR 30)
            NEG_INF = jnp.float32(-jnp.inf)
            F32_EXACT = jnp.uint64(1 << 24)
            cap = datas[0].shape[0]
            cols = [Column(f.dtype, d, v)
                    for f, d, v in zip(src_schema, datas, valids)]
            b = _TracedBatch(src_schema, cols, num_rows, cap)
            live = jnp.arange(cap) < num_rows
            b, live = apply_ops_masked(pre_ops or (), b, live)
            kcols = [ec.eval_as_column(e, b) for e in bound_keys]
            kwords = [canon.value_words(c, b.num_rows)[0] for c in kcols]
            kvalids = [c.validity for c in kcols]
            bucket, fit, mins, cards = agg_k.table_bucket(
                kwords, kvalids, live, table)
            icols = [[ec.eval_as_column(e, b) for e in bs] or [None]
                     for bs in bound_inputs]
            live_f = jnp.where(live, 1.0, 0.0).astype(jnp.float32)

            # collect every reduce row for the ONE fused table-reduce.
            # Shared rows (counts, chunk decompositions) are keyed by the
            # bound input expression, so sum(x)+avg(x)+min(x) share one
            # count row and one 15-lane chunk decomposition.
            sum_rows, max_rows = [jnp.asarray(live_f)], []
            srow_of, mrow_of = {"__ones__": 0}, {}
            dks = [repr(bs[0]) if bs else ("*", i)
                   for i, bs in enumerate(bound_inputs)]
            chunk_of = {}            # dk -> (lane0, emax)
            # exact float mode, ALL 32-bit (64-bit scatters cost ~5x):
            # - sums: each f64 value splits into its two f32 components,
            #   each component into signed 8-bit integer chunks on a
            #   120-bit window anchored at the column's batch max
            #   exponent; the i32 chunk lanes ride ONE stacked i32
            #   scatter (exact: lane sums < 2^31), recombined per
            #   bucket in the output phase.  A fit flag sends batches
            #   with >2^63 exponent spread to the sort path.
            # - min/max: two-stage u32 scatter-max over the (hi, lo)
            #   pair order-words.
            chunk_rows = []                # i32 lanes, one scatter
            mm_hi_rows, mm_lo_src = [], []  # two-stage u32 minmax
            agg_meta = []   # per agg: lowering info for the output phase

            def add_sum(tag, arr):
                if tag not in srow_of:
                    srow_of[tag] = len(sum_rows)
                    sum_rows.append(arr)

            def add_max(tag, arr):
                mrow_of[tag] = len(max_rows)
                max_rows.append(arr)

            for ai, (a, cols_a) in enumerate(zip(self.aggs, icols)):
                kind = descs[ai][0]
                dk = dks[ai]
                c = cols_a[0]
                if kind == "count":
                    if c is not None:
                        add_sum(("cnt", dk),
                                jnp.where(live & c.validity, 1.0, 0.0)
                                .astype(jnp.float32))
                    agg_meta.append(None)
                elif kind in ("fsum", "avg"):
                    ok = live & c.validity
                    v32 = c.data.astype(jnp.float32)
                    fit = fit & jnp.all(
                        jnp.where(ok, jnp.isfinite(v32), True))
                    add_sum(("sum", dk), jnp.where(ok, v32, 0.0))
                    add_sum(("cnt", dk),
                            jnp.where(ok, 1.0, 0.0).astype(jnp.float32))
                    agg_meta.append(None)
                elif kind in ("fsum64", "favg64"):
                    ok = live & c.validity
                    v = c.data.astype(jnp.float64)
                    fin = jnp.isfinite(v)
                    okf = ok & fin
                    add_sum(("cnt", dk),
                            jnp.where(ok, 1.0, 0.0).astype(jnp.float32))
                    add_sum(("nan", dk),
                            jnp.where(ok & jnp.isnan(v), 1.0, 0.0)
                            .astype(jnp.float32))
                    add_sum(("pinf", dk),
                            jnp.where(ok & jnp.isposinf(v), 1.0, 0.0)
                            .astype(jnp.float32))
                    add_sum(("ninf", dk),
                            jnp.where(ok & jnp.isneginf(v), 1.0, 0.0)
                            .astype(jnp.float32))
                    vq = jnp.where(okf, v, 0.0)
                    hi32 = vq.astype(jnp.float32)
                    # finite f64 beyond f32 range: hi overflows to inf
                    # and the chunk lattice cannot hold it (same
                    # contract as the fminmax f32 path below)
                    fit = fit & jnp.all(
                        jnp.where(okf, jnp.isfinite(hi32), True))
                    lo32 = (vq - hi32.astype(jnp.float64)) \
                        .astype(jnp.float32)
                    ehi, sighi, _ = _f32_exp(hi32)
                    contrib = okf & (sighi != jnp.uint32(0))
                    emax = jnp.max(jnp.where(contrib, ehi, jnp.int32(0)))
                    emin = jnp.min(jnp.where(contrib, ehi,
                                             jnp.int32(255)))
                    # spread beyond the window -> exact sort path
                    fit = fit & ((emax - emin) <= jnp.int32(CH_W0 - 25))
                    hrows = _part_chunk_rows(hi32, contrib, emax)
                    lrows = _part_chunk_rows(lo32, okf, emax)
                    lane0 = len(chunk_rows)
                    for hr, lr in zip(hrows, lrows):
                        chunk_rows.append(hr + lr)
                    agg_meta.append(("chunks", lane0, emax))
                elif kind == "fminmax64":
                    want_max = descs[ai][1]
                    ok = live & c.validity
                    v = c.data.astype(jnp.float64)
                    # Spark total order: NaN greatest, -0.0 == 0.0
                    v = jnp.where(v == 0.0, jnp.float64(0.0), v)
                    nan = jnp.isnan(v)
                    okn = ok & ~nan
                    add_sum(("cnt", dk),
                            jnp.where(ok, 1.0, 0.0).astype(jnp.float32))
                    add_sum(("nn", dk),
                            jnp.where(okn, 1.0, 0.0).astype(jnp.float32))
                    hi32 = v.astype(jnp.float32)
                    # finite f64 beyond f32 range would alias real inf
                    fit = fit & jnp.all(
                        jnp.where(ok & jnp.isfinite(v),
                                  jnp.isfinite(hi32), True))
                    # +/-inf: hi carries the order; v-hi is NaN -> 0
                    lo32 = jnp.where(
                        jnp.isfinite(v),
                        (v - hi32.astype(jnp.float64)), 0.0) \
                        .astype(jnp.float32)
                    whi = _flip32(hi32)
                    if not want_max:
                        whi = ~whi
                    whi = jnp.where(okn, whi, jnp.uint32(0))
                    mi = len(mm_hi_rows)
                    mm_hi_rows.append(whi)
                    mm_lo_src.append((lo32, okn, want_max))
                    agg_meta.append(("mm", mi))
                elif kind == "fminmax":
                    want_max = descs[ai][1]
                    ok = live & c.validity
                    v32 = c.data.astype(jnp.float32)
                    # finite f64 whose f32 cast overflows to +/-inf would
                    # silently corrupt min/max: detect on device and send
                    # the batch to the exact path (same contract as
                    # fsum/avg above)
                    fit = fit & jnp.all(
                        jnp.where(ok & jnp.isfinite(c.data),
                                  jnp.isfinite(v32), True))
                    # Spark total order: NaN greatest, -0.0 == 0.0
                    v32 = jnp.where(v32 == 0.0, jnp.float32(0.0), v32)
                    nan = jnp.isnan(v32)
                    add_sum(("cnt", dk),
                            jnp.where(ok, 1.0, 0.0).astype(jnp.float32))
                    add_sum(("nn", dk),
                            jnp.where(ok & ~nan, 1.0, 0.0)
                            .astype(jnp.float32))
                    add_max(("m", ai),
                            jnp.where(ok & ~nan,
                                      v32 if want_max else -v32, NEG_INF))
                    agg_meta.append(None)
                elif kind == "iminmax":
                    want_max = descs[ai][1]
                    ok = live & c.validity
                    w = canon.value_words(c, b.num_rows)[0]
                    any_v = jnp.any(ok)
                    vmin = jnp.where(
                        any_v,
                        jnp.min(jnp.where(ok, w, jnp.uint64(2**64 - 1))),
                        jnp.uint64(0))
                    vmax = jnp.where(
                        any_v, jnp.max(jnp.where(ok, w, jnp.uint64(0))),
                        jnp.uint64(0))
                    # reduce rows are f32: exact only below 2^24
                    fit = fit & ((vmax - vmin) < F32_EXACT)
                    narrow = jnp.minimum(w - vmin, F32_EXACT) \
                        .astype(jnp.float32)
                    add_sum(("cnt", dk),
                            jnp.where(ok, 1.0, 0.0).astype(jnp.float32))
                    add_max(("m", ai),
                            jnp.where(ok, narrow if want_max else -narrow,
                                      NEG_INF))
                    agg_meta.append(("vmin", vmin))
                elif kind == "firstlast":
                    want_last, ignore_nulls = descs[ai][1], descs[ai][2]
                    ok = (live & c.validity) if ignore_nulls else live
                    pos = jnp.arange(cap, dtype=jnp.int32) \
                        .astype(jnp.float32)
                    add_max(("m", ai),
                            jnp.where(ok, pos if want_last else -pos,
                                      NEG_INF))
                    agg_meta.append(None)

            sums, maxs = agg_k.table_reduce(bucket, sum_rows, max_rows,
                                            table)
            # i32 chunk lanes: ONE stacked scatter (multi-column scatter
            # costs the same as single-column; lane sums < 2^31, exact)
            chunk_out = None
            if chunk_rows:
                with _obs_trace.launch("table_chunk_scatter", 1,
                                       bucket.shape[0]):
                    chunk_out = jax.ops.segment_sum(
                        jnp.stack(chunk_rows, 1), bucket,
                        num_segments=table + 1)[:table]
            # two-stage u32 min/max: hi words, then lo among hi-winners
            mm1 = mm2 = None
            if mm_hi_rows:
                mm1 = jax.ops.segment_max(
                    jnp.stack(mm_hi_rows, 1), bucket,
                    num_segments=table + 1)
                lo_rows = []
                for i, (lo32, okn, wmax) in enumerate(mm_lo_src):
                    win = okn & (mm_hi_rows[i] ==
                                 jnp.take(mm1[:, i], bucket))
                    wlo = _flip32(lo32)
                    if not wmax:
                        wlo = ~wlo
                    lo_rows.append(jnp.where(win, wlo, jnp.uint32(0)))
                mm2 = jax.ops.segment_max(
                    jnp.stack(lo_rows, 1), bucket,
                    num_segments=table + 1)
            counts_all = sums[0]
            present, order, ng = agg_k.table_compact(counts_all, table)
            live_g = jnp.arange(table) < ng

            def compact(tab):
                return jnp.take(tab, order)
            # keys: decode bucket digits arithmetically (no gathers)
            key_pairs = []
            strides = []
            st = jnp.int32(1)
            for card in reversed(cards):
                strides.append(st)
                st = st * card
            strides = list(reversed(strides))
            for e, wmin, card, stride in zip(bound_keys, mins, cards,
                                             strides):
                digit = (order // stride) % card
                word = wmin + (digit - 1).astype(jnp.uint64)
                data = decode_word(e.dtype(), word)
                key_pairs.append((data, (digit > 0) & live_g))
            # agg buffers
            buf_groups = []
            for ai, (a, cols_a) in enumerate(zip(self.aggs, icols)):
                kind = descs[ai][0]
                dk = dks[ai]
                c = cols_a[0]
                if kind == "count":
                    cnt = sums[srow_of[("cnt", dk)] if c is not None
                               else 0]
                    cnt = compact(cnt)
                    buf_groups.append([(
                        jnp.where(live_g, cnt, 0.0).astype(jnp.int64),
                        jnp.ones(table, bool))])
                elif kind == "fsum":
                    ssum = compact(sums[srow_of[("sum", dk)]])
                    cntv = compact(sums[srow_of[("cnt", dk)]])
                    dt = a.func.buffer_dtypes()[0]
                    buf_groups.append([(
                        ssum.astype(dt.np_dtype),
                        (cntv > 0) & live_g)])
                elif kind == "avg":
                    ssum = compact(sums[srow_of[("sum", dk)]])
                    cntv = compact(sums[srow_of[("cnt", dk)]])
                    buf_groups.append([
                        (ssum.astype(jnp.float64), live_g),
                        (cntv.astype(jnp.int64), live_g)])
                elif kind in ("fsum64", "favg64"):
                    _, lane0, emax = agg_meta[ai]
                    lanes = chunk_out[:, lane0:lane0 + CH_LANES] \
                        .astype(jnp.float64)
                    lanes = jnp.take(lanes, order, axis=0)
                    ssum = _chunk_recombine(lanes, emax)
                    nanv = compact(sums[srow_of[("nan", dk)]])
                    pinfv = compact(sums[srow_of[("pinf", dk)]])
                    ninfv = compact(sums[srow_of[("ninf", dk)]])
                    ssum = jnp.where(pinfv > 0, jnp.float64(jnp.inf),
                                     ssum)
                    ssum = jnp.where(ninfv > 0, jnp.float64(-jnp.inf),
                                     ssum)
                    ssum = jnp.where(
                        (nanv > 0) | ((pinfv > 0) & (ninfv > 0)),
                        jnp.float64(jnp.nan), ssum)
                    cntv = compact(sums[srow_of[("cnt", dk)]])
                    if kind == "fsum64":
                        buf_groups.append([(ssum, (cntv > 0) & live_g)])
                    else:
                        buf_groups.append([
                            (ssum, live_g),
                            (cntv.astype(jnp.int64), live_g)])
                elif kind == "fminmax64":
                    want_max = descs[ai][1]
                    mi = agg_meta[ai][1]
                    w1 = compact(mm1[:table, mi])
                    w2 = compact(mm2[:table, mi])
                    if not want_max:
                        w1, w2 = ~w1, ~w2
                    m = _unflip32(w1).astype(jnp.float64) + \
                        _unflip32(w2).astype(jnp.float64)
                    cntv = compact(sums[srow_of[("cnt", dk)]])
                    nnv = compact(sums[srow_of[("nn", dk)]])
                    if want_max:
                        # any NaN in the group wins
                        m = jnp.where(cntv > nnv,
                                      jnp.float64(jnp.nan), m)
                    else:
                        # min ignores NaN unless the group is all-NaN
                        m = jnp.where(nnv > 0, m, jnp.float64(jnp.nan))
                    buf_groups.append([(m, (cntv > 0) & live_g)])
                elif kind == "fminmax":
                    want_max = descs[ai][1]
                    m = compact(maxs[mrow_of[("m", ai)]])
                    if not want_max:
                        m = -m
                    cntv = compact(sums[srow_of[("cnt", dk)]])
                    nnv = compact(sums[srow_of[("nn", dk)]])
                    if want_max:
                        # any NaN in the group wins
                        m = jnp.where(cntv > nnv, jnp.float32(jnp.nan), m)
                    else:
                        # min ignores NaN unless the group is all-NaN
                        m = jnp.where(nnv > 0, m, jnp.float32(jnp.nan))
                    dt = a.func.buffer_dtypes()[0]
                    buf_groups.append([(m.astype(dt.np_dtype),
                                        (cntv > 0) & live_g)])
                elif kind == "iminmax":
                    want_max = descs[ai][1]
                    vmin = agg_meta[ai][1]
                    m = compact(maxs[mrow_of[("m", ai)]])
                    if not want_max:
                        m = -m
                    word = vmin + jnp.maximum(m, 0).astype(jnp.uint64)
                    cntv = compact(sums[srow_of[("cnt", dk)]])
                    dt = a.func.buffer_dtypes()[0]
                    buf_groups.append([(
                        decode_word(dt, word),
                        (cntv > 0) & live_g)])
                elif kind == "firstlast":
                    want_last = descs[ai][1]
                    m = compact(maxs[mrow_of[("m", ai)]])
                    has_g = (m > NEG_INF) & live_g
                    if not want_last:
                        m = -m
                    pos_g = jnp.clip(m, 0, cap - 1).astype(jnp.int32)
                    data = jnp.take(c.data, pos_g)
                    vld = jnp.take(c.validity, pos_g)
                    buf_groups.append([(data, has_g & vld)])
            return (fit.astype(jnp.int32), ng, key_pairs, buf_groups)

        return _core

    def _ws_prepare(self, src_schema):
        """One-time guards + signature derivation for the whole-stage
        core; False when this (pre_ops, schema) can never fuse."""
        from .fused import _tree_fusable, expr_signature
        from .staged import ops_maskable, ops_signature, passthrough_ordinal
        if not ops_maskable(self.pre_ops):
            return False
        osig = ops_signature(self.pre_ops)
        if osig is None:
            return False
        post_schema = self.pre_ops[-1][2]
        try:
            bound_keys = [e.bind(post_schema) for e in self.group_exprs]
            bound_inputs = [[c.bind(post_schema) for c in a.func.children]
                            for a in self.aggs]
        except KeyError:
            return False
        # a STRING key must be a source column handed through the chain
        # untouched: the core gets its packed words, the output gathers
        # its bytes from the source at the groups' representative rows
        string_keys = {}
        for i, e in enumerate(bound_keys):
            if _tree_fusable(e):
                continue
            if e.dtype() != T.STRING:
                return False
            at = passthrough_ordinal(e)
            for kind, payload, _ in reversed(self.pre_ops):
                if at is not None and kind == "project":
                    at = passthrough_ordinal(payload[at])
            if at is None:
                return False
            string_keys[i] = at
        for bs in bound_inputs:
            if not all(_tree_fusable(e) for e in bs):
                return False
        if not all(isinstance(a.func, self._FUSABLE_FUNCS)
                   for a in self.aggs):
            return False
        ksigs = [expr_signature(e) for e in bound_keys]
        isigs = [tuple(expr_signature(e) for e in bs)
                 for bs in bound_inputs]
        if any(s is None for s in ksigs) or \
                any(s is None for t in isigs for s in t):
            return False
        cache_key = ("ws", osig, tuple(ksigs),
                     tuple(x for t in isigs for x in t),
                     tuple(f.dtype.name for f in src_schema),
                     _agg_signature(self.aggs))
        return cache_key, bound_keys, bound_inputs, string_keys

    def _fused_whole_stage_core(self, batch: ColumnarBatch,
                                emit_buffers: bool = True,
                                out_cap: Optional[int] = None):
        """scan-side filter/project chain + key eval + grouping + update
        + output assembly as ONE jitted program (whole-stage codegen
        role, exec/staged.py).  Filters fold into row liveness
        (``staged.apply_ops_masked``): nothing is compacted.  A STRING
        source column enters only as a key, as its packed words; STRING
        columns nothing reads are not passed in.

        Returns (num_groups, fit, output columns in schema order) or
        None to fall back (the caller then applies pre_ops eagerly and
        tries ``_fused_agg_core``).  ``out_cap`` requests speculative
        device-side compaction to that capacity; ``fit`` is the device
        flag that the group count fit (always-1 when uncompacted)."""
        from ..columnar.column import StringColumn
        if batch.capacity > _CORE_MAX_CAPACITY or not batch.columns:
            return None
        if not all(type(c) is Column or isinstance(c, StringColumn)
                   for c in batch.columns):
            return None
        # the guard walks + signature derivation are schema-invariant:
        # compute once per (source dtypes), not per batch
        mkey = tuple(f.dtype.name for f in batch.schema)
        prep = self._ws_memo.get(mkey)
        if prep is None:
            prep = self._ws_prepare(batch.schema)
            self._ws_memo[mkey] = prep
        if prep is False:
            return None
        cache_key, bound_keys, bound_inputs, string_keys = prep
        # source ordinal -> ((words, validity), byte bound)
        packed = {at: _pack_string_key(batch.columns[at], batch.rows_dev)
                  for at in set(string_keys.values())}
        bounds = tuple(sorted((at, p[1]) for at, p in packed.items()))
        cache_key = cache_key + (emit_buffers, out_cap, bounds)
        src_schema = batch.schema
        pre_ops = self.pre_ops
        aggs = self.aggs

        def _core(datas, valids, num_rows):
            live, kcols, agg_cols = _masked_chain(
                src_schema, pre_ops, bounds, bound_keys, bound_inputs,
                datas, valids, num_rows)
            return _group_reduce(kcols, live, num_rows, aggs, agg_cols,
                                 True, out_cap, emit_buffers, cache_key)

        ws_nps = tuple(f.dtype.np_dtype for f in src_schema)

        def warm(core, bucket: int) -> None:
            ds = tuple(jnp.zeros(bucket, d) for d in ws_nps)
            vs = tuple(jnp.zeros(bucket, jnp.bool_) for _ in ws_nps)
            core(ds, vs, jnp.int32(0))
        datas, valids = [], []
        for i, c in enumerate(batch.columns):
            if type(c) is Column:
                d, v = c.data, c.validity
            elif i in packed:
                d, v = packed[i][0]
            else:
                d = v = None
            datas.append(d)
            valids.append(v)
        out = self._run_core(
            cache_key,
            lambda: _compile_watch.jit(_core, "agg_whole_stage_core"),
            (tuple(datas), tuple(valids), batch.rows_dev), batch,
            "whole-stage",
            warmer=None if None in ws_nps
            else ("hash_aggregate_whole_stage", warm))
        if out is None:
            return None
        ng, fit, pairs = out
        return ng, fit, _output_columns(
            self._out_schema(emit_buffers), pairs,
            {i: batch.columns[at] for i, at in string_keys.items()})

    # -- one batch ---------------------------------------------------------
    def _aggregate_batch(self, batch: ColumnarBatch, mode: str,
                         emit_buffers: bool = False,
                         no_table: bool = False,
                         no_compact: bool = False) -> ColumnarBatch:
        """One step over one batch: ``mode`` PARTIAL updates from input
        rows (how a COMPLETE node runs its batches too) and emits
        buffers; FINAL merges a buffer-shaped batch and finalizes unless
        ``emit_buffers``.  Top to bottom: a keyed update the bucket
        table admits -> the table core; an update whose pre_ops trace ->
        the whole-stage core, or with no keys the global core with the
        chain folded in (``agg.global.folded``); else pre_ops run
        eagerly, then no keys -> the global core; the grouped core
        takes the update, as it takes every merge; else the eager
        grouped fallback.
        ``agg.batches.{table,fused,eager}`` count where a batch settled.

        What still lands on the eager fallback: STRING or nested
        aggregate inputs, functions outside ``_FUSABLE_FUNCS``
        (``collect_*``), ``exactDouble``, capacities over 2^22."""
        update = mode == PARTIAL
        emit = emit_buffers or update
        if update and self.group_exprs and not no_table:
            t = self._fused_table_core(batch)
            if t is not None:
                _obs_trace.count("agg.batches.table")
                # a table batch that fits reads 0 misfits, not nothing
                _obs_trace.count("agg.table.misfit", 0)
                return t
        # speculative device-side compaction: hand downstream a small-
        # capacity batch instead of the input-capacity one (group counts
        # are almost always << rows); the fit flag is verified at the
        # consumer's flush barrier, a misfit recomputes uncompacted.  An
        # update's misfit turns compaction off for this exec's later
        # updates; a merge sees all its partials' groups at once and
        # says nothing of the next
        compact_cap = None
        if not no_compact and self.group_exprs and not (
                update and self._ws_memo.get("compact_state") == "off"):
            from ..config import get_active, AGG_COMPACT_ROWS
            cc = int(get_active().get(AGG_COMPACT_ROWS))
            if cc > 0 and batch.capacity > cc:
                compact_cap = cc
        source = batch

        def fused_out(core_out) -> ColumnarBatch:
            ng, fit, cols = core_out
            _obs_trace.count("agg.batches.fused")
            out = ColumnarBatch(self._out_schema(emit), cols, LazyCount(ng))
            if compact_cap is None:
                return out

            def redo():
                if update:
                    self._ws_memo["compact_state"] = "off"
                return resolve_speculative(self._aggregate_batch(
                    source, mode, emit_buffers=emit_buffers,
                    no_table=no_table, no_compact=True))
            out._speculative = SpeculativeResult([LazyCount(fit)], redo)
            return out

        if update and self.pre_ops:
            if not self.group_exprs:
                out = self._fused_global_core(batch)
                if out is not None:
                    _obs_trace.count("agg.global.folded")
                    _obs_trace.count("agg.batches.fused")
                    return out
            else:
                ws = self._fused_whole_stage_core(batch, emit,
                                                  out_cap=compact_cap)
                if ws is not None:
                    return fused_out(ws)
            from .staged import apply_ops_eager, build_fused_per_op
            fkey = ("fpo", tuple(f.dtype.name for f in batch.schema))
            fpo = self._ws_memo.get(fkey)
            if fpo is None:
                fpo = build_fused_per_op(self.pre_ops, batch.schema)
                self._ws_memo[fkey] = fpo
            batch = apply_ops_eager(self.pre_ops, batch, fpo)
        nkeys = len(self.group_exprs)
        if update:
            schema = batch.schema
            key_cols = [ec.eval_as_column(e.bind(schema), batch)
                        for e in self.group_exprs]
            input_cols = [
                [ec.eval_as_column(c.bind(schema), batch)
                 for c in a.func.children] or [None] for a in self.aggs]
        else:       # keys + buffers laid out by buffer_schema
            key_cols = batch.columns[:nkeys]
            input_cols, pos = [], nkeys
            for a in self.aggs:
                input_cols.append(
                    batch.columns[pos: pos + a.func.num_buffers])
                pos += a.func.num_buffers
        if not nkeys:
            return self._global_agg(batch, input_cols, update, emit)
        fused = self._fused_agg_core(key_cols, input_cols, update, batch,
                                     emit, out_cap=compact_cap)
        if fused is not None:
            return fused_out(fused)
        _obs_trace.count("agg.batches.eager")
        return self._eager_grouped(batch, key_cols, input_cols, update,
                                   emit)

    def _eager_grouped(self, batch: ColumnarBatch, key_cols, input_cols,
                       update: bool, emit: bool) -> ColumnarBatch:
        """The grouped fallback: the same sort + segmented reduce, one
        jax launch an operation, output at the input's capacity."""
        words = canon.batch_key_words(key_cols, batch.rows_dev)
        plan = agg_k.groupby_plan(words)
        # group count stays on device: a per-batch int(num_groups) pull
        # would sync the host with the device once per batch (LazyCount
        # doc); output capacity = input capacity (groups <= rows) so no
        # host value is needed to shape the result
        ng = plan.num_groups
        out_cap = batch.capacity
        live = jnp.arange(out_cap) < ng
        # compact group keys: representative original-row indices
        rep = plan.rep_indices
        take = jnp.where(live,
                         rep[:out_cap] if out_cap <= rep.shape[0] else
                         jnp.pad(rep, (0, out_cap - rep.shape[0]))[:out_cap],
                         0)
        out_cols = gather_columns(key_cols, take, live, unique=True)
        # agg outputs: buffer arrays are already segment-indexed
        seg_take = jnp.where(live, jnp.arange(out_cap), 0)
        outs = []
        for a, cols in zip(self.aggs, input_cols):
            bufs = a.func.update(plan, cols) if update else \
                a.func.merge(plan, cols)
            for o in (bufs if emit else [a.func.finalize(bufs)]):
                assert o.capacity >= out_cap, (o.capacity, out_cap)
                outs.append(o)
        out_cols += gather_columns(outs, seg_take, live, unique=True)
        return ColumnarBatch(self._out_schema(emit), out_cols, LazyCount(ng))

    def _global_agg(self, batch: ColumnarBatch,
                    input_cols: List[List[Column]], update_mode: bool,
                    emit: bool) -> ColumnarBatch:
        """No group keys: aggregate everything into one row
        (``_global_reduce``) as one jitted program, ``agg_global_core``;
        exotic columns (Binary64, strings) run the same body eagerly."""
        aggs = self.aggs
        in_dts = tuple(tuple(None if c is None else c.dtype for c in cols)
                       for cols in input_cols)
        cap = batch.capacity  # an int: the cached core must not pin the batch

        def _core(in_arrays, num_rows):
            it = iter(in_arrays)
            agg_cols = [[None if dt is None else Column(dt, *next(it))
                         for dt in dts] or [None] for dts in in_dts]
            return _global_reduce(aggs, agg_cols, jnp.arange(cap) < num_rows,
                                  update_mode, emit)

        in_arrays = tuple((c.data, c.validity)
                          for cols in input_cols for c in cols
                          if c is not None)
        pairs = None
        if all(c is None or type(c) is Column
               for cols in input_cols for c in cols):
            cache_key = ("global", update_mode, emit, in_dts, cap,
                         _agg_signature(aggs))
            pairs = self._run_global(cache_key, _core,
                                     (in_arrays, batch.rows_dev), batch)
        _obs_trace.count("agg.batches.eager" if pairs is None
                         else "agg.batches.fused")
        if pairs is None:
            pairs = _global_reduce(aggs, input_cols,
                                   jnp.arange(cap) < batch.rows_dev,
                                   update_mode, emit)
        return self._global_batch(pairs, emit)

    def _run_global(self, cache_key, body, args, batch: ColumnarBatch):
        """``body`` as the one global program, ``agg_global_core``."""
        return self._run_core(
            cache_key, lambda: _compile_watch.jit(body, "agg_global_core"),
            args, batch, "global")

    def _global_batch(self, pairs, emit: bool) -> ColumnarBatch:
        out_schema = self._out_schema(emit)
        return ColumnarBatch(out_schema, [Column(f.dtype, d, v) for f, (d, v)
                                          in zip(out_schema, pairs)], 1)

    def _fused_global_core(self, batch: ColumnarBatch):
        """A global update's filter / project chain, its inputs and the
        reduce as ONE program (the same ``agg_global_core``): filters
        fold into row liveness (``staged.apply_ops_masked``) and
        projections evaluate at the batch's capacity, so nothing is
        compacted.  Returns the buffer batch, or None when the chain or
        an input cannot trace (the caller runs the chain eagerly)."""
        from ..columnar.binary64 import exact_double_enabled
        from ..columnar.column import StringColumn
        if exact_double_enabled() or batch.capacity > _CORE_MAX_CAPACITY:
            return None
        if not any(type(c) is Column for c in batch.columns) or not all(
                type(c) is Column or isinstance(c, StringColumn)
                for c in batch.columns):
            return None
        mkey = tuple(f.dtype.name for f in batch.schema)
        prep = self._ws_memo.get(mkey)
        if prep is None:
            prep = self._ws_memo[mkey] = self._ws_prepare(batch.schema)
        if prep is False:
            return None
        src_schema = batch.schema
        pre_ops = self.pre_ops
        aggs = self.aggs
        bound_inputs = prep[2]

        def _core(datas, valids, num_rows):
            live, _, agg_cols = _masked_chain(
                src_schema, pre_ops, (), (), bound_inputs, datas, valids,
                num_rows)
            return _global_reduce(aggs, agg_cols, live, True, True)

        datas = tuple(c.data if type(c) is Column else None
                      for c in batch.columns)
        valids = tuple(c.validity if type(c) is Column else None
                       for c in batch.columns)
        pairs = self._run_global(prep[0] + ("global",), _core,
                                 (datas, valids, batch.rows_dev), batch)
        return None if pairs is None else self._global_batch(pairs, True)


# ---------------------------------------------------------------------------
# program audit registration (analysis/program_audit.py): the four
# hash_aggregate core sites (_fused_agg_core, _fused_whole_stage_core,
# _global_agg, _fused_global_core) build their programs per-batch inside
# the exec, so each provider DRIVES a tiny CPU batch through the real
# site and then pulls the freshly cached core out of _CORE_CACHE for
# abstract tracing.
# ---------------------------------------------------------------------------

def _int_col(cap, fill=None):
    data = jnp.arange(cap, dtype=jnp.int64) if fill is None \
        else jnp.full((cap,), fill, jnp.int64)
    return Column(T.INT64, data, jnp.ones((cap,), bool))


def _audit_agg(group=True):
    agg = object.__new__(TpuHashAggregate)
    agg.aggs = [AggExpr(ea.Sum(ec.BoundReference(1 if group else 0,
                                                 T.INT64)), "s")]
    agg.group_exprs = [ec.BoundReference(0, T.INT64)] if group else []
    agg.pre_ops = None
    agg.mode = PARTIAL
    agg._ws_memo = {}
    return agg


def _cached_core(cache_key, what):
    core = TpuHashAggregate._CORE_CACHE.get(cache_key)
    if core is None or core is False:
        raise RuntimeError(
            f"audit drive did not populate the {what} core under the "
            f"reconstructed cache key {cache_key!r}")
    return core


def _audit_specs():
    import jax
    import numpy as np
    from ..analysis.program_audit import AuditSpec

    def _pair_sds(cap):
        return (jax.ShapeDtypeStruct((cap,), np.int64),
                jax.ShapeDtypeStruct((cap,), np.bool_))

    def _grouped():
        agg = _audit_agg()
        cap = 16
        key_col, val_col = _int_col(cap), _int_col(cap, 1)
        schema = Schema([Field("k", T.INT64, True),
                         Field("v", T.INT64, True)])
        batch = ColumnarBatch(schema, [key_col, val_col], 8)
        out = agg._fused_agg_core([key_col], [[val_col]], True, batch,
                                  False)
        assert out is not None, "grouped agg core fell back"
        cache_key = (True, False, (T.INT64,), ((T.INT64,),), None,
                     _agg_signature(agg.aggs))
        core = _cached_core(cache_key, "grouped")
        c = batch.capacity
        args = ((_pair_sds(c),), (_pair_sds(c),),
                jax.ShapeDtypeStruct((), np.int32))
        return core, args, {}

    def _whole_stage():
        from ..expr.predicates import GreaterThan
        agg = _audit_agg()
        schema = Schema([Field("k", T.INT64, True),
                         Field("v", T.INT64, True)])
        agg.pre_ops = [("filter",
                        GreaterThan(ec.BoundReference(1, T.INT64),
                                    ec.lit(0)), schema)]
        cap = 16
        batch = ColumnarBatch(schema, [_int_col(cap), _int_col(cap, 1)],
                              8)
        out = agg._fused_whole_stage_core(batch, emit_buffers=True)
        assert out is not None, "whole-stage agg core fell back"
        mkey = tuple(f.dtype.name for f in batch.schema)
        prep = agg._ws_memo[mkey]
        cache_key = prep[0] + (True, None, ())
        core = _cached_core(cache_key, "whole-stage")
        c = batch.capacity
        d = jax.ShapeDtypeStruct((c,), np.int64)
        v = jax.ShapeDtypeStruct((c,), np.bool_)
        args = ((d, d), (v, v), jax.ShapeDtypeStruct((), np.int32))
        return core, args, {}

    def _global():
        agg = _audit_agg(group=False)
        cap = 16
        val_col = _int_col(cap, 1)
        schema = Schema([Field("v", T.INT64, True)])
        batch = ColumnarBatch(schema, [val_col], 8)
        agg._global_agg(batch, [[val_col]], True, True)
        cache_key = ("global", True, True, ((T.INT64,),),
                     batch.capacity, _agg_signature(agg.aggs))
        core = _cached_core(cache_key, "global")
        c = batch.capacity
        args = ((_pair_sds(c),), jax.ShapeDtypeStruct((), np.int32))
        return core, args, {}

    def _global_folded():
        from ..expr.predicates import GreaterThan
        agg = _audit_agg(group=False)
        schema = Schema([Field("v", T.INT64, True)])
        agg.pre_ops = [("filter",
                        GreaterThan(ec.BoundReference(0, T.INT64),
                                    ec.lit(0)), schema)]
        batch = ColumnarBatch(schema, [_int_col(16, 1)], 8)
        assert agg._fused_global_core(batch) is not None, \
            "folded global core fell back"
        mkey = tuple(f.dtype.name for f in schema)
        core = _cached_core(agg._ws_memo[mkey][0] + ("global",),
                            "folded global")
        c = batch.capacity
        args = ((jax.ShapeDtypeStruct((c,), np.int64),),
                (jax.ShapeDtypeStruct((c,), np.bool_),),
                jax.ShapeDtypeStruct((), np.int32))
        return core, args, {}

    return [
        AuditSpec("hash_aggregate_grouped", "hash_aggregate", _grouped,
                  notes="sum(v) group by k, update mode",
                  budgets={"gather": 12, "scatter": 2, "transpose": 4,
                           "sort": 3}),
        AuditSpec("hash_aggregate_whole_stage", "hash_aggregate",
                  _whole_stage,
                  notes="filter(v>0) chain folded into sum(v) by k",
                  budgets={"gather": 12, "scatter": 2, "transpose": 4,
                           "sort": 3}),
        AuditSpec("hash_aggregate_global", "hash_aggregate", _global,
                  notes="global (no group keys) sum, partial mode",
                  budgets={"gather": 2, "scatter": 0, "transpose": 0,
                           "sort": 0}),
        AuditSpec("hash_aggregate_global_folded", "hash_aggregate",
                  _global_folded,
                  notes="filter(v>0) chain folded into a global sum(v)",
                  budgets={"gather": 2, "scatter": 0, "transpose": 0,
                           "sort": 0}),
    ]
