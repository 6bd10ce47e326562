"""Group-by aggregation kernels — the device core of GpuHashAggregateExec

(reference: aggregate.scala:240).

TPU-first: instead of cuDF's open-addressing hash groupby, we sort by
canonical key words and run segmented reductions over the sorted rows —
XLA's native sort tiles onto the VPU far better than data-dependent hash
probing (SURVEY.md §7 "hard parts").  One compiled kernel per (schema,
capacity) bucket.

Which path moves what (PR 29; on the chip a 1-D take of 2^20 slots costs
about 11 ms whatever its width, a row gather of eleven 32-bit lanes 8 ms,
a pair sort 3 ms, a float64 scatter-add 74 ms):

- inside the aggregate's fused cores (``exec/tpu_aggregate.py``
  ``_fused_agg_core`` / ``_fused_whole_stage_core``) the key words come
  merged (``canon.group_key_words``: two flag-like string keys are one
  22-bit word, one sort pass), and ``groupby_plan`` is given every array
  the aggregates read in sorted order: all of them move in ONE row
  gather of a 32-bit-lane matrix (``gather_rows_once``: each distinct
  data array once, the validities as packed bits); the DOUBLE sums of
  every aggregate are one stacked segmented scan (``stack_float_sums``:
  sum(x) and avg(x) share a lane); boundary takes and per-group outputs
  run at the plan's ``num_slots`` (the core's output capacity), not at
  the batch's capacity;
- everywhere else (the eager grouped fallback, the mesh step) the plan
  is the chained pair sort of ``kernels/sort.py`` over unmerged words,
  an input is gathered into sorted order on first use, once per array
  (``GroupPlan.in_order``), each DOUBLE sum is its own ``segment_sum``,
  and ``num_slots`` is the batch's capacity;
- a global aggregate (no group keys) takes ``single_group_plan``: no
  sort, the rows stay where they are, and every segment kernel reduces
  the masked rows instead of scattering them into one slot.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..obs import trace as _trace
from . import canon
from .basic import prefix_sum, rows_flagged_first
from .gather import gather_rows_once
from .sort import sorted_words


@dataclasses.dataclass
class GroupPlan:
    perm: jnp.ndarray          # sort permutation over the input rows
    seg_id: jnp.ndarray        # segment id per sorted row (live rows: 0..G-1)
    live_sorted: jnp.ndarray   # sorted-row liveness mask (in-range rows)
    rep_indices: jnp.ndarray   # original row index of each group representative
    num_groups: jnp.ndarray    # scalar int
    head_pos: jnp.ndarray      # sorted position of each group's FIRST row
    last_pos: jnp.ndarray      # sorted position of each group's LAST row
    boundary: jnp.ndarray      # sorted-row mask: first row of a live group
    # id(array) -> (array, the array in sorted order): every row-moving
    # step happens once per array.  The unsorted array rides along so
    # its id stays its own for the plan's lifetime.
    moved: dict = dataclasses.field(default_factory=dict)
    # (id(data), id(validity)) -> per-group float64 sum (stack_float_sums)
    sums: dict = dataclasses.field(default_factory=dict)
    # static: the one-group plan (``single_group_plan``), whose rows are
    # in place and whose kernels reduce instead of scattering
    single: bool = False

    @property
    def num_slots(self) -> int:
        """Static length of every per-group output (``rep_indices``,
        ``head_pos``, each buffer the segment kernels return)."""
        return self.head_pos.shape[0]

    def in_order(self, values):
        """``values`` in the plan's sorted order: moved there with the
        other inputs (``gather_rows_once``), or gathered on first use."""
        if self.single:
            return values
        hit = self.moved.get(id(values))
        if hit is None:
            hit = self.moved[id(values)] = (values,
                                            jnp.take(values, self.perm))
        return hit[1]

    def source_rows(self, pos):
        """The input rows at sorted positions ``pos``."""
        return pos if self.single else jnp.take(self.perm, pos)


@jax.named_scope("groupby_plan")
def groupby_plan(words: List[jnp.ndarray], num_slots: Optional[int] = None,
                 inputs: Optional[list] = None, live=None) -> GroupPlan:
    """Build the sort+segment plan for a set of canonical key words.

    ``words`` must come from canon.batch_key_words (first word of each key is
    the null/range rank; rank 2 == dead row: past num_rows, or dropped by
    a folded-in filter).

    ``inputs`` (the fused cores): the arrays the aggregates read in
    sorted order (input data and validities), all brought there by one
    row gather (``gather_rows_once``); without it each is gathered on
    first use.  ``num_slots`` bounds the groups the per-group outputs
    have room for (default: one per row); a caller that passes less
    checks ``num_groups <= num_slots``.  ``live`` (with words from
    ``canon.group_key_words``, whose first word no longer IS the rank):
    the rows that are not dead, in input order.

    Besides the segment ids, the plan carries each group's first/last
    SORTED position (``head_pos``/``last_pos``): groups are contiguous
    runs after the sort, so per-group reductions of sums/counts become
    prefix-scan + two boundary gathers — a prefix sum is near-free on the
    VPU while a 64-bit scatter-add costs ~5x an f32 one (measured; XLA
    emulates i64 as 32-bit pairs and scatters serialize badly).
    """
    sorted_ws, perm = sorted_words(words)
    moved = {} if inputs is None else gather_rows_once(perm, inputs)
    n = sorted_ws[0].shape[0]
    if live is None:
        live = sorted_ws[0] != jnp.uint64(2)
    else:
        # dead rows sort past every live one
        live = jnp.arange(n) < jnp.sum(live.astype(jnp.int32))
    boundary = canon.words_equal_adjacent(sorted_ws) & live
    seg_id = prefix_sum(boundary.astype(jnp.int32)) - 1
    seg_id = jnp.maximum(seg_id, 0)
    num_groups = jnp.sum(boundary)
    slots = n if num_slots is None else min(num_slots, n)
    # the groups' first sorted rows, in order
    rep_order = rows_flagged_first(boundary).astype(jnp.int32)
    rep_indices = jnp.take(perm, rep_order[:slots])
    # group g spans sorted rows [head_pos[g], last_pos[g]]; dead rows sort
    # after all live rows, so the last live group ends at live_count-1
    head_pos = rep_order[:slots]
    live_count = jnp.sum(live.astype(jnp.int32))
    gi = jnp.arange(slots, dtype=jnp.int32)
    nxt = jnp.concatenate([rep_order, jnp.zeros(1, jnp.int32)])[1:slots + 1]
    last_pos = jnp.where(gi + 1 < num_groups, nxt - 1, live_count - 1)
    return GroupPlan(perm, seg_id, live, rep_indices, num_groups,
                     head_pos, last_pos, boundary, moved)


def single_group_plan(live) -> GroupPlan:
    """The plan of a global aggregate: every ``live`` row (in input
    order) is one group.  Nothing is sorted or moved; the group spans
    every slot (a dead row contributes nothing: each kernel masks by
    ``live_sorted``), and every per-group output has one slot.  The
    segment kernels see ``single`` and reduce the masked rows where a
    grouped plan scatters them (``seg_reduce``)."""
    cap = live.shape[0]
    first = jnp.zeros(1, jnp.int32)
    return GroupPlan(perm=jnp.arange(cap, dtype=jnp.int32),
                     seg_id=jnp.zeros(cap, jnp.int32), live_sorted=live,
                     rep_indices=first, num_groups=jnp.int32(1),
                     head_pos=first,
                     last_pos=jnp.full(1, cap - 1, jnp.int32),
                     boundary=jnp.arange(cap) == 0, single=True)


def _sorted_vals(plan: GroupPlan, values, validity):
    return plan.in_order(values), plan.in_order(validity) & plan.live_sorted


def _one_group_totals(stack):
    """float64 totals of ``stack`` ([k, rows], dead rows zero) along its
    rows as [k, 1]: halves added pairwise, full float64 adds in tree
    order (an error of at most log2(rows) roundings of the absolute
    sum, as ``_segmented_totals`` gives a group)."""
    k, n = stack.shape
    width = 1 << max(0, n - 1).bit_length()
    run = jnp.pad(stack, ((0, 0), (0, width - n)))
    while width > 1:
        width //= 2
        run = run[:, :width] + run[:, width:]
    return run


def seg_reduce(plan: GroupPlan, contrib, how: str):
    """Per-group ``how`` ("sum", "min", "max") of an already-masked
    per-sorted-row array: a scatter into ``num_slots`` segments, or on
    the one-group plan a reduce (float64 sums in tree order)."""
    if plan.single:
        if how != "sum":
            return getattr(jnp, how)(contrib, keepdims=True)
        if jnp.issubdtype(contrib.dtype, jnp.floating):
            return _one_group_totals(contrib[None])[0]
        return jnp.sum(contrib, dtype=contrib.dtype, keepdims=True)
    scatter = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}[how]
    return scatter(contrib, plan.seg_id, num_segments=plan.num_slots)


def seg_spread(plan: GroupPlan, per_group):
    """A per-group array read back at every sorted row."""
    if plan.single:
        return jnp.broadcast_to(per_group[0], plan.seg_id.shape)
    return jnp.take(per_group, plan.seg_id, mode="clip")


def seg_prefix_sum(plan: GroupPlan, contrib):
    """Per-group sum of an already-masked per-SORTED-row integer array via
    cumsum + boundary gathers (no scatter).  Exact for any integer dtype:
    the whole-batch running sum may wrap, but wraparound cancels in the
    boundary subtraction (two's complement), so each group's total is
    exact whenever it fits the dtype — the same contract as a direct
    per-group sum."""
    cap = contrib.shape[0]
    if contrib.dtype == jnp.bool_:
        contrib = contrib.astype(jnp.int64)     # as cumsum widens it
    if plan.single:
        return seg_reduce(plan, contrib, "sum")
    cum = prefix_sum(contrib)
    ex = cum - contrib                       # exclusive prefix per row
    hp = jnp.clip(plan.head_pos, 0, cap - 1)
    lp = jnp.clip(plan.last_pos, 0, cap - 1)
    total = jnp.take(cum, lp) - jnp.take(ex, hp)
    gi = jnp.arange(plan.num_slots, dtype=jnp.int32)
    return jnp.where(gi < plan.num_groups, total,
                     jnp.zeros_like(total))


def stack_float_sums(plan: GroupPlan, cols) -> None:
    """The float64 segment sums of every Column in ``cols`` (Sum and
    Average inputs, named by ``AggregateFunction.float_sum_cols``) as ONE
    segmented scan over a ``[rows, k]`` stack, left in ``plan.sums`` for
    ``seg_sum`` to find.  Each distinct (data, validity) is summed once:
    sum(x) and avg(x) share a lane.

    The scan restarts at every group's first sorted row and the group's
    total is the running value at its last row: full float64 adds in
    tree order, no scatter.  At 2^20 rows and five columns the chip
    takes 5.9 ms for the scan, 90 ms for one stacked ``segment_sum`` and
    386 ms for five (PERF.md section 5 has the table)."""
    todo = {}
    for c in cols:
        key = (id(c.data), id(c.validity))
        if key not in plan.sums:
            todo.setdefault(key, c)
    if not todo:
        return
    lanes = []
    for c in todo.values():
        v, ok = _sorted_vals(plan, c.data, c.validity)
        lanes.append(jnp.where(ok, v.astype(jnp.float64), 0.0))
    stack = jnp.stack(lanes)
    totals = _one_group_totals(stack) if plan.single \
        else _segmented_totals(plan, stack)
    for i, (key, c) in enumerate(todo.items()):
        # the Column rides along so the ids in the key stay its own
        plan.sums[key] = (c, totals[i])


def _segmented_totals(plan: GroupPlan, stack):
    """Per-group totals of ``stack`` ([k, rows], sorted order, dead rows
    zero) by a segmented inclusive scan: log2(rows) shift-and-add steps
    that stop at each group's first row (``plan.boundary``), then one
    read at each group's last row.  Full float64 adds in tree order; the
    steps are elementwise, so the program compiles in seconds where
    ``lax.associative_scan`` took minutes."""
    k, n = stack.shape
    run, stop = stack, plan.boundary
    d = 1
    while d < n:
        prev = jnp.concatenate([jnp.zeros((k, d), run.dtype), run[:, :-d]], 1)
        run = jnp.where(stop[None, :], run, run + prev)
        stop = stop | jnp.concatenate([jnp.ones(d, bool), stop[:-d]])
        d *= 2
    lp = jnp.clip(plan.last_pos, 0, n - 1)
    totals = jnp.take(run, lp, axis=1)
    gi = jnp.arange(plan.num_slots, dtype=jnp.int32)
    return jnp.where((gi < plan.num_groups)[None, :], totals, 0.0)


def seg_sum(plan: GroupPlan, values, validity, out_dtype=None):
    acc_dtype = jnp.dtype(out_dtype or values.dtype)
    if acc_dtype == jnp.float64:
        hit = plan.sums.get((id(values), id(validity)))
        if hit is not None:
            return hit[1]
    v, ok = _sorted_vals(plan, values, validity)
    acc = v.astype(acc_dtype)
    contrib = jnp.where(ok, acc, jnp.zeros_like(acc))
    if jnp.issubdtype(contrib.dtype, jnp.integer) or \
            contrib.dtype == jnp.bool_:
        return seg_prefix_sum(plan, contrib)
    if plan.single:
        return seg_reduce(plan, contrib, "sum")
    with _trace.launch("seg_sum_scatter", 1, contrib.shape[0]):
        return jax.ops.segment_sum(contrib, plan.seg_id,
                                   num_segments=plan.num_slots)


def seg_count(plan: GroupPlan, validity):
    ok = plan.in_order(validity) & plan.live_sorted
    return seg_prefix_sum(plan, ok.astype(jnp.int32)).astype(jnp.int64)


def seg_count_all(plan: GroupPlan):
    return seg_prefix_sum(
        plan, plan.live_sorted.astype(jnp.int32)).astype(jnp.int64)


def _type_extreme(dtype, want_max: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if not want_max else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if not want_max else info.min, dtype)


def seg_minmax_u64(plan: GroupPlan, words, ok, want_max: bool):
    """Per-group min/max of uint64 order-words WITHOUT a 64-bit scatter:
    two u32 passes (hi word, then lo word among hi-winners).
    64-bit scatters are ~5x slower than 32-bit ones on the chip (XLA
    lowers i64 as 32-bit pairs); this keeps the reduction native."""
    w = words.astype(jnp.uint64)
    if not want_max:
        w = ~w                               # min == max of complement
    hi = (w >> jnp.uint64(32)).astype(jnp.uint32)
    lo = (w & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    z = jnp.uint32(0)
    mhi = seg_reduce(plan, jnp.where(ok, hi, z), "max")
    on_hi = ok & (hi == seg_spread(plan, mhi))
    mlo = seg_reduce(plan, jnp.where(on_hi, lo, z), "max")
    out = (mhi.astype(jnp.uint64) << jnp.uint64(32)) | \
        mlo.astype(jnp.uint64)
    if not want_max:
        out = ~out
    return out


def _seg_minmax_i64(plan, v, ok, want_max: bool):
    # order-preserving int64 -> uint64 (flip sign bit), two-stage u32
    w = v.astype(jnp.uint64) ^ jnp.uint64(1 << 63)
    # masked-off rows contribute the identity via ok in seg_minmax_u64
    m = seg_minmax_u64(plan, w, ok, want_max)
    out = (m ^ jnp.uint64(1 << 63)).astype(jnp.int64)
    # groups with no contributing rows keep the type identity (the
    # caller masks validity by count anyway)
    has = seg_prefix_sum(plan, ok.astype(jnp.int32)) > 0
    ident = _type_extreme(jnp.int64, want_max)
    return jnp.where(has, out, ident)


def seg_min(plan: GroupPlan, values, validity):
    v, ok = _sorted_vals(plan, values, validity)
    if jnp.issubdtype(v.dtype, jnp.floating):
        # Spark total order: NaN greatest, -0.0 == 0.0.  No bit encoding
        # (the chip cannot bitcast f64): min over non-NaN values, falling
        # back to NaN only when a group has nothing else.
        v = jnp.where(v == 0.0, jnp.array(0.0, v.dtype), v)
        nan = jnp.isnan(v)
        contrib = jnp.where(ok & ~nan, v, jnp.array(jnp.inf, v.dtype))
        m = seg_reduce(plan, contrib, "min")
        has_num = seg_prefix_sum(plan, (ok & ~nan).astype(jnp.int32)) > 0
        return jnp.where(has_num, m, jnp.array(jnp.nan, v.dtype))
    if v.dtype in (jnp.int64, jnp.uint64):
        if v.dtype == jnp.uint64:
            return seg_minmax_u64(plan, v, ok, want_max=False)
        return _seg_minmax_i64(plan, v, ok, want_max=False)
    ident = _type_extreme(v.dtype, want_max=False)
    contrib = jnp.where(ok, v, ident)
    return seg_reduce(plan, contrib, "min")


def seg_max(plan: GroupPlan, values, validity):
    v, ok = _sorted_vals(plan, values, validity)
    if jnp.issubdtype(v.dtype, jnp.floating):
        # NaN is the greatest value: any NaN in the group wins
        v = jnp.where(v == 0.0, jnp.array(0.0, v.dtype), v)
        nan = jnp.isnan(v)
        contrib = jnp.where(ok & ~nan, v, jnp.array(-jnp.inf, v.dtype))
        m = seg_reduce(plan, contrib, "max")
        has_nan = seg_prefix_sum(plan, (ok & nan).astype(jnp.int32)) > 0
        return jnp.where(has_nan, jnp.array(jnp.nan, v.dtype), m)
    if v.dtype in (jnp.int64, jnp.uint64):
        if v.dtype == jnp.uint64:
            return seg_minmax_u64(plan, v, ok, want_max=True)
        return _seg_minmax_i64(plan, v, ok, want_max=True)
    ident = _type_extreme(v.dtype, want_max=True)
    contrib = jnp.where(ok, v, ident)
    return seg_reduce(plan, contrib, "max")


def seg_first_index(plan: GroupPlan, validity, ignore_nulls: bool = True):
    """Original-row index of the first (valid) row per group."""
    cap = validity.shape[0]
    ok = plan.in_order(validity) & plan.live_sorted if ignore_nulls \
        else plan.live_sorted
    pos = jnp.arange(cap, dtype=jnp.int32)
    contrib = jnp.where(ok, pos, jnp.int32(cap))
    first_pos = seg_reduce(plan, contrib, "min")
    safe = jnp.clip(first_pos, 0, cap - 1).astype(jnp.int32)
    return plan.source_rows(safe), first_pos < cap


def seg_first_index_by_order(plan: GroupPlan, col, want_min: bool = True,
                             num_rows: int = None):
    """Index of the lexicographically min/max value per group (strings etc.).

    Works on canonical value words: iteratively narrow candidates word by
    word with segment_min, then take the first surviving index.
    """
    from . import canon
    cap = col.capacity
    if num_rows is None:
        num_rows = cap
    words = canon.value_words(col, num_rows)
    if not want_min:
        words = [~w for w in words]
    ok = plan.in_order(col.validity) & plan.live_sorted
    cand = ok
    for w in words:
        ws = plan.in_order(w).astype(jnp.uint64)
        m = seg_minmax_u64(plan, ws, cand, want_max=False)
        cand = cand & (ws == seg_spread(plan, m))
    pos = jnp.arange(cap, dtype=jnp.int32)
    contrib = jnp.where(cand, pos, jnp.int32(cap))
    first_pos = seg_reduce(plan, contrib, "min")
    has = first_pos < cap
    safe = jnp.clip(first_pos, 0, cap - 1).astype(jnp.int32)
    return plan.source_rows(safe), has


def seg_last_index(plan: GroupPlan, validity, ignore_nulls: bool = True):
    cap = validity.shape[0]
    ok = plan.in_order(validity) & plan.live_sorted if ignore_nulls \
        else plan.live_sorted
    pos = jnp.arange(cap, dtype=jnp.int32)
    contrib = jnp.where(ok, pos, jnp.int32(-1))
    last_pos = seg_reduce(plan, contrib, "max")
    safe = jnp.clip(last_pos, 0, cap - 1).astype(jnp.int32)
    return plan.source_rows(safe), last_pos >= 0


# ---------------------------------------------------------------------------
# Sort-free bucket-table group-by (the TPU-native fast path).
#
# Reference context: cuDF's hash group-by (aggregate.scala:240 lowers to
# open-addressing hash tables on GPU).  Hash probing is hostile to XLA,
# but most BI group-bys have small combined key cardinality RANGE —
# so instead of hashing, each key word is rebased by its device-computed
# minimum and the keys mixed-radix-packed into a bucket id < table_size.
# Aggregation is then direct per-bucket reduction: sums/counts ride one
# stacked small-output scatter-add, min/max a scatter-max each.
# No sort, no gathers, no 64-bit scatters (which cost ~20x f32 on TPU).
#
# A device-side `fit` flag records whether the batch really fit the
# table (key range, u32 value range for int min/max, f32 finiteness for
# float sums); callers dispatch speculatively and re-run the rare
# non-fitting batch on the general sort path (exec/tpu_aggregate.py).
# ---------------------------------------------------------------------------


def table_bucket(key_words, key_valids, live, table: int):
    """Mixed-radix bucket assignment over single-word keys.

    key_words: one uint64 word per key (canon.value_words[0]);
    key_valids: per-key validity; live: row mask (in-range AND past any
    folded-in filters).  Each key contributes digit 0 for null and
    1 + (word - min) otherwise; digits pack most-significant-first, so
    bucket ascending == (nulls-first key tuple) ascending — matching the
    sort path's group order.  Dead rows get bucket == table.
    Returns (bucket i32[cap], fit bool, mins, cards).
    """
    cap = key_words[0].shape[0]
    bucket = jnp.zeros(cap, jnp.int32)
    total = jnp.uint64(1)
    fit = jnp.bool_(True)
    mins, cards = [], []
    for w, valid in zip(key_words, key_valids):
        lv = live & valid
        any_v = jnp.any(lv)
        wmin = jnp.where(any_v,
                         jnp.min(jnp.where(lv, w, jnp.uint64(2**64 - 1))),
                         jnp.uint64(0))
        wmax = jnp.where(any_v,
                         jnp.max(jnp.where(lv, w, jnp.uint64(0))),
                         jnp.uint64(0))
        rng = wmax - wmin
        # card clamped so products can't wrap; fit goes False anyway
        card = jnp.minimum(rng, jnp.uint64(table)).astype(jnp.int32) + 2
        total = jnp.minimum(total * card.astype(jnp.uint64),
                            jnp.uint64(1) << jnp.uint64(32))
        digit = jnp.where(
            valid,
            jnp.minimum(w - wmin, jnp.uint64(table)).astype(jnp.int32) + 1,
            0)
        bucket = jnp.minimum(bucket * card + digit, table)
        mins.append(wmin)
        cards.append(card)
    fit = total <= jnp.uint64(table)
    bucket = jnp.where(live, bucket, table).astype(jnp.int32)
    return bucket, fit, mins, cards


def table_compact(counts, table: int):
    """Group directory from per-bucket live counts: (present, order,
    num_groups) where order[g] = bucket of group g, ascending."""
    present = counts > 0
    num_groups = jnp.sum(present).astype(jnp.int32)
    order = rows_flagged_first(present).astype(jnp.int32)
    return present, order, num_groups


def table_reduce(bucket, sum_rows, max_rows, table: int):
    """Reduce f32 rows into ``table`` buckets (+1 dead slot dropped).

    sum_rows: list of f32[n] contribution rows (dead rows must be 0).
    max_rows: list of f32[n] rows (dead rows must be -inf); min via
    caller-side negation.  Returns (sums: list of f32[table],
    maxs: list of f32[table]).  One multi-column scatter-add for all
    the sum rows (it costs what a single-column one does) and a
    scatter-max a max row."""
    sums = []
    if sum_rows:
        out = jax.ops.segment_sum(jnp.stack(sum_rows, 1), bucket,
                                  num_segments=table + 1)
        sums = [out[:table, i] for i in range(len(sum_rows))]
    maxs = [jax.ops.segment_max(r, bucket, num_segments=table + 1)[:table]
            for r in max_rows]
    return sums, maxs
