"""Join kernels — the device core of GpuHashJoin/JoinGatherer

(reference: GpuHashJoin.scala:62, JoinGatherer.scala).

TPU-first: instead of cuDF's GPU hash table build+probe, the build side is
sorted by canonical key words and every probe row runs a vectorized binary
search (lower/upper bound) — O(log n) integer compares per row, fully
static-shape, no data-dependent control flow.  Match expansion ("gather
maps") is one pass over the output rows: each probe row scatters a +1 at
its first output row and a running sum (``kernels/basic.prefix_sum``) hands
every output row its probe row; its build position comes the same way (the
step of the row's shift scattered, a second running sum) or by one gather
through the probe row, whichever costs fewer indices at the launch's
shapes; one gather reads ``perm``.  The output capacity is host-sized,
playing the JoinGatherer role of bounding output batch size.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import trace as _obs_trace
from . import canon
from .basic import prefix_sum
from .sort import sorted_words, stable_sort_rows


@dataclasses.dataclass
class BuildTable:
    """Sorted build side: canonical words + permutation back to original rows."""
    sorted_words: List[jnp.ndarray]
    perm: jnp.ndarray
    capacity: int


@jax.named_scope("join_build")
def build(words: List[jnp.ndarray]) -> BuildTable:
    ws, perm = sorted_words(words)
    return BuildTable(ws, perm, int(perm.shape[0]))


def _bsearch(build_words: List[jnp.ndarray], probe_words: List[jnp.ndarray],
             upper: bool):
    """Vectorized lower/upper bound of each probe tuple in sorted build words."""
    bcap = build_words[0].shape[0]
    pcap = probe_words[0].shape[0]
    steps = max(1, (bcap - 1).bit_length() + 1)
    # zero derived from the probe words so the fori_loop carry keeps
    # their varying-manual-axes type under shard_map (a plain
    # jnp.zeros carry is unvarying and the loop rejects the mismatch)
    lo = (probe_words[0] ^ probe_words[0]).astype(jnp.int32)
    hi = lo + jnp.int32(bcap)
    prows = jnp.arange(pcap, dtype=jnp.int32)

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, bcap - 1)
        if upper:
            # first index where probe < build[mid]
            plt = canon.words_less(probe_words, prows, build_words, midc)
            go_right = ~plt
        else:
            # first index where NOT build[mid] < probe
            blt = canon.words_less(build_words, midc, probe_words, prows)
            go_right = blt
        active = lo < hi
        new_lo = jnp.where(active & go_right, mid + 1, lo)
        new_hi = jnp.where(active & ~go_right, mid, hi)
        return new_lo, new_hi

    lo, hi = lax.fori_loop(0, steps, body, (lo, hi))
    return lo


@dataclasses.dataclass
class JoinCounts:
    lo: jnp.ndarray            # per-probe-row first build position
    counts: jnp.ndarray        # per-probe-row match count
    matched: jnp.ndarray       # counts > 0 (valid probe rows only)


@jax.named_scope("join_probe_counts")
def probe_counts(bt: BuildTable, probe_words: List[jnp.ndarray],
                 probe_num_rows: int,
                 null_equals_null: bool = False) -> JoinCounts:
    pcap = probe_words[0].shape[0]
    lo = _bsearch(bt.sorted_words, probe_words, upper=False)
    hi = _bsearch(bt.sorted_words, probe_words, upper=True)
    counts = (hi - lo).astype(jnp.int32)
    in_range = jnp.arange(pcap) < probe_num_rows
    # probe rows with any null key never match (rank word 0), unless
    # null-safe equality is requested (reference: GpuEqualNullSafe)
    if null_equals_null:
        usable = in_range
    else:
        all_valid = probe_words[0] == jnp.uint64(1)
        usable = in_range & all_valid
    counts = jnp.where(usable, counts, 0)
    return JoinCounts(lo, counts, counts > 0)


def _expand_lanes(lo, counts, perm, out_cap: int) -> int:
    """A launch's output lanes: the rows its running sums run over and
    the indices it gathers; the probe rows it scatters are the plain
    count ``join.expand.probe_rows``."""
    _obs_trace.count("join.expand.probe_rows", counts.shape[0])
    return out_cap


@_obs_trace.launched(lanes=_expand_lanes)
@functools.partial(jax.jit, static_argnames=("out_cap",))
def join_expand_matches(lo, counts, perm, out_cap: int):
    """Expand (lo, counts) into flat (probe_idx, build_idx) gather maps.

    Output row t belongs to probe row p where exclusive-sum[p] <= t <
    inclusive-sum[p]; its build position is lo[p] + (t - excl[p]).

    Linear in probe rows + output rows: the output rows of a probe row
    are a run, so both maps are piecewise.  Each probe row scatters a +1
    at its first output row ``excl[p]`` (rows with no match share a lane
    with the next matched row) and a running sum over the output rows
    gives ``p + 1``.  The build position is ``t + shift[p]`` with
    ``shift = lo - excl``, by whichever costs fewer indices: with fewer
    probe rows than output rows the step ``shift[p] - shift[p - 1]`` is
    scattered the same way (``lo`` need not be sorted, the step is
    signed; the steps of rows that share a lane add up) and a second
    running sum hands each output row its shift; otherwise each output
    row gathers its shift by ``p``.  The chip pays per scattered or
    gathered index, 7-9 ns, and next to nothing for a running sum, so
    the binary search an output row this replaces cost eight to thirty
    times either.  Neither wins at every shape (PERF.md section 5, PR
    35): the gather is 34% ahead at 2^20 probe rows into 2^18 output
    rows and 4-10% at equal counts, the scatter 13% ahead at 2^17 into
    2^18 and 40% at 2^16 into 2^20, so the shapes the program is traced
    with decide.  ``total`` is the exact int64 sum even past
    ``out_cap``; a row whose first output row is at or past ``out_cap``
    is dropped by the scatter.  On dead lanes (``t >= total``) both maps
    stay in range and mean nothing."""
    n = counts.shape[0]
    incl = prefix_sum(counts.astype(jnp.int64))
    excl = incl - counts
    total = incl[-1]
    first = jnp.minimum(excl, out_cap).astype(jnp.int32)
    lanes = jnp.zeros(out_cap, jnp.int32)
    rows = prefix_sum(lanes.at[first].add(
        1, indices_are_sorted=True, mode="drop"))
    pc = jnp.clip(rows - 1, 0, n - 1)
    # int32 wrap-around cancels: a live lane's shift is its own row's,
    # which fits, and the steps telescope to it
    shift = lo.astype(jnp.int32) - excl.astype(jnp.int32)
    if n >= out_cap:
        lane_shift = jnp.take(shift, pc)
    else:
        step = shift - jnp.concatenate([jnp.zeros(1, jnp.int32), shift[:-1]])
        lane_shift = prefix_sum(lanes.at[first].add(
            step, indices_are_sorted=True, mode="drop"))
    t = jnp.arange(out_cap, dtype=jnp.int32)
    build_pos = jnp.clip(t + lane_shift, 0, perm.shape[0] - 1)
    build_idx = jnp.take(perm, build_pos)
    live = t < jnp.minimum(total, out_cap).astype(jnp.int32)
    return pc, build_idx, live, total


def order_word(v):
    """An integer array (at most 64 bits) as unsigned words in its
    order: 32-bit words for 32-bit values and narrower, which the chip
    sorts for a sixth of a 64-bit word's cost."""
    if v.dtype.itemsize <= 4:
        return lax.bitcast_convert_type(v.astype(jnp.int32), jnp.uint32) \
            ^ jnp.uint32(1 << 31)
    return v.astype(jnp.int64).view(jnp.uint64) ^ jnp.uint64(1 << 63)


def sort_within_runs(sorted_words: List[jnp.ndarray], perm, vals, valid):
    """Each equal-key run of a sorted build (``sorted_words``, ``perm``)
    ordered by ``vals`` (integers in build row order; NULLs, ``~valid``,
    last in their run), stably: two (key, row) sorts, the value word
    first, then the run number beside a NULL flag (32 bits), so the runs
    keep their positions and a probe's ``lo`` and ``counts`` still name
    its run.  -> (the reordered ``perm``, the values in that order, each
    position's count of non-NULL values in its run, and the rounds a
    binary search over the longest run takes)."""
    n = perm.shape[0]
    run = prefix_sum(canon.words_equal_adjacent(sorted_words)
                     .astype(jnp.int32)) - 1
    v = jnp.take(vals, perm)
    ok = jnp.take(valid, perm)
    _, by_value = stable_sort_rows(order_word(v))
    runs_key = (run.astype(jnp.uint32) << 1) | (~ok).astype(jnp.uint32)
    _, by_run = stable_sort_rows(jnp.take(runs_key, by_value))
    order = jnp.take(by_value, by_run)
    per_run = jnp.zeros(n, jnp.int32).at[run].add(ok.astype(jnp.int32))
    longest = jnp.max(jnp.zeros(n, jnp.int32).at[run].add(1))
    rounds = jnp.int32(32) - lax.clz(longest)
    return (jnp.take(perm, order), jnp.take(v, order),
            jnp.take(per_run, run), rounds)


def run_bounds(lo, counts, vals_r, nvalid, rounds, s, s_valid,
               strict: bool, prefix: bool):
    """Each probe row's surviving sub-range of its run under one
    comparison ``b OP s`` of the run's values ``b`` (``sort_within_runs``
    order: ascending, NULLs last) with the row's value ``s``: a binary
    search of ``rounds`` rounds for the first ``b > s`` (``strict``) or
    ``b >= s`` among the run's non-NULL values.  ``b < s`` and ``b <=
    s`` keep the values before it (``prefix``), ``b >= s`` and ``b >
    s`` those from it on; a NULL ``s`` keeps none.  -> (lo, counts) of
    the survivors."""
    n = vals_r.shape[0]
    nv = jnp.where((counts > 0) & s_valid,
                   jnp.minimum(jnp.take(nvalid, jnp.clip(lo, 0, n - 1)),
                               counts), 0)

    def body(_, state):
        a, b = state
        mid = (a + b) >> 1
        bm = jnp.take(vals_r, jnp.clip(lo + mid, 0, n - 1))
        hit = (bm > s) if strict else (bm >= s)
        active = a < b
        return (jnp.where(active & ~hit, mid + 1, a),
                jnp.where(active & hit, mid, b))

    j, _ = lax.fori_loop(0, rounds, body, (jnp.zeros_like(nv), nv))
    if prefix:
        return lo, j
    return lo + j, nv - j


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnames=("limit",))
def join_pairs_window(lo, eff, base, limit: int):
    """The rows' share of the flattened pairs ``[base, base + limit)``
    (each row's pairs in row order, ``eff`` a row): each row's first
    pair in the window as a build position, and its pairs there."""
    incl = prefix_sum(eff.astype(jnp.int64))
    start = incl - eff
    n_here = jnp.maximum(jnp.minimum(incl, base + limit) -
                         jnp.maximum(start, base), 0)
    lo_w = lo.astype(jnp.int64) + jnp.maximum(base - start, 0)
    return lo_w.astype(jnp.int32), n_here.astype(jnp.int32)


def total_matches(counts) -> int:
    """Host sync: total output rows (sizes the output capacity bucket)."""
    from ..analysis import residency  # lazy: avoids import cycle
    with residency.declared_transfer(site="size_probe"):
        return int(jnp.sum(counts.astype(jnp.int64)))
