"""Window kernels: the traced bodies of ``exec/tpu_window.py``'s
programs (reference: GpuWindowExec.scala:338, GpuWindowExpression.scala).

TPU-first: ONE sort by (partition keys, order keys) a spec
(``sorted_partitions``), after which a partition is a run of sorted rows
and every function is a scan over the runs: elementwise
shift-and-combine steps (``kernels/basic.prefix_sum`` and its kin here)
in place of ``jnp.cumsum`` (32 s of compile at 2^20 on the chip), of
``segment_min`` / ``segment_sum`` with ``num_segments=cap`` (a scatter
over the whole capacity: 77 ms a float64 one at 2^20) and of
``jnp.argsort(perm)`` (a second full sort to invert a permutation one
scatter inverts).  Everything here is shape-static: keyed by capacity
and dtypes, never by a row count.
"""
from __future__ import annotations

from typing import List, NamedTuple

import jax.numpy as jnp

from . import canon
from .aggregate import _type_extreme as extreme_of
from .basic import prefix_sum
from .sort import sorted_words


class SortedPartitions(NamedTuple):
    """One window spec's sort, shared by every function over it.  All
    ``[capacity]``; positions are places in the sorted order."""
    perm: jnp.ndarray        # int32: the row at each sorted position
    inv: jnp.ndarray         # int32: the sorted position of each row
    live: jnp.ndarray        # bool: the position holds a row, not padding
    seg_first: jnp.ndarray   # bool: first row of its partition
    run_first: jnp.ndarray   # bool: first row of its peers (equal order keys)
    seg_start: jnp.ndarray   # int32: position of the partition's first row
    seg_end: jnp.ndarray     # int32: position of the partition's last row


def _shifted(x, d: int, fill, reverse: bool = False):
    pad = jnp.full((d,), fill, x.dtype)
    return jnp.concatenate([x[d:], pad]) if reverse \
        else jnp.concatenate([pad, x[:-d]])


def running_max(x):
    """Inclusive running maximum of a non-negative integer array."""
    d = 1
    while d < x.shape[0]:
        x = jnp.maximum(x, _shifted(x, d, 0))
        d *= 2
    return x


def running_min_from_end(x, top):
    """``out[i] = min(x[i:])``; ``top`` is no smaller than any value."""
    d = 1
    while d < x.shape[0]:
        x = jnp.minimum(x, _shifted(x, d, top, reverse=True))
        d *= 2
    return x


def ends_of(first, live):
    """Per sorted row, the position of the last row of the run that
    ``first`` starts (a run also ends where the padding begins)."""
    n = first.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_last = jnp.concatenate([first[1:] | ~live[1:], jnp.ones(1, bool)])
    return running_min_from_end(jnp.where(is_last, pos, n - 1), n - 1)


def starts_of(first):
    """Per sorted row, the position of the first row of its run."""
    pos = jnp.arange(first.shape[0], dtype=jnp.int32)
    return running_max(jnp.where(first, pos, 0))


def seg_scan(x, first, combine, reverse: bool = False):
    """Inclusive scan of ``x`` by ``combine`` that restarts at every
    run's first row (``reverse``: runs from each run's LAST row
    backwards, ``first`` then marking last rows): log2(n) elementwise
    steps in tree order, as ``kernels/aggregate._segmented_totals``
    (5.9 ms for five float64 lanes at 2^20 rows where five
    ``segment_sum``s took 386)."""
    run, stop = x, first
    d = 1
    while d < x.shape[0]:
        prev = _shifted(run, d, 0, reverse)
        run = jnp.where(stop, run, combine(run, prev))
        stop = stop | _shifted(stop, d, True, reverse)
        d *= 2
    return run


def sorted_partitions(pcols: List, ocols: List, num_rows,
                      descending: List[bool], nulls_last: List[bool],
                      cap: int) -> SortedPartitions:
    """Sort ``cap`` row slots by (partition keys, order keys) and mark
    the runs.  Key columns are ``Column``s or
    ``canon.PackedStringKey``s; with no partition key every row is one
    partition."""
    if pcols:
        pwords = canon.batch_key_words(pcols, num_rows)
    else:
        # the word only pushes the padding to the end
        pwords = [jnp.where(jnp.arange(cap) < num_rows, jnp.uint64(1),
                            jnp.uint64(2))]
    owords = canon.batch_key_words(ocols, num_rows, descending=descending,
                                   nulls_last=nulls_last) if ocols else []
    # a new program: its chain is a loop whatever its length
    sorted_ws, perm = sorted_words(pwords + owords, roll_from=2)
    perm = perm.astype(jnp.int32)
    # the first word is a null rank: 2 marks a row past num_rows
    live = sorted_ws[0] != jnp.uint64(2)
    seg_first = canon.words_equal_adjacent(sorted_ws[:len(pwords)]) & live
    run_first = canon.words_equal_adjacent(sorted_ws) & live
    pos = jnp.arange(cap, dtype=jnp.int32)
    inv = jnp.zeros_like(perm).at[perm].set(pos, unique_indices=True)
    return SortedPartitions(perm, inv, live, seg_first, run_first,
                            starts_of(seg_first), ends_of(seg_first, live))


def ranking(p: SortedPartitions, kind: str, buckets: int = 0):
    """``row_number`` / ``rank`` / ``dense_rank`` / ``ntile`` /
    ``percent_rank`` / ``cume_dist`` per SORTED row."""
    pos = jnp.arange(p.perm.shape[0], dtype=jnp.int32)
    row_in_seg = (pos - p.seg_start).astype(jnp.int64)
    if kind == "row_number":
        return row_in_seg + 1
    if kind == "dense_rank":
        run_id = prefix_sum(p.run_first.astype(jnp.int32))
        first_id = running_max(jnp.where(p.seg_first, run_id, 0))
        return (run_id - first_id + 1).astype(jnp.int64)
    length = (p.seg_end - p.seg_start + 1).astype(jnp.int64)
    if kind == "ntile":
        # Spark NTile: the first (L % n) buckets hold ceil(L / n) rows
        nb = jnp.int64(buckets)
        base, rem = length // nb, length % nb
        cut = rem * (base + 1)
        return jnp.where(
            row_in_seg < cut, row_in_seg // jnp.maximum(base + 1, 1),
            rem + (row_in_seg - cut) // jnp.maximum(base, 1)) + 1
    rank = (starts_of(p.run_first) - p.seg_start + 1).astype(jnp.int64)
    if kind == "rank":
        return rank
    if kind == "percent_rank":
        return jnp.where(length > 1, (rank - 1).astype(jnp.float64) /
                         jnp.maximum(length - 1, 1).astype(jnp.float64), 0.0)
    assert kind == "cume_dist", kind
    # rows <= current / partition rows
    through = (ends_of(p.run_first, p.live) - p.seg_start + 1)
    return through.astype(jnp.float64) / \
        jnp.maximum(length, 1).astype(jnp.float64)


def partition_aggregate(p: SortedPartitions, kind: str, sv, sok,
                        fractional: bool):
    """A whole-partition ``sum`` / ``count`` / ``avg`` / ``min`` /
    ``max`` of the sorted values ``sv`` (contributing where ``sok``),
    read at each partition's last row: (values, validity), one a
    PARTITION'S LAST sorted row (gather them by ``seg_end``)."""
    count = seg_scan(sok.astype(jnp.int32), p.seg_first, jnp.add)
    if kind == "count":
        return count.astype(jnp.int64), jnp.ones_like(sok)
    if kind in ("sum", "avg"):
        acc = jnp.float64 if fractional or kind == "avg" else jnp.int64
        x = jnp.where(sok, sv.astype(acc), jnp.zeros((), acc))
        total = seg_scan(x, p.seg_first, jnp.add)
        if kind == "avg":
            total = total / jnp.maximum(count, 1)
        return total, count > 0
    assert kind in ("min", "max"), kind
    want_max = kind == "max"
    x = jnp.where(sok, sv, extreme_of(sv.dtype, want_max))
    return seg_scan(x, p.seg_first,
                    jnp.maximum if want_max else jnp.minimum), count > 0
