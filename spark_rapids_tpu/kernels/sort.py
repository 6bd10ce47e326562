"""Sort kernels — the device core of GpuSortExec (reference:

GpuSortExec.scala:56, SortUtils.scala).

TPU-first: multi-key sorts run as **LSD chained single-key passes** —
for each canonical uint64 key word (kernels/canon.py), least-significant
first, a stable (key, perm) ``lax.sort`` re-orders the permutation.
Rationale: a variadic ``lax.sort`` compiles a distinct XLA comparator
per (capacity, operand-count) pair, and TPU sort compiles are slow and
grow with the operand count (the per-kernel seconds that motivated
this were taken on a backend that no longer exists; not measured on
the current machine).  Chaining means ONE compiled pair-sort per
capacity bucket serves every sort/group-by/join/window in the engine,
at the cost of K executions of that one cached kernel — the right trade
where compiles are expensive and reused kernels are nearly free.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import trace as _obs_trace



def stable_sort_rows(key):
    """``key`` in stable ascending order beside the row numbers (int32)
    in that order.  The row number is the second sort key, so no two
    rows compare equal and the sort itself need not be a stable one:
    the same answer as ``is_stable=True`` with one key, which the
    chip's compiler takes longer over (at 2^20 rows 39.9 s against
    28.4 s on a uint64 key, 3.0 ms to run either way; PERF.md section
    6, PR 32)."""
    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    return lax.sort((key, iota), num_keys=2, is_stable=False)


@_obs_trace.launched()
@jax.jit
def sort_stable_pair(key):
    """The one compiled sort primitive: the row numbers in the stable
    ascending order of ``key`` — shape-cached per (capacity bucket, key
    dtype).  A caller that sorts rows already permuted gathers its
    permutation by the result (``lsd_pass``): carrying it through the
    sort would be a second program a capacity.

    64-bit keys cost ~6x a u32 sort on real TPU (u64 ops lower to u32
    pairs), so callers with provably-narrow keys (partition ids, table
    buckets, range-rebased words) pass u32 keys directly."""
    return stable_sort_rows(key)[1]


def lsd_pass(key, perm):
    """One pass of an LSD chain: ``perm`` (None: the rows as they lie)
    re-ordered stably by ``key``, a word in row order."""
    if perm is None:
        return sort_stable_pair(key)
    return jnp.take(perm, sort_stable_pair(jnp.take(key, perm)))


#: inside a traced program a chain of this many passes or more runs as
#: a loop (``_lsd_loop``); shorter chains stay unrolled, as every
#: program compiled before PR 34 has them
ROLL_FROM = 8


def _lsd_loop(words: List[jnp.ndarray]) -> jnp.ndarray:
    """The LSD chain as ONE loop over the stacked words.  Inside a
    traced program the nested ``sort_stable_pair`` is inlined once a
    pass, and the chip's compiler emits each sort's code again: a core
    that sorts 16 key words at 2^18 slots came to 66.8 MB of code
    against 4.7 MB for this loop (the same 31-33 s to compile), fourteen
    such cores overran the machine's 192 MiB persistent compile cache,
    and every run of ``tpcds_sf1_olap.power`` compiled for 1,000 s
    (PERF.md section 6, PR 34).  The first pass gathers by the identity:
    one take more than the unrolled chain.  Every word gets its pass,
    one that every row shares too (the zero words of a key a grouping
    set rolled up): a ``lax.cond`` round the pass to skip those read
    33% slower on the chip for every pass it did not skip
    (``jit_window_plan`` 0.905 -> 1.207 s; PERF.md section 6, PR 34)."""
    n = words[0].shape[0]
    stacked = jnp.stack([w.astype(jnp.uint64) for w in reversed(words)])

    def one_pass(perm, key):
        return lsd_pass(key, perm), None
    perm, _ = lax.scan(one_pass, jnp.arange(n, dtype=jnp.int32), stacked)
    return perm


@jax.named_scope("sort_permutation")
def sort_permutation(words: List[jnp.ndarray],
                     roll_from: int = ROLL_FROM) -> jnp.ndarray:
    """Stable ascending sort over word tuples; returns permutation indices."""
    if len(words) == 1:
        w = words[0]
        if w.dtype != jnp.dtype(jnp.uint32):
            w = w.astype(jnp.uint64)
        return sort_stable_pair(w)
    # LSD: least-significant word first; stability makes later (more
    # significant) passes dominate.  Run eagerly, every pass launches
    # the one compiled pair sort of its capacity: nothing to roll.
    if len(words) >= roll_from and isinstance(words[0], jax.core.Tracer):
        return _lsd_loop(words)
    perm = None
    for w in reversed(words):
        perm = lsd_pass(w.astype(jnp.uint64), perm)
    return perm


def sorted_words(words: List[jnp.ndarray], roll_from: int = ROLL_FROM):
    """Sort and also return the sorted word arrays (for boundary detection)."""
    if len(words) == 1:
        # one word: the sort hands back the sorted key beside the rows
        key, perm = stable_sort_rows(words[0])
        return [key], perm
    perm = sort_permutation(words, roll_from)
    return [jnp.take(w, perm) for w in words], perm
