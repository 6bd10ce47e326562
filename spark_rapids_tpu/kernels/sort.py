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



@jax.jit
def sort_stable_pair(key, perm):
    """The one compiled sort primitive: stable ascending by ``key``,
    carrying ``perm`` — shape-cached per (capacity bucket, key dtype).

    64-bit keys cost ~6x a u32 sort on real TPU (u64 ops lower to u32
    pairs), so callers with provably-narrow keys (partition ids, table
    buckets, range-rebased words) pass u32 keys directly."""
    _, out = lax.sort((key, perm), num_keys=1, is_stable=True)
    return out


@jax.named_scope("sort_permutation")
def sort_permutation(words: List[jnp.ndarray]) -> jnp.ndarray:
    """Stable ascending sort over word tuples; returns permutation indices."""
    cap = words[0].shape[0]
    perm = jnp.arange(cap, dtype=jnp.int32)
    if len(words) == 1:
        w = words[0]
        if w.dtype != jnp.dtype(jnp.uint32):
            w = w.astype(jnp.uint64)
        return sort_stable_pair(w, perm)
    # LSD: least-significant word first; stability makes later (more
    # significant) passes dominate
    for i, w in enumerate(reversed(words)):
        k = w.astype(jnp.uint64)
        # the first pass sorts the rows as they lie
        perm = sort_stable_pair(k if i == 0 else jnp.take(k, perm), perm)
    return perm


def sorted_words(words: List[jnp.ndarray]):
    """Sort and also return the sorted word arrays (for boundary detection)."""
    if len(words) == 1:
        # one word: the sort hands back the sorted key beside the rows
        w = words[0]
        iota = jnp.arange(w.shape[0], dtype=jnp.int32)
        key, perm = lax.sort((w, iota), num_keys=1, is_stable=True)
        return [key], perm
    perm = sort_permutation(words)
    return [jnp.take(w, perm) for w in words], perm
