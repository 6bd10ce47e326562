"""Basic device kernels: selection compaction, gather plans, hashing.

Reference analogues: cuDF apply_boolean_mask/gather (used by GpuFilterExec,
basicPhysicalOperators.scala:230) and spark murmur3 hashing
(HashFunctions.scala, GpuHashPartitioning.scala).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import trace as _obs_trace


def prefix_sum(x):
    """Inclusive running sum of a 1-D integer array by log2(n)
    shift-and-add steps; exact, wraps as the dtype does.

    In place of ``jnp.cumsum`` inside device programs: the chip's
    compiler takes 32 s for an int32 ``cumsum`` at 2^20 elements and
    twice that for an int64 one (3 s at 2^23: the time follows the
    length by no rule), which made ``join_expand_matches`` the costliest
    program of a cold start (39-103 s a capacity).  These steps are
    elementwise, compile in under a second at any length and run no
    slower (0.65 ms against 0.86 at 2^20; PERF.md section 6, PR 32)."""
    d = 1
    while d < x.shape[0]:
        x = x + jnp.concatenate([jnp.zeros((d,), x.dtype), x[:-d]])
        d *= 2
    return x


def prefix_max(x, fill):
    """Inclusive running maximum of a 1-D array by ``prefix_sum``'s
    log2(n) shift steps; ``fill`` (at most every value) enters from the
    left.  ``lax.cummax`` lowers to the windowed reduce whose compile is
    the ``cumsum`` trouble ``prefix_sum`` describes."""
    d = 1
    while d < x.shape[0]:
        x = jnp.maximum(x, jnp.concatenate([jnp.full((d,), fill, x.dtype),
                                            x[:-d]]))
        d *= 2
    return x


def rows_flagged_first(flag):
    """Row numbers (uint32) of a 1-D boolean array, the flagged rows
    first and each side in row order: one uint32 word a row (the flag's
    complement above the row number) sorted alone.  The stable pair sort
    of (flag, row number) this replaces compiled for 22 s on the chip at
    2^20 rows (40 s with 64-bit flags) and ran for 2.4 ms; a lone uint32
    operand compiles in 3 s and runs in 1.1 ms (PERF.md section 6, PR
    32)."""
    n = flag.shape[0]
    assert n <= 1 << 31
    word = jnp.where(flag, jnp.uint32(0), jnp.uint32(1 << 31)) \
        | jnp.arange(n, dtype=jnp.uint32)
    return lax.sort(word, is_stable=False) & jnp.uint32(0x7FFFFFFF)


@_obs_trace.launched()
@jax.jit
def filter_compact_indices(keep_mask, num_rows):
    """Turn a boolean keep-mask into a stable gather plan.

    Returns (indices[cap], new_count).  Rows where keep is True are moved to
    the front preserving order; the tail is filled with clipped indices whose
    validity the caller masks off.
    """
    cap = keep_mask.shape[0]
    in_range = jnp.arange(cap) < num_rows
    keep = keep_mask & in_range
    order = rows_flagged_first(keep).astype(jnp.int64)
    new_count = jnp.sum(keep)
    return order, new_count


@_obs_trace.launched()
@jax.jit
def filter_prefix_positions(keep_mask):
    """positions[i] = output slot of row i if kept (cumsum-1)."""
    return prefix_sum(keep_mask.astype(jnp.int32)) - 1


# ---------------------------------------------------------------------------
# Murmur3-style 64-bit mixing for partitioning / hash expressions.
# Self-consistent across the framework (our oracle is our CPU path, not
# JVM Spark), matching the role of Spark's Murmur3_x86_32(seed=42).
# ---------------------------------------------------------------------------

M1 = 0xff51afd7ed558ccd
M2 = 0xc4ceb9fe1a85ec53


@_obs_trace.launched()
@jax.jit
def hash_mix64(x):
    x = x.astype(jnp.uint64)
    x = x ^ (x >> jnp.uint64(33))
    x = x * jnp.uint64(M1)
    x = x ^ (x >> jnp.uint64(33))
    x = x * jnp.uint64(M2)
    x = x ^ (x >> jnp.uint64(33))
    return x


@jax.named_scope("hash_words")
def hash_words(word_lists, seed: int = 42):
    """Combine lists of uint64 word arrays into one 64-bit hash per row."""
    h = jnp.full(word_lists[0].shape, jnp.uint64(seed))
    for w in word_lists:
        h = hash_mix64(h ^ w)
    return h


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnames=("num_parts",))
def hash_to_partition(hashes, num_parts: int):
    return (hashes % jnp.uint64(num_parts)).astype(jnp.int32)
