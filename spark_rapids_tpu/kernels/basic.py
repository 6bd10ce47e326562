"""Basic device kernels: selection compaction, gather plans, hashing.

Reference analogues: cuDF apply_boolean_mask/gather (used by GpuFilterExec,
basicPhysicalOperators.scala:230) and spark murmur3 hashing
(HashFunctions.scala, GpuHashPartitioning.scala).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp



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
    # stable: argsort of (not keep) keeps relative order of kept rows
    order = jnp.argsort(jnp.where(keep, 0, 1), stable=True)
    new_count = jnp.sum(keep)
    return order, new_count


@jax.jit
def filter_prefix_positions(keep_mask):
    """positions[i] = output slot of row i if kept (cumsum-1)."""
    return jnp.cumsum(keep_mask.astype(jnp.int32)) - 1


# ---------------------------------------------------------------------------
# Murmur3-style 64-bit mixing for partitioning / hash expressions.
# Self-consistent across the framework (our oracle is our CPU path, not
# JVM Spark), matching the role of Spark's Murmur3_x86_32(seed=42).
# ---------------------------------------------------------------------------

M1 = 0xff51afd7ed558ccd
M2 = 0xc4ceb9fe1a85ec53


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


@functools.partial(jax.jit, static_argnames=("num_parts",))
def hash_to_partition(hashes, num_parts: int):
    return (hashes % jnp.uint64(num_parts)).astype(jnp.int32)
