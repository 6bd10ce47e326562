"""List (ArrayType) kernels over Arrow offsets+elements device layout.

Reference analogue: cuDF list kernels used by collectionOperations.scala
(Size/ElementAt/ArrayContains/SortArray) and GpuGenerateExec.scala
(explode/posexplode).  TPU-first: lists have no native XLA type, so every
op is integer arithmetic over the offsets buffer — searchsorted row
assignment, segmented reductions (jax.ops.segment_*), and gathers —
all static-shape, mirroring the string kernels (kernels/strings.py).

The one dynamic quantity (total element count of a gather/explode result)
is a single scalar pulled to host to pick the power-of-two output bucket,
the same "size on host, fill on device" two-phase pattern gather_strings
uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..columnar.column import bucket_capacity
from ..obs import trace as _obs_trace


@_obs_trace.launched()
@jax.jit
def list_lengths(offsets) -> jnp.ndarray:
    return (offsets[1:] - offsets[:-1]).astype(jnp.int32)


@_obs_trace.launched()
@jax.jit
def list_gather_offsets(offsets, validity, indices):
    """Phase 1 of a list-column row gather: new offsets + element total.

    Returns (new_offsets[ncap+1], gathered_validity[ncap],
    src_starts[ncap], total_elements scalar).
    """
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    src = jnp.clip(indices, 0, starts.shape[0] - 1)
    glens = jnp.take(lens, src)
    gvalid = jnp.take(validity, src)
    glens = jnp.where(gvalid, glens, 0)
    new_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(glens).astype(jnp.int32)])
    return new_offsets, gvalid, jnp.take(starts, src), new_offsets[-1]


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnames=("elem_cap",))
def list_element_gather_indices(new_offsets, src_starts, elem_cap: int):
    """Phase 2: for each output element slot, the source element index.

    Returns (src_idx[elem_cap], live[elem_cap]): slot j belongs to output
    row r = searchsorted(new_offsets, j); its source element is
    src_starts[r] + (j - new_offsets[r]).
    """
    j = jnp.arange(elem_cap, dtype=jnp.int32)
    row = jnp.searchsorted(new_offsets[1:], j, side="right").astype(jnp.int32)
    row = jnp.clip(row, 0, new_offsets.shape[0] - 2)
    within = j - new_offsets[row]
    src_idx = jnp.take(src_starts, row) + within
    live = j < new_offsets[-1]
    return jnp.where(live, src_idx, 0), live


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnames=("num_rows", "outer"))
def list_explode_offsets(offsets, validity, num_rows: int, outer: bool):
    """Per-row output counts for explode (GpuGenerateExec.scala role).

    explode emits one output row per element; null/empty lists emit 0 rows
    (or exactly 1 all-null row when ``outer``).  Returns
    (out_offsets[cap+1], total scalar).
    """
    cap = offsets.shape[0] - 1
    lens = offsets[1:] - offsets[:-1]
    live_row = jnp.arange(cap) < num_rows
    counts = jnp.where(validity & live_row, lens, 0)
    if outer:
        counts = jnp.where(live_row & (counts == 0), 1, counts)
    out_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
    return out_offsets, out_offsets[-1]


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnames=("out_cap",))
def list_explode_indices(offsets, validity, out_offsets, out_cap: int):
    """Row/element/position indices for each exploded output row.

    Returns (row_idx, elem_idx, pos, elem_valid, live) each [out_cap]:
    output slot j came from input row row_idx[j], source element
    elem_idx[j] (= offsets[row]+pos), at list position pos[j].
    ``elem_valid`` is False for the synthetic null row of outer-explode
    on an empty/null list.
    """
    j = jnp.arange(out_cap, dtype=jnp.int32)
    row = jnp.searchsorted(out_offsets[1:], j, side="right").astype(jnp.int32)
    row = jnp.clip(row, 0, out_offsets.shape[0] - 2)
    pos = j - out_offsets[row]
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    elem_idx = jnp.take(starts, row) + pos
    elem_valid = jnp.take(validity, row) & (pos < jnp.take(lens, row))
    live = j < out_offsets[-1]
    return row, jnp.where(elem_valid & live, elem_idx, 0), pos, \
        elem_valid & live, live


def segment_ids_for(offsets, elem_cap: int):
    """Row id [elem_cap] of each element; n_lists for dead slots."""
    return list_segment_ids(offsets, elem_cap)


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnames=("elem_cap",))
def list_segment_ids(offsets, elem_cap: int):
    j = jnp.arange(elem_cap, dtype=jnp.int32)
    row = jnp.searchsorted(offsets[1:], j, side="right").astype(jnp.int32)
    n_lists = offsets.shape[0] - 1
    # offsets may start past 0 for sliced columns; leading slots are dead
    live = (j >= offsets[0]) & (j < offsets[-1])
    return jnp.where(live, jnp.clip(row, 0, n_lists - 1), n_lists)


@_obs_trace.launched()
@functools.partial(jax.jit, static_argnames=("num_segments",))
def list_segmented_any(flags, seg_ids, num_segments: int):
    """OR-reduce boolean flags per segment."""
    return jax.ops.segment_max(flags.astype(jnp.int32), seg_ids,
                               num_segments=num_segments) > 0


