"""Row gathers of many arrays by one index vector.

On the chip a gather costs per index, not per byte: one row gather of a
``[rows, lanes]`` uint32 matrix takes about what one 1-D take does
(2^20 rows: eleven lanes 8.26 ms, one uint32 lane 8.29 ms; PERF.md
section 5).  So where several arrays move by the same indices, their
rows move together: fixed-width values as 32-bit lanes (64-bit values as
two), boolean arrays 32 to a lane.  The aggregate's fused cores bring
their inputs into sorted order this way (``kernels/aggregate.py``
``groupby_plan``), and so does every eager gather of a batch's columns
(``columnar/gather.py``).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def as_lanes(a):
    """A fixed-width array as uint32 lanes (a list of [rows] arrays) and
    the function that puts the lanes back together.  Integers and
    float32 are bit patterns; a float64 is its bit pattern where the
    backend can bitcast one (CPU), and on the chip, where a float64 IS a
    pair of float32s, that pair: exact either way."""
    from . import canon     # lazy: canon imports the columnar layer
    dt = a.dtype
    u32 = jnp.uint32

    def bits32(x):
        return lax.bitcast_convert_type(x, u32)
    if dt == jnp.float64 and canon._f64_bitcast_supported():
        pair = lax.bitcast_convert_type(a, u32)          # [rows, 2]
        return [pair[:, 0], pair[:, 1]], lambda p: \
            lax.bitcast_convert_type(jnp.stack(p, 1), jnp.float64)
    if dt == jnp.float64:
        hi = a.astype(jnp.float32)
        lo = jnp.where(jnp.isfinite(hi), a - hi.astype(jnp.float64),
                       0.0).astype(jnp.float32)

        def join(p):
            h = lax.bitcast_convert_type(p[0], jnp.float32)
            lw = lax.bitcast_convert_type(p[1], jnp.float32)
            # h alone when there is no low part: keeps -0.0 and inf
            return jnp.where(lw == 0, h.astype(jnp.float64),
                             h.astype(jnp.float64) + lw.astype(jnp.float64))
        return [bits32(hi), bits32(lo)], join
    if dt.itemsize == 8:
        w = a.view(jnp.uint64)
        return [(w & jnp.uint64(0xFFFFFFFF)).astype(u32),
                (w >> jnp.uint64(32)).astype(u32)], lambda p: \
            ((p[1].astype(jnp.uint64) << jnp.uint64(32)) |
             p[0].astype(jnp.uint64)).view(dt)
    if dt.itemsize == 4:
        return [bits32(a)], lambda p: lax.bitcast_convert_type(p[0], dt)
    # 8- and 16-bit integers ride widened
    return [bits32(a.astype(jnp.int32))], lambda p: \
        lax.bitcast_convert_type(p[0], jnp.int32).astype(dt)


def lane_count(dtype) -> int:
    """The uint32 lanes ``as_lanes`` gives an array of ``dtype``; a
    boolean array is one bit of a shared lane (0 here)."""
    dt = jnp.dtype(dtype)
    if dt == jnp.bool_:
        return 0
    return 2 if dt.itemsize == 8 else 1


def pack_rows(arrays):
    """Every distinct array of ``arrays`` (rows leading, as many rows
    each) as the columns of ONE ``[rows, lanes]`` uint32 matrix: 64-bit
    values as two lanes, boolean arrays 32 to a lane.  Returns (the
    matrix, or None when there is nothing to pack, and the function that
    takes any rows of it back to ``{id(array): (array, those rows of
    the array)}``)."""
    distinct = list({id(a): a for a in arrays}.values())
    flags = [a for a in distinct if a.dtype == jnp.bool_]
    lanes, joins = [], []
    for a in distinct:
        if a.dtype != jnp.bool_:
            mine, join = as_lanes(a)
            joins.append((a, len(lanes), len(mine), join))
            lanes.extend(mine)
    flag_lane0 = len(lanes)
    for at in range(0, len(flags), 32):
        word = jnp.zeros(flags[at].shape[0], jnp.uint32)
        for bit, v in enumerate(flags[at:at + 32]):
            word = word | (v.astype(jnp.uint32) << jnp.uint32(bit))
        lanes.append(word)

    def unpack(got):
        moved = {id(a): (a, join([got[:, at + i] for i in range(n)]))
                 for a, at, n, join in joins}
        for i, v in enumerate(flags):
            bit = (got[:, flag_lane0 + i // 32] >> jnp.uint32(i % 32)) \
                & jnp.uint32(1)
            moved[id(v)] = (v, bit != jnp.uint32(0))
        return moved
    return (jnp.stack(lanes, 1) if lanes else None), unpack


def gather_rows_once(perm, arrays, mode=None):
    """Every distinct array of ``arrays`` brought into ``perm``'s order
    by ONE row gather of their ``pack_rows`` matrix.  On the chip the row
    gather of ten lanes takes 8.3 ms at 2^20 rows where five 1-D float64
    takes in one program take 90 ms (PERF.md section 5).  ``mode`` is
    ``jnp.take``'s for an index out of range.  Returns ``{id(array):
    (array, the array in perm's order)}``."""
    matrix, unpack = pack_rows(arrays)
    if matrix is None:
        return {}
    return unpack(jnp.take(matrix, perm, axis=0, mode=mode))
