"""Eager row moves of a batch's columns: one program a launch.

Every column that moves by one index vector moves in ONE row gather of
a ``[rows, lanes]`` uint32 matrix (``kernels/gather.py``): fixed-width
data as 32-bit lanes (a 64-bit value as two), every validity as one bit
of a shared lane, a lazy string view's row map as one lane.  The chip
charges a gather per index, so k columns cost one index a row where two
1-D takes a column cost 2k.  A string's bytes never move here: its
gather stays a view (``GatheredStringColumn``).  List, map and struct
columns keep their own gathers.

Under a ``jax.jit`` trace (a core's body) a column's ``gather`` is the
per-column takes of the program being built; these programs are for
eager calls only.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.gather import lane_count, pack_rows
from ..obs import trace as _trace
from .binary64 import Binary64Column
from .column import Column, GatheredStringColumn, StringColumn

#: the two programs, built once (jax keys their compiled forms by the
#: arguments' shapes), and their first-call wrappers by cache key
_PROGRAMS: dict = {}


def _kind(col) -> Optional[str]:
    """How ``col`` rides the matrix: ``fixed`` (data and validity),
    ``string`` (its validity; the view's row map is the indices),
    ``view`` (an unmaterialized view: its row map and validity), or None
    (a nested column: its own gather)."""
    t = type(col)
    if t is Column or t is Binary64Column:
        return "fixed"
    if t is StringColumn:
        return "string"
    if t is GatheredStringColumn:
        return "view" if col._mat is None else "string"
    return None


#: the most uint32 lanes one row gather takes: at 2^20 rows a 17-lane
#: gather cost the chip 11.9 ms and a 33-lane one 52.4 ms, twice what
#: two of 17 cost (``benchmarks/batch_gather_chip.py``, PERF.md section
#: 5); wider batches move in several matrices, one program all the same
MAX_LANES = 17


def _chunks(arrays):
    """``arrays`` in runs whose ``pack_rows`` matrix has at most
    ``MAX_LANES`` lanes."""
    runs, run, data, flags = [], [], 0, 0
    for a in arrays:
        d = data + lane_count(a.dtype)
        f = flags + (a.dtype == jnp.bool_)
        if run and d + -(-f // 32) > MAX_LANES:
            runs.append(run)
            run, d, f = [], lane_count(a.dtype), int(a.dtype == jnp.bool_)
        run.append(a)
        data, flags = d, f
    return runs + [run] if run else runs


def _move(rows_of, live, fixed, strings, views):
    """The traced body both programs share: every array's rows as
    ``rows_of`` picks them from its packed matrix (``MAX_LANES`` at a
    time), ``live`` ANDed into every validity."""
    moved = {}
    for run in _chunks([a for pair in fixed + views for a in pair] +
                       list(strings)):
        matrix, unpack = pack_rows(run)
        moved.update(unpack(rows_of(matrix)))

    def got(a):
        return moved[id(a)][1]

    def valid(v):
        return got(v) if live is None else got(v) & live
    return (tuple((got(d), valid(v)) for d, v in fixed),
            tuple(valid(v) for v in strings),
            tuple((got(i), valid(v)) for i, v in views))


def _string_map(rows, strings):
    """A plain string column's view map: the rows, clipped into range."""
    if not strings:
        return None
    return jnp.clip(rows, 0, strings[0].shape[0] - 1).astype(jnp.int32)


def _batch_gather(indices, live, fixed, strings, views):
    def rows_of(matrix):
        return jnp.take(matrix, indices, axis=0, mode="clip")
    return _move(rows_of, live, fixed, strings, views) + \
        (_string_map(indices, strings),)


def _batch_slice(start, nvalid, fixed, strings, views, out_cap):
    """Rows ``[start, start + out_cap)`` by a dynamic slice of the
    matrix padded by ``out_cap`` zero rows: a window past the end reads
    dead zero rows where a gather would repeat the last row (2.23 ms for
    3.02 at 2^17 of 2^20 rows, 1.93 for 2.28 at 2^14 of 2^17: PERF.md
    section 5)."""
    rows = jnp.arange(out_cap)

    def rows_of(matrix):
        return lax.dynamic_slice_in_dim(
            jnp.pad(matrix, ((0, out_cap), (0, 0))), start, out_cap, 0)
    return _move(rows_of, rows < nvalid, fixed, strings, views) + \
        (_string_map(rows + start, strings),)


def _program(name: str, key):
    """The program ``name`` behind the first-call timing of ``key``."""
    fn = _PROGRAMS.get(key)
    if fn is None:
        from ..obs import compile_watch as _cw
        prog = _PROGRAMS.get(name)
        if prog is None:
            if name == "batch_gather":
                prog = _cw.jit(_batch_gather, "batch_gather")
                # a launch's lanes: one index a row of the matrix
                prog.lanes = lambda indices, *a, **k: int(indices.shape[0])
            else:
                prog = _cw.jit(_batch_slice, "batch_slice",
                               static_argnames=("out_cap",))
            _PROGRAMS[name] = prog
        fn = _cw.wrap_miss(name, prog, key)
        if len(_PROGRAMS) < 4096:
            _PROGRAMS[key] = fn
    return fn


def _split(cols: Sequence[Column]):
    """-> (the matrix's columns by capacity: {cap: [positions]}, the
    nested columns' positions)."""
    by_cap, nested = {}, []
    for i, c in enumerate(cols):
        if _kind(c) is None:
            nested.append(i)
        else:
            by_cap.setdefault(c.capacity, []).append(i)
    return by_cap, nested


def _sig(col) -> tuple:
    kind = _kind(col)
    return (kind, str(col.data.dtype)) if kind == "fixed" else (kind,)


def _operands(cols, pos):
    """-> (signature, fixed, strings, views) of the columns at ``pos``,
    which are sorted by kind and dtype: batches of the same columns in
    another order share a program."""
    pos.sort(key=lambda i: _sig(cols[i]))
    sig, fixed, strings, views = [], [], [], []
    for i in pos:
        c = cols[i]
        kind = _kind(c)
        sig.append(_sig(c))
        if kind == "fixed":
            fixed.append((c.data, c.validity))
        elif kind == "string":
            strings.append(c.validity)
        else:
            views.append((c.idx, c.validity))
    return tuple(sig), tuple(fixed), tuple(strings), tuple(views)


def _rebuild(cols, pos, out, unique: bool, res: list) -> None:
    """Put a program's outputs back into columns at ``pos`` of ``res``."""
    fixed, strings, views, smap = (iter(out[0]), iter(out[1]),
                                   iter(out[2]), out[3])
    for i in pos:
        c = cols[i]
        kind = _kind(c)
        if kind == "fixed":
            d, v = next(fixed)
            res[i] = Binary64Column(d, v) if type(c) is Binary64Column \
                else Column(c.dtype, d, v)
        elif kind == "string":
            src = c if type(c) is StringColumn else c._mat
            res[i] = GatheredStringColumn(src, smap, next(strings),
                                          unique=unique)
        else:
            idx, v = next(views)
            res[i] = GatheredStringColumn(c.src, idx, v,
                                          unique=unique and c._unique)


def gather_columns(cols: Sequence[Column], indices, live=None,
                   unique: bool = False) -> List[Column]:
    """Every column of ``cols`` at rows ``indices`` (clipped into range),
    with ``live`` (a mask over the output rows) ANDed into every
    validity; ``unique``: no index repeats (a sizing hint for string
    views).  Eagerly: one ``batch_gather`` launch for the columns of a
    capacity; nested columns gather on their own, ``live`` ANDed into
    their top validity too.  Under a trace: each column's own gather,
    as the cores have always traced it."""
    res: List[Optional[Column]] = [None] * len(cols)
    by_cap, own = _split(cols) if _trace.eager() else ({}, range(len(cols)))
    for cap, pos in by_cap.items():
        sig, fixed, strings, views = _operands(cols, pos)
        key = ("gather", sig, cap, int(indices.shape[0]),
               str(indices.dtype), live is not None)
        out = _program("batch_gather", key)(indices, live, fixed, strings,
                                            views)
        _trace.count("gather.batch.columns", len(pos))
        _rebuild(cols, pos, out, unique, res)
    for i in own:
        c = cols[i]
        g = c.gather(indices, live=live, unique=unique)
        # a nested column's own gather leaves its top validity as it was
        res[i] = g if live is None or _kind(c) else g.mask_validity(live)
    return res


def slice_columns(cols: Sequence[Column], start: int, out_cap: int,
                  valid_rows: int) -> List[Column]:
    """Rows ``[start, start + out_cap)`` of every column, rows from
    ``valid_rows`` on invalid: the columns of a capacity in one
    ``batch_slice`` launch, nested columns on their own."""
    res: List[Optional[Column]] = [None] * len(cols)
    by_cap, nested = _split(cols)
    for cap, pos in by_cap.items():
        sig, fixed, strings, views = _operands(cols, pos)
        out = _program("batch_slice", ("slice", sig, cap, out_cap))(
            np.int32(start), np.int32(valid_rows), fixed, strings, views,
            out_cap=out_cap)
        _rebuild(cols, pos, out, False, res)
    if nested:
        idx = jnp.arange(out_cap) + start
        mask = jnp.arange(out_cap) < valid_rows
        for i in nested:
            res[i] = cols[i].gather(idx).mask_validity(mask)
    return res
