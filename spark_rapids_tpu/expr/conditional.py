"""Conditional expressions: If / CaseWhen / Coalesce / Nvl family.

Reference analogue: conditionalExpressions.scala, nullExpressions.scala.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column, StringColumn
from .core import Expression, eval_data_valid, as_column


def _result_dtype(exprs: List[Expression]) -> T.DType:
    dt: Optional[T.DType] = None
    for e in exprs:
        et = e.dtype()
        if et == T.NULL:
            continue
        dt = et if dt is None else (dt if dt == et else T.common_type(dt, et))
    return dt if dt is not None else T.NULL


def _select_columns(branches, batch, out_t):
    """Evaluate (cond_mask, value) branches into a single (data, valid)."""
    cap = batch.capacity
    if out_t == T.STRING:
        # strings: build per-row source selection then gather bytes from the
        # concatenation of branch columns (host-free, static shapes)
        cols = []
        conds = []
        for cond, val in branches:
            cols.append(as_column(val.columnar_eval(batch), cap,
                                  batch.num_rows))
            conds.append(cond)
        return _select_strings(conds, cols, cap)
    data = jnp.zeros(cap, out_t.np_dtype)
    valid = jnp.zeros(cap, bool)
    decided = jnp.zeros(cap, bool)
    for cond, val in branches:
        a, v, vt = eval_data_valid(val, batch)
        if isinstance(a, StringColumn):
            raise AssertionError("string handled above")
        if vt == T.NULL:
            a = jnp.zeros(cap, out_t.np_dtype)
            v = jnp.zeros(cap, bool)
        take = cond & ~decided
        data = jnp.where(take, a.astype(out_t.np_dtype), data)
        valid = jnp.where(take, v, valid)
        decided = decided | cond
    return data, valid


def _select_strings(conds, cols, cap):
    """Row-wise select among string columns via indexed gather."""
    from ..kernels.strings import str_materialize_bytes
    from ..columnar.column import bucket_capacity
    sel = jnp.full(cap, len(cols), jnp.int32)
    decided = jnp.zeros(cap, bool)
    for i, cond in enumerate(conds):
        take = cond & ~decided
        sel = jnp.where(take, i, sel)
        decided = decided | cond
    starts = []
    lens = []
    valids = []
    for c in cols:
        starts.append(c.offsets[:-1])
        lens.append(c.offsets[1:] - c.offsets[:-1])
        valids.append(c.validity)
    starts.append(jnp.zeros(cap, jnp.int32))
    lens.append(jnp.zeros(cap, jnp.int32))
    valids.append(jnp.zeros(cap, bool))
    starts_m = jnp.stack(starts)   # [k+1, cap]
    lens_m = jnp.stack(lens)
    valids_m = jnp.stack(valids)
    rows = jnp.arange(cap)
    src_start = starts_m[sel, rows]
    src_len = lens_m[sel, rows]
    valid = valids_m[sel, rows]
    src_len = jnp.where(valid, src_len, 0)
    new_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(src_len).astype(jnp.int32)])
    from ..analysis import residency  # lazy: avoids import cycle
    with residency.declared_transfer(site="size_probe"):
        total = int(new_offsets[-1])
    out_bytes = bucket_capacity(max(1, total))
    # byte source: per-row from its chosen column's byte buffer; buffers
    # differ per column, so materialize per column then select
    out = jnp.zeros(out_bytes, jnp.uint8)
    for i, c in enumerate(cols):
        buf_i = str_materialize_bytes(c.data, new_offsets, src_start, out_bytes)
        j = jnp.arange(out_bytes, dtype=jnp.int32)
        row_of_j = jnp.clip(
            jnp.searchsorted(new_offsets[1:], j, side="right"), 0, cap - 1)
        out = jnp.where(sel[row_of_j] == i, buf_i, out)
    mb = StringColumn.combined_max_bytes(cols)
    return StringColumn(new_offsets, out, valid, max_bytes=mb), valid


class If(Expression):
    def __init__(self, pred: Expression, if_true: Expression,
                 if_false: Expression):
        self.children = [pred, if_true, if_false]

    def with_children(self, c):
        return If(c[0], c[1], c[2])

    def dtype(self):
        return _result_dtype(self.children[1:])

    def columnar_eval(self, batch):
        p, pv, _ = eval_data_valid(self.children[0], batch)
        cond_true = p.astype(bool) & pv
        cond_false = ~cond_true
        out_t = self.dtype()
        res = _select_columns(
            [(cond_true, self.children[1]), (cond_false, self.children[2])],
            batch, out_t)
        if out_t == T.STRING:
            col, valid = res
            return col
        data, valid = res
        return Column(out_t, data, valid)


class CaseWhen(Expression):
    """CASE WHEN c1 THEN v1 ... ELSE e END."""

    def __init__(self, branches: List[Tuple[Expression, Expression]],
                 else_value: Optional[Expression] = None):
        self.branches = branches
        self.else_value = else_value
        self.children = [x for (c, v) in branches for x in (c, v)] + (
            [else_value] if else_value is not None else [])

    def with_children(self, c):
        n = len(self.branches)
        branches = [(c[2 * i], c[2 * i + 1]) for i in range(n)]
        els = c[2 * n] if len(c) > 2 * n else None
        return CaseWhen(branches, els)

    def dtype(self):
        vals = [v for _, v in self.branches]
        if self.else_value is not None:
            vals.append(self.else_value)
        return _result_dtype(vals)

    def columnar_eval(self, batch):
        out_t = self.dtype()
        sel_branches = []
        for cond, val in self.branches:
            p, pv, _ = eval_data_valid(cond, batch)
            sel_branches.append((p.astype(bool) & pv, val))
        if self.else_value is not None:
            from .core import Literal
            always = jnp.ones(batch.capacity, bool)
            sel_branches.append((always, self.else_value))
        res = _select_columns(sel_branches, batch, out_t)
        if out_t == T.STRING:
            col, _ = res
            return col
        data, valid = res
        return Column(out_t, data, valid)


class Coalesce(Expression):
    def __init__(self, *children):
        self.children = list(children)

    def with_children(self, c):
        return Coalesce(*c)

    def dtype(self):
        return _result_dtype(self.children)

    def columnar_eval(self, batch):
        out_t = self.dtype()
        if out_t == T.STRING:
            conds = []
            cols = []
            for ch in self.children:
                col = as_column(ch.columnar_eval(batch), batch.capacity,
                                batch.num_rows)
                conds.append(col.validity)
                cols.append(col)
            col, _ = _select_strings(conds, cols, batch.capacity)
            return col
        data = jnp.zeros(batch.capacity, out_t.np_dtype)
        valid = jnp.zeros(batch.capacity, bool)
        for ch in self.children:
            a, v, vt = eval_data_valid(ch, batch)
            if vt == T.NULL:
                continue
            take = v & ~valid
            data = jnp.where(take, a.astype(out_t.np_dtype), data)
            valid = valid | v
        return Column(out_t, data, valid)


def Nvl(a, b):
    return Coalesce(a, b)


class NaNvl(Expression):
    """nanvl(a, b): a unless a is NaN, then b."""

    def __init__(self, left, right):
        self.children = [left, right]

    def with_children(self, c):
        return NaNvl(c[0], c[1])

    def dtype(self):
        return T.common_type(self.children[0].dtype(),
                             self.children[1].dtype())

    def columnar_eval(self, batch):
        a, av, _ = eval_data_valid(self.children[0], batch)
        b, bv, _ = eval_data_valid(self.children[1], batch)
        out_t = self.dtype()
        a = a.astype(out_t.np_dtype)
        b = b.astype(out_t.np_dtype)
        use_b = jnp.isnan(a) & av
        return Column(out_t, jnp.where(use_b, b, a),
                      jnp.where(use_b, bv, av))
