"""Collection (array) expressions.

Reference analogues: complexTypeCreator.scala (CreateArray),
complexTypeExtractors.scala (GetArrayItem, ElementAt) and
collectionOperations.scala (Size, ArrayContains, SortArray), registered at
GpuOverrides.scala:773+.  Explode/PosExplode are generator expressions
consumed only by the Generate exec (GpuGenerateExec.scala role) — they do
not evaluate standalone.

TPU-first: all ops are offsets arithmetic + segmented reductions over the
ListColumn layout (kernels/lists.py); no per-row Python.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import (Column, ListColumn, StringColumn,
                               bucket_capacity)
from ..columnar.batch import ColumnarBatch
from ..kernels import lists as lk
from ..kernels import canon
from . import core as ec


class CreateArray(ec.Expression):
    """array(e1, e2, ...) — fixed-length list per row.

    Reference: complexTypeCreator.scala GpuCreateArray.
    """

    def __init__(self, *children: ec.Expression):
        self.children = list(children)

    def with_children(self, c):
        return CreateArray(*c)

    def dtype(self):
        if not self.children:
            return T.ArrayType(T.NULL)
        et = self.children[0].dtype()
        for c in self.children[1:]:
            et = T.common_type(et, c.dtype())
        return T.ArrayType(et)

    @property
    def nullable(self):
        return False  # the array itself is never null; elements may be

    def columnar_eval(self, batch: ColumnarBatch):
        from .cast import Cast
        k = len(self.children)
        cap = batch.capacity
        n = batch.num_rows
        et = self.dtype().element_type
        offsets = (jnp.arange(cap + 1, dtype=jnp.int32) *
                   jnp.int32(k)).clip(max=np.int32(n * k))
        kids = []
        for c in self.children:
            e = c if c.dtype() == et else Cast(c, et)
            kids.append(ec.eval_as_column(e, batch))
        if k == 0:
            elems = Column.all_null(et, 16)
        elif et == T.STRING:
            # concat children byte-wise then interleave via gather:
            # output element i*k+j reads child j's row i
            from ..columnar.batch import _concat_string_cols
            combined = _concat_string_cols(kids, [cap] * k,
                                           bucket_capacity(cap * k))
            j = jnp.arange(bucket_capacity(max(1, cap * k)), dtype=jnp.int32)
            src = (j % k) * cap + (j // k)
            elems = combined.gather(src)
        else:
            # [cap, k] stack -> row-major flatten is exactly interleaved
            data = jnp.stack([c.data for c in kids], axis=1).reshape(-1)
            valid = jnp.stack([c.validity for c in kids], axis=1).reshape(-1)
            ecap = bucket_capacity(max(1, cap * k))
            if data.shape[0] < ecap:
                data = jnp.pad(data, (0, ecap - data.shape[0]))
                valid = jnp.pad(valid, (0, ecap - valid.shape[0]))
            elems = Column(et, data, valid)
        live = jnp.arange(cap) < n
        return ListColumn(T.ArrayType(et), offsets, elems, live)


class Size(ec.Expression):
    """size(array) — Spark legacy semantics: size(null) = -1.

    Reference: collectionOperations.scala GpuSize.
    """

    def __init__(self, child: ec.Expression, legacy_null: bool = True):
        self.children = [child]
        self.legacy_null = legacy_null

    def with_children(self, c):
        return Size(c[0], self.legacy_null)

    def dtype(self):
        return T.INT32

    @property
    def nullable(self):
        return not self.legacy_null

    def columnar_eval(self, batch: ColumnarBatch):
        col: ListColumn = ec.eval_as_column(self.children[0], batch)
        lens = lk.list_lengths(col.offsets)
        if self.legacy_null:
            data = jnp.where(col.validity, lens, jnp.int32(-1))
            return Column(T.INT32, data,
                          jnp.ones(col.capacity, jnp.bool_))
        return Column(T.INT32, lens, col.validity)


class GetArrayItem(ec.Expression):
    """arr[i] — 0-based index; null when out of bounds or null input.

    Reference: complexTypeExtractors.scala GpuGetArrayItem.
    """

    def __init__(self, child: ec.Expression, index: ec.Expression):
        self.children = [child, index]

    def with_children(self, c):
        return GetArrayItem(c[0], c[1])

    def dtype(self):
        return self.children[0].dtype().element_type

    def columnar_eval(self, batch: ColumnarBatch):
        return _extract_at(self.children[0], self.children[1], batch,
                           one_based=False)


class ElementAt(ec.Expression):
    """element_at(arr, i) — 1-based, negative counts from the end —
    or element_at(map, key).

    Reference: collectionOperations.scala GpuElementAt (non-ANSI: null on
    out-of-bound / missing key).
    """

    def __init__(self, child: ec.Expression, index: ec.Expression):
        self.children = [child, index]

    def with_children(self, c):
        return ElementAt(c[0], c[1])

    def dtype(self):
        dt = self.children[0].dtype()
        if isinstance(dt, T.MapType):
            return dt.value_type
        return dt.element_type

    def columnar_eval(self, batch: ColumnarBatch):
        if isinstance(self.children[0].dtype(), T.MapType):
            return GetMapValue(
                self.children[0], self.children[1]).columnar_eval(batch)
        return _extract_at(self.children[0], self.children[1], batch,
                           one_based=True)


def _extract_at(arr_e: ec.Expression, idx_e: ec.Expression,
                batch: ColumnarBatch, one_based: bool):
    col: ListColumn = ec.eval_as_column(arr_e, batch)
    idx_col = ec.eval_as_column(idx_e, batch)
    cap = col.capacity
    starts = col.offsets[:-1]
    lens = (col.offsets[1:] - starts).astype(jnp.int32)
    raw = idx_col.data.astype(jnp.int32)
    if one_based:
        # 1-based; negative indexes from the end; 0 is invalid -> null
        pos = jnp.where(raw > 0, raw - 1, lens + raw)
        ok_idx = raw != 0
    else:
        pos = raw
        ok_idx = raw >= 0
    in_bounds = (pos >= 0) & (pos < lens)
    valid = col.validity & idx_col.validity & ok_idx & in_bounds
    src = starts + jnp.where(in_bounds, pos, 0)
    # gather with one index per output row -> result capacity == cap
    elems = col.elements.gather(jnp.where(valid, src, 0))
    return elems.mask_validity(valid)


class ArrayContains(ec.Expression):
    """array_contains(arr, value).

    Reference: collectionOperations.scala GpuArrayContains.  Spark
    semantics: null if the array is null; true if any element equals the
    value; null if no match but the array has null elements.
    """

    def __init__(self, child: ec.Expression, value: ec.Expression):
        self.children = [child, value]

    def with_children(self, c):
        return ArrayContains(c[0], c[1])

    def dtype(self):
        return T.BOOL

    def columnar_eval(self, batch: ColumnarBatch):
        col: ListColumn = ec.eval_as_column(self.children[0], batch)
        needle = self.children[1].columnar_eval(batch)
        cap = col.capacity
        ecap = col.elements.capacity
        seg = lk.segment_ids_for(col.offsets, ecap)
        seg_rows = jnp.clip(seg, 0, cap - 1)
        evalid = col.elements.validity
        needle, needle_valid = _needle_column(needle, cap, batch.num_rows)
        eq = _segment_equals(col.elements, needle, needle_valid, seg_rows,
                             batch.num_rows)
        hit = lk.list_segmented_any(eq & evalid, seg, cap + 1)[:cap]
        has_null_elem = lk.list_segmented_any(~evalid & (seg < cap), seg,
                                         cap + 1)[:cap]
        valid = col.validity & needle_valid[:cap] & (hit | ~has_null_elem)
        return Column(T.BOOL, hit, valid)


def _needle_column(needle, cap: int, num_rows: int):
    """Normalize a scalar-or-column lookup value to (column, validity)."""
    if isinstance(needle, ec.Scalar):
        # Spark: a null needle yields NULL for every non-null container
        valid = jnp.full(cap, needle.value is not None)
        return needle.to_column(cap, num_rows), valid
    return needle, needle.validity


def _segment_equals(elements: Column, needle: Column, needle_valid,
                    seg_rows, num_rows: int):
    """eq[ecap]: does element e equal its row's needle value?"""
    if isinstance(elements, StringColumn):
        from ..kernels import strings as sk
        nw = max(sk.needed_key_words(elements, elements.capacity),
                 sk.needed_key_words(needle, num_rows))
        ewords = sk.pack_words(elements, nw)
        nwords = sk.pack_words(needle, nw)
        eq = jnp.all(ewords == jnp.take(nwords, seg_rows, axis=0), axis=1)
        elens = elements.offsets[1:] - elements.offsets[:-1]
        nlens = needle.offsets[1:] - needle.offsets[:-1]
        eq = eq & (elens == jnp.take(nlens, seg_rows))
    else:
        eq = (elements.data ==
              jnp.take(needle.data, seg_rows).astype(elements.data.dtype))
    return eq & jnp.take(needle_valid, seg_rows)


class SortArray(ec.Expression):
    """sort_array(arr, asc) — sorts each list; nulls first when ascending,
    last when descending (Spark semantics).

    Reference: collectionOperations.scala GpuSortArray.
    """

    def __init__(self, child: ec.Expression, asc: bool = True):
        self.children = [child]
        self.asc = asc

    def with_children(self, c):
        return SortArray(c[0], self.asc)

    def dtype(self):
        return self.children[0].dtype()

    def columnar_eval(self, batch: ColumnarBatch):
        col: ListColumn = ec.eval_as_column(self.children[0], batch)
        ecap = col.elements.capacity
        seg = lk.segment_ids_for(col.offsets, ecap)
        n_elems = int(np.asarray(col.offsets)[min(batch.num_rows,
                                                  col.capacity)])
        words = canon.value_words(col.elements, n_elems)
        evalid = col.elements.validity
        nk = jnp.where(evalid, jnp.uint64(1), jnp.uint64(0)) if self.asc \
            else jnp.where(evalid, jnp.uint64(0), jnp.uint64(1))
        # LSD chained pair-sorts (kernels/sort.py rationale): significance
        # order is segment > null rank > value words, so least first
        from ..kernels.sort import lsd_pass
        perm = None
        passes = list(reversed([seg.astype(jnp.uint64), nk] +
                               [(w if self.asc else ~w) for w in words]))
        for w in passes:
            perm = lsd_pass(w, perm)
        elems = col.elements.gather(perm)
        return ListColumn(col.dtype, col.offsets, elems, col.validity)


class ArrayMin(ec.Expression):
    """array_min — segmented min ignoring nulls."""

    def __init__(self, child: ec.Expression):
        self.children = [child]

    def with_children(self, c):
        return ArrayMin(c[0])

    def dtype(self):
        return self.children[0].dtype().element_type

    def columnar_eval(self, batch: ColumnarBatch):
        return _seg_minmax(self.children[0], batch, is_min=True)


class ArrayMax(ec.Expression):
    def __init__(self, child: ec.Expression):
        self.children = [child]

    def with_children(self, c):
        return ArrayMax(c[0])

    def dtype(self):
        return self.children[0].dtype().element_type

    def columnar_eval(self, batch: ColumnarBatch):
        return _seg_minmax(self.children[0], batch, is_min=False)


def _seg_minmax(arr_e, batch, is_min: bool):
    import jax
    col: ListColumn = ec.eval_as_column(arr_e, batch)
    cap = col.capacity
    ecap = col.elements.capacity
    seg = lk.segment_ids_for(col.offsets, ecap)
    dt = col.dtype.element_type
    data = col.elements.data
    evalid = col.elements.validity
    if dt.is_fractional:
        # Spark float total order: NaN greatest, -0.0 == 0.0
        data = jnp.where(data == 0.0, jnp.array(0.0, data.dtype), data)
        nan = jnp.isnan(data)
        neutral = jnp.array(jnp.inf if is_min else -jnp.inf, data.dtype)
        masked = jnp.where(evalid & ~nan, data, neutral)
        fn = jax.ops.segment_min if is_min else jax.ops.segment_max
        red = fn(masked, seg, num_segments=cap + 1)[:cap]
        if is_min:
            has_num = lk.list_segmented_any(evalid & ~nan, seg, cap + 1)[:cap]
            red = jnp.where(has_num, red, jnp.array(jnp.nan, data.dtype))
        else:
            has_nan = lk.list_segmented_any(evalid & nan, seg, cap + 1)[:cap]
            red = jnp.where(has_nan, jnp.array(jnp.nan, data.dtype), red)
        any_valid = lk.list_segmented_any(evalid, seg, cap + 1)[:cap]
        return Column(dt, red, col.validity & any_valid)
    if dt == T.BOOL:
        neutral = is_min  # True for min, False for max
    else:
        info = np.iinfo(dt.np_dtype)
        neutral = info.max if is_min else info.min
    masked = jnp.where(evalid, data, jnp.asarray(neutral, data.dtype))
    fn = jax.ops.segment_min if is_min else jax.ops.segment_max
    red = fn(masked, seg, num_segments=cap + 1)[:cap]
    any_valid = lk.list_segmented_any(evalid, seg, cap + 1)[:cap]
    return Column(dt, red.astype(data.dtype), col.validity & any_valid)


class CreateNamedStruct(ec.Expression):
    """named_struct / struct(col...) — one child column per field.

    Reference: complexTypeCreator.scala GpuCreateNamedStruct.
    """

    def __init__(self, names: List[str], *children: ec.Expression):
        if len(names) != len(children):
            raise ValueError("CreateNamedStruct: one name per child")
        self.names = list(names)
        self.children = list(children)

    def with_children(self, c):
        return CreateNamedStruct(self.names, *c)

    def dtype(self):
        return T.StructType([
            T.StructField(n, c.dtype(), c.nullable)
            for n, c in zip(self.names, self.children)])

    @property
    def nullable(self):
        return False

    def columnar_eval(self, batch: ColumnarBatch):
        from ..columnar.column import StructColumn
        kids = [ec.eval_as_column(c, batch) for c in self.children]
        live = jnp.arange(batch.capacity) < batch.num_rows
        return StructColumn(self.dtype(), kids, live)


class GetStructField(ec.Expression):
    """struct.field extraction.

    Reference: complexTypeExtractors.scala GpuGetStructField.
    """

    def __init__(self, child: ec.Expression, field_name: str):
        self.children = [child]
        self.field_name = field_name

    def with_children(self, c):
        return GetStructField(c[0], self.field_name)

    def _field_index(self):
        st = self.children[0].dtype()
        for i, f in enumerate(st.fields):
            if f.name == self.field_name:
                return i, f
        raise ValueError(f"no field {self.field_name} in {st.name}")

    def dtype(self):
        return self._field_index()[1].dtype

    def columnar_eval(self, batch: ColumnarBatch):
        col = ec.eval_as_column(self.children[0], batch)
        i, _ = self._field_index()
        return col.children[i].mask_validity(col.validity)


class CreateMap(ec.Expression):
    """map(k1, v1, k2, v2, ...) — fixed entries per row.

    Reference: complexTypeCreator.scala GpuCreateMap.
    """

    def __init__(self, *children: ec.Expression):
        assert len(children) % 2 == 0, "map() needs key/value pairs"
        self.children = list(children)

    def with_children(self, c):
        return CreateMap(*c)

    def dtype(self):
        kt = self.children[0].dtype() if self.children else T.STRING
        vt = self.children[1].dtype() if self.children else T.STRING
        return T.MapType(kt, vt)

    @property
    def nullable(self):
        return False

    def columnar_eval(self, batch: ColumnarBatch):
        from ..columnar.column import MapColumn, StructColumn
        dt = self.dtype()
        keys_arr = CreateArray(*self.children[0::2]).columnar_eval(batch)
        vals_arr = CreateArray(*self.children[1::2]).columnar_eval(batch)
        est = MapColumn.entry_struct_type(dt)
        ecap = keys_arr.elements.capacity
        elems = StructColumn(
            est, [keys_arr.elements, vals_arr.elements],
            jnp.ones(ecap, jnp.bool_))
        return MapColumn(dt, keys_arr.offsets, elems, keys_arr.validity)


class GetMapValue(ec.Expression):
    """map[key] lookup: value of the matching key, null when absent.

    Reference: complexTypeExtractors.scala GpuGetMapValue.
    """

    def __init__(self, child: ec.Expression, key: ec.Expression):
        self.children = [child, key]

    def with_children(self, c):
        return GetMapValue(c[0], c[1])

    def dtype(self):
        return self.children[0].dtype().value_type

    def columnar_eval(self, batch: ColumnarBatch):
        import jax
        col = ec.eval_as_column(self.children[0], batch)
        needle = self.children[1].columnar_eval(batch)
        cap = col.capacity
        ecap = col.elements.capacity
        seg = lk.segment_ids_for(col.offsets, ecap)
        seg_rows = jnp.clip(seg, 0, cap - 1)
        needle, needle_valid = _needle_column(needle, cap, batch.num_rows)
        eq = _segment_equals(col.keys, needle, needle_valid, seg_rows,
                             batch.num_rows)
        live_elem = seg < cap
        # last matching entry wins (Spark keeps the last duplicate key)
        idx = jnp.where(eq & live_elem, jnp.arange(ecap), -1)
        best = jax.ops.segment_max(idx, seg, num_segments=cap + 1)[:cap]
        found = best >= 0
        vals = col.values.gather(jnp.where(found, best, 0))
        return vals.mask_validity(col.validity & needle_valid[:cap] & found)


class MapKeys(ec.Expression):
    """map_keys(m) -> array of keys."""

    def __init__(self, child: ec.Expression):
        self.children = [child]

    def with_children(self, c):
        return MapKeys(c[0])

    def dtype(self):
        return T.ArrayType(self.children[0].dtype().key_type)

    def columnar_eval(self, batch: ColumnarBatch):
        col = ec.eval_as_column(self.children[0], batch)
        return ListColumn(self.dtype(), col.offsets, col.keys, col.validity)


class MapValues(ec.Expression):
    """map_values(m) -> array of values."""

    def __init__(self, child: ec.Expression):
        self.children = [child]

    def with_children(self, c):
        return MapValues(c[0])

    def dtype(self):
        return T.ArrayType(self.children[0].dtype().value_type)

    def columnar_eval(self, batch: ColumnarBatch):
        col = ec.eval_as_column(self.children[0], batch)
        return ListColumn(self.dtype(), col.offsets, col.values,
                          col.validity)


class ExtractValue(ec.Expression):
    """Col.getItem: dispatches by the child's type once resolved —
    array[int index], map[key], or struct.field (Spark's
    UnresolvedExtractValue role)."""

    def __init__(self, child: ec.Expression, key):
        # the key rides as a child expression so bind()/resolve() reach it;
        # a plain-str key additionally remembers the struct-field name
        self.key = key
        key_expr = key if isinstance(key, ec.Expression) else ec.lit(key)
        self.children = [child, key_expr]

    def with_children(self, c):
        out = ExtractValue(c[0], self.key)
        out.children = list(c)
        return out

    def _resolved(self) -> ec.Expression:
        dt = self.children[0].dtype()
        if isinstance(dt, T.StructType) and isinstance(self.key, str):
            return GetStructField(self.children[0], self.key)
        if isinstance(dt, T.MapType):
            return GetMapValue(self.children[0], self.children[1])
        if isinstance(dt, T.ArrayType):
            return GetArrayItem(self.children[0], self.children[1])
        raise ValueError(f"cannot extract {self.key!r} from {dt.name}")

    def dtype(self):
        return self._resolved().dtype()

    def columnar_eval(self, batch: ColumnarBatch):
        return self._resolved().columnar_eval(batch)


class Explode(ec.Expression):
    """Generator marker — consumed by the Generate exec only.

    Reference: GpuExplode in GpuGenerateExec.scala.
    """

    def __init__(self, child: ec.Expression, pos: bool = False,
                 outer: bool = False):
        self.children = [child]
        self.pos = pos
        self.outer = outer

    def with_children(self, c):
        return Explode(c[0], self.pos, self.outer)

    def dtype(self):
        return self.children[0].dtype().element_type

    def columnar_eval(self, batch):
        raise RuntimeError(
            "Explode is a generator; it must be planned into a Generate "
            "node (DataFrame.select handles this)")
