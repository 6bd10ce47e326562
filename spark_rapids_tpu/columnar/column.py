"""Device column vectors — the TPU analogue of ``GpuColumnVector``.

Role parity: reference sql-plugin/src/main/java/com/nvidia/spark/rapids/
GpuColumnVector.java (cuDF-backed device vectors) and RapidsHostColumnVector.java.

TPU-first design:
- Every column is a set of dense JAX arrays padded to a *bucketed capacity*
  (power of two).  XLA requires static shapes, so kernels are compiled per
  (schema, capacity-bucket) and reused; the live row count travels as data.
- Validity is a separate bool array (Arrow-style), True = valid.
- Strings use Arrow offsets+bytes layout.  For key operations (sort/join/group)
  strings are packed into big-endian uint64 "key words" so ordering/equality is
  exact byte-wise UTF-8 order — which equals code-point order — using only
  integer ops the MXU/VPU likes.
"""
from __future__ import annotations

import os
from typing import List, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from . import dtypes as T
from ..obs import trace as _trace

# Minimum capacity bucket; batches are padded up to powers of two so the
# jit-cache stays small (SURVEY.md §7 "compile-cache keyed by padded size").
MIN_CAPACITY = int(os.environ.get("SPARK_RAPIDS_TPU_MIN_CAPACITY", "1024"))


#: capacity-bucketing override installed by the AOT compile subsystem
#: (compile/aot.py configure): a lattice with a conf'd growth ratio.
#: None = the classic pow2 padding below.  A plain module slot (not an
#: import) so columnar never depends on compile/.
_BUCKET_FN = None


def set_bucket_fn(fn) -> None:
    global _BUCKET_FN
    _BUCKET_FN = fn


def bucket_capacity(n: int) -> int:
    fn = _BUCKET_FN
    if fn is not None:
        return fn(n)
    cap = MIN_CAPACITY
    while cap < n:
        cap *= 2
    return cap


def _pad_np(arr: np.ndarray, capacity: int, fill=0) -> np.ndarray:
    if arr.shape[0] == capacity:
        return arr
    out = np.full((capacity,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


class Column:
    """Fixed-width device column: data[capacity] + validity[capacity]."""

    def __init__(self, dtype: T.DType, data, validity):
        self.dtype = dtype
        self.data = data
        self.validity = validity

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    # -- constructors -----------------------------------------------------------
    @staticmethod
    def from_numpy(values, dtype: Optional[T.DType] = None,
                   capacity: Optional[int] = None) -> "Column":
        """Build from a numpy array or a Python list that may contain None."""
        if isinstance(values, (list, tuple)):
            validity = np.array([v is not None for v in values], dtype=np.bool_)
            if dtype is None:
                probe = [v for v in values if v is not None]
                if probe and isinstance(probe[0], (list, tuple)):
                    return ListColumn.from_pylist(list(values),
                                                  capacity=capacity)
                np_arr = np.array(probe if probe else [0])
                dtype = T.from_numpy_dtype(np_arr.dtype)
            if isinstance(dtype, T.ArrayType):
                return ListColumn.from_pylist(
                    list(values), element_type=dtype.element_type,
                    capacity=capacity)
            if dtype == T.STRING:
                return StringColumn.from_pylist(list(values), capacity=capacity)
            clean = [v if v is not None else dtype.default_value for v in values]
            arr = np.array(clean, dtype=dtype.np_dtype)
        else:
            arr = np.asarray(values)
            if dtype is None:
                dtype = T.from_numpy_dtype(arr.dtype)
            if dtype == T.STRING:
                return StringColumn.from_pylist(list(arr), capacity=capacity)
            if arr.dtype.kind == "M":
                arr = arr.astype("datetime64[us]").astype(np.int64)
            arr = arr.astype(dtype.np_dtype)
            validity = np.ones(arr.shape[0], dtype=np.bool_)
        n = arr.shape[0]
        cap = capacity or bucket_capacity(n)
        data = jnp.asarray(_pad_np(arr, cap))
        valid = jnp.asarray(_pad_np(validity, cap, fill=False))
        return Column(dtype, data, valid)

    @staticmethod
    def all_null(dtype: T.DType, capacity: int) -> "Column":
        if dtype == T.STRING:
            return StringColumn(
                jnp.zeros(capacity + 1, jnp.int32),
                jnp.zeros(MIN_CAPACITY, jnp.uint8),
                jnp.zeros(capacity, jnp.bool_))
        if isinstance(dtype, T.ArrayType):
            return ListColumn(
                dtype, jnp.zeros(capacity + 1, jnp.int32),
                Column.all_null(dtype.element_type, MIN_CAPACITY),
                jnp.zeros(capacity, jnp.bool_))
        if isinstance(dtype, T.StructType):
            return StructColumn(
                dtype, [Column.all_null(f.dtype, capacity)
                        for f in dtype.fields],
                jnp.zeros(capacity, jnp.bool_))
        if isinstance(dtype, T.MapType):
            est = MapColumn.entry_struct_type(dtype)
            return MapColumn(
                dtype, jnp.zeros(capacity + 1, jnp.int32),
                Column.all_null(est, MIN_CAPACITY),
                jnp.zeros(capacity, jnp.bool_))
        data = jnp.zeros(capacity, dtype=dtype.np_dtype)
        return Column(dtype, data, jnp.zeros(capacity, jnp.bool_))

    @staticmethod
    def from_scalar(value, dtype: T.DType, capacity: int,
                    num_rows: Optional[int] = None) -> "Column":
        n = capacity if num_rows is None else num_rows
        if dtype == T.STRING:
            # host-built buffer: needs the concrete count (may sync)
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="size_probe"):
                n = int(n)
            return StringColumn.from_pylist(
                [value] * n, capacity=capacity)
        if dtype == T.FLOAT64:
            from .binary64 import Binary64Column, exact_double_enabled
            if exact_double_enabled():
                return Binary64Column.from_scalar_value(value, capacity, n)
        if value is None:
            return Column.all_null(dtype, capacity)
        data = jnp.full((capacity,), value, dtype=dtype.np_dtype)
        valid = (jnp.arange(capacity) < n)
        return Column(dtype, data, valid)

    # -- host interop -----------------------------------------------------------
    # Device buffer names pulled to host via the one-flush pending pool
    # (columnar/pending.py); subclasses override.
    _HOST_ATTRS = ("data", "validity")

    def _host_children(self):
        return ()

    def stage_host(self):
        """Stage every device buffer (recursively) for the next fused
        device->host flush; to_numpy/to_pylist then read the staged copy."""
        from . import pending
        cache = self.__dict__.setdefault("_host_staged", {})
        for attr in self._HOST_ATTRS:
            if attr not in cache:
                cache[attr] = pending.stage(getattr(self, attr))
        for child in self._host_children():
            child.stage_host()

    def _hnp(self, attr: str) -> np.ndarray:
        """Host copy of a device buffer, via the fused pending pool."""
        from . import pending
        cache = self.__dict__.setdefault("_host_staged", {})
        st = cache.get(attr)
        if st is None:
            st = pending.stage(getattr(self, attr))
            cache[attr] = st
        return st.np

    def to_numpy(self, num_rows: int):
        """Return (values ndarray, validity ndarray) truncated to num_rows."""
        return (self._hnp("data")[:num_rows],
                self._hnp("validity")[:num_rows])

    def to_pylist(self, num_rows: int) -> List:
        vals, valid = self.to_numpy(num_rows)
        return [v.item() if ok else None for v, ok in zip(vals, valid)]

    # -- structural ops (host-driven, device-executed) --------------------------
    def with_capacity(self, capacity: int, num_rows: int) -> "Column":
        if capacity == self.capacity:
            return self
        if capacity > self.capacity:
            pad = capacity - self.capacity
            data = jnp.pad(self.data, (0, pad))
            valid = jnp.pad(self.validity, (0, pad))
        else:
            data = self.data[:capacity]
            valid = self.validity[:capacity] & (jnp.arange(capacity) < num_rows)
        return Column(self.dtype, data, valid)

    def gather(self, indices, live=None, unique=False) -> "Column":
        """Take rows by index (device gather). indices: int array [new_cap].

        ``live`` (a mask over the new rows) is ANDed into the validity;
        ``unique`` is a sizing hint for variable-width columns
        (kernels/strings.py gather_strings).  Eagerly one
        ``batch_gather`` launch (columnar/gather.py); under a trace the
        two takes of the program being built."""
        if _trace.eager():
            from .gather import gather_columns
            return gather_columns([self], indices, live, unique)[0]
        valid = jnp.take(self.validity, indices, axis=0, mode="clip")
        data = jnp.take(self.data, indices, axis=0, mode="clip")
        if live is not None:
            valid = valid & live
        return Column(self.dtype, data, valid)

    def mask_validity(self, keep_mask) -> "Column":
        return Column(self.dtype, self.data, self.validity & keep_mask)

    def nbytes(self) -> int:
        return self.data.nbytes + self.validity.nbytes

    def device_buffers(self):
        return [self.data, self.validity]


class StringColumn(Column):
    """Arrow-layout string column: offsets int32[cap+1], bytes uint8[byte_cap].

    Reference analogue: cuDF STRING columns used throughout stringFunctions.scala.
    """

    def __init__(self, offsets, data, validity, max_bytes=None):
        self.dtype = T.STRING
        self.offsets = offsets
        self.data = data  # uint8 byte buffer
        self.validity = validity
        # host-known upper bound on any row's byte length, when cheap
        # to carry (ingest, gather, slices).  None -> computed lazily
        # with ONE device sync and cached; without the bound every
        # key-word encoding syncs the offsets buffer to host
        # (kernels/strings.needed_key_words), which serialized string
        # comparisons behind all pending device work
        self.max_bytes = max_bytes

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @property
    def byte_capacity(self) -> int:
        return int(self.data.shape[0])

    @staticmethod
    def from_pylist(values: Sequence[Optional[str]],
                    capacity: Optional[int] = None) -> "StringColumn":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        validity = np.zeros(cap, dtype=np.bool_)
        encoded: List[bytes] = []
        for i, v in enumerate(values):
            if v is None:
                encoded.append(b"")
            else:
                validity[i] = True
                encoded.append(str(v).encode("utf-8"))
        offsets = np.zeros(cap + 1, dtype=np.int32)
        lens = [len(e) for e in encoded]
        offsets[1: n + 1] = np.cumsum(lens)
        offsets[n + 1:] = offsets[n]
        total = int(offsets[n])
        byte_cap = bucket_capacity(max(total, 1))
        buf = np.zeros(byte_cap, dtype=np.uint8)
        if total:
            buf[:total] = np.frombuffer(b"".join(encoded), dtype=np.uint8)
        return StringColumn(jnp.asarray(offsets), jnp.asarray(buf),
                            jnp.asarray(validity),
                            max_bytes=int(max(lens)) if lens else 0)

    _HOST_ATTRS = ("offsets", "data", "validity")

    def to_numpy(self, num_rows: int):
        offs = self._hnp("offsets")
        buf = self._hnp("data").tobytes()
        valid = self._hnp("validity")[:num_rows]
        vals = np.empty(num_rows, dtype=object)
        for i in range(num_rows):
            vals[i] = buf[offs[i]:offs[i + 1]].decode("utf-8", "replace")
        return vals, valid

    def to_pylist(self, num_rows: int) -> List:
        vals, valid = self.to_numpy(num_rows)
        return [v if ok else None for v, ok in zip(vals, valid)]

    def with_capacity(self, capacity: int, num_rows: int) -> "StringColumn":
        if capacity == self.capacity:
            return self
        if capacity > self.capacity:
            pad = capacity - self.capacity
            offsets = jnp.pad(self.offsets, (0, pad), mode="edge")
            valid = jnp.pad(self.validity, (0, pad))
        else:
            offsets = self.offsets[:capacity + 1]
            valid = self.validity[:capacity] & (jnp.arange(capacity) < num_rows)
        return StringColumn(offsets, self.data, valid,
                            max_bytes=self.max_bytes)

    def gather(self, indices, live=None,
               unique=False) -> "StringColumn":
        # Gathers are LAZY: the result is a view (row indices into this
        # column) and byte materialization is deferred until something
        # reads .offsets/.data.  Chained gathers compose into one index
        # map, so a join expansion to fact capacity followed by an
        # aggregate's 1000x row reduction never materializes the
        # intermediate gigabytes (and never pays its sizing sync) —
        # the cuDF-style dictionary/gather-map trick.  Eagerly the
        # validity (and a view's map) move in one ``batch_gather``.
        if _trace.eager():
            from .gather import gather_columns
            return gather_columns([self], indices, live, unique)[0]
        valid = jnp.take(self.validity, indices, axis=0, mode="clip")
        if live is not None:
            valid = valid & live
        src_idx = jnp.clip(indices, 0, self.capacity - 1) \
            .astype(jnp.int32)
        return GatheredStringColumn(self, src_idx, valid, unique=unique)

    def mask_validity(self, keep_mask) -> "StringColumn":
        return StringColumn(self.offsets, self.data,
                            self.validity & keep_mask,
                            max_bytes=self.max_bytes)

    def nbytes(self) -> int:
        return self.offsets.nbytes + self.data.nbytes + self.validity.nbytes

    @staticmethod
    def combined_max_bytes(cols):
        """Upper bound for a column combined from ``cols`` (concat /
        case-when select); None when any input bound is unknown."""
        mbs = [c.max_bytes for c in cols]
        return max(mbs) if mbs and all(m is not None for m in mbs) \
            else None

    def device_buffers(self):
        return [self.offsets, self.data, self.validity]


class GatheredStringColumn(StringColumn):
    """Lazy string gather: row indices into a source StringColumn.

    Produced by StringColumn.gather.  Byte materialization — the
    expensive part of a string gather (an O(out_bytes) device windowed
    copy PLUS a host sync to size it) — is deferred until .offsets or
    .data is read.  Sort/group/join key words come straight from the
    source column's words gathered by index (kernels/canon.value_words
    fast path), so select-expand-reduce pipelines only ever materialize
    their final small outputs.  Chained gathers compose index maps.
    """

    def __init__(self, src: "StringColumn", idx, validity, unique=False):
        # deliberately no super().__init__: offsets/data are properties
        self.dtype = T.STRING
        while type(src) is GatheredStringColumn:
            if src._mat is not None:
                src = src._mat
                continue
            with _trace.launch("string_gather_compose", 1, idx.shape[0]):
                idx = jnp.take(src.idx, idx, axis=0, mode="clip")
            # a composed map repeats source rows unless EVERY stage was
            # repeat-free
            unique = unique and src._unique
            src = src.src
        self.src = src
        self.idx = idx
        self.validity = validity
        self.max_bytes = src.max_bytes
        self._unique = unique
        self._mat: Optional[StringColumn] = None

    def _materialize(self) -> StringColumn:
        if self._mat is None:
            from ..kernels import strings as skern
            offs, buf, valid = skern.gather_strings(
                self.src.offsets, self.src.data, self.src.validity,
                self.idx, live=self.validity, unique=self._unique,
                max_bytes=self.max_bytes)
            self._mat = StringColumn(offs, buf, valid,
                                     max_bytes=self.max_bytes)
        return self._mat

    @property
    def offsets(self):
        return self._materialize().offsets

    @property
    def data(self):
        return self._materialize().data

    # gather() is inherited: StringColumn.gather already produces a
    # composed view via this class's constructor.

    def mask_validity(self, keep_mask) -> "StringColumn":
        out = GatheredStringColumn(self.src, self.idx,
                                   self.validity & keep_mask,
                                   unique=self._unique)
        out._mat = None if self._mat is None else \
            self._mat.mask_validity(keep_mask)
        return out

    def with_capacity(self, capacity: int,
                      num_rows: int) -> "StringColumn":
        if capacity == self.capacity:
            return self
        if capacity > self.capacity:
            pad = capacity - self.capacity
            idx = jnp.pad(self.idx, (0, pad))
            valid = jnp.pad(self.validity, (0, pad))
        else:
            idx = self.idx[:capacity]
            valid = self.validity[:capacity] & \
                (jnp.arange(capacity) < num_rows)
        return GatheredStringColumn(self.src, idx, valid,
                                    unique=self._unique)

    def nbytes(self) -> int:
        # a live view PINS its source buffers: memory accounting must
        # see them or spill/coalesce budgets undercount by the whole
        # source batch (several views over one source over-count — the
        # safe direction for pressure decisions)
        own = self.idx.nbytes + self.validity.nbytes
        if self._mat is not None:
            return own + self._mat.nbytes()
        return own + self.src.nbytes()

    def device_buffers(self):
        # spill/wire serialization needs real buffers in StringColumn
        # layout (a view pins its source; a spilled copy must not) —
        # the materialized validity already folds the view's in
        return self._materialize().device_buffers()


class ListColumn(Column):
    """Arrow-layout list column: offsets int32[cap+1] + element child column.

    Reference analogue: cuDF LIST columns used by collectionOperations.scala
    and GpuGenerateExec.  The child may itself be any Column (fixed-width,
    StringColumn, or a nested ListColumn) — gathers recurse.
    Offsets are absolute indices into the child and need not start at 0
    (slices stay zero-copy); the invariant is monotonicity plus
    edge-padding past the live row count.
    """

    def __init__(self, dtype: T.ArrayType, offsets, elements: Column,
                 validity):
        self.dtype = dtype
        self.offsets = offsets
        self.elements = elements
        self.validity = validity

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @staticmethod
    def from_pylist(values: Sequence, element_type: Optional[T.DType] = None,
                    capacity: Optional[int] = None) -> "ListColumn":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        validity = np.zeros(cap, dtype=np.bool_)
        flat: List = []
        offsets = np.zeros(cap + 1, dtype=np.int32)
        for i, v in enumerate(values):
            if v is not None:
                validity[i] = True
                flat.extend(v)
            offsets[i + 1] = len(flat)
        offsets[n + 1:] = offsets[n]
        if element_type is None:
            probe = [x for x in flat if x is not None]
            if probe and isinstance(probe[0], str):
                element_type = T.STRING
            elif probe and isinstance(probe[0], (list, tuple)):
                raise ValueError("nested list needs explicit element_type")
            else:
                arr = np.array(probe if probe else [0])
                element_type = T.from_numpy_dtype(arr.dtype)
        elems = _column_from_pylist(flat, element_type)
        return ListColumn(T.ArrayType(element_type), jnp.asarray(offsets),
                          elems, jnp.asarray(validity))

    @property
    def element_capacity(self) -> int:
        return self.elements.capacity

    _HOST_ATTRS = ("offsets", "validity")

    def _host_children(self):
        return (self.elements,)

    def to_pylist(self, num_rows: int) -> List:
        offs = self._hnp("offsets")
        valid = self._hnp("validity")[:num_rows]
        n_elems = int(offs[num_rows]) if num_rows else 0
        elems = self.elements.to_pylist(n_elems) if n_elems else []
        out: List = []
        for i in range(num_rows):
            if not valid[i]:
                out.append(None)
            else:
                out.append(elems[offs[i]:offs[i + 1]])
        return out

    def to_numpy(self, num_rows: int):
        vals = np.empty(num_rows, dtype=object)
        lst = self.to_pylist(num_rows)
        for i, v in enumerate(lst):
            vals[i] = v
        return vals, self._hnp("validity")[:num_rows]

    def with_capacity(self, capacity: int, num_rows: int) -> "ListColumn":
        if capacity == self.capacity:
            return self
        if capacity > self.capacity:
            pad = capacity - self.capacity
            offsets = jnp.pad(self.offsets, (0, pad), mode="edge")
            valid = jnp.pad(self.validity, (0, pad))
        else:
            offsets = self.offsets[:capacity + 1]
            valid = self.validity[:capacity] & (jnp.arange(capacity) < num_rows)
        return ListColumn(self.dtype, offsets, self.elements, valid)

    def gather(self, indices, live=None, unique=False) -> "ListColumn":
        from ..kernels import lists as lkern
        from ..analysis import residency  # lazy: avoids import cycle
        new_offsets, gvalid, src_starts, total = lkern.list_gather_offsets(
            self.offsets, self.validity, indices)
        with residency.declared_transfer(site="size_probe"):
            elem_cap = bucket_capacity(max(1, int(total)))
        src_idx, live = lkern.list_element_gather_indices(
            new_offsets, src_starts, elem_cap)
        elems = self.elements.gather(src_idx).mask_validity(live)
        return ListColumn(self.dtype, new_offsets, elems, gvalid)

    def mask_validity(self, keep_mask) -> "ListColumn":
        return ListColumn(self.dtype, self.offsets, self.elements,
                          self.validity & keep_mask)

    def nbytes(self) -> int:
        return (self.offsets.nbytes + self.elements.nbytes() +
                self.validity.nbytes)

    def device_buffers(self):
        return [self.offsets, self.validity] + self.elements.device_buffers()


class StructColumn(Column):
    """Struct column: one child column per field + top-level validity.

    Reference analogue: cuDF STRUCT columns (complexTypeCreator.scala /
    complexTypeExtractors.scala).  All structural ops delegate to the
    children, so structs nest freely with lists/strings/maps.
    """

    def __init__(self, dtype: T.StructType, children: List[Column],
                 validity):
        self.dtype = dtype
        self.children = children
        self.validity = validity

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @staticmethod
    def from_pylist(values: Sequence, dtype: T.StructType,
                    capacity: Optional[int] = None) -> "StructColumn":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        validity = np.zeros(cap, dtype=np.bool_)
        per_field: List[List] = [[] for _ in dtype.fields]
        for i, v in enumerate(values):
            if v is None:
                for lst in per_field:
                    lst.append(None)
            else:
                validity[i] = True
                if isinstance(v, dict):
                    for lst, f in zip(per_field, dtype.fields):
                        lst.append(v.get(f.name))
                else:
                    for lst, x in zip(per_field, v):
                        lst.append(x)
        kids = [_column_from_pylist(vals, f.dtype, cap)
                for vals, f in zip(per_field, dtype.fields)]
        return StructColumn(dtype, kids, jnp.asarray(validity))

    _HOST_ATTRS = ("validity",)

    def _host_children(self):
        return tuple(self.children)

    def to_pylist(self, num_rows: int) -> List:
        valid = self._hnp("validity")[:num_rows]
        kid_vals = [c.to_pylist(num_rows) for c in self.children]
        names = [f.name for f in self.dtype.fields]
        return [dict(zip(names, vals)) if ok else None
                for ok, *vals in zip(valid, *kid_vals)] if kid_vals else \
            [{} if ok else None for ok in valid]

    def to_numpy(self, num_rows: int):
        vals = np.empty(num_rows, dtype=object)
        for i, v in enumerate(self.to_pylist(num_rows)):
            vals[i] = v
        return vals, self._hnp("validity")[:num_rows]

    def with_capacity(self, capacity: int, num_rows: int) -> "StructColumn":
        if capacity == self.capacity:
            return self
        kids = [c.with_capacity(capacity, num_rows) for c in self.children]
        if capacity > self.capacity:
            valid = jnp.pad(self.validity, (0, capacity - self.capacity))
        else:
            valid = self.validity[:capacity] & (jnp.arange(capacity) < num_rows)
        return StructColumn(self.dtype, kids, valid)

    def gather(self, indices, live=None,
               unique=False) -> "StructColumn":
        with _trace.launch("struct_gather", 1, indices.shape[0]):
            valid = jnp.take(self.validity, indices, axis=0, mode="clip")
        return StructColumn(
            self.dtype,
            [c.gather(indices, live=live, unique=unique)
             for c in self.children], valid)

    def mask_validity(self, keep_mask) -> "StructColumn":
        return StructColumn(self.dtype, self.children,
                            self.validity & keep_mask)

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.children) + self.validity.nbytes

    def device_buffers(self):
        out = [self.validity]
        for c in self.children:
            out.extend(c.device_buffers())
        return out


class MapColumn(ListColumn):
    """Map column = list<struct<key, value>> (the Arrow model).

    Reference analogue: cuDF LIST<STRUCT> maps (GetMapValue in
    complexTypeExtractors.scala).  Inherits all gather/slice mechanics
    from ListColumn; ``elements`` is a two-field StructColumn.
    """

    def __init__(self, dtype: T.MapType, offsets, elements: StructColumn,
                 validity):
        self.dtype = dtype
        self.offsets = offsets
        self.elements = elements
        self.validity = validity

    @property
    def keys(self) -> Column:
        return self.elements.children[0]

    @property
    def values(self) -> Column:
        return self.elements.children[1]

    @staticmethod
    def entry_struct_type(dtype: T.MapType) -> T.StructType:
        return T.StructType([T.StructField("key", dtype.key_type, False),
                             T.StructField("value", dtype.value_type, True)])

    @staticmethod
    def from_pylist(values: Sequence, dtype: T.MapType,
                    capacity: Optional[int] = None) -> "MapColumn":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        validity = np.zeros(cap, dtype=np.bool_)
        offsets = np.zeros(cap + 1, dtype=np.int32)
        entries: List = []
        for i, v in enumerate(values):
            if v is not None:
                validity[i] = True
                items = v.items() if isinstance(v, dict) else v
                entries.extend(tuple(kv) for kv in items)
            offsets[i + 1] = len(entries)
        offsets[n + 1:] = offsets[n]
        est = MapColumn.entry_struct_type(dtype)
        elems = StructColumn.from_pylist(entries, est)
        return MapColumn(dtype, jnp.asarray(offsets), elems,
                         jnp.asarray(validity))

    def to_pylist(self, num_rows: int) -> List:
        offs = self._hnp("offsets")
        valid = self._hnp("validity")[:num_rows]
        n_elems = int(offs[num_rows]) if num_rows else 0
        keys = self.keys.to_pylist(n_elems) if n_elems else []
        vals = self.values.to_pylist(n_elems) if n_elems else []
        out: List = []
        for i in range(num_rows):
            if not valid[i]:
                out.append(None)
            else:
                out.append(dict(zip(keys[offs[i]:offs[i + 1]],
                                    vals[offs[i]:offs[i + 1]])))
        return out

    def with_capacity(self, capacity: int, num_rows: int) -> "MapColumn":
        lc = ListColumn.with_capacity(self, capacity, num_rows)
        return MapColumn(self.dtype, lc.offsets, lc.elements, lc.validity)

    def gather(self, indices, live=None, unique=False) -> "MapColumn":
        lc = ListColumn.gather(self, indices)
        return MapColumn(self.dtype, lc.offsets, lc.elements, lc.validity)

    def mask_validity(self, keep_mask) -> "MapColumn":
        return MapColumn(self.dtype, self.offsets, self.elements,
                         self.validity & keep_mask)

    def as_list(self) -> ListColumn:
        """View as list<struct<key,value>> (for MapKeys/MapValues/Size)."""
        return ListColumn(T.ArrayType(self.elements.dtype), self.offsets,
                          self.elements, self.validity)


def _column_from_pylist(values: Sequence, dtype: T.DType,
                        capacity: Optional[int] = None) -> Column:
    """Build any column type from a python list (host staging path)."""
    if isinstance(dtype, T.StructType):
        return StructColumn.from_pylist(values, dtype, capacity)
    if isinstance(dtype, T.MapType):
        return MapColumn.from_pylist(values, dtype, capacity)
    if isinstance(dtype, T.ArrayType):
        return ListColumn.from_pylist(values, dtype.element_type, capacity)
    if dtype == T.STRING:
        return StringColumn.from_pylist(values, capacity)
    return Column.from_numpy(list(values), dtype=dtype, capacity=capacity)


ColumnLike = Union[Column, StringColumn, ListColumn, StructColumn,
                   MapColumn]
