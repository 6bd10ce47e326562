"""ColumnarBatch — a set of equal-capacity device columns + a live row count.

Reference analogue: Spark ``ColumnarBatch`` wrapping ``GpuColumnVector``s
(reference: sql-plugin/.../GpuColumnVector.java) produced/consumed by every
``GpuExec.doExecuteColumnar``.

TPU-first: capacity is a power-of-two bucket (static shape for XLA); the
number of live rows is a host int known at batch boundaries, mirroring the
reference where cuDF row counts are host-visible after each kernel.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from . import dtypes as T
from .column import Column, StringColumn, bucket_capacity
from .schema import Field, Schema


from . import pending


def _flush_pending():
    """Resolve every staged host value in one fused transfer
    (columnar/pending.py)."""
    pending.flush()


class LazyCount:
    """A row count still resident on device.

    Every device->host pull triggers a remote execution round trip on
    this backend (fully lazy dispatch), which made per-batch
    ``int(count)`` pulls the dominant cost of small queries.  Execs
    producing data-dependent row counts (filter, group count, join size)
    wrap the device scalar in a LazyCount; the first forced value
    resolves EVERY outstanding staged pull (counts, bincounts, output
    buffers — columnar/pending.py) in one fused transfer.
    """
    __slots__ = ("dev", "_staged", "_val")

    def __init__(self, dev):
        self.dev = dev
        self._staged = pending.stage(dev)
        self._val: Optional[int] = None

    @property
    def value(self) -> int:
        if self._val is None:
            self._val = int(self._staged.np.ravel()[0])
            self._staged = None
        return self._val

    def __int__(self):
        return self.value

    __index__ = __int__

    def __bool__(self):
        return self.value > 0

    def __eq__(self, o):
        return self.value == int(o)

    def __lt__(self, o):
        return self.value < int(o)

    def __le__(self, o):
        return self.value <= int(o)

    def __gt__(self, o):
        return self.value > int(o)

    def __ge__(self, o):
        return self.value >= int(o)

    def __add__(self, o):
        return self.value + o

    __radd__ = __add__

    def __sub__(self, o):
        return self.value - o

    def __rsub__(self, o):
        return o - self.value

    def __mul__(self, o):
        return self.value * o

    __rmul__ = __mul__

    def __hash__(self):
        return hash(self.value)

    def __repr__(self):
        return f"LazyCount({self._val if self._val is not None else '?'})"


class LazyArray:
    """A small device int vector resolved through the pending pool
    (e.g. per-partition bincounts in the shuffle split)."""
    __slots__ = ("dev", "_staged", "_val")

    def __init__(self, dev):
        self.dev = dev
        self._staged = pending.stage(jnp.asarray(dev))
        self._val = None

    @property
    def np(self) -> np.ndarray:
        if self._val is None:
            self._val = self._staged.np
            self._staged = None
        return self._val


class SpeculativeResult:
    """Attached (as ``batch._speculative``) to a batch computed by a
    speculative fast-path program whose data assumptions are verified by
    a device-side flag (e.g. the sort-free bucket-table aggregate,
    kernels/aggregate.py table_plan).  Consumers holding a natural flush
    barrier (the shuffle exchange, the aggregate merge) call ``ok()``
    after the fused flush and ``redo()`` for the rare non-fitting batch.
    """

    __slots__ = ("fits", "_redo")

    def __init__(self, fits, redo):
        self.fits = list(fits)   # LazyCounts: nonzero == assumption held
        self._redo = redo

    def ok(self) -> bool:
        return all(int(f) != 0 for f in self.fits)

    def redo(self) -> "ColumnarBatch":
        return self._redo()


def chain_speculative(out: "ColumnarBatch", inp: "ColumnarBatch",
                      recompute) -> "ColumnarBatch":
    """Carry ``inp``'s unverified fit flags onto ``out``, a batch computed
    FROM ``inp`` by a count-preserving device transform (project, staged
    chain, lazy sort/limit): the consumer's flush barrier then vouches
    for the whole chain at once, and a failed fit recomputes via
    ``recompute(exact_input)``.  No-op when the input is not speculative
    — the superstage sync-free paths are the only producers."""
    spec = getattr(inp, "_speculative", None)
    if spec is None:
        return out
    own = getattr(out, "_speculative", None)

    def _redo():
        return recompute(resolve_speculative(inp))
    out._speculative = SpeculativeResult(
        list(spec.fits) + (list(own.fits) if own is not None else []),
        _redo)
    return out


def resolve_speculative(batch: "ColumnarBatch") -> "ColumnarBatch":
    """Verify-and-replace helper: returns the batch itself when its
    speculative assumptions held (or it has none), else the re-computed
    exact batch.  Loops: a redo may itself return a speculative batch
    (e.g. the bucket-table redo falls back to the sort path, which can
    attach its own compaction fit flag)."""
    for _ in range(4):
        spec = getattr(batch, "_speculative", None)
        if spec is None or spec.ok():
            return batch
        batch = spec.redo()
    spec = getattr(batch, "_speculative", None)
    assert spec is None or spec.ok(), \
        "speculative redo did not converge to a verified batch"
    return batch


class ColumnarBatch:
    def __init__(self, schema: Schema, columns: Sequence[Column], num_rows):
        assert len(schema) == len(columns), (len(schema), len(columns))
        self.schema = schema
        self.columns = list(columns)
        self._rows = num_rows if isinstance(num_rows, LazyCount) \
            else int(num_rows)
        self._rows_dev = None
        if columns:
            caps = {c.capacity for c in columns}
            assert len(caps) == 1, f"mixed capacities {caps}"
            self._capacity = caps.pop()
        else:
            self._capacity = bucket_capacity(int(num_rows))

    @property
    def num_rows(self) -> int:
        r = self._rows
        return r.value if isinstance(r, LazyCount) else r

    @num_rows.setter
    def num_rows(self, v):
        self._rows = v if isinstance(v, LazyCount) else int(v)
        self._rows_dev = None

    @property
    def rows_lazy(self):
        """The count as-is (int or LazyCount) — pass to derived batches
        so one eventual pull serves the whole lineage."""
        return self._rows

    @property
    def rows_dev(self):
        """The count as a device scalar, never forcing a host pull."""
        r = self._rows
        if isinstance(r, LazyCount):
            return r.dev
        if self._rows_dev is None:
            self._rows_dev = jnp.int32(r)
        return self._rows_dev

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    def column(self, key) -> Column:
        if isinstance(key, str):
            return self.columns[self.schema.index_of(key)]
        return self.columns[key]

    # -- constructors -----------------------------------------------------------
    @staticmethod
    def from_pydict(data: Dict[str, Sequence], schema: Optional[Schema] = None,
                    capacity: Optional[int] = None) -> "ColumnarBatch":
        names = list(data.keys())
        n = len(next(iter(data.values()))) if data else 0
        cap = capacity or bucket_capacity(n)
        cols, fields = [], []
        for name in names:
            dtype = schema[name].dtype if schema is not None else None
            col = Column.from_numpy(data[name], dtype=dtype, capacity=cap)
            cols.append(col)
            fields.append(Field(name, col.dtype))
        return ColumnarBatch(schema or Schema(fields), cols, n)

    @staticmethod
    def from_numpy(arrays: Dict[str, np.ndarray],
                   capacity: Optional[int] = None) -> "ColumnarBatch":
        return ColumnarBatch.from_pydict(arrays, capacity=capacity)

    @staticmethod
    def empty(schema: Schema, capacity: int = 16) -> "ColumnarBatch":
        cols = [Column.all_null(f.dtype, capacity) for f in schema]
        return ColumnarBatch(schema, cols, 0)

    # -- host interop -----------------------------------------------------------
    def to_pydict(self) -> Dict[str, List]:
        return {f.name: c.to_pylist(self.num_rows)
                for f, c in zip(self.schema, self.columns)}

    def to_pylist(self) -> List[tuple]:
        cols = [c.to_pylist(self.num_rows) for c in self.columns]
        return list(zip(*cols)) if cols else []

    # -- structural -------------------------------------------------------------
    def select(self, names: Iterable[str]) -> "ColumnarBatch":
        names = list(names)
        cols = [self.column(n) for n in names]
        fields = [self.schema[n] for n in names]
        return ColumnarBatch(Schema(fields), cols, self.rows_lazy)

    def with_column(self, name: str, col: Column) -> "ColumnarBatch":
        if name in self.schema.names:
            idx = self.schema.index_of(name)
            cols = list(self.columns)
            cols[idx] = col
            fields = list(self.schema.fields)
            fields[idx] = Field(name, col.dtype)
            return ColumnarBatch(Schema(fields), cols, self.num_rows)
        return ColumnarBatch(
            Schema(list(self.schema.fields) + [Field(name, col.dtype)]),
            self.columns + [col], self.num_rows)

    def with_capacity(self, capacity: int) -> "ColumnarBatch":
        if capacity == self.capacity:
            return self
        cols = [c.with_capacity(capacity, self.num_rows) for c in self.columns]
        b = ColumnarBatch(self.schema, cols, self.num_rows)
        return b

    def gather(self, indices, num_rows, live=None,
               unique=False) -> "ColumnarBatch":
        """Every column at rows ``indices``, ``live`` ANDed into every
        validity: eagerly ONE ``batch_gather`` launch
        (``columnar/gather.py``), not two takes a column."""
        from .gather import gather_columns
        return ColumnarBatch(
            self.schema, gather_columns(self.columns, indices, live, unique),
            num_rows)

    def slice(self, start: int, length: int) -> "ColumnarBatch":
        """Rows ``[start, start + length)``: the columns in one
        ``batch_slice`` launch (``columnar/gather.py``)."""
        from .gather import slice_columns
        valid_rows = min(length, max(self.num_rows - start, 0))
        return ColumnarBatch(
            self.schema,
            slice_columns(self.columns, start, bucket_capacity(length),
                          valid_rows), valid_rows)

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns)

    def device_buffers(self):
        out = []
        for c in self.columns:
            out.extend(c.device_buffers())
        return out

    def __repr__(self):
        return (f"ColumnarBatch(rows={self.num_rows}, cap={self.capacity}, "
                f"schema={self.schema})")


def concat_batches(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Concatenate batches of identical schema (the GpuCoalesceBatches core,

    reference: GpuCoalesceBatches.scala:195)."""
    # concat reads num_rows (a flush barrier) — the right moment to
    # verify any speculative fast-path batches before baking them in
    batches = [resolve_speculative(b) for b in batches]
    assert batches, "concat of zero batches"
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    nrows = [b.num_rows for b in batches]
    total = sum(nrows)
    cap = bucket_capacity(total)
    # every fixed-width column's data and validity, and every string
    # column's offsets and validity, are row lanes: one pass appends
    # them all (``_concat_lanes``); string bytes go through the host and
    # nested columns keep their own concat
    lanes, caps, fills, slots = [], [], [], []
    out_cols: List[Optional[Column]] = [None] * len(schema)
    for ci, field in enumerate(schema):
        cols = [b.columns[ci] for b in batches]
        dt = field.dtype
        if dt == T.STRING:
            parts, data, mb = _concat_string_bytes(cols, nrows)
            slots.append((ci, dt, data, mb))
            lanes += [[shifted for shifted, _ in parts],
                      [c.validity for c in cols]]
            caps += [cap + 1, cap]
            fills += [parts[-1][1], False]      # offsets end at the bytes
        elif isinstance(dt, (T.StructType, T.ArrayType, T.MapType)):
            out_cols[ci] = _concat_cols(dt, cols, nrows, cap)
        else:
            slots.append((ci, dt, None, None))
            lanes += [[c.data for c in cols], [c.validity for c in cols]]
            caps += [cap, cap]
            fills += [0, False]
    if lanes:
        outs = _concat_lanes(lanes, nrows, caps, fills)
        for k, (ci, dt, data, mb) in enumerate(slots):
            first, valid = outs[2 * k], outs[2 * k + 1]
            if data is not None:
                out_cols[ci] = StringColumn(first, data, valid, max_bytes=mb)
            else:
                out_cols[ci] = Column(dt, first, valid)
    return ColumnarBatch(schema, out_cols, total)


#: the concat programs by the shapes they were first called with
_CONCAT_JIT: dict = {}


def _lane_start(parts, rooms):
    from jax import lax
    return tuple(lax.dynamic_update_slice_in_dim(
        jnp.zeros((room,) + p.shape[1:], p.dtype), p, 0, 0)
        for p, room in zip(parts, rooms))


def _lane_append(outs, parts, off):
    from jax import lax
    return tuple(lax.dynamic_update_slice_in_dim(o, p, off, 0)
                 for o, p in zip(outs, parts))


def _lane_finish(outs, total, fills, caps):
    res = []
    for o, f, cap in zip(outs, fills, caps):
        live = (jnp.arange(cap) < total).reshape(
            (cap,) + (1,) * (o.ndim - 1))
        res.append(jnp.where(live, o[:cap], f.astype(o.dtype)))
    return tuple(res)


def _concat_programs():
    """The three programs of ``_concat_lanes``, built once; jax keys
    their compiled forms by argument shapes."""
    progs = _CONCAT_JIT.get("programs")
    if progs is None:
        from ..obs import compile_watch as _cw
        progs = _CONCAT_JIT["programs"] = (
            _cw.jit(_lane_start, "batch_concat_start", static_argnums=(1,)),
            _cw.jit(_lane_append, "batch_concat_append",
                    donate_argnums=(0,)),
            _cw.jit(_lane_finish, "batch_concat_finish",
                    static_argnums=(3,)))
    return progs


def _concat_step(jitted, key):
    """``jitted`` behind the first-call timing of its shapes ``key``."""
    prog = _CONCAT_JIT.get(key)
    if prog is None:
        from ..obs import compile_watch as _cw
        prog = _cw.wrap_miss("batch_concat", jitted, key)
        if len(_CONCAT_JIT) < 4096:
            _CONCAT_JIT[key] = prog
    return prog


def _concat_lanes(lanes, nrows: Sequence[int], caps: Sequence[int],
                  fills) -> tuple:
    """For every lane (one array a batch, rows leading, the batch's first
    ``nrows[k]`` rows live): those live rows one after another, filled
    with the lane's ``fill`` from the total up to the lane's ``cap``.

    The row counts are traced, never static: batch k's whole array is
    written at the running offset (what lies past its live rows is
    overwritten by batch k + 1, or filled at the end), so a program is
    keyed by the arrays' capacities and dtypes alone and a new seed's
    row counts compile nothing.  One launch a batch, all lanes in it."""
    def shapes(arrays):
        return tuple((str(a.dtype), tuple(a.shape)) for a in arrays)
    start, append, finish = _concat_programs()
    rooms = tuple(cap + max(int(p.shape[0]) for p in lane)
                  for lane, cap in zip(lanes, caps))
    parts = tuple(lane[0] for lane in lanes)
    outs = _concat_step(start, ("start", shapes(parts), rooms))(parts, rooms)
    room_shapes = shapes(outs)          # an append keeps them
    off = nrows[0]
    for k in range(1, len(nrows)):
        parts = tuple(lane[k] for lane in lanes)
        outs = _concat_step(append, ("append", room_shapes, shapes(parts)))(
            outs, parts, np.int32(off))
        off += nrows[k]
    caps = tuple(caps)
    fills = tuple(np.asarray(f, o.dtype) for f, o in zip(fills, outs))
    return _concat_step(finish, ("finish", room_shapes, caps))(
        outs, np.int32(off), fills, caps)


def _concat_cols(dtype: T.DType, cols: Sequence[Column],
                 nrows: Sequence[int], cap: int) -> Column:
    if dtype == T.STRING:
        return _concat_string_cols(cols, nrows, cap)
    if isinstance(dtype, T.StructType):
        return _concat_struct_cols(dtype, cols, nrows, cap)
    if isinstance(dtype, (T.ArrayType, T.MapType)):
        return _concat_list_cols(cols, nrows, cap)
    data, valid = _concat_lanes(
        [[c.data for c in cols], [c.validity for c in cols]], nrows,
        [cap, cap], [0, False])
    return Column(dtype, data, valid)


def _slice_elements(col: Column, o0: int, o1: int) -> Column:
    """Child slice covering absolute element range [o0, o1)."""
    from .column import ListColumn, MapColumn, StructColumn
    if isinstance(col, (ListColumn, MapColumn)):
        out = type(col)(col.dtype, col.offsets[o0:o1 + 1], col.elements,
                        col.validity[o0:o1])
        return out
    if isinstance(col, StructColumn):
        return StructColumn(
            col.dtype, [_slice_elements(c, o0, o1) for c in col.children],
            col.validity[o0:o1])
    if isinstance(col, StringColumn):
        return StringColumn(col.offsets[o0:o1 + 1], col.data,
                            col.validity[o0:o1], max_bytes=col.max_bytes)
    return Column(col.dtype, col.data[o0:o1], col.validity[o0:o1])


def _concat_struct_cols(dtype: T.StructType, cols: Sequence[Column],
                        nrows: Sequence[int], cap: int) -> Column:
    from .column import StructColumn
    kids = []
    for fi, f in enumerate(dtype.fields):
        kids.append(_concat_cols(f.dtype,
                                 [c.children[fi] for c in cols],
                                 nrows, cap))
    valid = jnp.concatenate([c.validity[:n] for c, n in zip(cols, nrows)])
    vpad = cap - int(valid.shape[0])
    if vpad > 0:
        valid = jnp.pad(valid, (0, vpad))
    return StructColumn(dtype, kids, valid)


def _concat_list_cols(cols: Sequence[Column], nrows: Sequence[int],
                      cap: int) -> Column:
    """Concat of List/MapColumns: rebase offsets, recursively concat
    children."""
    from ..analysis import residency  # lazy: avoids import cycle
    offsets_parts: List = []
    valid_parts: List = []
    child_cols: List[Column] = []
    child_ns: List[int] = []
    base = 0
    with residency.declared_transfer(site="batch_concat"):
        for c, n in zip(cols, nrows):
            offs = np.asarray(c.offsets)
            o0, o1 = int(offs[0]), int(offs[n])
            offsets_parts.append(
                c.offsets[:n].astype(jnp.int32) - jnp.int32(o0 - base))
            valid_parts.append(c.validity[:n])
            child_cols.append(_slice_elements(c.elements, o0, o1))
            child_ns.append(o1 - o0)
            base += o1 - o0
    child_cap = bucket_capacity(max(1, sum(child_ns)))
    elem_dtype = cols[0].elements.dtype
    elements = _concat_cols(elem_dtype, child_cols, child_ns, child_cap)
    offsets = jnp.concatenate(
        offsets_parts + [jnp.array([base], jnp.int32)])
    pad = cap + 1 - int(offsets.shape[0])
    if pad > 0:
        offsets = jnp.pad(offsets, (0, pad), mode="edge")
    valid = jnp.concatenate(valid_parts)
    vpad = cap - int(valid.shape[0])
    if vpad > 0:
        valid = jnp.pad(valid, (0, vpad))
    return type(cols[0])(cols[0].dtype, offsets.astype(jnp.int32), elements,
                         valid)


def _concat_string_bytes(cols: Sequence[StringColumn],
                         nrows: Sequence[int]):
    """-> (one (offsets rebased onto the joint buffer, total bytes)
    pair a column, the joint byte buffer, max_bytes)."""
    from ..analysis import residency  # lazy: avoids import cycle
    parts, np_bytes = [], []
    base = 0
    with residency.declared_transfer(site="batch_concat"):
        # bytes: need exact live bytes from each column; slicing with
        # dynamic sizes is not static-shape friendly on device, so
        # gather via numpy on host (concat is a batch boundary; the
        # reference also round-trips host for shuffle concat of
        # serialized batches).
        for c, n in zip(cols, nrows):
            offs = np.asarray(c.offsets)
            o0, o1 = int(offs[0]), int(offs[n])
            np_bytes.append(np.asarray(c.data)[o0:o1])
            shifted = c.offsets.astype(jnp.int32) - jnp.int32(o0 - base)
            base += o1 - o0
            parts.append((shifted, base))
    all_bytes = np.concatenate(np_bytes) if np_bytes else np.zeros(0, np.uint8)
    byte_cap = bucket_capacity(max(1, all_bytes.shape[0]))
    buf = np.zeros(byte_cap, np.uint8)
    buf[: all_bytes.shape[0]] = all_bytes
    return parts, jnp.asarray(buf), StringColumn.combined_max_bytes(cols)


def _concat_string_cols(cols: Sequence[StringColumn], nrows: Sequence[int],
                        cap: int) -> StringColumn:
    parts, data, mb = _concat_string_bytes(cols, nrows)
    off, valid = _concat_lanes(
        [[shifted for shifted, _ in parts], [c.validity for c in cols]],
        nrows, [cap + 1, cap], [parts[-1][1], False])
    return StringColumn(off, data, valid, max_bytes=mb)
