"""Fused expression evaluation: whole projection trees under one jit.

TPU-first rationale (SURVEY.md §7 / pallas guide): the engine's eager
mode dispatches every jnp op separately — on real hardware each dispatch
is a host->device round trip, so a 20-op projection pays 20 RPCs.  Under
``jax.jit`` the entire bound expression tree traces into ONE XLA
computation: elementwise ops fuse, intermediates never materialize in
HBM, and a batch is processed with a single dispatch.  This is the
moral equivalent of the reference running a whole projection as one
fused cuDF AST kernel instead of op-by-op JNI calls
(GpuProjectExec + cuDF compute-on-columns).

Fusion is per-expression: the fusable subset of a projection jits as one
computation; the rest (strings/lists size buffers host-side; UDF/rand/
partition-id expressions carry host state, flagged via
``Expression.trace_safe``) evaluates eagerly, and outputs merge by
position — one string passthrough column doesn't forfeit fusion for the
numeric expressions beside it.
"""
from __future__ import annotations

import logging
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column
from ..columnar.batch import ColumnarBatch
from ..compile import aot as _aot
from ..expr import core as ec
from ..obs import compile_watch as _compile_watch
from ..obs import costplane as _costplane
from ..obs.registry import compile_cache_event

_LOG = logging.getLogger("spark_rapids_tpu.exec.fused")

# (expr signatures, schema dtypes, needed ordinals) -> jitted callable
_JIT_CACHE: dict = {}


def _tree_fusable(expr: ec.Expression) -> bool:
    """Conservative gate: every node must be fixed-width (strings/nested
    kernels size outputs on host and cannot trace) AND declared
    trace-safe (no host state: UDFs, rand, partition ids)."""
    if not expr.trace_safe:
        return False
    try:
        dt = expr.dtype()
    except (ValueError, NotImplementedError):
        return False
    if dt == T.STRING or dt.is_nested or dt == T.NULL:
        return False
    return all(_tree_fusable(c) for c in expr.children)


def expr_signature(e: ec.Expression) -> Optional[str]:
    """Stable structural signature of an expression tree: identical
    signatures trace to identical computations, so jitted callables can
    be shared ACROSS query plans (a new FusedEval per query would
    otherwise re-trace + re-lower every run — ~20ms per jit even on a
    persistent-cache hit, dozens of jits per query).  Returns None when
    any attribute is opaque (functions, host objects) — id()-based keys
    would be unsound after GC address reuse, so such trees are simply
    not shared."""
    extras = []
    for k in sorted(vars(e)):
        if k in ("children", "_name"):
            continue
        sv = _sig_value(getattr(e, k))
        if sv is None:
            return None
        extras.append(f"{k}={sv}")
    kids = []
    for c in e.children:
        sc = expr_signature(c)
        if sc is None:
            return None
        kids.append(sc)
    return f"{type(e).__name__}({';'.join(extras)})[{','.join(kids)}]"


def _sig_value(v) -> Optional[str]:
    if isinstance(v, (int, float, str, bool, type(None), bytes)):
        return repr(v)
    if isinstance(v, T.DType):
        return v.name
    if isinstance(v, ec.Expression):
        return expr_signature(v)
    if isinstance(v, (list, tuple)):
        parts = [_sig_value(x) for x in v]
        if any(p is None for p in parts):
            return None
        return "[" + ",".join(parts) + "]"
    return None


def _needed_ordinals(exprs: Sequence[ec.Expression]) -> List[int]:
    out = set()
    for e in exprs:
        for r in e.collect(lambda n: isinstance(n, ec.BoundReference)):
            out.add(r.ordinal)
    return sorted(out)


class FusedEval:
    """One jitted computation for the fusable subset of bound exprs.

    ``__call__(batch) -> Optional[List[Column]]`` returns one Column per
    input expression (fused and eager results merged by position), or
    None when nothing could fuse — callers then use their own eager
    path unchanged.  jax.jit's shape-keyed cache handles
    per-capacity-bucket compilation automatically.
    """

    def __init__(self, bound_exprs: Sequence[ec.Expression], child_schema):
        self.exprs = list(bound_exprs)
        self.schema = child_schema
        self.fusable = [_tree_fusable(e) for e in self.exprs]
        self.fused_idx = [i for i, ok in enumerate(self.fusable) if ok]
        self.out_dtypes = []
        for e in self.exprs:
            try:
                self.out_dtypes.append(e.dtype())
            except (ValueError, NotImplementedError):
                self.out_dtypes.append(None)
        self.needed = _needed_ordinals(
            [self.exprs[i] for i in self.fused_idx])
        self.ok = bool(self.fused_idx)
        self._jitted = None
        if self.ok:
            # share one jitted callable across all query plans with the
            # same expression structure (process-level trace cache);
            # trees with opaque attributes (signature None) get a
            # private jit instead of an unsound id()-keyed entry
            sigs = [expr_signature(self.exprs[i]) for i in self.fused_idx]
            key = None
            if not any(s is None for s in sigs):
                key = (tuple(sigs),
                       tuple(f.dtype.name for f in self.schema),
                       tuple(self.needed))
                self._jitted = _JIT_CACHE.get(key)
                compile_cache_event("fused_project",
                                    self._jitted is not None)
            if self._jitted is None:
                self._jitted = _compile_watch.wrap_miss(
                    "fused_project",
                    _compile_watch.jit(self._eval, "fused_project_eval",
                                       static_argnums=(0,)),
                    "opaque" if key is None else str(key))
                if key is not None and len(_JIT_CACHE) < 4096:
                    _JIT_CACHE[key] = self._jitted
            if key is not None:
                self._register_warmer(str(hash(key)))

    def _register_warmer(self, variant: str) -> None:
        """Hand the AOT subsystem a closure that drives this cached
        program at an arbitrary bucket capacity with zero-filled
        columns and num_rows=0 (every padded row invalid — the
        masking contract makes the dummy batch safe for any fused
        tree)."""
        jitted = self._jitted
        dts = tuple(self.schema[i].dtype.np_dtype for i in self.needed)
        if jitted is None or any(d is None for d in dts):
            return
        def warm(bucket: int) -> None:
            datas = tuple(jnp.zeros(bucket, d) for d in dts)
            valids = tuple(jnp.zeros(bucket, jnp.bool_) for _ in dts)
            jitted(bucket, datas, valids, jnp.int32(0))
        _aot.register_warmer("fused_project", warm, variant)

    # traced function: capacity static; column buffers + live row count
    # are device values
    def _eval(self, capacity: int, datas, valids, num_rows):
        by_ordinal = {}
        for i, d, v in zip(self.needed, datas, valids):
            by_ordinal[i] = Column(self.schema[i].dtype, d, v)
        # only referenced ordinals are real; BoundReference never touches
        # the rest
        filled = [by_ordinal.get(i) for i in range(len(self.schema))]
        batch = _TracedBatch(self.schema, filled, num_rows, capacity)
        outs = []
        for i in self.fused_idx:
            r = self.exprs[i].columnar_eval(batch)
            if isinstance(r, ec.Scalar):
                r = r.to_column(capacity, None)
                # scalar fills are valid only on live rows
                live = jnp.arange(capacity) < num_rows
                r = Column(r.dtype, r.data, r.validity & live)
            outs.append((r.data, r.validity))
        return outs

    def __call__(self, batch: ColumnarBatch) -> Optional[List[Column]]:
        if not self.ok:
            return None
        from ..columnar.binary64 import exact_double_enabled
        if exact_double_enabled():
            # exactDouble: expressions may CREATE Binary64Columns inside
            # the trace; reassembling traced arrays as plain Columns
            # would silently reinterpret bit patterns as values, so the
            # fused path stands down (exactness over fusion)
            return None
        if not all(type(batch.columns[i]) is Column for i in self.needed):
            return None
        datas = tuple(batch.columns[i].data for i in self.needed)
        valids = tuple(batch.columns[i].validity for i in self.needed)
        _aot.note_demand("fused_project", batch.capacity,
                         _costplane.rows_if_resolved(batch))
        try:
            fused_out = self._jitted(batch.capacity, datas, valids,
                                     batch.rows_dev)
        except Exception:  # noqa: BLE001 - fall back, but loudly
            _LOG.warning(
                "fused evaluation failed for %s; falling back to eager",
                [repr(self.exprs[i]) for i in self.fused_idx],
                exc_info=True)
            self.ok = False
            return None
        cols: List[Optional[Column]] = [None] * len(self.exprs)
        for i, (d, v) in zip(self.fused_idx, fused_out):
            cols[i] = Column(self.out_dtypes[i], d, v)
        for i, c in enumerate(cols):
            if c is None:
                cols[i] = ec.eval_as_column(self.exprs[i], batch)
        return cols

class _TracedBatch(ColumnarBatch):
    """ColumnarBatch whose num_rows is a traced scalar (no host int)."""

    def __init__(self, schema, columns, num_rows, capacity):
        self.schema = schema
        self.columns = list(columns)
        self._rows = num_rows           # jnp scalar under trace
        self._rows_dev = num_rows
        self._capacity = capacity


# ---------------------------------------------------------------------------
# program audit registration (analysis/program_audit.py): the audited
# object is the REAL cached program (wrap_miss + jit), traced over
# representative avals — never a re-implementation.
# ---------------------------------------------------------------------------

def _audit_specs():
    from ..analysis.program_audit import AuditSpec

    def _build():
        import jax
        import numpy as np
        from ..columnar.schema import Field, Schema
        from ..expr.arithmetic import Add
        schema = Schema([Field("a", T.INT64, True),
                         Field("b", T.INT64, True)])
        fe = FusedEval(
            [Add(ec.BoundReference(0, T.INT64),
                 ec.BoundReference(1, T.INT64))], schema)
        assert fe.ok, "representative fused projection did not fuse"
        cap = 64
        d = jax.ShapeDtypeStruct((cap,), np.int64)
        v = jax.ShapeDtypeStruct((cap,), np.bool_)
        args = (cap, tuple(d for _ in fe.needed),
                tuple(v for _ in fe.needed),
                jax.ShapeDtypeStruct((), np.int32))
        return fe._jitted, args, {"static_argnums": (0,)}

    return [AuditSpec(
        "fused_project", "fused_project", _build,
        notes="int64 a+b projection over a 64-row bucket",
        budgets={"gather": 2, "scatter": 2, "transpose": 2, "sort": 1})]
