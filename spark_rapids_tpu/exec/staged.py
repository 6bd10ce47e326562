"""Whole-stage fusion: chains of row-wise operators as ONE jitted program.

TPU-first rationale (the engine's analog of Spark's whole-stage codegen,
and of the reference running fused cuDF AST kernels): on real hardware
every separately-dispatched program launch pays fixed overhead, so a
pipeline of filter -> project -> ... executed op-by-op is
launch-overhead-bound.  Here a chain of row-preserving/row-filtering
operators is traced into one XLA computation per (chain structure,
schema, capacity bucket): predicates compact via in-trace gathers, and
the live row count stays a traced scalar throughout.

The planner collapses physical TpuFilter/TpuProject chains into
``TpuStagedCompute`` (plan/overrides.py post-pass), and the hash
aggregate absorbs a leading chain into its own cores
(tpu_aggregate._fused_whole_stage_core / _fused_table_core), so scan ->
filter -> project -> partial-agg runs as a single program launch per
batch.  There a filter does not compact: it clears row liveness
(``apply_ops_masked``), and the aggregate ranks a dead row past every
group.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column
from ..columnar.batch import ColumnarBatch, LazyCount
from ..columnar.schema import Schema
from ..compile import aot as _aot
from ..expr import core as ec
from ..kernels import basic as bk
from ..obs import compile_watch as _compile_watch
from ..obs import costplane as _costplane
from ..obs.registry import compile_cache_event
from .base import NUM_OUTPUT_ROWS, OP_TIME, timed
from .fused import FusedEval, _TracedBatch, _tree_fusable, expr_signature
from .tpu_basic import TpuExec

# op = ("filter", bound_condition, out_schema) |
#      ("project", [bound_exprs], out_schema)
Op = Tuple[str, object, Schema]


def ops_signature(ops: Sequence[Op]) -> Optional[str]:
    """Stable signature of an op chain; None if any expr is opaque."""
    parts = []
    for kind, payload, out_schema in ops:
        exprs = [payload] if kind == "filter" else list(payload)
        sigs = [expr_signature(e) for e in exprs]
        if any(s is None for s in sigs):
            return None
        parts.append(f"{kind}({';'.join(sigs)})")
    return ">".join(parts)


def ops_fusable(ops: Sequence[Op]) -> bool:
    for kind, payload, out_schema in ops:
        exprs = [payload] if kind == "filter" else list(payload)
        if not all(_tree_fusable(e) for e in exprs):
            return False
        # gathers re-order every column, so the whole row must be
        # fixed-width for the filter steps
        if kind == "filter" and any(
                f.dtype == T.STRING or f.dtype.is_nested
                for f in out_schema):
            return False
    return True


def apply_ops_traced(ops: Sequence[Op], batch) -> "_TracedBatch":
    """Run the chain under trace; batch.num_rows is a traced scalar."""
    for kind, payload, out_schema in ops:
        n = batch.num_rows
        if kind == "filter":
            pred = ec.eval_as_column(payload, batch)
            cap = batch.capacity
            keep = pred.data.astype(bool) & pred.validity
            order, cnt = bk.filter_compact_indices(keep, n)
            live = jnp.arange(cap) < cnt
            cols = [c.gather(order, live=live, unique=True)
                    for c in batch.columns]
            cols = [c.mask_validity(live) for c in cols]
            batch = _TracedBatch(out_schema, cols, cnt, cap)
        else:
            cols = [ec.eval_as_column(e, batch) for e in payload]
            batch = _TracedBatch(out_schema, cols, n, batch.capacity)
    return batch


def passthrough_ordinal(expr) -> Optional[int]:
    """The input ordinal a project expression hands on untouched (a bare
    column reference, aliased or not); None for anything computed."""
    while isinstance(expr, ec.Alias):
        expr = expr.children[0]
    return expr.ordinal if isinstance(expr, ec.BoundReference) else None


def ops_maskable(ops: Sequence[Op]) -> bool:
    """Can ``apply_ops_masked`` trace this chain?  Predicates and
    computed projections must be fixed-width and trace-safe; a STRING
    column may only be handed on untouched (nothing re-orders rows here,
    so ``ops_fusable``'s whole-row rule does not apply)."""
    for kind, payload, _ in ops:
        for e in ([payload] if kind == "filter" else payload):
            if _tree_fusable(e):
                continue
            # _tree_fusable refuses what has no dtype, so a passthrough
            # that got here has one
            if kind == "filter" or passthrough_ordinal(e) is None or \
                    e.dtype() != T.STRING:
                return False
    return True


def apply_ops_masked(ops: Sequence[Op], batch, live):
    """Run the chain under trace with every filter folded into the
    ``live`` row mask instead of compacting: for consumers that do not
    need contiguous rows (the aggregate's cores: the bucket table never
    did, the sort path ranks a dead row past every group).  Compaction's
    argsort and per-column gathers were the dominant map-side cost.
    Returns (batch at the input's capacity and row count, live)."""
    for kind, payload, out_schema in ops:
        if kind == "filter":
            pred = ec.eval_as_column(payload, batch)
            live = live & pred.data.astype(bool) & pred.validity
        else:
            cols = [ec.eval_as_column(e, batch) for e in payload]
            batch = _TracedBatch(out_schema, cols, batch.num_rows,
                                 batch.capacity)
    return batch, live


def apply_ops_eager(ops: Sequence[Op], batch: ColumnarBatch,
                    fused_per_op: Optional[list] = None) -> ColumnarBatch:
    """Host-driven fallback (strings/nested/host-state expressions).

    Per-op FusedEval instances (pass fused_per_op from the exec so they
    are built once, not per batch) keep the fusable SUBSET of each op
    jitted even when the chain as a whole cannot trace."""
    for i, (kind, payload, out_schema) in enumerate(ops):
        fused = fused_per_op[i] if fused_per_op is not None else None
        if kind == "filter":
            pred = None
            if fused is not None:
                cols = fused(batch)
                if cols is not None:
                    pred = cols[0]
            if pred is None:
                pred = ec.eval_as_column(payload, batch)
            keep = pred.data.astype(bool) & pred.validity
            idx, cnt = bk.filter_compact_indices(keep, batch.rows_dev)
            n = LazyCount(cnt)
            mask = jnp.arange(batch.capacity) < cnt
            batch = ColumnarBatch(
                out_schema,
                batch.gather(idx, n, live=mask, unique=True).columns, n)
        else:
            cols = fused(batch) if fused is not None else None
            if cols is None:
                cols = [ec.eval_as_column(e, batch) for e in payload]
            batch = ColumnarBatch(out_schema, cols, batch.rows_lazy)
    return batch


def build_fused_per_op(ops: Sequence[Op], src_schema: Schema):
    """One FusedEval per op for the eager fallback path."""
    out = []
    schema = src_schema
    for kind, payload, out_schema in ops:
        exprs = [payload] if kind == "filter" else list(payload)
        out.append(FusedEval(exprs, schema))
        schema = out_schema
    return out


class TpuStagedCompute(TpuExec):
    """A collapsed chain of filters/projections (one launch per batch).

    Reference analogue: GpuProjectExec/GpuFilterExec pipelines that the
    reference executes as fused cuDF AST expressions; Spark's own
    WholeStageCodegenExec plays the same role on CPU."""

    _JIT_CACHE: dict = {}

    def __init__(self, child, ops: List[Op], src_schema: Schema):
        super().__init__(child)
        self.ops = ops
        self.src_schema = src_schema

    @property
    def output_schema(self):
        return self.ops[-1][2]

    def _node_string(self):
        kinds = "+".join(k for k, _, _ in self.ops)
        return f"TpuStagedCompute[{kinds}]"

    def _jitted(self):
        sig = ops_signature(self.ops)
        key = None
        if sig is not None:
            key = (sig, tuple(f.dtype.name for f in self.src_schema))
            hit = TpuStagedCompute._JIT_CACHE.get(key)
            compile_cache_event("staged_compute", hit is not None)
            if hit is not None:
                return hit

        ops = self.ops
        src_schema = self.src_schema

        def _eval(capacity: int, datas, valids, num_rows):
            cols = [Column(f.dtype, d, v)
                    for f, d, v in zip(src_schema, datas, valids)]
            batch = _TracedBatch(src_schema, cols, num_rows, capacity)
            out = apply_ops_traced(ops, batch)
            return ([(c.data, c.validity) for c in out.columns],
                    out.num_rows)

        fn = _compile_watch.jit(_eval, "staged_eval", static_argnums=(0,))
        # compile telemetry: the first call (trace + XLA compile) is
        # wall-timed into the tpu_compile_seconds plane
        fn = _compile_watch.wrap_miss(
            "staged_compute", fn, "opaque" if key is None else str(key))
        if key is not None and len(TpuStagedCompute._JIT_CACHE) < 4096:
            TpuStagedCompute._JIT_CACHE[key] = fn
            dts = tuple(f.dtype.np_dtype for f in src_schema)
            if not any(d is None for d in dts):
                def warm(bucket: int) -> None:
                    datas = tuple(jnp.zeros(bucket, d) for d in dts)
                    valids = tuple(jnp.zeros(bucket, jnp.bool_)
                                   for _ in dts)
                    fn(bucket, datas, valids, jnp.int32(0))
                _aot.register_warmer("staged_compute", warm,
                                     str(hash(key)))
        return fn

    def execute(self):
        from .base import NUM_OUTPUT_BATCHES
        fusable = ops_fusable(self.ops)
        jitted = self._jitted() if fusable else None
        fused_per_op = None if fusable else \
            build_fused_per_op(self.ops, self.src_schema)
        out_schema = self.output_schema
        has_filter = any(k == "filter" for k, _, _ in self.ops)

        def run(part):
            from ..columnar.binary64 import exact_double_enabled
            from ..columnar.batch import chain_speculative

            def stage_one(batch):
                # exactDouble: traced reassembly would strip
                # Binary64Columns created inside the program
                if jitted is not None and \
                        not exact_double_enabled() and all(
                        type(c) is Column for c in batch.columns):
                    datas = tuple(c.data for c in batch.columns)
                    valids = tuple(c.validity for c in batch.columns)
                    _aot.note_demand(
                        "staged_compute", batch.capacity,
                        _costplane.rows_if_resolved(batch))
                    pairs, cnt = jitted(batch.capacity, datas, valids,
                                        batch.rows_dev)
                    n = LazyCount(cnt) if has_filter else \
                        batch.rows_lazy
                    return ColumnarBatch(
                        out_schema,
                        [Column(f.dtype, d, v) for f, (d, v) in
                         zip(out_schema, pairs)], n)
                return apply_ops_eager(self.ops, batch, fused_per_op)

            for batch in part:
                with timed(self.metrics[OP_TIME], self):
                    out = chain_speculative(stage_one(batch), batch,
                                            stage_one)
                self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield out
        return [run(p) for p in self.children[0].execute()]


# ---------------------------------------------------------------------------
# program audit registration (analysis/program_audit.py)
# ---------------------------------------------------------------------------

def _audit_specs():
    from ..analysis.program_audit import AuditSpec

    def _build():
        import numpy as np
        from ..columnar.schema import Field
        from ..expr.arithmetic import Add
        from ..expr.predicates import GreaterThan
        schema = Schema([Field("a", T.INT64, True),
                         Field("b", T.INT64, True)])
        pred = GreaterThan(ec.BoundReference(0, T.INT64), ec.lit(3))
        proj = Add(ec.BoundReference(0, T.INT64),
                   ec.BoundReference(1, T.INT64))
        out_schema = Schema([Field("s", T.INT64, True)])
        ops = [("filter", pred, schema), ("project", [proj], out_schema)]
        assert ops_fusable(ops), "representative chain did not fuse"
        st = object.__new__(TpuStagedCompute)
        st.ops = ops
        st.src_schema = schema
        fn = st._jitted()
        cap = 64
        d = jax.ShapeDtypeStruct((cap,), np.int64)
        v = jax.ShapeDtypeStruct((cap,), np.bool_)
        args = (cap, (d, d), (v, v),
                jax.ShapeDtypeStruct((), np.int32))
        return fn, args, {"static_argnums": (0,)}

    return [AuditSpec(
        "staged_compute", "staged_compute", _build,
        notes="filter(a>3) -> project(a+b) chain as one program",
        budgets={"gather": 8, "scatter": 2, "transpose": 2, "sort": 2})]
