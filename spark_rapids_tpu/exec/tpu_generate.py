"""TPU generate operator: explode/posexplode (+outer variants).

Reference: GpuGenerateExec.scala (498 LoC) — explode via cuDF
``explode``/``explode_position`` kernels.  TPU-first: the output row plan
is pure offsets arithmetic (kernels/lists.py list_explode_offsets/
list_explode_indices); the single dynamic scalar (output row count) is pulled
to host to choose the power-of-two output bucket, then one gather per
column materializes the result — the same two-phase pattern as filter.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.schema import Field, Schema
from ..columnar.column import Column, bucket_capacity
from ..columnar.gather import gather_columns
from ..columnar.batch import ColumnarBatch
from ..expr import core as ec
from ..kernels import lists as lk
from .base import PhysicalPlan, NUM_OUTPUT_ROWS, NUM_OUTPUT_BATCHES, \
    OP_TIME, timed
from .tpu_basic import TpuExec


class TpuGenerate(TpuExec):
    def __init__(self, logical, child: PhysicalPlan):
        super().__init__(child)
        self.logical = logical

    @property
    def output_schema(self):
        return self.logical.schema

    def execute(self):
        gen = self.logical.generator
        child_schema = self.children[0].output_schema
        bound = gen.children[0].bind(child_schema)
        out_schema = self.output_schema
        pos = gen.pos
        outer = gen.outer

        def run(part):
            for batch in part:
                with timed(self.metrics[OP_TIME], self):
                    out = self._generate(batch, bound, pos, outer,
                                         out_schema)
                self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield out
        return [run(p) for p in self.children[0].execute()]

    def _generate(self, batch: ColumnarBatch, bound, pos: bool, outer: bool,
                  out_schema: Schema) -> ColumnarBatch:
        fast = self._literal_array_fast_path(batch, bound, pos, out_schema)
        if fast is not None:
            return fast
        lcol = ec.eval_as_column(bound, batch)
        out_offsets, total = lk.list_explode_offsets(
            lcol.offsets, lcol.validity, batch.num_rows, outer)
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="size_probe"):
            n = int(total)
        out_cap = bucket_capacity(max(1, n))
        row_idx, elem_idx, posv, elem_valid, live = lk.list_explode_indices(
            lcol.offsets, lcol.validity, out_offsets, out_cap)
        cols = gather_columns(batch.columns, row_idx, live)
        if pos:
            # outer's synthetic null row has a null position (Spark
            # PosExplode outer semantics)
            cols.append(Column(T.INT32, posv.astype(jnp.int32),
                               elem_valid if outer else live))
        gen_col = lcol.elements.gather(elem_idx).mask_validity(elem_valid)
        if gen_col.capacity != out_cap:
            gen_col = gen_col.with_capacity(out_cap, n)
        cols.append(gen_col)
        return ColumnarBatch(out_schema, cols, n)

    def _literal_array_fast_path(self, batch: ColumnarBatch, bound,
                                 pos: bool, out_schema: Schema):
        """explode(array(lit...)) is a pure k-way row repeat: out[j] =
        in[j // k], value[j] = consts[j % k].  The reference's mortgage
        ETL leans on exactly this idiom ("explode ... is actually
        slightly more efficient than a cross join",
        MortgageSpark.scala:271) — no offsets machinery, one gather.
        """
        from ..expr.collections import CreateArray
        if not isinstance(bound, CreateArray) or not bound.children or \
                not all(isinstance(c, ec.Literal) for c in bound.children):
            return None
        values = [c.value for c in bound.children]
        if any(v is None for v in values):
            return None
        k = len(values)
        n = batch.num_rows * k
        out_cap = bucket_capacity(max(1, n))
        j = jnp.arange(out_cap, dtype=jnp.int32)
        row_idx = j // k
        posv = j % k
        live = j < n
        cols = gather_columns(batch.columns, row_idx, live)
        if pos:
            cols.append(Column(T.INT32, posv, live))
        et = bound.dtype().element_type
        consts = Column.from_numpy(values, dtype=et,
                                   capacity=bucket_capacity(k))
        gen = consts.gather(posv).mask_validity(live)
        cols.append(gen)
        return ColumnarBatch(out_schema, cols, n)

    def _node_string(self):
        g = self.logical.generator
        kind = "posexplode" if g.pos else "explode"
        if g.outer:
            kind += "_outer"
        return f"TpuGenerate[{kind}]"
