"""TPU expand operator (grouping sets) — reference: GpuExpandExec.scala.

Each input row is replicated once per projection list; implemented as a
tiled gather (row i of projection p reads input row i), fully static.
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch, resolve_speculative
from ..columnar.column import bucket_capacity
from ..expr import core as ec
from ..obs import trace as _trace
from ..plan.logical import Expand
from .base import PhysicalPlan, NUM_OUTPUT_ROWS, OP_TIME, timed
from .tpu_basic import TpuExec


class TpuExpand(TpuExec):
    def __init__(self, logical: Expand, child: PhysicalPlan):
        super().__init__(child)
        self.logical = logical

    @property
    def output_schema(self):
        return self.logical.schema

    def execute(self):
        child_schema = self.children[0].output_schema
        bound = [[e.bind(child_schema) for e in proj]
                 for proj in self.logical.projections]

        def run(part):
            for batch in part:
                # every projection reads the batch: vouch for it once
                batch = resolve_speculative(batch)
                for proj in bound:
                    with timed(self.metrics[OP_TIME], self):
                        cols = [ec.eval_as_column(e, batch) for e in proj]
                        out = ColumnarBatch(self.output_schema, cols,
                                            batch.rows_lazy)
                    # the slots the aggregate above runs over
                    _trace.count("expand.batches")
                    _trace.count("expand.rows", out.capacity)
                    self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                    yield out
        return [run(p) for p in self.children[0].execute()]
