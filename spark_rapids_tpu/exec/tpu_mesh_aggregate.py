"""Mesh-distributed hash aggregate: the whole group-by as ONE SPMD program.

Reference role: BASELINE.json config 4 — "RapidsShuffleManager over
multi-host ICI".  The reference realizes a distributed aggregation as
partial agg -> UCX shuffle (catalog + client/server state machines +
bounce buffers) -> final agg.  On a TPU mesh the same pipeline is a
single jitted shard_map program: rows shard across devices, each device
partially groups its shard, key groups hash-route to an owner device via
``lax.all_to_all`` (XLA schedules the ICI), and the owner merges and
finalizes.  No transport code on the hot path.

Enabled with ``spark.rapids.tpu.shuffle.mode=mesh`` when more than one
device is visible (tests use the 8-device virtual CPU mesh; the driver's
``dryrun_multichip`` exercises the same kernels).  Row counts that
overflow a device's receive region fall back to the in-process path —
the same "fail loudly, never silently drop" contract as
parallel/mesh.py's overflow flag.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar import dtypes as T
from ..columnar.schema import Field, Schema
from ..columnar.column import Column, bucket_capacity
from ..columnar.batch import ColumnarBatch, concat_batches
from ..expr import core as ec
from ..kernels import canon, aggregate as agg_k
from ..obs import compile_watch as _compile_watch
from ..obs import timeline as _timeline
from ..obs.registry import compile_cache_event
from ..parallel.mesh import MIX, _route_to_owners, make_mesh
from .base import (PhysicalPlan, AGG_TIME, NUM_OUTPUT_ROWS, timed,
                   note_mesh_input, note_mesh_overflow)
from .tpu_basic import TpuExec

_AXIS = "data"

# dtypes whose canonical encoding is (rank word, one value word)
_SINGLE_WORD = (T.BooleanType, T.ByteType, T.ShortType, T.IntegerType,
                T.LongType, T.FloatType, T.DoubleType, T.DateType,
                T.TimestampType)


def mesh_aggregate_supported(p, n_devices: int) -> bool:
    from ..expr import aggregates as ea
    if n_devices < 2 or not p.group_exprs:
        return False
    try:
        key_ts = [e.dtype() for e in p.group_exprs]
        in_ts = [c.dtype() for a in p.aggs for c in a.func.children]
    except (ValueError, NotImplementedError):
        return False
    if not all(isinstance(t, _SINGLE_WORD) for t in key_ts):
        return False
    if not all(isinstance(t, _SINGLE_WORD) for t in in_ts):
        return False
    return all(isinstance(a.func, (ea.Sum, ea.Count, ea.Min, ea.Max,
                                   ea.Average, ea.First, ea.Last))
               for a in p.aggs)


class TpuMeshAggregate(TpuExec):
    _PROGRAM_CACHE: dict = {}

    def __init__(self, logical, child: PhysicalPlan,
                 mesh: Optional[Mesh] = None):
        super().__init__(child)
        self.logical = logical
        self.mesh = mesh

    @property
    def output_schema(self):
        p = self.logical
        fields = [Field(ec.output_name(e), e.dtype(), True)
                  for e in p.group_exprs]
        fields += [Field(a.alias, a.func.dtype(), a.func.nullable)
                   for a in p.aggs]
        return Schema(fields)

    def _node_string(self):
        n = self.mesh.devices.size if self.mesh is not None else "?"
        return f"TpuMeshAggregate[{n} devices]"

    # ------------------------------------------------------------------
    def _program(self, mesh: Mesh, nkeys: int, key_dts, in_layout,
                 in_dts):
        """Build (or fetch) the jitted SPMD program.

        in_layout: per agg, number of input columns (0 for count(*)).
        The traced signature: flat key (data, valid) pairs, flat input
        (data, valid) pairs, per-shard live mask.
        """
        from ..shims import get_shard_map
        shard_map = get_shard_map()
        p = self.logical
        key = (id(mesh), nkeys, tuple(d.name for d in key_dts),
               tuple(in_layout), tuple(d.name for d in in_dts),
               tuple((type(a.func).__name__, repr(a.func),
                      getattr(a.func, "ignore_nulls", None))
                     for a in p.aggs))
        hit = TpuMeshAggregate._PROGRAM_CACHE.get(key)
        compile_cache_event("mesh_aggregate", hit is not None)
        if hit is not None:
            return hit
        n_dev = mesh.devices.size
        aggs = p.aggs

        def step(*flat):
            pos = 0
            kdatas, kvalids = [], []
            for _ in range(nkeys):
                kdatas.append(flat[pos])
                kvalids.append(flat[pos + 1])
                pos += 2
            idatas, ivalids = [], []
            for _ in range(sum(in_layout)):
                idatas.append(flat[pos])
                ivalids.append(flat[pos + 1])
                pos += 2
            live = flat[pos]

            # canonical words per key (rank + value) for routing+grouping
            words: List[jnp.ndarray] = []
            for d, v, dt in zip(kdatas, kvalids, key_dts):
                col = Column(dt, d, v & live)
                cap = d.shape[0]
                w = canon.column_key_words(
                    col, jnp.sum(live.astype(jnp.int32)))
                words.extend(w)
            # rows past the live count were masked invalid, not dead:
            # re-mark dead rows in the FIRST word (rank 2 == padding)
            words[0] = jnp.where(live, words[0], jnp.uint64(2))

            h = jnp.zeros_like(words[0])
            for w in words:
                h = (h ^ w) * jnp.uint64(MIX)
            owner = (h >> jnp.uint64(33)) % jnp.uint64(n_dev)
            owner = jnp.where(live, owner.astype(jnp.int32), n_dev)

            payload = list(words) + kdatas + kvalids + idatas + ivalids
            fills = ([jnp.uint64(2)] + [jnp.uint64(0)] * (len(words) - 1)
                     + [jnp.zeros((), d.dtype)[()] for d in kdatas]
                     + [False] * len(kvalids)
                     + [jnp.zeros((), d.dtype)[()] for d in idatas]
                     + [False] * len(ivalids))
            routed, rlive, overflow = _route_to_owners(
                owner, payload, fills, n_dev, _AXIS, slack=2)
            rwords = routed[:len(words)]
            pos = len(words)
            rkd = routed[pos:pos + nkeys]
            pos += nkeys
            rkv = [v & rlive for v in routed[pos:pos + nkeys]]
            pos += nkeys
            rid = routed[pos:pos + sum(in_layout)]
            pos += sum(in_layout)
            riv = [v & rlive for v in routed[pos:pos + sum(in_layout)]]

            rwords = [jnp.asarray(w) for w in rwords]
            rwords[0] = jnp.where(rlive, rwords[0], jnp.uint64(2))
            plan = agg_k.groupby_plan(rwords)

            outs = []
            it = 0
            for a, n_in in zip(aggs, in_layout):
                if n_in == 0:
                    cols = [None]
                else:
                    cols = [Column(dt, rid[it + j], riv[it + j])
                            for j, dt in enumerate(
                                in_dts[it:it + n_in])]
                    it += n_in
                bufs = a.func.update(plan, cols)
                final = a.func.finalize(bufs)
                outs.append((final.data, final.validity))

            cap = rwords[0].shape[0]
            ng = plan.num_groups
            sel = jnp.where(jnp.arange(cap) < ng,
                            jnp.pad(plan.rep_indices,
                                    (0, max(0, cap -
                                            plan.rep_indices.shape[0])
                                     ))[:cap], 0)
            glive = jnp.arange(cap) < ng
            out_flat = []
            for d, v in zip(rkd, rkv):
                out_flat.append(jnp.take(d, sel))
                out_flat.append(jnp.take(v, sel) & glive)
            for d, v in outs:
                seg_take = jnp.where(glive, jnp.arange(cap), 0)
                out_flat.append(jnp.take(d, seg_take))
                out_flat.append(jnp.take(v, seg_take) & glive)
            out_flat.append(ng[None])
            out_flat.append(overflow[None])
            return tuple(out_flat)

        n_out = 2 * nkeys + 2 * len(aggs) + 2
        fn = _compile_watch.jit(shard_map(
            step, mesh=mesh,
            in_specs=tuple(P(_AXIS) for _ in
                           range(2 * (nkeys + sum(in_layout)) + 1)),
            out_specs=tuple(P(_AXIS) for _ in range(n_out))),
            "mesh_aggregate_step")
        # perf plane: per-device busy windows + first-call compile
        # telemetry (signature drops the unstable id(mesh))
        fn = _timeline.device_busy_wrap(
            fn, tuple(str(d.id) for d in mesh.devices.ravel()))
        fn = _compile_watch.wrap_miss("mesh_aggregate", fn,
                                      str(key[1:]))
        TpuMeshAggregate._PROGRAM_CACHE[key] = fn
        return fn

    # ------------------------------------------------------------------
    def execute(self):
        p = self.logical
        mesh = self.mesh or make_mesh()
        n_dev = mesh.devices.size
        child = self.children[0]

        def run():
            batches = [b for part in child.execute() for b in part]
            batch = concat_batches(batches) if len(batches) > 1 else \
                batches[0]
            schema = batch.schema
            key_cols = [ec.eval_as_column(e.bind(schema), batch)
                        for e in p.group_exprs]
            in_cols, in_layout, in_dts = [], [], []
            for a in p.aggs:
                bound = [c.bind(schema) for c in a.func.children]
                cols = [ec.eval_as_column(b, batch) for b in bound]
                in_layout.append(len(cols))
                in_cols.extend(cols)
                in_dts.extend(c.dtype for c in cols)

            # shard over devices: capacity must divide evenly
            cap = batch.capacity
            if cap % n_dev != 0:
                cap = bucket_capacity(cap * n_dev)  # unreachable for 2^k
            live = np.zeros(cap, bool)
            live[:batch.num_rows] = True
            flat = []
            for c in key_cols:
                flat.append(c.data)
                flat.append(c.validity)
            for c in in_cols:
                flat.append(c.data)
                flat.append(c.validity)
            flat.append(jnp.asarray(live))
            sharding = NamedSharding(mesh, P(_AXIS))
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="mesh_reshard"):
                flat = [jax.device_put(a, sharding) for a in flat]
            note_mesh_input(self, flat[0])

            program = self._program(mesh, len(key_cols),
                                    [c.dtype for c in key_cols],
                                    in_layout, in_dts)
            from ..compile import aot as _aot
            _aot.note_demand("mesh_aggregate", flat[0].shape[0])
            with timed(self.metrics[AGG_TIME], self):
                out = program(*flat)
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="mesh_collect"):
                overflow = bool(np.asarray(out[-1]).any())
            if overflow:
                # receive region overflowed: rerun via the in-process
                # aggregate on the materialized input (loud fallback)
                note_mesh_overflow(self)
                from .tpu_aggregate import TpuHashAggregate

                class _One(PhysicalPlan):
                    columnar = True

                    def __init__(self, b, s):
                        super().__init__()
                        self._b, self._s = b, s

                    @property
                    def output_schema(self):
                        return self._s

                    def execute(self):
                        return [iter([self._b])]
                agg = TpuHashAggregate(p.group_exprs, p.aggs,
                                       _One(batch, schema))
                for part in agg.execute():
                    yield from part
                return
            with residency.declared_transfer(site="mesh_collect"):
                ngs = np.asarray(out[-2])      # [n_dev] group counts
            per = out[0].shape[0] // n_dev
            out_schema = self.output_schema
            for d in range(n_dev):
                ng = int(ngs[d])
                if ng == 0:
                    continue
                cols = []
                lo = d * per
                seg_cap = bucket_capacity(max(ng, 1))
                idx = jnp.arange(seg_cap) + lo
                for i, f in enumerate(out_schema):
                    data = jnp.take(out[2 * i], idx, mode="clip")
                    valid = jnp.take(out[2 * i + 1], idx, mode="clip") \
                        & (jnp.arange(seg_cap) < ng)
                    cols.append(Column(f.dtype, data, valid))
                ob = ColumnarBatch(out_schema, cols, ng)
                self.metrics[NUM_OUTPUT_ROWS] += ng
                yield ob
        return [run()]


# ---------------------------------------------------------------------------
# program audit registration (analysis/program_audit.py)
# ---------------------------------------------------------------------------

def _audit_specs():
    from types import SimpleNamespace
    from ..analysis.program_audit import AuditSpec

    def _build():
        import jax
        import numpy as np
        from ..expr import aggregates as ea
        from ..expr import core as ec
        from ..parallel.mesh import make_mesh
        from ..plan.logical import AggExpr
        # 2-device mesh: 1 device degenerates the splitter /
        # routing structure (empty splitter gathers); the test harness
        # and ci/audit.py force >=2 host devices via XLA_FLAGS
        mesh = make_mesh(2)
        a = object.__new__(TpuMeshAggregate)
        a.logical = SimpleNamespace(
            aggs=[AggExpr(ea.Sum(ec.BoundReference(1, T.INT64)), "s")])
        fn = a._program(mesh, 1, (T.INT64,), (1,), (T.INT64,))
        cap = 64
        d = jax.ShapeDtypeStruct((cap,), np.int64)
        v = jax.ShapeDtypeStruct((cap,), np.bool_)
        # interleaved flat layout: (key data, key valid) per key, then
        # (input data, input valid) per agg input, then live
        args = (d, v, d, v, v)
        return fn, args, {}

    return [AuditSpec(
        "mesh_aggregate", "mesh_aggregate", _build,
        notes="2-device mesh, sum(v) group by one int64 key",
        budgets={"gather": 50, "scatter": 18, "transpose": 4,
                 "sort": 8})]
