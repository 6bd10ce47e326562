"""Mesh-distributed shuffled hash join: the whole join as ONE SPMD program.

Reference role: GpuShuffledHashJoinBase.scala:28 +
GpuShuffleExchangeExec.scala:176 — the reference realizes a distributed
equi-join as [hash exchange left] + [hash exchange right] + local hash
join per partition, with the exchange riding UCX.  On a TPU mesh the
same pipeline is a single jitted shard_map program: both sides shard
across devices, rows hash-route by canonical key words to owner devices
via ``lax.all_to_all`` (co-partitioning both sides on the SAME hash),
and each owner runs the local sort + binary-search probe + static-shape
cumsum expansion (kernels/join.py — already fully device-pure).  XLA
schedules the ICI collectives; no transport code on the hot path.

Row-producing: the program returns the gathered output COLUMNS (left
payload at probe indices, right payload at build indices), per-device
match totals, and an overflow flag.  Join types inner / left outer /
semi / anti lower to count surgery exactly like the in-process join.
Overflow (receive region or output capacity) falls back loudly to the
in-process join on the materialized inputs — never silent truncation.

Enabled by ``spark.rapids.tpu.shuffle.mode=mesh`` with >1 device, equi
conditions, and fixed-width key/payload dtypes (strings route later).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar import dtypes as T
from ..columnar.schema import Schema
from ..columnar.column import Column, bucket_capacity
from ..columnar.batch import ColumnarBatch, concat_batches
from ..expr import core as ec
from ..kernels import canon
from ..kernels import join as join_k
from ..obs import compile_watch as _compile_watch
from ..obs import timeline as _timeline
from ..obs.registry import compile_cache_event
from ..parallel.mesh import MIX, _route_to_owners, make_mesh
from .base import (PhysicalPlan, JOIN_TIME, NUM_OUTPUT_ROWS, timed,
                   note_mesh_input, note_mesh_overflow)
from .tpu_basic import TpuExec
from .tpu_mesh_aggregate import _SINGLE_WORD

_AXIS = "data"
_JOIN_HASH_FINAL = 0xD6E8FEB86659FD93

_MESH_JOIN_TYPES = ("inner", "left", "right", "semi", "anti")


def mesh_join_supported(p, n_devices: int) -> bool:
    """Mesh-joinable: equi condition, inner/left/right/semi/anti, and
    fixed-width OUTPUT columns.  Keys may be STRINGS (multi-word): key
    words are computed eagerly per batch with statically-unified widths
    and routed through the all_to_all as plain u64 arrays; only the
    PAYLOAD columns must be fixed-width (a string key that is also
    projected into the output still blocks, via out_ts)."""
    if n_devices < 2 or p.condition is not None or not p.left_keys:
        return False
    if p.join_type not in _MESH_JOIN_TYPES:
        return False
    try:
        key_ts = [e.dtype() for e in p.left_keys] + \
                 [e.dtype() for e in p.right_keys]
        out_ts = [f.dtype for f in p.schema]
    except (ValueError, NotImplementedError):
        return False
    if not all(isinstance(t, _SINGLE_WORD) or t == T.STRING
               for t in key_ts):
        return False
    required = getattr(p, "required_out", None)
    if required is not None:
        out_ts = [f.dtype for f in p.schema if f.name in set(required)]
    return all(isinstance(t, _SINGLE_WORD) for t in out_ts)


class TpuMeshShuffledJoin(TpuExec):
    _PROGRAM_CACHE: dict = {}

    def __init__(self, logical, left: PhysicalPlan, right: PhysicalPlan,
                 mesh: Optional[Mesh] = None):
        super().__init__(left, right)
        self.logical = logical
        self.mesh = mesh

    @property
    def output_schema(self) -> Schema:
        required = getattr(self.logical, "required_out", None)
        if required is None:
            return self.logical.schema
        req = set(required)
        return Schema([f for f in self.logical.schema.fields
                       if f.name in req])

    def _node_string(self):
        n = self.mesh.devices.size if self.mesh is not None else "?"
        return (f"TpuMeshShuffledJoin[{self.logical.join_type}, "
                f"{n} devices]")

    # ------------------------------------------------------------------
    def _program(self, mesh: Mesh, jt: str, key_groups, l_dts, r_dts,
                 emit_right: bool):
        """``key_groups``: static word-count of each key column's canon
        encoding (1 rank word + value words; strings contribute several
        value words).  Key words are computed EAGERLY per batch (string
        kernels need host-known widths) and routed as plain u64 inputs,
        so the shard program itself is dtype-agnostic about keys."""
        from ..shims import get_shard_map
        shard_map = get_shard_map()
        key = (id(mesh), jt, tuple(key_groups),
               tuple(d.name for d in l_dts), tuple(d.name for d in r_dts),
               emit_right)
        hit = TpuMeshShuffledJoin._PROGRAM_CACHE.get(key)
        compile_cache_event("mesh_join", hit is not None)
        if hit is not None:
            return hit
        n_dev = mesh.devices.size
        nw = sum(key_groups)
        rank_pos = []
        off = 0
        for g in key_groups:
            rank_pos.append(off)
            off += g

        def side_route(words, datas, valids, live):
            words = list(words)
            words[0] = jnp.where(live, words[0], jnp.uint64(2))
            h = jnp.zeros_like(words[0])
            for w in words:
                h = (h ^ w) * jnp.uint64(MIX)
            # finalized apart from the mesh aggregate's owner hash (the
            # same fold): a join fed by a mesh aggregate on the same key
            # would otherwise find every shard's rows bound for ONE
            # owner, overflow its slack-2 receive region and re-run in
            # process every time
            h = h ^ (h >> jnp.uint64(29))
            h = h * jnp.uint64(_JOIN_HASH_FINAL)
            h = h ^ (h >> jnp.uint64(32))
            owner = (h >> jnp.uint64(33)) % jnp.uint64(n_dev)
            owner = jnp.where(live, owner.astype(jnp.int32), n_dev)
            payload = list(words) + list(datas) + list(valids)
            fills = ([jnp.uint64(2)] + [jnp.uint64(0)] * (len(words) - 1)
                     + [jnp.zeros((), d.dtype)[()] for d in datas]
                     + [False] * len(valids))
            routed, rlive, ovf = _route_to_owners(
                owner, payload, fills, n_dev, _AXIS, slack=2)
            rwords = [jnp.asarray(w) for w in routed[:len(words)]]
            rwords[0] = jnp.where(rlive, rwords[0], jnp.uint64(2))
            nd = len(datas)
            rdatas = routed[len(words):len(words) + nd]
            rvalids = [v & rlive for v in routed[len(words) + nd:]]
            return rwords, rdatas, rvalids, rlive, ovf

        def step(*flat):
            pos = 0
            lwords = list(flat[pos:pos + nw]); pos += nw
            ld = list(flat[pos:pos + len(l_dts)]); pos += len(l_dts)
            lv = list(flat[pos:pos + len(l_dts)]); pos += len(l_dts)
            llive = flat[pos]; pos += 1
            rwords = list(flat[pos:pos + nw]); pos += nw
            rd = list(flat[pos:pos + len(r_dts)]); pos += len(r_dts)
            rv = list(flat[pos:pos + len(r_dts)]); pos += len(r_dts)
            rlive = flat[pos]

            lw, lrd, lrv, lrl, ovf_l = side_route(lwords, ld, lv, llive)
            rw, rrd, rrv, rrl, ovf_r = side_route(rwords, rd, rv, rlive)

            # local join on the owner shard: sorted build + binary probe
            bt = join_k.build(rw)
            lo = join_k._bsearch(bt.sorted_words, lw, upper=False)
            hi = join_k._bsearch(bt.sorted_words, lw, upper=True)
            counts = (hi - lo).astype(jnp.int32)
            # null keys never match: each key group leads with its
            # null/range rank word, rank 1 == valid
            usable = lrl
            for rp in rank_pos:
                usable = usable & (lw[rp] == jnp.uint64(1))
            counts = jnp.where(usable, counts, 0)

            if jt == "inner":
                counts_eff = counts
            elif jt == "left":
                counts_eff = jnp.where(lrl & (counts == 0), 1, counts)
            elif jt == "semi":
                counts_eff = jnp.where(counts > 0, 1, 0)
            else:   # anti: live probe rows with no match (incl. null key)
                counts_eff = jnp.where(lrl & (counts == 0), 1, 0)

            pcap = lw[0].shape[0]
            out_cap = pcap * 2
            pc, build_idx, live_out, total = join_k.join_expand_matches(
                lo, counts_eff, bt.perm, out_cap)
            ovf_out = total > out_cap
            matched_slot = jnp.take(counts, pc) > 0

            # live output slots are contiguous at the front by
            # construction (expand fills t = 0..total-1)
            out_flat = []
            for d, v in zip(lrd, lrv):
                out_flat.append(jnp.take(d, pc, mode="clip"))
                out_flat.append(jnp.take(v, pc, mode="clip") & live_out)
            if emit_right:
                for d, v in zip(rrd, rrv):
                    out_flat.append(jnp.take(d, build_idx, mode="clip"))
                    out_flat.append(jnp.take(v, build_idx, mode="clip")
                                    & live_out & matched_slot)
            ovf = ovf_l | ovf_r | ovf_out
            out_flat.append(total.astype(jnp.int32)[None])
            out_flat.append(ovf[None])
            return tuple(out_flat)

        n_in = nw + 2 * len(l_dts) + 1 + nw + 2 * len(r_dts) + 1
        n_out = 2 * len(l_dts) + (2 * len(r_dts) if emit_right else 0) + 2
        fn = _compile_watch.jit(shard_map(
            step, mesh=mesh,
            in_specs=tuple(P(_AXIS) for _ in range(n_in)),
            out_specs=tuple(P(_AXIS) for _ in range(n_out))),
            "mesh_join_step")
        # perf plane: each dispatch window is busy time on every mesh
        # device; the first call (jit compile) lands in compile_watch
        # with the cache key (minus the unstable id(mesh)) as signature
        fn = _timeline.device_busy_wrap(
            fn, tuple(str(d.id) for d in mesh.devices.ravel()))
        fn = _compile_watch.wrap_miss("mesh_join", fn, str(key[1:]))
        TpuMeshShuffledJoin._PROGRAM_CACHE[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _gather_side(self, child, keys, n_dev):
        batches = [b for part in child.execute() for b in part]
        batches = [b for b in batches if b.num_rows > 0]
        if not batches:
            batches = [ColumnarBatch.empty(child.output_schema)]
        batch = concat_batches(batches) if len(batches) > 1 else batches[0]
        schema = batch.schema
        key_cols = [ec.eval_as_column(e.bind(schema), batch)
                    for e in keys]
        out_cols = list(batch.columns)
        cap = batch.capacity
        # capacities are bucket powers of two and mesh sizes are powers
        # of two, so the shard constraint holds (same invariant as
        # TpuMeshAggregate.execute)
        assert cap % n_dev == 0, (cap, n_dev)
        live = np.zeros(cap, bool)
        live[:batch.num_rows] = True
        return batch, key_cols, out_cols, jnp.asarray(live)

    def execute(self):
        p = self.logical
        mesh = self.mesh or make_mesh()
        n_dev = mesh.devices.size
        jt = p.join_type
        # RIGHT outer = LEFT outer with the sides swapped: the probe
        # side is the row-preserving one, so probe on the original
        # RIGHT and reorder output columns back afterwards
        swapped = jt == "right"
        prog_jt = "left" if swapped else jt
        emit_right = prog_jt in ("inner", "left")

        def run():
            from ..kernels import strings as skern
            if swapped:
                lbatch, lkeys, lcols, llive = self._gather_side(
                    self.children[1], p.right_keys, n_dev)
                rbatch, rkeys, rcols, rlive = self._gather_side(
                    self.children[0], p.left_keys, n_dev)
            else:
                lbatch, lkeys, lcols, llive = self._gather_side(
                    self.children[0], p.left_keys, n_dev)
                rbatch, rkeys, rcols, rlive = self._gather_side(
                    self.children[1], p.right_keys, n_dev)
            # only the REQUIRED output columns ride the all_to_all
            # (a string join key the parent projects away is words-only)
            required = getattr(p, "required_out", None)
            if required is not None:
                req = set(required)
                lcols_f, rcols_f = [], []
                for c, f in zip(lcols, lbatch.schema.fields):
                    if f.name in req:
                        lcols_f.append(c)
                for c, f in zip(rcols, rbatch.schema.fields):
                    if f.name in req:
                        rcols_f.append(c)
                lcols, rcols = lcols_f, rcols_f
            # key WORDS are computed eagerly with statically-unified
            # string widths (strings are multi-word; the program routes
            # words, not key columns)
            str_widths = []
            for lk, rk in zip(lkeys, rkeys):
                if lk.dtype == T.STRING:
                    w = max(skern.needed_key_words(lk, lbatch.num_rows),
                            skern.needed_key_words(rk, rbatch.num_rows))
                    str_widths.append(w)
                else:
                    str_widths.append(None)
            lparts = [canon.batch_key_words([c], lbatch.num_rows,
                                            str_words=[w])
                      for c, w in zip(lkeys, str_widths)]
            rparts = [canon.batch_key_words([c], rbatch.num_rows,
                                            str_words=[w])
                      for c, w in zip(rkeys, str_widths)]
            key_groups = tuple(len(ws) for ws in lparts)
            assert key_groups == tuple(len(ws) for ws in rparts), \
                (key_groups, [len(ws) for ws in rparts])
            lwords = [w for ws in lparts for w in ws]
            rwords = [w for ws in rparts for w in ws]
            l_dts = [c.dtype for c in lcols]
            r_dts = [c.dtype for c in rcols]

            sharding = NamedSharding(mesh, P(_AXIS))
            flat = (list(lwords) + [c.data for c in lcols] +
                    [c.validity for c in lcols] + [llive] +
                    list(rwords) + [c.data for c in rcols] +
                    [c.validity for c in rcols] + [rlive])
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="mesh_reshard"):
                flat = [jax.device_put(a, sharding) for a in flat]
            note_mesh_input(self, flat[0])

            program = self._program(mesh, prog_jt, key_groups,
                                    l_dts, r_dts, emit_right)
            from ..compile import aot as _aot
            _aot.note_demand("mesh_join", flat[0].shape[0])
            with timed(self.metrics[JOIN_TIME], self):
                out = program(*flat)
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="mesh_collect"):
                overflowed = bool(np.asarray(out[-1]).any())
            if overflowed:
                note_mesh_overflow(self)
                yield from self._fallback(lbatch, rbatch, swapped)
                return
            with residency.declared_transfer(site="mesh_collect"):
                totals = np.asarray(out[-2]).reshape(-1)
            per = out[0].shape[0] // n_dev
            out_schema = self.output_schema
            # program output layout: probe payload then build payload;
            # output schema wants original-left columns then
            # original-right — for a swapped (right outer) run the
            # build side (original left) comes FIRST in the schema
            probe_slots = [2 * i for i in range(len(lcols))]
            build_slots = [2 * len(lcols) + 2 * i
                           for i in range(len(rcols))] if emit_right \
                else []
            col_slots = (build_slots + probe_slots) if swapped else \
                (probe_slots + build_slots)
            for d in range(n_dev):
                nr = int(totals[d])
                if nr == 0:
                    continue
                lo_ = d * per
                seg = bucket_capacity(max(nr, 1))
                idx = jnp.arange(seg) + lo_
                cols = []
                for f, slot in zip(out_schema, col_slots):
                    data = jnp.take(out[slot], idx, mode="clip")
                    valid = jnp.take(out[slot + 1], idx, mode="clip") \
                        & (jnp.arange(seg) < nr)
                    cols.append(Column(f.dtype, data, valid))
                ob = ColumnarBatch(out_schema, cols, nr)
                self.metrics[NUM_OUTPUT_ROWS] += nr
                yield ob
        return [run()]

    # ------------------------------------------------------------------
    def _fallback(self, lbatch: ColumnarBatch, rbatch: ColumnarBatch,
                  swapped: bool = False):
        """Receive/output region overflowed: rerun via the in-process
        join on the materialized inputs (loud fallback, never silent)."""
        from .tpu_join import TpuShuffledHashJoin
        if swapped:
            # the swapped (right outer) run gathered sides reversed
            lbatch, rbatch = rbatch, lbatch

        class _One(PhysicalPlan):
            columnar = True

            def __init__(self, b):
                super().__init__()
                self._b = b

            @property
            def output_schema(self):
                return self._b.schema

            def execute(self):
                return [iter([self._b])]

        j = TpuShuffledHashJoin(
            self.logical, _One(lbatch), _One(rbatch),
            # the in-process join realizes RIGHT outer by building on
            # the LEFT (planner contract: build opposite the preserved
            # side)
            build_right=self.logical.join_type != "right")
        out_schema = self.output_schema
        prune = len(out_schema) != len(self.logical.schema)
        for part in j.execute():
            for b in part:
                if prune:
                    keep = {f.name for f in out_schema.fields}
                    cols = [c for c, f in zip(b.columns, b.schema.fields)
                            if f.name in keep]
                    b = ColumnarBatch(out_schema, cols, b.rows_lazy)
                yield b


# ---------------------------------------------------------------------------
# program audit registration (analysis/program_audit.py)
# ---------------------------------------------------------------------------

def _audit_specs():
    from ..analysis.program_audit import AuditSpec

    def _build():
        import jax
        import numpy as np
        from ..parallel.mesh import make_mesh
        # 2-device mesh: 1 device degenerates the splitter /
        # routing structure (empty splitter gathers); the test harness
        # and ci/audit.py force >=2 host devices via XLA_FLAGS
        mesh = make_mesh(2)
        j = object.__new__(TpuMeshShuffledJoin)
        fn = j._program(mesh, "inner", (2,), (T.INT64,), (T.INT64,),
                        True)
        cap = 64
        w = jax.ShapeDtypeStruct((cap,), np.uint64)
        d = jax.ShapeDtypeStruct((cap,), np.int64)
        v = jax.ShapeDtypeStruct((cap,), np.bool_)
        # flat layout: lwords + l payload (data, valid) + l live, then
        # the same for the right side
        args = (w, w, d, v, v, w, w, d, v, v)
        return fn, args, {}

    return [AuditSpec(
        "mesh_join", "mesh_join", _build,
        notes="2-device mesh, inner join, one int64 payload per side",
        budgets={"gather": 66, "scatter": 24, "transpose": 4,
                 "sort": 8})]
