"""TPU join operators.

Reference: GpuHashJoin.scala:62 (build+probe core), JoinGatherer.scala
(bounded gather maps), GpuShuffledHashJoinBase / GpuBroadcastHashJoinExec /
GpuBroadcastNestedLoopJoinExec / GpuCartesianProductExec.

TPU-first: the build side is sorted once per partition by canonical key
words; every probe batch runs a vectorized binary search + cumsum
expansion (kernels/join.py).  Join types are realized by count surgery:
  outer  -> unmatched probe rows get one null-extended output row
  semi   -> filter probe rows with count > 0
  anti   -> filter probe rows with count == 0
  full   -> left-outer + unmatched build rows appended
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.schema import Field, Schema
from ..columnar.column import Column, StringColumn, bucket_capacity
from ..columnar.batch import ColumnarBatch, concat_batches
from ..columnar.gather import gather_columns
from ..expr import core as ec
from ..kernels import canon, join as join_k
from ..kernels.basic import prefix_sum
from ..kernels import strings as skern
from ..obs import compile_watch as _compile_watch
from ..obs import trace as _trace
from .base import (PhysicalPlan, BUILD_TIME, JOIN_TIME, NUM_OUTPUT_ROWS,
                   timed)
from .tpu_basic import TpuExec


def _host_int(x) -> int:
    """Declared d2h pull of one count scalar (join verify barrier)."""
    from ..analysis import residency  # lazy: avoids import cycle
    with residency.declared_transfer(site="join_verify"):
        return int(x)


def _key_words(cols: List[Column], num_rows: int,
               str_words: List[Optional[int]]):
    return canon.batch_key_words(cols, num_rows, str_words=str_words)


def _null_column(dtype: T.DType, capacity: int) -> Column:
    return Column.all_null(dtype, capacity)


def _with_gathered(cols, plain_outs, idx, live) -> List[Column]:
    """``cols`` at rows ``idx``: each plain column from the program's
    (data, validity) pairs, in order, and the others (strings) in one
    eager gather."""
    rest = [i for i, c in enumerate(cols) if type(c) is not Column]
    moved = dict(zip(rest, gather_columns([cols[i] for i in rest], idx,
                                          live)))
    pairs = iter(plain_outs)
    return [moved[i] if i in moved else Column(c.dtype, *next(pairs))
            for i, c in enumerate(cols)]


def _bound_ordinals(e: ec.Expression) -> set:
    if isinstance(e, ec.BoundReference):
        return {e.ordinal}
    return set().union(*[_bound_ordinals(c) for c in e.children])


def _rebind(e: ec.Expression, ordinal: dict) -> ec.Expression:
    """``e`` with every column reference moved to ``ordinal[old]``."""
    if isinstance(e, ec.BoundReference):
        return ec.BoundReference(ordinal[e.ordinal], e.dtype(), True,
                                 e.col_name)
    return e.map_children(lambda c: _rebind(c, ordinal))


class TpuHashJoinBase(TpuExec):
    """Shared build/probe logic.  children = [left, right]; the build side

    is chosen by the subclass (broadcast: the broadcast side; shuffled:
    right for inner/left, left for right joins)."""

    def __init__(self, logical, left: PhysicalPlan, right: PhysicalPlan,
                 build_right: bool = True):
        super().__init__(left, right)
        self.logical = logical
        self.build_right = build_right

    @property
    def output_schema(self) -> Schema:
        return self.logical.schema

    def _node_string(self):
        return (f"{self.name}[{self.logical.join_type}, "
                f"build={'right' if self.build_right else 'left'}]")

    # ------------------------------------------------------------------
    def _run_partition(self, left_iter, right_iter):
        lg = self.logical
        lschema = self.children[0].output_schema
        rschema = self.children[1].output_schema
        from ..columnar.batch import resolve_speculative as _resolve
        if self.build_right:
            build_batches = [_resolve(b) for b in right_iter]
            stream_iter = left_iter
            build_schema, stream_schema = rschema, lschema
            build_keys = [e.bind(rschema) for e in lg.right_keys]
            stream_keys = [e.bind(lschema) for e in lg.left_keys]
        else:
            build_batches = [_resolve(b) for b in left_iter]
            stream_iter = right_iter
            build_schema, stream_schema = lschema, rschema
            build_keys = [e.bind(lschema) for e in lg.left_keys]
            stream_keys = [e.bind(rschema) for e in lg.right_keys]

        with timed(self.metrics[BUILD_TIME], self):
            # broadcast joins run every stream partition against the SAME
            # build batches: sort the build table once per exec.  The memo
            # retains build_batches itself so the id()s in the key cannot
            # be recycled by a later partition's freshly-allocated batches
            # (a stale id()-only key could silently probe against the
            # wrong build table).
            bb_key = tuple(id(b) for b in build_batches)
            memo = getattr(self, "_build_memo", None)
            if memo is not None and memo["key"] == bb_key:
                build, bkey_cols = memo["build"], memo["bkey_cols"]
            else:
                if build_batches:
                    build = concat_batches(build_batches)
                else:
                    build = ColumnarBatch.empty(build_schema)
                bkey_cols = [ec.eval_as_column(e, build)
                             for e in build_keys]
                self._build_memo = {"key": bb_key,
                                    "batches": build_batches,
                                    "build": build,
                                    "bkey_cols": bkey_cols}

        # what every probe of this partition sorts or searches against
        if build.capacity:
            _trace.count("join.build_rows", build.capacity)
        stream_batches = list(stream_iter)
        if not stream_batches:
            stream_batches = [ColumnarBatch.empty(stream_schema)]

        # unify string key widths across sides per key position
        skey_cols_per_batch = []
        str_words: List[Optional[int]] = []
        for b in stream_batches:
            skey_cols_per_batch.append(
                [ec.eval_as_column(e, b) for e in stream_keys])
        for ki in range(len(build_keys)):
            if bkey_cols and isinstance(bkey_cols[ki], StringColumn):
                w = skern.needed_key_words(bkey_cols[ki], build.num_rows)
                for b, scols in zip(stream_batches, skey_cols_per_batch):
                    w = max(w, skern.needed_key_words(scols[ki], b.num_rows))
                str_words.append(w)
            else:
                str_words.append(None)

        memo = getattr(self, "_build_memo", None)
        if (memo is not None and "bt" in memo and memo["key"] == bb_key
                and memo.get("str_words") == str_words):
            bt = memo["bt"]
        else:
            # non-string keys never need the host count: the canon rank
            # word masks dead rows with the device count, keeping a
            # lazily-counted broadcast build sync-free
            b_nr = build.num_rows if any(w is not None
                                         for w in str_words) \
                else build.rows_dev
            bwords = _key_words(bkey_cols, b_nr, str_words)
            bt = join_k.build(bwords)
            memo = {"key": bb_key,
                    "batches": build_batches,
                    "build": build,
                    "bkey_cols": bkey_cols,
                    "str_words": list(str_words),
                    "bt": bt, "direct": None, "direct_done": False}
            self._build_memo = memo
        # the direct-address table costs ONE host sync to learn the
        # build key range (it sizes the table) — worth it only when the
        # probe side is large enough to amortize the round trip; small
        # streams (dimension-sized post-agg probes) keep the sync-free
        # binary search.  The decision is PER PARTITION (a broadcast
        # join's first small partition must not freeze the strategy for
        # later large ones); once built, the table is memoized.
        stream_cap = sum(b.capacity for b in stream_batches)
        if (not memo["direct_done"] and lg.join_type != "full"
                and stream_cap >= (1 << 19)):
            memo["direct"] = self._prepare_direct(bt, bkey_cols, build)
            memo["direct_done"] = True
        direct = memo["direct"]

        build_matched = np.zeros(build.capacity, dtype=bool) \
            if lg.join_type == "full" else None

        # Superstage path (compile/): sync-free speculative unique-match
        # join — no flush barrier at all; the fit flag rides to the next
        # superstage boundary.  Only the carve pass sets _superstage, and
        # only under a consumer that resolves speculative batches.  Its
        # output keeps the stream's capacity, so a partition holding a
        # stream batch at or above _SIZED_MIN_CAPACITY pays the phase-A
        # barrier below instead and sizes its outputs (decided once per
        # partition: a None from batch k would discard batches 0..k-1).
        if getattr(self, "_superstage", False) and lg.join_type == "inner" \
                and lg.condition is None and build_matched is None \
                and all(w is None for w in str_words) \
                and build.capacity > 0 \
                and max(b.capacity for b in stream_batches) \
                < self._SIZED_MIN_CAPACITY:
            from ..obs import profile
            spec_outs = []
            for sb, skey_cols in zip(stream_batches,
                                     skey_cols_per_batch):
                with timed(self.metrics[JOIN_TIME], self), \
                        profile.dispatch(profile.SITE_SPEC_PROBE):
                    out = self._spec_join_batch(
                        sb, skey_cols, bt, build, direct,
                        stream_keys, str_words)
                if out is None:
                    spec_outs = None
                    break
                spec_outs.append(out)
            if spec_outs is not None:
                _trace.count("join.batches.spec", len(spec_outs))
                for out in spec_outs:
                    yield self._note_output(out)
                return

        kept = []   # a residual join's surviving pairs, a batch each
        # Phase A: probe counts for EVERY stream batch first; the output
        # sizes (total matches) stage into the pending pool so one fused
        # flush covers all of them (columnar/pending.py).  Phase B then
        # expands/gathers with host-known output capacities.
        phase_a = []
        for sb, skey_cols in zip(stream_batches, skey_cols_per_batch):
            with timed(self.metrics[JOIN_TIME], self):
                phase_a.append(self._probe_phase(sb, skey_cols, bt,
                                                 str_words,
                                                 build_matched, direct))
        from ..columnar import pending
        from ..columnar.batch import resolve_speculative
        pending.flush()
        _trace.count("join.batches.sized", len(stream_batches))
        for (sb, skey_cols), pa in zip(
                zip(stream_batches, skey_cols_per_batch), phase_a):
            # this flush is a verification barrier: upstream (the FINAL
            # aggregate) may defer its speculative fit flag to here; the
            # flags resolved in the fused flush above, so checking is
            # free — the rare misfit batch recomputes exactly, and its
            # probe phase re-runs on the exact rows
            checked = resolve_speculative(sb)
            if checked is not sb:
                sb = checked
                skey_cols = [ec.eval_as_column(e, sb)
                             for e in stream_keys]
                with timed(self.metrics[JOIN_TIME], self):
                    pa = self._probe_phase(sb, skey_cols, bt, str_words,
                                           build_matched, direct)
                pending.flush()
            if pa is None:   # legacy eager path (full outer, etc.)
                with timed(self.metrics[JOIN_TIME], self):
                    outs = [self._join_batch(sb, skey_cols, build, bt,
                                             str_words, build_matched, kept)]
            elif lg.condition is not None:
                # generator: each chunk of pairs times itself
                _, _, lo, counts, _, total = pa
                outs = self._residual_batches(sb, build, bt, lo, counts,
                                              int(total), None, kept)
            else:
                # generator: each chunk's expansion times itself
                outs = self._expand_phases(sb, build, bt, *pa)
            for out in outs:
                if out is not None:
                    yield self._note_output(out)

        if lg.join_type == "full" and build is not None:
            out = self._unmatched_build_rows(build, build_matched,
                                             stream_schema)
            if out is not None and out.num_rows > 0:
                yield self._note_output(out)
        if kept:
            # read once the partition is done: by then a later flush of
            # the pool has most likely brought the counts over already
            _trace.count("join.residual.kept", sum(int(k) for k in kept))

    def _note_output(self, out: ColumnarBatch) -> ColumnarBatch:
        self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
        # the slots every later gather over this batch runs over
        _trace.count("join.out_capacity_rows", out.capacity)
        return out

    # -- fused probe/expand (one program each; totals via pending pool) --
    _PROBE_JIT: dict = {}
    _EXPAND_JIT: dict = {}
    _SPEC_JIT: dict = {}
    _DIRECT_JIT: dict = {}

    # max entries in the direct-address probe table (64 MB of i32 HBM)
    _DIRECT_MAX_RANGE = 1 << 24

    # stream-batch capacity from which a superstage join sizes its
    # outputs (probe / flush / expand) rather than probing speculatively
    # at the stream's capacity: where one speculative launch costs the
    # device what the extra flush costs the query (PERF.md section 5)
    _SIZED_MIN_CAPACITY = 1 << 14

    def _prepare_direct(self, bt, bkey_cols, build):
        """Direct-address probe tables for single fixed-width int keys.

        The general probe is a vectorized binary search — ~2*log2(build)
        random 64-bit gathers per probe batch, the dominant join cost on
        TPU.  When the build side has ONE int-family key whose value
        range fits a table, matching becomes two i32 gathers: per key k,
        hist[k - min] = #build rows, excl[k - min] = first position in
        the SORTED build.  Dimension keys are dense ints in practice
        (TPC-DS/mortgage), so this covers the hot joins; wide/multi/string
        keys keep the binary search.  One host sync per build (cached).
        """
        if len(bkey_cols) != 1 or type(bkey_cols[0]) is not Column:
            return None
        dt = bkey_cols[0].dtype
        if not (dt.is_integral or dt in (T.DATE, T.TIMESTAMP) or
                isinstance(dt, T.DecimalType)):
            return None
        import jax
        c = bkey_cols[0]
        w = canon.value_words(c, build.num_rows)[0]

        # both programs are kept by what they close over (nothing, the
        # table's size): a jit built per partition would be traced and
        # looked up in the compile cache again on every warm query
        minmax = TpuHashJoinBase._DIRECT_JIT.get("minmax")
        if minmax is None:
            def _minmax(w, validity, num_rows):
                valid = validity & (jnp.arange(validity.shape[0]) <
                                    num_rows)
                any_v = jnp.any(valid)
                wmin = jnp.where(any_v,
                                 jnp.min(jnp.where(valid, w,
                                                   jnp.uint64(2**64 - 1))),
                                 jnp.uint64(0))
                wmax = jnp.where(any_v,
                                 jnp.max(jnp.where(valid, w,
                                                   jnp.uint64(0))),
                                 jnp.uint64(0))
                nvalid = jnp.sum(valid)
                return wmin, wmax, nvalid
            minmax = TpuHashJoinBase._DIRECT_JIT["minmax"] = \
                _compile_watch.jit(_minmax, "join_direct_minmax")
        wmin, wmax, nvalid = minmax(w, c.validity,
                                    jnp.int32(build.num_rows))
        # one host pull per build table (cached on the exec)
        import numpy as _np
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="join_verify"):
            wmin_h, wmax_h = int(_np.asarray(wmin)), int(_np.asarray(wmax))
            nnull_h = build.num_rows - int(_np.asarray(nvalid))
        rng = wmax_h - wmin_h + 1
        if rng <= 0 or rng > self._DIRECT_MAX_RANGE:
            return None
        tbl = bucket_capacity(rng)

        tables = TpuHashJoinBase._DIRECT_JIT.get(tbl)
        if tables is None:
            def _tables(w, validity, num_rows, wmin, nnull):
                valid = validity & (jnp.arange(validity.shape[0]) <
                                    num_rows)
                idx = jnp.clip((w - wmin).astype(jnp.int32), 0, tbl - 1)
                contrib = jnp.where(valid, idx, tbl)
                hist = jnp.bincount(contrib, length=tbl + 1)[:tbl] \
                    .astype(jnp.int32)
                excl = (prefix_sum(hist) - hist + nnull).astype(jnp.int32)
                return hist, excl
            tables = TpuHashJoinBase._DIRECT_JIT[tbl] = \
                _compile_watch.jit(_tables, "join_direct_tables")
        hist, excl = tables(w, c.validity, jnp.int32(build.num_rows), wmin,
                            jnp.int32(nnull_h))
        return (jnp.uint64(wmin_h), jnp.uint64(wmax_h), hist, excl, tbl)

    def _probe_phase(self, sb, skey_cols, bt, str_words, build_matched,
                     direct=None):
        """Phase A: key eval + match lookup + join-type count surgery as
        ONE jitted program; the total output size stages into the pending
        pool.  The lookup is the direct-address table when available
        (two i32 gathers) else the vectorized binary search.  Returns
        None to use the legacy eager path."""
        import jax
        from ..columnar.batch import LazyCount
        lg = self.logical
        jt = lg.join_type
        if jt == "full" or build_matched is not None:
            return None
        if not all(type(c) is Column for c in skey_cols):
            return None
        # a residual condition decides per candidate pair: the total to
        # size is the pairs, whatever the join type
        residual = lg.condition is not None
        key = ("probe", jt, tuple(c.dtype.name for c in skey_cols),
               sb.capacity, bt.capacity, len(bt.sorted_words),
               self.build_right, direct is not None and direct[4], residual)
        fn = TpuHashJoinBase._PROBE_JIT.get(key)
        if fn is False:
            return None
        outer_stream = ((jt == "left" and self.build_right) or
                        (jt == "right" and not self.build_right))
        if fn is None:
            key_dts = tuple(c.dtype for c in skey_cols)
            tbl = direct[4] if direct is not None else 0

            def _core(bws, dparams, key_arrays, num_rows):
                kcols = [Column(dt, d, v)
                         for dt, (d, v) in zip(key_dts, key_arrays)]
                cap = key_arrays[0][0].shape[0]
                in_range = jnp.arange(cap) < num_rows
                if dparams is not None:
                    wmin, wmax, hist, excl = dparams
                    w = canon.value_words(kcols[0], num_rows)[0]
                    idx = jnp.clip((w - wmin).astype(jnp.int32), 0,
                                   tbl - 1)
                    hit = (w >= wmin) & (w <= wmax) & \
                        kcols[0].validity & in_range
                    counts = jnp.where(hit, jnp.take(hist, idx), 0)
                    lo = jnp.take(excl, idx)
                else:
                    with jax.named_scope("key_words"):
                        swords = canon.batch_key_words(kcols, num_rows)
                    bt2 = join_k.BuildTable(list(bws), None, None)
                    jc = join_k.probe_counts(bt2, swords, num_rows)
                    counts, lo = jc.counts, jc.lo
                if residual:
                    eff = counts
                elif jt in ("semi", "anti"):
                    keep = (counts > 0) if jt == "semi" else \
                        ((counts == 0) & in_range)
                    eff = keep.astype(jnp.int32)
                elif outer_stream:
                    eff = jnp.where((counts == 0) & in_range, 1, counts)
                else:
                    eff = counts
                total = jnp.sum(eff.astype(jnp.int64))
                return lo, counts, eff, total
            fn = _compile_watch.wrap_miss(
                "join_probe", _compile_watch.jit(_core, "join_probe_core"),
                str(key))
            TpuHashJoinBase._PROBE_JIT[key] = fn
        key_arrays = tuple((c.data, c.validity) for c in skey_cols)
        dparams = tuple(direct[:4]) if direct is not None else None
        from ..compile import aot as _aot
        from ..obs import costplane as _costplane
        _aot.note_demand("join_probe", sb.capacity,
                         _costplane.rows_if_resolved(sb))
        try:
            lo, counts, eff, total = fn(tuple(bt.sorted_words), dparams,
                                        key_arrays, sb.rows_dev)
        except Exception:  # noqa: BLE001 - fall back, but loudly
            import logging
            logging.getLogger("spark_rapids_tpu.exec.join").warning(
                "fused probe failed; falling back", exc_info=True)
            TpuHashJoinBase._PROBE_JIT[key] = False
            return None
        return (jt, outer_stream, lo, counts, eff, LazyCount(total))

    def _spec_join_batch(self, sb, skey_cols, bt, build, direct,
                         stream_keys, str_words):
        """Speculative unique-match inner join: probe + compact + ALL
        output gathers as ONE program with a STATIC output capacity (the
        probe capacity), so no host round trip sizes the result.

        Valid when every probe row matches at most one build row — the
        star-schema dimension case.  The match total stays a LazyCount
        and a fit flag (max matches per probe row <= 1) rides the
        speculative redo machinery to the consumer's flush barrier; a
        violating batch (duplicate build keys) recomputes on the exact
        sized path.  Returns None to use the barrier path."""
        import jax
        from ..kernels import basic as bk
        from ..columnar.batch import (LazyCount, SpeculativeResult,
                                      resolve_speculative)
        if not all(type(c) is Column for c in skey_cols):
            return None
        # plain columns gather inside the program; strings gather as lazy
        # views outside it (zero dispatches); nested gathers host-sync,
        # so their presence keeps the exact path
        for c in list(sb.columns) + list(build.columns):
            if not isinstance(c, (Column, StringColumn)):
                return None
        plain_s = [i for i, c in enumerate(sb.columns)
                   if type(c) is Column]
        plain_b = [i for i, c in enumerate(build.columns)
                   if type(c) is Column]
        key = ("spec", tuple(c.dtype.name for c in skey_cols),
               sb.capacity, bt.capacity, len(bt.sorted_words),
               tuple(sb.columns[i].dtype.name for i in plain_s),
               tuple(build.columns[i].dtype.name for i in plain_b),
               tuple(plain_s), tuple(plain_b), self.build_right,
               direct is not None and direct[4])
        fn = TpuHashJoinBase._SPEC_JIT.get(key)
        if fn is False:
            return None
        if fn is None:
            key_dts = tuple(c.dtype for c in skey_cols)
            tbl = direct[4] if direct is not None else 0

            def _core(bws, dparams, key_arrays, num_rows, perm,
                      sdatas, svalids, bdatas, bvalids):
                kcols = [Column(dt, d, v)
                         for dt, (d, v) in zip(key_dts, key_arrays)]
                cap = key_arrays[0][0].shape[0]
                in_range = jnp.arange(cap) < num_rows
                if dparams is not None:
                    wmin, wmax, hist, excl = dparams
                    w = canon.value_words(kcols[0], num_rows)[0]
                    idx = jnp.clip((w - wmin).astype(jnp.int32), 0,
                                   tbl - 1)
                    hit = (w >= wmin) & (w <= wmax) & \
                        kcols[0].validity & in_range
                    counts = jnp.where(hit, jnp.take(hist, idx), 0)
                    lo = jnp.take(excl, idx)
                else:
                    with jax.named_scope("key_words"):
                        swords = canon.batch_key_words(kcols, num_rows)
                    bt2 = join_k.BuildTable(list(bws), None, None)
                    jc = join_k.probe_counts(bt2, swords, num_rows)
                    counts, lo = jc.counts, jc.lo
                eff = jnp.where(in_range, counts, 0)
                fit = (jnp.max(eff) <= 1).astype(jnp.int32)
                p_idx, cnt = bk.filter_compact_indices(eff > 0, num_rows)
                live = jnp.arange(cap) < cnt
                b_pos = jnp.clip(jnp.take(lo, p_idx, mode="clip"), 0,
                                 perm.shape[0] - 1)
                b_idx = jnp.take(perm, b_pos)
                with jax.named_scope("gather_stream"):
                    souts = [(jnp.take(d, p_idx, axis=0, mode="clip"),
                              jnp.take(v, p_idx, axis=0, mode="clip")
                              & live)
                             for d, v in zip(sdatas, svalids)]
                with jax.named_scope("gather_build"):
                    bouts = [(jnp.take(d, b_idx, axis=0, mode="clip"),
                              jnp.take(v, b_idx, axis=0, mode="clip")
                              & live)
                             for d, v in zip(bdatas, bvalids)]
                return souts, bouts, p_idx, b_idx, live, \
                    cnt.astype(jnp.int64), fit
            fn = _compile_watch.wrap_miss(
                "join_spec_probe",
                _compile_watch.jit(_core, "join_spec_probe_core"),
                str(key))
            if len(TpuHashJoinBase._SPEC_JIT) < 4096:
                TpuHashJoinBase._SPEC_JIT[key] = fn
        key_arrays = tuple((c.data, c.validity) for c in skey_cols)
        dparams = tuple(direct[:4]) if direct is not None else None
        from ..compile import aot as _aot
        from ..obs import costplane as _costplane
        _aot.note_demand("join_spec_probe", sb.capacity,
                         _costplane.rows_if_resolved(sb))
        try:
            souts, bouts, p_idx, b_idx, live, cnt, fit = fn(
                tuple(bt.sorted_words), dparams, key_arrays, sb.rows_dev,
                bt.perm,
                tuple(sb.columns[i].data for i in plain_s),
                tuple(sb.columns[i].validity for i in plain_s),
                tuple(build.columns[i].data for i in plain_b),
                tuple(build.columns[i].validity for i in plain_b))
        except Exception:  # noqa: BLE001 - fall back, but loudly
            import logging
            logging.getLogger("spark_rapids_tpu.exec.join").warning(
                "speculative join failed; falling back", exc_info=True)
            TpuHashJoinBase._SPEC_JIT[key] = False
            return None
        out = self._assemble(_with_gathered(sb.columns, souts, p_idx, live),
                             _with_gathered(build.columns, bouts, b_idx,
                                            live),
                             LazyCount(cnt))
        # the probe ran on possibly-speculative input: compose its fits
        # with ours so one failed assumption anywhere redoes the chain
        in_spec = getattr(sb, "_speculative", None)
        fits = (list(in_spec.fits) if in_spec is not None else []) \
            + [LazyCount(fit)]

        def _redo(sb=sb, skey_cols=skey_cols):
            from ..columnar import pending
            from ..obs import profile
            from ..obs.registry import superstage_event
            superstage_event("spec_redo")
            with profile.dispatch(profile.SITE_SPEC_REDO):
                fixed = resolve_speculative(sb)
                kc = skey_cols if fixed is sb else \
                    [ec.eval_as_column(e, fixed) for e in stream_keys]
                with timed(self.metrics[JOIN_TIME], self):
                    pa = self._probe_phase(fixed, kc, bt, str_words,
                                           None, direct)
                pending.flush()
                if pa is None:
                    with timed(self.metrics[JOIN_TIME], self):
                        return self._join_batch(fixed, kc, build, bt,
                                                str_words, None)
                outs = [o for o in
                        self._expand_phases(fixed, build, bt, *pa)
                        if o is not None]
                if not outs:
                    return ColumnarBatch.empty(self.output_schema)
                return outs[0] if len(outs) == 1 \
                    else concat_batches(outs)

        out._speculative = SpeculativeResult(fits, _redo)
        return out

    def _expand_phases(self, sb, build, bt, jt, outer_stream, lo, counts,
                       eff, total_lazy, by_rows: bool = False):
        """Bounded incremental gather (JoinGatherer.scala:1 role).

        A skewed key can explode one (stream batch, build) pair far past
        device memory; when the total exceeds the chunk budget, expand in
        probe-row ranges — splitting even a single probe row's matches
        across chunks by advancing its ``lo`` offset — so no single
        output allocation exceeds the budget.  Chunk k holds the pairs
        ``[k * budget, (k + 1) * budget)`` in row order: its rows' share
        is computed on the device (``join_k.join_pairs_window``), and its
        size is known from the total alone.  Yields chunks lazily so
        downstream can consume (or spill) chunk k before chunk k+1's
        gather allocates.  ``by_rows``: see ``_expand_phase``."""
        from ..config import get_active, JOIN_GATHER_CHUNK_ROWS
        total = int(total_lazy)
        limit = int(get_active().get(JOIN_GATHER_CHUNK_ROWS))
        if total <= limit or jt in ("semi", "anti"):
            with timed(self.metrics[JOIN_TIME], self):
                out = self._expand_phase(sb, build, bt, jt, outer_stream,
                                         lo, counts, eff, total, by_rows)
            if out is not None:
                yield out
            return
        for base in range(0, total, limit):
            with timed(self.metrics[JOIN_TIME], self):
                chunk_lo, chunk_eff = join_k.join_pairs_window(
                    lo, eff, np.int64(base), limit)
                out = self._expand_phase(
                    sb, build, bt, jt, outer_stream, chunk_lo, counts,
                    chunk_eff, min(limit, total - base), by_rows)
            if out is not None:
                yield out

    def _expand_phase(self, sb, build, bt, jt, outer_stream, lo, counts,
                      eff, total_lazy,
                      by_rows: bool = False) -> Optional[ColumnarBatch]:
        """Phase B: expansion + all output gathers as ONE jitted program
        with a host-known output capacity.  ``by_rows`` (a residual
        join's survivors, tens of millions of rows): each side's rows
        move by one row gather instead (``_expand_eager``), where the
        program's two takes a column cost q72's 47.6M survivors of
        twelve columns 20.1 s a pass on the chip (PERF.md section 6)."""
        import jax
        total = int(total_lazy)
        if total == 0:
            return ColumnarBatch.empty(self.output_schema)
        out_cap = bucket_capacity(total)
        if jt in ("semi", "anti"):
            out = sb.slice_by_mask(eff > 0, total) if hasattr(
                sb, "slice_by_mask") else None
            if out is None:
                from ..kernels import basic as bk
                idx, _ = bk.filter_compact_indices(eff > 0, sb.rows_dev)
                idx = idx[:out_cap] if out_cap <= sb.capacity \
                    else jnp.pad(idx, (0, out_cap - sb.capacity))[:out_cap]
                out = ColumnarBatch(
                    self.output_schema,
                    gather_columns(sb.columns, idx,
                                   jnp.arange(out_cap) < total), total)
            return out
        if by_rows or not all(type(c) is Column for c in sb.columns) or \
                not all(type(c) is Column for c in build.columns):
            return self._expand_eager(sb, build, bt, outer_stream, lo,
                                      counts, eff, total)
        key = ("expand", out_cap, outer_stream,
               tuple(f.dtype.name for f in sb.schema),
               tuple(f.dtype.name for f in build.schema),
               sb.capacity, build.capacity)
        fn = TpuHashJoinBase._EXPAND_JIT.get(key)
        if fn is None:
            def _core(lo, counts, eff, perm, sdatas, svalids, bdatas,
                      bvalids):
                p_idx, b_idx, live, _ = join_k.join_expand_matches(
                    lo, eff, perm, out_cap)
                with jax.named_scope("gather_stream"):
                    souts = [(jnp.take(d, p_idx, axis=0, mode="clip"),
                              jnp.take(v, p_idx, axis=0, mode="clip")
                              & live)
                             for d, v in zip(sdatas, svalids)]
                bvalid_mask = live
                if outer_stream:
                    matched = jnp.take(counts > 0, jnp.clip(
                        p_idx, 0, counts.shape[0] - 1))
                    bvalid_mask = live & matched
                with jax.named_scope("gather_build"):
                    bouts = [(jnp.take(d, b_idx, axis=0, mode="clip"),
                              jnp.take(v, b_idx, axis=0, mode="clip") &
                              bvalid_mask)
                             for d, v in zip(bdatas, bvalids)]
                return souts, bouts
            fn = _compile_watch.wrap_miss(
                "join_expand",
                _compile_watch.jit(_core, "join_expand_core"), str(key))
            if len(TpuHashJoinBase._EXPAND_JIT) < 4096:
                TpuHashJoinBase._EXPAND_JIT[key] = fn
        souts, bouts = fn(
            lo, counts, eff, bt.perm,
            tuple(c.data for c in sb.columns),
            tuple(c.validity for c in sb.columns),
            tuple(c.data for c in build.columns),
            tuple(c.validity for c in build.columns))
        scols = [Column(c.dtype, d, v)
                 for c, (d, v) in zip(sb.columns, souts)]
        bcols = [Column(c.dtype, d, v)
                 for c, (d, v) in zip(build.columns, bouts)]
        return self._assemble(scols, bcols, total)

    def _expand_eager(self, sb, build, bt, outer_stream, lo, counts, eff,
                      total):
        """Non-plain columns (strings/nested), or ``by_rows``: the
        expansion, then each side's columns in one eager gather
        (``gather_columns``)."""
        out_cap = bucket_capacity(total)
        p_idx, b_idx, live, _ = join_k.join_expand_matches(lo, eff, bt.perm,
                                                      out_cap)
        bmask = live
        if outer_stream:
            bmask = live & jnp.take(counts > 0,
                                    jnp.clip(p_idx, 0, sb.capacity - 1))
        return self._assemble(gather_columns(sb.columns, p_idx, live),
                              gather_columns(build.columns, b_idx, bmask),
                              total)

    # ------------------------------------------------------------------
    def _join_batch(self, sb: ColumnarBatch, skey_cols, build, bt,
                    str_words, build_matched,
                    kept=None) -> Optional[ColumnarBatch]:
        lg = self.logical
        jt = lg.join_type
        swords = _key_words(skey_cols, sb.num_rows, str_words)
        jc = join_k.probe_counts(bt, swords, sb.num_rows)

        if lg.condition is not None:
            # residual restricts which PAIRS match; outer/semi/anti row
            # semantics are decided on the surviving pairs (a plain
            # post-filter would wrongly drop null-extended outer rows)
            parts = list(self._residual_batches(
                sb, build, bt, jc.lo, jc.counts,
                int(join_k.total_matches(jc.counts)), build_matched,
                [] if kept is None else kept))
            if not parts:
                return ColumnarBatch.empty(self.output_schema)
            return parts[0] if len(parts) == 1 else concat_batches(parts)

        if jt in ("semi", "anti"):
            from ..kernels import basic as bk
            in_range = jnp.arange(sb.capacity) < sb.num_rows
            keep = (jc.counts > 0) if jt == "semi" else \
                ((jc.counts == 0) & in_range)
            idx, cnt = bk.filter_compact_indices(keep, sb.num_rows)
            n = _host_int(cnt)
            return ColumnarBatch(
                self.output_schema,
                gather_columns(sb.columns, idx,
                               jnp.arange(idx.shape[0]) < n), n)

        outer_stream = ((jt == "left" and self.build_right) or
                        (jt == "right" and not self.build_right) or
                        jt == "full")
        counts = jc.counts
        if outer_stream:
            in_range = jnp.arange(sb.capacity) < sb.num_rows
            unmatched = (counts == 0) & in_range
            counts = jnp.where(unmatched, 1, counts)

        total = join_k.total_matches(counts)
        if total == 0:
            return ColumnarBatch.empty(self.output_schema)
        out_cap = bucket_capacity(total)
        p_idx, b_idx, live, _ = join_k.join_expand_matches(
            jc.lo, counts, bt.perm, out_cap)

        bmask = live
        if outer_stream:
            # rows that came from the unmatched path carry null build side
            bmask = live & jnp.take(jc.counts > 0,
                                    jnp.clip(p_idx, 0, sb.capacity - 1))
        if build_matched is not None:
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="join_verify"):
                matched_idx = np.asarray(jnp.where(
                    live & jnp.take(jc.counts > 0,
                                    jnp.clip(p_idx, 0, sb.capacity - 1)),
                    b_idx, 0))
                flags = np.zeros(build.capacity, dtype=bool)
                lv = np.asarray(live)
                mi = np.asarray(matched_idx)
                ok = np.asarray(jnp.take(jc.counts > 0,
                                         jnp.clip(p_idx, 0,
                                                  sb.capacity - 1)))
            flags[mi[lv & ok]] = True
            build_matched |= flags
        return self._assemble(gather_columns(sb.columns, p_idx, live),
                              gather_columns(build.columns, b_idx, bmask),
                              total)

    _RESIDUAL_JIT: dict = {}

    def _residual_plan(self):
        """-> (the condition bound to the columns it reads, in order,
        its signature, their ordinals on the stream side, on the build
        side, their schema), once an exec.  The signature is taken before
        any evaluation: evaluating memoizes on the nodes (``EqualTo``'s
        promoted sides), which a later signature would read as another
        program."""
        plan = getattr(self, "_residual_memo", None)
        if plan is not None:
            return plan
        lschema = self.children[0].output_schema
        rschema = self.children[1].output_schema
        fields = [Field(f.name, f.dtype, True)
                  for f in list(lschema) + list(rschema)]
        bound = self.logical.condition.bind(Schema(fields))
        read = sorted(_bound_ordinals(bound))
        nleft = len(lschema)
        left = [i for i in read if i < nleft]
        right = [i - nleft for i in read if i >= nleft]
        s_ords, b_ords = (left, right) if self.build_right else (right, left)
        # the gathered columns: the stream side's first, then the build's
        order = ([i for i in read if (i < nleft) == self.build_right] +
                 [i for i in read if (i < nleft) != self.build_right])
        cond = _rebind(bound, {o: k for k, o in enumerate(order)})
        from .fused import expr_signature
        plan = (cond, expr_signature(cond), s_ords, b_ords,
                Schema([fields[o] for o in order]))
        self._residual_memo = plan
        return plan

    def _range_plan(self):
        """How an inner join whose condition is ONE comparison of a
        build-side integer, date or timestamp expression with a
        stream-side one decides its pairs by bounds (``_range_batches``),
        once an exec, from the bound condition alone: -> (the build
        side's expression and schema, the stream side's, whether the
        search is for the first build value ``> s`` rather than ``>=
        s``, whether the survivors are the values before it), or None
        (another join type or condition: pair by pair)."""
        if not hasattr(self, "_range_memo"):
            self._range_memo = self._plan_range()
        return self._range_memo

    def _plan_range(self):
        from ..expr import predicates as pr
        cond, sig, s_ords, _, schema = self._residual_plan()
        # the operator, and the one it reads as with its sides swapped
        ops = {pr.LessThan: ("<", ">"), pr.LessThanOrEqual: ("<=", ">="),
               pr.GreaterThan: (">", "<"),
               pr.GreaterThanOrEqual: (">=", "<=")}.get(type(cond))
        if self.logical.join_type != "inner" or ops is None or sig is None:
            return None
        ns = len(s_ords)

        def ordered(dt):
            return dt.is_integral or dt in (T.DATE, T.TIMESTAMP)
        if not all(ordered(f.dtype) for f in schema):
            return None
        left, right = pr.promote_comparison_sides(*cond.children)
        try:
            if left.dtype() != right.dtype() or not ordered(left.dtype()):
                return None
        except (ValueError, NotImplementedError):
            return None
        sides = [{o < ns for o in _bound_ordinals(e)} for e in (left, right)]
        if sides == [{False}, {True}]:
            b, s, op = left, right, ops[0]
        elif sides == [{True}, {False}]:
            b, s, op = right, left, ops[1]
        else:
            return None
        fields = list(schema)
        b = _rebind(b, {ns + k: k for k in range(len(fields) - ns)})
        return (b, Schema(fields[ns:]), s, Schema(fields[:ns]),
                op in ("<=", ">"), op in ("<", "<="))

    def _range_sorted(self, build, bt):
        """The build side's comparison value in ``bt``'s order, each
        equal-key run sorted by it (``join_k.sort_within_runs``): one
        program a build, kept in the build's memo beside ``bt``, which
        every other user of the build reads unchanged."""
        memo = getattr(self, "_build_memo", None) or {}
        if memo.get("bt") is not bt:
            memo = {}
        if "residual_range" in memo:
            return memo["residual_range"]
        b_expr, b_schema, *_ = self._range_plan()
        _, sig, _, b_ords, _ = self._residual_plan()
        bcols = [build.columns[i] for i in b_ords]
        key = ("residual_range_sorted", sig, build.capacity,
               len(bt.sorted_words), tuple(c.dtype.name for c in bcols))
        fn = TpuHashJoinBase._RESIDUAL_JIT.get(key)
        if fn is None:
            cap = build.capacity

            def _sorted(words, perm, barrs):
                cols = [Column(f.dtype, d, v)
                        for f, (d, v) in zip(b_schema, barrs)]
                val = ec.eval_as_column(b_expr,
                                        ColumnarBatch(b_schema, cols, cap))
                return join_k.sort_within_runs(list(words), perm, val.data,
                                               val.validity)
            fn = _compile_watch.wrap_miss(
                "join_residual",
                _compile_watch.jit(_sorted, "join_residual_run_sort"),
                str(key))
            if len(TpuHashJoinBase._RESIDUAL_JIT) < 4096:
                TpuHashJoinBase._RESIDUAL_JIT[key] = fn
        memo["residual_range"] = fn(
            tuple(bt.sorted_words), bt.perm,
            tuple((c.data, c.validity) for c in bcols))
        return memo["residual_range"]

    def _range_batches(self, sb, build, bt, lo, counts, kept):
        """An inner join's pairs decided by bounds: the build's runs
        sorted by the comparison's build value (``_range_sorted``), each
        stream row's survivors are one sub-range of its run, found by a
        binary search in ONE program a stream batch
        (``join_residual_range``), whose total is the batch's one read.
        The survivors alone are then expanded, through the reordered
        ``perm``, by the equi join's own bounded chunks
        (``_expand_phases``), and each side's rows gathered once: no
        failing pair is ever formed."""
        from ..columnar.batch import LazyCount
        _, _, s_expr, s_schema, strict, prefix = self._range_plan()
        _, sig, s_ords, _, _ = self._residual_plan()
        perm_r, vals_r, nvalid, rounds = self._range_sorted(build, bt)
        scols = [sb.columns[i] for i in s_ords]
        key = ("residual_range", sig, sb.capacity, perm_r.shape[0],
               tuple(c.dtype.name for c in scols))
        fn = TpuHashJoinBase._RESIDUAL_JIT.get(key)
        if fn is None:
            cap = sb.capacity

            def _range(lo, counts, sarrs, vals_r, nvalid, rounds):
                cols = [Column(f.dtype, d, v)
                        for f, (d, v) in zip(s_schema, sarrs)]
                sv = ec.eval_as_column(s_expr,
                                       ColumnarBatch(s_schema, cols, cap))
                lo2, n2 = join_k.run_bounds(
                    lo, counts, vals_r, nvalid, rounds,
                    sv.data.astype(vals_r.dtype), sv.validity, strict,
                    prefix)
                return lo2, n2, jnp.sum(n2.astype(jnp.int64))
            fn = _compile_watch.wrap_miss(
                "join_residual",
                _compile_watch.jit(_range, "join_residual_range"), str(key))
            if len(TpuHashJoinBase._RESIDUAL_JIT) < 4096:
                TpuHashJoinBase._RESIDUAL_JIT[key] = fn
        with timed(self.metrics[JOIN_TIME], self):
            _trace.count("join.residual.range")
            _trace.count("join.residual.chunks")
            lo2, n2, total = fn(lo, counts,
                                tuple((c.data, c.validity) for c in scols),
                                vals_r, nvalid, rounds)
        kept.append(LazyCount(total))
        yield from self._expand_phases(
            sb, build, join_k.BuildTable(bt.sorted_words, perm_r,
                                         bt.capacity),
            "inner", False, lo2, counts, n2, kept[-1], by_rows=True)

    def _residual_keep(self, sb, build, bt, lo, counts, out_cap: int,
                       pairs: bool):
        """ONE program over the candidate pairs: expand (lo, counts),
        gather the columns the condition reads and nothing else, evaluate
        it, and give each stream row whether one of its pairs survived
        (its pairs are a run of the output rows: a running sum of the
        survivors read at the run's two ends).  -> (surv, kept pairs, and
        with ``pairs`` the survivors' flags and both gather maps)."""
        import jax
        cond, sig, s_ords, b_ords, schema = self._residual_plan()
        scols = [sb.columns[i] for i in s_ords]
        bcols = [build.columns[i] for i in b_ords]
        n_read = len(scols) + len(bcols)

        def _core(lo, counts, perm, sarrs, barrs, scols=None, bcols=None):
            p_idx, b_idx, live, _ = join_k.join_expand_matches(
                lo, counts, perm, out_cap)
            if scols is None:
                with jax.named_scope("gather_condition"):
                    cols = [Column(f.dtype, jnp.take(d, i, mode="clip"),
                                   jnp.take(v, i, mode="clip") & live)
                            for f, (d, v), i in zip(
                                schema, sarrs + barrs,
                                [p_idx] * len(sarrs) + [b_idx] * len(barrs))]
            else:
                cols = [c.gather(p_idx, live=live) for c in scols] + \
                    [c.gather(b_idx, live=live) for c in bcols]
            pred = ec.eval_as_column(cond, ColumnarBatch(schema, cols,
                                                         out_cap))
            keep = pred.data.astype(bool) & pred.validity & live
            kc = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  prefix_sum(keep.astype(jnp.int32))])
            incl = prefix_sum(counts.astype(jnp.int32))
            surv = jnp.take(kc, incl) > jnp.take(kc, incl - counts)
            if pairs:
                return surv, kc[-1], keep, p_idx, b_idx
            return surv, kc[-1]

        if sig is None or not all(type(c) is Column
                                  for c in scols + bcols):
            # strings or nested columns size their buffers on the host
            # (and an opaque condition shares no program): the same
            # steps, eagerly, on the condition's columns alone
            return _core(lo, counts, bt.perm, (), (), scols, bcols)
        key = ("residual", sig, pairs, out_cap, sb.capacity,
               build.capacity, tuple(f.dtype.name for f in schema),
               len(scols))
        fn = TpuHashJoinBase._RESIDUAL_JIT.get(key)
        if fn is None:
            prog = _compile_watch.jit(_core, "join_residual_core")
            # a launch's lanes: the indices it gathers, out_cap a column
            prog.lanes = lambda *a, _n=out_cap * n_read, **k: _n
            fn = _compile_watch.wrap_miss("join_residual", prog, str(key))
            if len(TpuHashJoinBase._RESIDUAL_JIT) < 4096:
                TpuHashJoinBase._RESIDUAL_JIT[key] = fn
        return fn(lo, counts, bt.perm,
                  tuple((c.data, c.validity) for c in scols),
                  tuple((c.data, c.validity) for c in bcols))

    @staticmethod
    def _chunk_windows(counts_np, cap: int):
        """The chunks of ``cap`` candidate pairs of a stream batch past
        the budget, on the host from its match counts: for each, the
        pair it starts at (``base``), the window of stream rows that
        holds its pairs (first row, rows) and the first pair of the
        window's first row less ``base``.  A window is a bucket of rows,
        at least ``cap / 64`` (fewer programs; the rows cost a chunk a
        64th of its pairs' work at most) and at most the batch's
        capacity, placed to end inside the batch."""
        c = counts_np.astype(np.int64)
        incl = np.cumsum(c)
        start = incl - c
        n = c.shape[0]
        total = int(incl[-1]) if n else 0
        for base in range(0, total, cap):
            r0 = int(np.searchsorted(incl, base, side="right"))
            r1 = int(np.searchsorted(start, base + cap, side="left"))
            rows = min(bucket_capacity(max(r1 - r0, cap >> 6, 1)), n)
            first = min(r0, n - rows)
            yield base, first, rows, int(start[first]) - base

    def _residual_sorted(self, build, bt):
        """The build side's condition columns (data and validity) as one
        ``pack_rows`` matrix in ``bt``'s sorted order: one program a
        build, kept beside ``bt`` in the build's memo for its stream
        batches.  A chunk's pairs then read their build values by their
        sorted positions, with no gather through ``perm``."""
        from ..kernels.gather import pack_rows
        memo = getattr(self, "_build_memo", None) or {}
        if memo.get("bt") is not bt:
            memo = {}
        if "residual_sorted" in memo:
            return memo["residual_sorted"]
        _, _, _, b_ords, _ = self._residual_plan()
        arrs = tuple(a for i in b_ords for a in (build.columns[i].data,
                                                 build.columns[i].validity))
        if not arrs:
            return None
        key = ("residual_sorted", tuple(a.dtype.name for a in arrs),
               build.capacity)
        fn = TpuHashJoinBase._RESIDUAL_JIT.get(key)
        if fn is None:
            def _sorted(perm, arrs):
                matrix, _ = pack_rows(list(arrs))
                return jnp.take(matrix, perm, axis=0)
            fn = _compile_watch.wrap_miss(
                "join_residual_sorted",
                _compile_watch.jit(_sorted, "join_residual_sorted"),
                str(key))
            if len(TpuHashJoinBase._RESIDUAL_JIT) < 4096:
                TpuHashJoinBase._RESIDUAL_JIT[key] = fn
        memo["residual_sorted"] = fn(bt.perm, arrs)
        return memo["residual_sorted"]

    def _residual_chunk(self, sb, build, bt, lo, counts, cap: int,
                        window, surv_in, pairs: bool):
        """ONE program for one chunk of ``cap`` candidate pairs of a
        stream batch past the budget (``window``: a ``_chunk_windows``
        entry).  The row work runs over the window's rows alone.  Each
        pair slot takes its row, its sorted build position and its stream
        row's condition values from running sums of steps scattered at
        the rows' first slots (``join_expand_matches``'s method; every
        value a 32-bit bit pattern, so the sums are exact by
        wrap-around), and its build values by one row gather of
        ``_residual_sorted``'s matrix: one index a pair where a gather of
        each column (data and validity) by both maps and of ``perm`` took
        five.  ORs the window's survival flags into ``surv_in``.  ->
        (surv, kept pairs, and with ``pairs`` the slots survivors first,
        each slot's stream row and sorted build position)."""
        import jax
        from jax import lax
        from ..kernels.basic import rows_flagged_first
        from ..kernels.gather import pack_rows
        cond, sig, s_ords, b_ords, schema = self._residual_plan()
        scols = [sb.columns[i] for i in s_ords]
        bcols = [build.columns[i] for i in b_ords]
        plain = sig is not None and all(type(c) is Column
                                        for c in scols + bcols)
        _, first, rows, off0 = window

        def _core(lo, counts, perm, sarrs, barrs, bsorted, first, off0,
                  surv_in):
            def rows_of(a):
                return lax.dynamic_slice(a, (first,), (rows,))
            c = rows_of(counts).astype(jnp.int64)
            incl = off0 + prefix_sum(c)          # pair numbers less base
            start = incl - c
            n_here = jnp.maximum(jnp.minimum(incl, cap) -
                                 jnp.maximum(start, 0), 0).astype(jnp.int32)
            # a row's first slot; rows past the chunk fall off the scatter
            at = jnp.clip(start, 0, cap).astype(jnp.int32)
            lo_w = rows_of(lo).astype(jnp.int32) + \
                jnp.maximum(-start, 0).astype(jnp.int32)

            def spread(v):
                """Each slot its row's ``v`` (uint32): the steps between
                rows telescope; rows sharing a slot add theirs."""
                step = v - jnp.concatenate([jnp.zeros(1, v.dtype), v[:-1]])
                return prefix_sum(jnp.zeros(cap, v.dtype).at[at].add(
                    step, indices_are_sorted=True, mode="drop"))
            t = jnp.arange(cap, dtype=jnp.int32)
            live = t < jnp.sum(n_here)
            row = jnp.clip(prefix_sum(jnp.zeros(cap, jnp.int32).at[at].add(
                1, indices_are_sorted=True, mode="drop")) - 1, 0, rows - 1)
            shift = lax.bitcast_convert_type(lo_w - at, jnp.uint32)
            pos = jnp.clip(t + lax.bitcast_convert_type(spread(shift),
                                                        jnp.int32),
                           0, perm.shape[0] - 1)
            if plain:
                win = [rows_of(a) for d_v in sarrs for a in d_v]
                smat, s_unpack = pack_rows(win)
                got_s = {} if smat is None else s_unpack(jnp.stack(
                    [spread(smat[:, k]) for k in range(smat.shape[1])], 1))
                flat_b = [a for d_v in barrs for a in d_v]
                _, b_unpack = pack_rows(flat_b)
                with jax.named_scope("gather_condition"):
                    got_b = {} if bsorted is None else \
                        b_unpack(jnp.take(bsorted, pos, axis=0))
                vals = [got_s[id(a)][1] for a in win] + \
                    [got_b[id(a)][1] for a in flat_b]
                cols = [Column(f.dtype, vals[2 * k], vals[2 * k + 1] & live)
                        for k, f in enumerate(schema)]
            else:
                b_idx = jnp.take(perm, pos)
                cols = [c.gather(first + row, live=live) for c in scols] + \
                    [c.gather(b_idx, live=live) for c in bcols]
            pred = ec.eval_as_column(cond, ColumnarBatch(schema, cols, cap))
            keep = pred.data.astype(bool) & pred.validity & live
            kc = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  prefix_sum(keep.astype(jnp.int32))])
            surv_w = jnp.take(kc, at + n_here) > jnp.take(kc, at)
            surv = lax.dynamic_update_slice(
                surv_in, rows_of(surv_in) | surv_w, (first,))
            if pairs:
                return surv, kc[-1], rows_flagged_first(keep), \
                    first + row, pos
            return surv, kc[-1]

        if not plain:
            return _core(lo, counts, bt.perm, (), (), None, first,
                         np.int64(off0), surv_in)
        key = ("residual_chunk", sig, pairs, cap, rows, sb.capacity,
               build.capacity, tuple(f.dtype.name for f in schema),
               len(scols))
        fn = TpuHashJoinBase._RESIDUAL_JIT.get(key)
        if fn is None:
            prog = _compile_watch.jit(_core, "join_residual_chunk")
            # a launch's lanes: a pair's row gather, a window row's steps
            prog.lanes = lambda *a, _n=cap + rows * (2 + len(scols)), \
                **k: _n
            fn = _compile_watch.wrap_miss("join_residual", prog, str(key))
            if len(TpuHashJoinBase._RESIDUAL_JIT) < 4096:
                TpuHashJoinBase._RESIDUAL_JIT[key] = fn
        return fn(lo, counts, bt.perm,
                  tuple((c.data, c.validity) for c in scols),
                  tuple((c.data, c.validity) for c in bcols),
                  self._residual_sorted(build, bt), np.int32(first),
                  np.int64(off0), surv_in)

    def _residual_pair_bytes(self, sb, build) -> int:
        """Bytes a residual program must touch once for one candidate
        pair, from dtypes alone: each condition column's data and
        validity on both sides, the pair's two int32 gather maps and its
        survivor flag."""
        _, _, s_ords, b_ords, _ = self._residual_plan()
        cols = [sb.columns[i] for i in s_ords] + \
            [build.columns[i] for i in b_ords]
        per_row = 0
        for c in cols:
            # a string column by its byte buffer and offsets, a row's share
            arrs = [c.data, c.validity, getattr(c, "offsets", None)]
            per_row += sum(a.size * a.dtype.itemsize for a in arrs
                           if a is not None) // max(c.capacity, 1)
        return per_row + 2 * 4 + 1

    def _residual_batches(self, sb, build, bt, lo, counts, total,
                          build_matched, kept):
        """Join with a residual (non-equi) condition: decide every
        candidate pair (``lo``, ``counts``: ``total`` of them), then
        derive the join type's rows from the survivors.  An inner join
        whose condition is one comparison of a build value with a stream
        value (``_range_plan``) decides them by bounds instead
        (``_range_batches``): its survivors alone are formed.  Past
        ``join.gather.chunkRows`` pairs the decisions run in chunks of
        that many (``_residual_chunk``), each its own launch, and a
        stream row's survival is the OR over the chunks: no allocation
        grows with the batch's pairs.  Yields each launch's surviving
        pairs as a batch of their own (a chunk's once the next chunk is
        launched, so the device is not idle while the host reads its
        count), then (outer, semi and anti joins) the stream rows the
        survival flags pick.  Semi and anti joins compact the stream
        batch by the flags and never gather a pair's other columns, nor
        pull a count to the host.  ``kept`` gets the survivors' counts,
        still on the device."""
        from ..columnar.batch import LazyCount
        from ..config import get_active, JOIN_GATHER_CHUNK_ROWS
        from ..kernels import basic as bk
        jt = self.logical.join_type
        total = int(total)
        _trace.count("join.residual.pairs", total)
        if total:
            _trace.count("join.residual.bytes",
                         total * self._residual_pair_bytes(sb, build))
            if self._range_plan() is not None:
                # one comparison of a build value with a stream value:
                # by bounds, an inner join's survivors alone
                yield from self._range_batches(sb, build, bt, lo, counts,
                                               kept)
                return
        pairs = jt not in ("semi", "anti")
        in_range = jnp.arange(sb.capacity) < sb.rows_dev
        surv = jnp.zeros(sb.capacity, dtype=bool)
        limit = max(int(get_active().get(JOIN_GATHER_CHUNK_ROWS)), 1)
        if 0 < total <= limit:
            # under the budget: one launch at today's capacity
            with timed(self.metrics[JOIN_TIME], self):
                _trace.count("join.residual.chunks")
                got = self._residual_keep(sb, build, bt, lo, counts,
                                          bucket_capacity(total), pairs)
                surv = got[0]
                kept.append(LazyCount(got[1]))
                out = self._surviving_pairs(sb, build, got, build_matched,
                                            total) if pairs else None
            if out is not None:
                yield out
        elif total:
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="join_verify"):
                counts_np = np.asarray(counts)
            ahead = None
            for window in self._chunk_windows(counts_np, limit):
                with timed(self.metrics[JOIN_TIME], self):
                    _trace.count("join.residual.chunks")
                    got = self._residual_chunk(sb, build, bt, lo, counts,
                                               limit, window, surv, pairs)
                    surv = got[0]
                    kept.append(LazyCount(got[1]))
                    out = self._chunk_pairs(sb, build, bt, ahead,
                                            build_matched) \
                        if pairs and ahead is not None else None
                ahead = got
                if out is not None:
                    yield out
            if pairs:
                with timed(self.metrics[JOIN_TIME], self):
                    out = self._chunk_pairs(sb, build, bt, ahead,
                                            build_matched)
                if out is not None:
                    yield out

        with timed(self.metrics[JOIN_TIME], self):
            out = None
            if not pairs:
                sel = surv if jt == "semi" else (~surv & in_range)
                idx, cnt = bk.filter_compact_indices(sel, sb.rows_dev)
                mask = jnp.arange(sb.capacity) < cnt
                out = ColumnarBatch(
                    self.output_schema,
                    gather_columns(sb.columns, idx, mask, unique=True),
                    LazyCount(cnt))
            elif ((jt == "left" and self.build_right) or
                  (jt == "right" and not self.build_right) or
                  jt == "full"):
                uidx, ucnt = bk.filter_compact_indices(~surv & in_range,
                                                       sb.rows_dev)
                n_un = _host_int(ucnt)
                if n_un:
                    su_cols = gather_columns(
                        sb.columns, uidx, jnp.arange(uidx.shape[0]) < n_un)
                    nulls = [_null_column(f.dtype, uidx.shape[0])
                             for f in build.schema]
                    out = self._assemble(su_cols, nulls, n_un)
        if out is not None:
            yield out

    def _surviving_pairs(self, sb, build, got, build_matched,
                         total: int) -> Optional[ColumnarBatch]:
        """The pairs of one launch under the budget whose condition
        held, each output column gathered once."""
        from ..kernels import basic as bk
        _, _, keep, p_idx, b_idx = got
        if build_matched is not None:
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="join_verify"):
                midx = np.asarray(jnp.where(keep, b_idx, 0))
                keep_np = np.asarray(keep)
            build_matched[midx[keep_np]] = True
        pidx2, pcnt = bk.filter_compact_indices(keep, total)
        n_pairs = _host_int(pcnt)
        if not n_pairs:
            return None
        pmask = jnp.arange(pidx2.shape[0]) < n_pairs
        return self._assemble(
            gather_columns(sb.columns, jnp.take(p_idx, pidx2), pmask),
            gather_columns(build.columns, jnp.take(b_idx, pidx2), pmask),
            n_pairs)

    _CHUNK_PAIRS_JIT: dict = {}

    def _chunk_pairs(self, sb, build, bt, got,
                     build_matched) -> Optional[ColumnarBatch]:
        """A chunk's surviving pairs (``_residual_chunk``'s slots,
        survivors first), cut to their bucket: one small program maps
        them to stream rows and build rows, then each side's columns
        move in one gather."""
        _, kept, order, row, pos = got
        n_pairs = _host_int(kept)
        if not n_pairs:
            return None
        n_slots = min(bucket_capacity(n_pairs), order.shape[0])
        fn = TpuHashJoinBase._CHUNK_PAIRS_JIT.get(n_slots)
        if fn is None:
            def _maps(order, row, pos, perm):
                sel = order[:n_slots]
                return jnp.take(row, sel), jnp.take(perm, jnp.take(pos, sel))
            fn = TpuHashJoinBase._CHUNK_PAIRS_JIT[n_slots] = \
                _compile_watch.jit(_maps, "join_residual_pairs")
        p_idx, b_idx = fn(order, row, pos, bt.perm)
        if build_matched is not None:
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="join_verify"):
                build_matched[np.asarray(b_idx)[:n_pairs]] = True
        pmask = jnp.arange(n_slots) < n_pairs
        return self._assemble(gather_columns(sb.columns, p_idx, pmask),
                              gather_columns(build.columns, b_idx, pmask),
                              n_pairs)

    def _assemble(self, stream_cols, build_cols, total) -> ColumnarBatch:
        if self.build_right:
            cols = stream_cols + build_cols
        else:
            cols = build_cols + stream_cols
        return ColumnarBatch(self.output_schema, cols, total)

    def _unmatched_build_rows(self, build, build_matched,
                              stream_schema) -> Optional[ColumnarBatch]:
        from ..kernels import basic as bk
        in_range = np.arange(build.capacity) < build.num_rows
        keep = jnp.asarray(~build_matched & in_range)
        idx, cnt = bk.filter_compact_indices(keep, build.num_rows)
        n = _host_int(cnt)
        if n == 0:
            return None
        bcols = gather_columns(build.columns, idx,
                               jnp.arange(idx.shape[0]) < n)
        scols = [_null_column(f.dtype, idx.shape[0])
                 for f in stream_schema]
        return self._assemble(scols, bcols, n)

    def execute(self):
        lparts = self.children[0].execute()
        rparts = self.children[1].execute()
        assert len(lparts) == len(rparts), \
            f"join partition mismatch {len(lparts)} vs {len(rparts)}"
        return [self._run_partition(lp, rp)
                for lp, rp in zip(lparts, rparts)]


class TpuShuffledHashJoin(TpuHashJoinBase):
    """Both sides hash-partitioned by key (planner inserts exchanges).

    Reference: GpuShuffledHashJoinBase.scala:28."""


class TpuBroadcastHashJoin(TpuHashJoinBase):
    """Build side broadcast (single concat batch replicated to every

    stream partition).  Reference: GpuBroadcastHashJoinExec."""

    def execute(self):
        # broadcast side: materialize once, replicate per stream partition
        if self.build_right:
            stream_parts = self.children[0].execute()
            bparts = self.children[1].execute()
            build_batches = [b for p in bparts for b in p]
            return [self._run_partition(sp, iter(list(build_batches)))
                    for sp in stream_parts]
        else:
            stream_parts = self.children[1].execute()
            bparts = self.children[0].execute()
            build_batches = [b for p in bparts for b in p]
            return [self._run_partition(iter(list(build_batches)), sp)
                    for sp in stream_parts]


class TpuNestedLoopJoin(TpuExec):
    """Cartesian / nested-loop join for cross joins and non-equi conditions.

    Reference: GpuBroadcastNestedLoopJoinExec, GpuCartesianProductExec."""

    def __init__(self, logical, left: PhysicalPlan, right: PhysicalPlan):
        super().__init__(left, right)
        self.logical = logical

    @property
    def output_schema(self):
        return self.logical.schema

    def execute(self):
        from ..service.cancellation import cancel_checkpoint
        lparts = self.children[0].execute()
        rparts = self.children[1].execute()
        # the whole right side materializes before the first output
        # batch: checkpoint per pulled batch so service cancellation
        # can unwind the drain
        right_batches = []
        for p in rparts:
            for b in p:
                cancel_checkpoint()
                right_batches.append(b)
        if self.logical.join_type in ("right", "full"):
            # unmatched-right emission must observe EVERY left row, so
            # the left side collapses to one partition
            def all_left():
                for p in lparts:
                    yield from p
            return [self._run(all_left(), right_batches)]
        return [self._run(lp, right_batches) for lp in lparts]

    def _run(self, left_iter, right_batches):
        """Pair-level semantics for every join type: the condition
        restricts MATCHES; outer rows null-extend, semi/anti select left
        rows by surviving-pair existence (a plain post-filter would
        silently degrade outer/semi/anti to inner)."""
        from ..kernels import basic as bk
        jt = self.logical.join_type
        lschema = self.children[0].output_schema
        rschema = self.children[1].output_schema
        pair_schema = Schema(
            [Field(f.name, f.dtype, True) for f in lschema] +
            [Field(f.name, f.dtype, True) for f in rschema])
        rb = concat_batches(right_batches) if right_batches else \
            ColumnarBatch.empty(rschema)
        n_r = rb.num_rows
        right_matched = np.zeros(rb.capacity, dtype=bool) \
            if jt in ("right", "full") else None

        def select_left(lb, sel, n_hint):
            idx, cnt = bk.filter_compact_indices(sel, n_hint)
            n = _host_int(cnt)
            return ColumnarBatch(
                self.output_schema,
                gather_columns(lb.columns, idx, jnp.arange(idx.shape[0]) < n),
                n)

        from ..service.cancellation import cancel_checkpoint
        for lb in left_iter:
            cancel_checkpoint()
            n_l = lb.num_rows
            total = n_l * n_r
            if total == 0:
                if n_l and jt in ("left", "full", "anti"):
                    # empty right side: anti keeps everything, outer
                    # null-extends everything
                    in_range = jnp.arange(lb.capacity) < n_l
                    if jt == "anti":
                        yield select_left(lb, in_range, n_l)
                    else:
                        nulls = [_null_column(f.dtype, lb.capacity)
                                 for f in rschema]
                        cols = [c.mask_validity(in_range)
                                for c in lb.columns] + nulls
                        yield ColumnarBatch(self.output_schema, cols, n_l)
                continue
            out_cap = bucket_capacity(total)
            t = jnp.arange(out_cap)
            li = (t // max(n_r, 1)).astype(jnp.int32)
            ri = (t % max(n_r, 1)).astype(jnp.int32)
            live = t < total
            pairs = ColumnarBatch(
                pair_schema, gather_columns(lb.columns, li, live) +
                gather_columns(rb.columns, ri, live), total)
            if self.logical.condition is not None:
                cond = self.logical.condition.bind(pair_schema)
                pred = ec.eval_as_column(cond, pairs)
                keep = pred.data.astype(bool) & pred.validity & live
            else:
                keep = live

            if right_matched is not None:
                hit = jnp.zeros(rb.capacity, dtype=bool).at[
                    jnp.where(keep, ri, 0)].max(keep)
                from ..analysis import residency  # lazy import
                with residency.declared_transfer(site="join_verify"):
                    right_matched |= np.asarray(hit)

            if jt in ("semi", "anti"):
                surv = jnp.zeros(lb.capacity, dtype=bool).at[
                    jnp.where(keep, li, 0)].max(keep)
                in_range = jnp.arange(lb.capacity) < n_l
                sel = surv if jt == "semi" else (~surv & in_range)
                out = select_left(lb, sel, n_l)
                self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                yield out
                continue

            idx, cnt = bk.filter_compact_indices(keep, total)
            n_pairs = _host_int(cnt)
            parts = []
            if n_pairs:
                parts.append(ColumnarBatch(
                    self.output_schema,
                    gather_columns(pairs.columns, idx,
                                   jnp.arange(idx.shape[0]) < n_pairs),
                    n_pairs))
            if jt in ("left", "full"):
                surv = jnp.zeros(lb.capacity, dtype=bool).at[
                    jnp.where(keep, li, 0)].max(keep)
                un = ~surv & (jnp.arange(lb.capacity) < n_l)
                uidx, ucnt = bk.filter_compact_indices(un, n_l)
                n_un = _host_int(ucnt)
                if n_un:
                    nulls = [_null_column(f.dtype, uidx.shape[0])
                             for f in rschema]
                    parts.append(ColumnarBatch(
                        self.output_schema,
                        gather_columns(lb.columns, uidx,
                                       jnp.arange(uidx.shape[0]) < n_un)
                        + nulls, n_un))
            for out in parts:
                self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                yield out

        if right_matched is not None:
            un = jnp.asarray(~right_matched) & \
                (jnp.arange(rb.capacity) < n_r)
            uidx, ucnt = bk.filter_compact_indices(un, n_r)
            n_un = _host_int(ucnt)
            if n_un:
                nulls = [_null_column(f.dtype, uidx.shape[0])
                         for f in lschema]
                out = ColumnarBatch(
                    self.output_schema,
                    nulls + gather_columns(
                        rb.columns, uidx, jnp.arange(uidx.shape[0]) < n_un),
                    n_un)
                self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                yield out


# ---------------------------------------------------------------------------
# program audit registration (analysis/program_audit.py): the probe and
# speculative-probe programs build per (shape, dtype) signature inside
# _run_partition, so each provider drives a tiny CPU build+probe and
# pulls the freshly cached program for abstract tracing.
# ---------------------------------------------------------------------------

def _audit_specs():
    import jax
    from types import SimpleNamespace
    from ..analysis.program_audit import AuditSpec

    def _fixture():
        cap = 16
        sschema = Schema([Field("sk", T.INT64, True)])
        bschema = Schema([Field("bk", T.INT64, True)])
        j = object.__new__(TpuHashJoinBase)
        j.logical = SimpleNamespace(
            join_type="inner", condition=None,
            schema=Schema(list(sschema.fields) + list(bschema.fields)))
        j.build_right = True
        bcol = Column(T.INT64, jnp.arange(cap, dtype=jnp.int64),
                      jnp.ones((cap,), bool))
        build = ColumnarBatch(bschema, [bcol], cap)
        bt = join_k.build(_key_words([bcol], build.rows_dev, [None]))
        scol = Column(T.INT64, jnp.arange(cap, dtype=jnp.int64),
                      jnp.ones((cap,), bool))
        sb = ColumnarBatch(sschema, [scol], cap)
        return j, sb, scol, bt, build

    def _sds_args(sb, bt):
        import numpy as np
        sws = tuple(jax.ShapeDtypeStruct(w.shape, w.dtype)
                    for w in bt.sorted_words)
        ka = ((jax.ShapeDtypeStruct((sb.capacity,), np.int64),
               jax.ShapeDtypeStruct((sb.capacity,), np.bool_)),)
        return sws, ka, jax.ShapeDtypeStruct((), np.int32)

    def _probe_build():
        j, sb, scol, bt, _build_b = _fixture()
        out = j._probe_phase(sb, [scol], bt, [None], None, None)
        assert out is not None, "probe phase fell back"
        key = ("probe", "inner", (T.INT64.name,), sb.capacity,
               bt.capacity, len(bt.sorted_words), True, False, False)
        fn = TpuHashJoinBase._PROBE_JIT[key]
        sws, ka, nr = _sds_args(sb, bt)
        return fn, (sws, None, ka, nr), {}

    def _spec_build():
        import numpy as np
        j, sb, scol, bt, build = _fixture()
        out = j._spec_join_batch(sb, [scol], bt, build, None,
                                 [ec.BoundReference(0, T.INT64)],
                                 [None])
        assert out is not None, "speculative join fell back"
        key = ("spec", (T.INT64.name,), sb.capacity, bt.capacity,
               len(bt.sorted_words), (T.INT64.name,), (T.INT64.name,),
               (0,), (0,), True, False)
        fn = TpuHashJoinBase._SPEC_JIT[key]
        sws, ka, nr = _sds_args(sb, bt)
        perm = jax.ShapeDtypeStruct(bt.perm.shape, bt.perm.dtype)
        d = jax.ShapeDtypeStruct((sb.capacity,), np.int64)
        v = jax.ShapeDtypeStruct((sb.capacity,), np.bool_)
        args = (sws, None, ka, nr, perm, (d,), (v,), (d,), (v,))
        return fn, args, {}

    def _residual_build():
        """A semi join on ``sk = bk`` with the residual ``sk <> bk``."""
        from ..expr import predicates as ep
        j, sb, scol, bt, build = _fixture()
        j.logical = SimpleNamespace(
            join_type="semi", schema=sb.schema, condition=ep.Not(ep.EqualTo(
                ec.AttributeReference("sk", T.INT64, True),
                ec.AttributeReference("bk", T.INT64, True))))
        j.children = [SimpleNamespace(output_schema=sb.schema),
                      SimpleNamespace(output_schema=build.schema)]
        lo = jnp.arange(sb.capacity, dtype=jnp.int32)
        counts = jnp.ones(sb.capacity, jnp.int32)
        j._residual_keep(sb, build, bt, lo, counts, sb.capacity, False)
        fn = next(f for k, f in TpuHashJoinBase._RESIDUAL_JIT.items()
                  if k[2:5] == (False, sb.capacity, sb.capacity))
        i32 = jax.ShapeDtypeStruct((sb.capacity,), jnp.int32)
        col = (jax.ShapeDtypeStruct((sb.capacity,), jnp.int64),
               jax.ShapeDtypeStruct((sb.capacity,), jnp.bool_))
        perm = jax.ShapeDtypeStruct(bt.perm.shape, bt.perm.dtype)
        return fn, (i32, i32, perm, (col,), (col,)), {}

    return [
        AuditSpec("join_residual", "join_residual", _residual_build,
                  notes="residual semi join: expansion, the condition's "
                        "two columns gathered, survival by a running sum",
                  budgets={"gather": 8, "scatter": 1, "transpose": 0,
                           "sort": 0}),
        AuditSpec("join_probe", "join_probe", _probe_build,
                  notes="phase-A probe counts, inner join, int64 key",
                  budgets={"gather": 16, "scatter": 2, "transpose": 2,
                           "sort": 2}),
        AuditSpec("join_spec_probe", "join_spec_probe", _spec_build,
                  notes="speculative unique-match inner join program",
                  budgets={"gather": 28, "scatter": 2, "transpose": 2,
                           "sort": 2}),
    ]
