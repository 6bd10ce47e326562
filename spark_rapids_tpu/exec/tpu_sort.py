"""TPU sort operator — reference: GpuSortExec.scala:56 (sort-each-batch /

single-batch / out-of-core modes) + SortUtils.scala.

TPU-first: one multi-operand lax.sort over canonical key words.  Global
sorts are range-partitioned by the planner (RangePartitioner exchange)
then locally sorted here, matching the reference's
GpuRangePartitioning + GpuSortExec pipeline.
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp

from ..columnar.batch import (ColumnarBatch, concat_batches,
                              resolve_speculative)
from ..expr import core as ec
from ..kernels import canon
from ..kernels.sort import sort_permutation
from ..plan.logical import SortOrder
from ..service.cancellation import cancel_checkpoint
from .base import PhysicalPlan, SORT_TIME, NUM_OUTPUT_ROWS, timed
from .tpu_basic import TpuExec


class TpuSort(TpuExec):
    def __init__(self, orders: List[SortOrder], child: PhysicalPlan,
                 sort_each_batch: bool = False):
        super().__init__(child)
        self.orders = orders
        self.sort_each_batch = sort_each_batch

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def _key_cols(self, batch: ColumnarBatch):
        schema = batch.schema
        return [ec.eval_as_column(o.expr.bind(schema), batch)
                for o in self.orders]

    def _key_words(self, cols, num_rows, str_words=None):
        return canon.batch_key_words(
            cols, num_rows,
            descending=[not o.ascending for o in self.orders],
            nulls_last=[not o.effective_nulls_first for o in self.orders],
            str_words=str_words)

    def _sort_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        # a sort is a flush barrier: it needs the host count anyway, so
        # verifying a speculative input (superstage join/agg chain) here
        # is free — the fit flags resolve in the same fused flush the
        # count pull triggers
        batch = resolve_speculative(batch)
        if batch.num_rows == 0:
            return batch
        words = self._key_words(self._key_cols(batch), batch.num_rows)
        perm = sort_permutation(words)
        return batch.gather(perm, batch.num_rows,
                            live=jnp.arange(perm.shape[0]) < batch.num_rows,
                            unique=True)

    def _sort_lazy_spec(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Sort on device counts — no host pull.  Dead rows carry the
        past-rows rank word (canon), so they sort last and the valid
        prefix is exactly the sorted rows.  The input's speculative fit
        flags (superstage join/agg chain) ride onto the output; a failed
        fit re-sorts the exactly-recomputed input."""
        from ..columnar.batch import chain_speculative
        nr = batch.rows_dev
        words = self._key_words(self._key_cols(batch), nr)
        perm = sort_permutation(words)
        out = batch.gather(perm, batch.rows_lazy,
                           live=jnp.arange(perm.shape[0]) < nr, unique=True)
        return chain_speculative(out, batch, self._sort_batch)

    def execute(self):
        def run(part):
            if self.sort_each_batch:
                # mode 1: sort-each-batch (GpuSortExec.scala:56 first mode)
                for b in part:
                    with timed(self.metrics[SORT_TIME], self):
                        out = self._sort_batch(b)
                    self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                    yield out
                return
            # modes 2/3: buffer input as *sorted spillable runs* so device
            # pressure can push pending runs down the tiers while more
            # input streams in (the out-of-core design of
            # GpuSortExec.scala:219), then merge.
            from ..memory.spillable import SpillableBatch
            from ..memory.arena import DeviceManager
            from ..config import (get_active, SORT_OOC_CHUNK_ROWS,
                                  SUPERSTAGE)
            if get_active().get(SUPERSTAGE):
                # superstage fast path: a single device-counted batch
                # (the common post-agg shape) sorts WITHOUT the host
                # count pull, carrying any fit flags downstream so the
                # collect/exchange barrier resolves the whole chain in
                # one fused flush
                it = iter(part)
                first = next(it, None)
                if first is None:
                    return
                second = next(it, None)
                if second is None and (
                        not isinstance(first.rows_lazy, int) or
                        getattr(first, "_speculative", None) is not None):
                    out = self._sort_lazy_spec(first)
                    self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                    yield out
                    return
                part = [b for b in (first, second)
                        if b is not None] + list(it)
            runs = []          # (SpillableBatch, n_rows)
            total = 0
            for b in part:
                b = resolve_speculative(b)
                if b.num_rows == 0:
                    continue
                with timed(self.metrics[SORT_TIME], self):
                    sorted_run = self._sort_batch(b)
                    n = int(sorted_run.num_rows)
                DeviceManager.get().reserve(sorted_run.nbytes())
                runs.append((SpillableBatch(sorted_run, op="TpuSortExec",
                                            site="operator"), n))
                total += n
            if not runs:
                return
            chunk_rows = int(get_active().get(SORT_OOC_CHUNK_ROWS))
            if len(runs) == 1 or total <= chunk_rows:
                # in-core: one concat + resort (modes 1/2)
                with timed(self.metrics[SORT_TIME], self):
                    batches = [r.materialize() for r, _ in runs]
                    merged = concat_batches(batches) if len(batches) > 1 \
                        else batches[0]
                    out = self._sort_batch(merged)
                for r, _ in runs:
                    r.close()
                self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                yield out
                return
            # mode 3: out-of-core range merge over spillable runs.
            # Sampling happens HERE (not per-run above) so the common
            # in-core path never pays it; one run materializes at a
            # time, bounded by a single input batch.
            sampled = []
            for spill, n in runs:
                was_spilled = spill.is_spilled()
                with timed(self.metrics[SORT_TIME], self):
                    samples, strw = self._run_samples(
                        spill.materialize(), n)
                if was_spilled:
                    # push the run straight back down: sampling must not
                    # leave every run device-resident (that would defeat
                    # the out-of-core mode in exactly its target case)
                    spill.demote()
                sampled.append((spill, n, samples, strw))
            yield from self._merge_out_of_core(sampled, total, chunk_rows)
        return [run(p) for p in self.children[0].execute()]

    # -- out-of-core merge (GpuSortExec.scala:219 third mode) --------------
    def _run_samples(self, sorted_run: ColumnarBatch, n: int):
        """(sample key mini-batch positions+cols, string word counts)
        recorded while the sorted run is still on device."""
        import numpy as np
        from ..config import get_active, SORT_OOC_SAMPLES
        from ..columnar.column import StringColumn, bucket_capacity
        from ..kernels.strings import needed_key_words
        s = min(n, int(get_active().get(SORT_OOC_SAMPLES)))
        pos = np.unique(np.linspace(0, n - 1, s).astype(np.int64))
        key_cols = self._key_cols(sorted_run)
        # pad sample positions to a capacity bucket so the gather kernel
        # compiles once per bucket, not once per sample count
        cap = bucket_capacity(len(pos))
        padded = np.full(cap, pos[-1], np.int64)
        padded[:len(pos)] = pos
        idx = jnp.asarray(padded)
        sample_cols = [c.gather(idx) for c in key_cols]
        strw = [needed_key_words(c, n) if isinstance(c, StringColumn)
                else None for c in key_cols]
        return (pos, sample_cols), strw

    def _merge_out_of_core(self, runs, total: int, chunk_rows: int):
        """Range-partitioned k-way merge: choose boundary keys from the
        runs' samples, then per output chunk upload only each run's
        candidate slice (catalog.acquire_slice keeps spilled runs
        spilled), filter to the exact range, and sort.

        Exactness: a run's rows in [b_i, b_{i+1}) all lie between the
        last sample < b_i and the first sample >= b_{i+1} (runs are
        sorted), so slicing at sample positions over-covers and the
        device-side range filter trims to exact, half-open ranges.
        Keys are extended with (run index, row position) tiebreaker
        words so heavily duplicated sort keys still split into bounded
        chunks instead of collapsing every cut onto one key value."""
        import numpy as np

        # global word count per string key so words compare across runs
        nkeys = len(self.orders)
        strw_global = []
        for k in range(nkeys):
            ws = [r[3][k] for r in runs]
            strw_global.append(max(w for w in ws) if ws[0] is not None
                               else None)

        def to_void(word_arrays):
            """[n] u64 word columns -> [n] big-endian void keys whose
            memcmp order equals lexicographic word order.  byteswap AFTER
            stacking: np.stack silently casts '>u8' inputs back to
            native-endian."""
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="sort_ooc"):
                m = np.stack([np.asarray(w) for w in word_arrays],
                             axis=1).astype(np.uint64).byteswap()
            return np.ascontiguousarray(m).view(
                np.dtype((np.void, 8 * m.shape[1]))).reshape(-1)

        # sample words per run, encoded with the GLOBAL string widths,
        # extended with (run, position) tiebreakers for uniqueness
        run_sample_void = []
        all_void = []
        for ri, (spill, n, (pos, sample_cols), _) in enumerate(runs):
            words = self._key_words(sample_cols, len(pos),
                                    str_words=strw_global)
            from ..analysis import residency  # lazy: avoids import cycle
            with residency.declared_transfer(site="sort_ooc"):
                words = [np.asarray(w[:len(pos)]) for w in words]
            words.append(np.full(len(pos), ri, np.uint64))
            words.append(pos.astype(np.uint64))
            v = to_void(words)
            run_sample_void.append(v)
            all_void.append(v)
        merged_samples = np.sort(np.concatenate(all_void))
        n_chunks = max(1, -(-total // chunk_rows))
        cuts = np.unique(merged_samples[
            (np.arange(1, n_chunks) * len(merged_samples)) // n_chunks])

        bounds = [None] + list(cuts) + [None]
        try:
            yield from self._merge_chunks(runs, run_sample_void, bounds,
                                          strw_global)
        finally:
            # close even if the consumer stops early (limit over sort):
            # a leaked run keeps its catalog entry + spill files forever
            for spill, _, _, _ in runs:
                spill.close()

    def _merge_chunks(self, runs, run_sample_void, bounds, strw_global):
        import numpy as np
        for ci in range(len(bounds) - 1):
            b_lo, b_hi = bounds[ci], bounds[ci + 1]
            pieces = []
            for ri, ((spill, n, (pos, _), _), sv) in enumerate(
                    zip(runs, run_sample_void)):
                lo_i = 0 if b_lo is None else \
                    int(pos[max(np.searchsorted(sv, b_lo, "left") - 1, 0)])
                if b_hi is None:
                    hi_i = n
                else:
                    j = int(np.searchsorted(sv, b_hi, "left"))
                    hi_i = n if j >= len(pos) else int(pos[j])
                if hi_i > lo_i:
                    piece = spill.materialize_slice(lo_i, hi_i)
                    # filter per piece: the (run, position) tiebreaker
                    # words depend on the piece's run and offset
                    piece = self._range_filter(piece, b_lo, b_hi,
                                               strw_global, ri, lo_i)
                    if piece.num_rows:
                        pieces.append(piece)
            if not pieces:
                continue
            with timed(self.metrics[SORT_TIME], self):
                chunk = concat_batches(pieces) if len(pieces) > 1 \
                    else pieces[0]
                out = self._sort_batch(chunk)
            self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
            yield out

    def _range_filter(self, chunk: ColumnarBatch, b_lo, b_hi,
                      strw_global, run_idx: int,
                      row_offset: int) -> ColumnarBatch:
        """Keep rows with b_lo <= (key words, run, pos) < b_hi."""
        import numpy as np
        from ..kernels import basic as bk
        if b_lo is None and b_hi is None:
            return chunk
        cap = chunk.capacity
        words = self._key_words(self._key_cols(chunk), chunk.num_rows,
                                str_words=strw_global)
        words = list(words)
        words.append(jnp.full(cap, run_idx, jnp.uint64))
        words.append((jnp.arange(cap, dtype=jnp.int64) + row_offset)
                     .astype(jnp.uint64))

        def unpack(v):
            return np.frombuffer(bytes(v), dtype=">u8").astype(np.uint64)

        def cmp_lt(ws, bound):
            """row words < bound (lexicographic), vectorized."""
            lt = jnp.zeros(ws[0].shape[0], bool)
            eq = jnp.ones(ws[0].shape[0], bool)
            for w, b in zip(ws, bound):
                bv = jnp.uint64(int(b))
                lt = lt | (eq & (w < bv))
                eq = eq & (w == bv)
            return lt, eq
        keep = jnp.ones(words[0].shape[0], bool)
        if b_lo is not None:
            lt, _ = cmp_lt(words, unpack(b_lo))
            keep = keep & ~lt
        if b_hi is not None:
            lt, _ = cmp_lt(words, unpack(b_hi))
            keep = keep & lt
        idx, cnt = bk.filter_compact_indices(keep, chunk.num_rows)
        from ..analysis import residency  # lazy: avoids import cycle
        with residency.declared_transfer(site="sort_ooc"):
            n = int(cnt)
        return chunk.gather(idx, n, live=jnp.arange(idx.shape[0]) < n)


class TpuTopN(TpuExec):
    """limit-over-sort: per-partition sort + slice, then final merge.

    Reference: GpuTopN (limit.scala)."""

    def __init__(self, n: int, orders: List[SortOrder], child: PhysicalPlan):
        super().__init__(child)
        self.n = n
        self.orders = orders
        self._sorter = TpuSort(orders, child)

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def num_partitions_hint(self):
        return 1

    def _sort_lazy(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Sort + head-n entirely on device counts — no host pull.
        Dead rows carry the past-rows rank word, so they sort last and
        the head-n prefix is exactly the top rows."""
        from ..columnar.batch import LazyCount
        from ..columnar.column import bucket_capacity
        nr = batch.rows_dev
        words = self._sorter._key_words(
            self._sorter._key_cols(batch), nr)
        perm = sort_permutation(words)
        # the head of the sorted order: one gather by the permutation's
        # first ``cap`` entries, not a sort's gather and then a head's
        cap = min(bucket_capacity(max(self.n, 1)), batch.capacity)
        out_n = jnp.minimum(nr, jnp.int32(self.n))
        return batch.gather(perm[:cap], LazyCount(out_n),
                            live=jnp.arange(cap) < out_n, unique=True)

    def execute(self):
        from ..columnar.batch import (SpeculativeResult,
                                      resolve_speculative)
        parts = self.children[0].execute()

        def run():
            # TopN drains its entire input before emitting: checkpoint
            # per pulled batch so a cancelled/deadline-exceeded service
            # query unwinds mid-drain, not after it
            if len(parts) == 1:
                batches = []
                for b in parts[0]:
                    cancel_checkpoint()
                    batches.append(b)
                if len(batches) == 1 and not (
                        isinstance(batches[0].rows_lazy, int) and
                        batches[0].num_rows == 0):
                    # single-batch fast path: sort + head-n on device
                    # counts, PROPAGATING any speculative flag so an
                    # upstream aggregate's verify merges into the root
                    # collect's flush instead of costing its own
                    b = batches[0]
                    spec = getattr(b, "_speculative", None)
                    out = self._sort_lazy(b)
                    if spec is not None:
                        def redo(spec=spec):
                            fixed = resolve_speculative(spec.redo())
                            return self._sort_lazy(fixed)
                        out._speculative = SpeculativeResult(
                            list(spec.fits), redo)
                    self.metrics[NUM_OUTPUT_ROWS] += out.rows_lazy
                    yield out
                    return
                parts[0] = iter(batches)      # replay consumed batches
            tops = []
            for p in parts:
                cancel_checkpoint()
                batches = [resolve_speculative(b) for b in p]
                batches = [b for b in batches if b.num_rows > 0]
                if not batches:
                    continue
                batch = concat_batches(batches) if len(batches) > 1 else \
                    batches[0]
                s = self._sorter._sort_batch(batch)
                if s.num_rows > self.n:
                    s = s.slice(0, self.n)
                tops.append(s)
            if not tops:
                return
            merged = concat_batches(tops) if len(tops) > 1 else tops[0]
            final = self._sorter._sort_batch(merged)
            if final.num_rows > self.n:
                final = final.slice(0, self.n)
            self.metrics[NUM_OUTPUT_ROWS] += final.rows_lazy
            yield final
        return [run()]
