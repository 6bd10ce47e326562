"""Adaptive query execution over materialized shuffle statistics.

Reference parity: the AQE handling in the reference plugin —
``GpuCustomShuffleReaderExec`` (coalesced / skew-split shuffle reads),
``GpuOverrides.removeExtraneousShuffles`` and the AQE surgery in
``GpuTransitionOverrides.optimizeAdaptiveTransitions``.  Spark AQE
re-plans a query stage after its exchanges materialize; this engine's
exchanges are eager-on-first-pull, so the adaptive operators here force
the map side, read the per-partition statistics from the shuffle
catalog (the MapOutputStatistics role), and re-shape the reduce side:

- ``TpuAQEShuffleRead``: merges adjacent small reduce partitions up to
  the advisory target size (fewer, fuller partitions mean fewer XLA
  recompilations and fuller MXU batches — the TPU analogue of Spark's
  partition-coalescing rationale).
- ``TpuAdaptiveShuffledJoin``: materializes the build side first; when
  its total size is under the runtime broadcast threshold the probe
  shuffle is skipped entirely (AQE shuffled-join -> broadcast
  conversion); otherwise both sides shuffle and skewed probe partitions
  are split into batch slices, each joined against the full build
  partition (AQE skew-join mitigation).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from ..columnar.batch import ColumnarBatch, concat_batches
from ..obs import trace as _trace
from ..shuffle.partitioners import HashPartitioner
from .base import PhysicalPlan, NUM_OUTPUT_ROWS
from .exchange import TpuShuffleExchange
from .tpu_basic import TpuExec
from . import tpu_join as TJ


def coalesce_partition_ids(stats: List[Tuple[int, int]],
                           target_bytes: int) -> List[List[int]]:
    """Greedy adjacent merge of reduce ids below the advisory size.

    Mirrors Spark's ShufflePartitionsUtil.coalescePartitions: walk the
    partitions in order, packing neighbours until the target is reached.
    """
    groups: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for pid, (nbytes, _rows) in enumerate(stats):
        if cur and cur_bytes + nbytes > target_bytes:
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(pid)
        cur_bytes += nbytes
    if cur:
        groups.append(cur)
    return groups


def skew_split_sizes(stats: List[Tuple[int, int]], factor: float,
                     min_bytes: int) -> List[bool]:
    """Which partitions count as skewed (bytes > factor * median and
    above the absolute threshold)."""
    sizes = sorted(s for s, _ in stats)
    if not sizes:
        return []
    median = sizes[len(sizes) // 2]
    return [s > max(min_bytes, factor * max(median, 1)) for s, _ in stats]


class TpuAQEShuffleRead(TpuExec):
    """Coalesced shuffle read (GpuCustomShuffleReaderExec role)."""

    def __init__(self, child: TpuShuffleExchange, target_bytes: int):
        super().__init__(child)
        self.target_bytes = target_bytes
        self._groups: Optional[List[List[int]]] = None

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def num_partitions_hint(self):
        # unknown until runtime; report the exchange width
        return self.children[0].num_partitions_hint()

    def _plan_groups(self) -> List[List[int]]:
        if self._groups is None:
            ex: TpuShuffleExchange = self.children[0]
            stats = ex.partition_stats()
            self._groups = coalesce_partition_ids(stats, self.target_bytes)
        return self._groups

    def execute(self):
        ex: TpuShuffleExchange = self.children[0]
        schema = self.output_schema

        def read_group(pids):
            got = False
            for pid in pids:
                for b in ex.stream_reduce(pid):
                    if b.num_rows == 0:
                        continue
                    got = True
                    self.metrics[NUM_OUTPUT_ROWS] += b.rows_lazy
                    yield b
            if not got:
                yield ColumnarBatch.empty(schema)

        groups = self._plan_groups()
        return [read_group(g) for g in groups]

    def _node_string(self):
        g = f"{len(self._groups)} groups" if self._groups else "pending"
        return f"TpuAQEShuffleRead[{g}]"


class TpuAdaptiveShuffledJoin(TpuExec):
    """Shuffled hash join with runtime stats-driven strategy.

    Holds the *pre-exchange* children; at execution time it materializes
    the build side and picks:
      1. broadcast conversion (small build): probe side never shuffles;
      2. co-partitioned shuffled join with symmetric partition
         coalescing and probe-side skew splitting.
    """

    # join types whose build side never emits unmatched rows: safe to
    # duplicate the build partition across skew slices
    _SKEW_SAFE = {"inner", "left", "semi", "anti"}

    def __init__(self, logical, left: PhysicalPlan, right: PhysicalPlan,
                 build_right: bool, num_partitions: int,
                 broadcast_bytes: int, target_bytes: int,
                 skew_factor: float, skew_min_bytes: int):
        super().__init__(left, right)
        self.logical = logical
        self.build_right = build_right
        self.num_partitions = num_partitions
        self.broadcast_bytes = broadcast_bytes
        self.target_bytes = target_bytes
        self.skew_factor = skew_factor
        self.skew_min_bytes = skew_min_bytes
        self.strategy: Optional[str] = None   # set at execute time

    @property
    def output_schema(self):
        return self.logical.schema

    def num_partitions_hint(self):
        return self.num_partitions

    def _node_string(self):
        return (f"TpuAdaptiveShuffledJoin[{self.logical.join_type}, "
                f"strategy={self.strategy or 'pending'}]")

    # -- strategy pieces ---------------------------------------------------
    def _exchange(self, side: PhysicalPlan, keys) -> TpuShuffleExchange:
        return TpuShuffleExchange(
            side, HashPartitioner(keys, self.num_partitions))

    def _decide(self):
        p = self.logical
        left, right = self.children
        bkeys = p.right_keys if self.build_right else p.left_keys
        build_side = right if self.build_right else left
        build_ex = self._exchange(build_side, bkeys)
        stats = build_ex.partition_stats()
        total_build = sum(s for s, _ in stats)
        can_broadcast = (total_build <= self.broadcast_bytes and
                         p.join_type not in ("full",) and
                         not (p.join_type == "right" and self.build_right)
                         and not (p.join_type == "left" and
                                  not self.build_right))
        return build_ex, stats, can_broadcast

    def execute(self):
        p = self.logical
        left, right = self.children
        build_ex, build_stats, can_broadcast = self._decide()

        # the join node borrows _run_partition; its children provide only
        # binding schemas (same pre- and post-exchange)
        join = TJ.TpuShuffledHashJoin(p, left, right,
                                      build_right=self.build_right)

        # +1 per decision: which way the runtime threshold fell
        self.strategy = "broadcast" if can_broadcast else "shuffled"
        _trace.count("join.adaptive." + self.strategy)
        if can_broadcast:
            # the build side is already materialized in the catalog; the
            # probe side streams its ORIGINAL partitions — no shuffle
            batches = []
            for pid in range(self.num_partitions):
                batches.extend(b for b in build_ex.read_reduce(pid)
                               if b.num_rows > 0)
            build_batch = concat_batches(batches) if batches else \
                ColumnarBatch.empty(build_ex.output_schema)
            probe = left if self.build_right else right

            def run_bcast(part):
                if self.build_right:
                    yield from join._run_partition(part,
                                                   iter([build_batch]))
                else:
                    yield from join._run_partition(iter([build_batch]),
                                                   part)
            return [run_bcast(part) for part in probe.execute()]

        pkeys = p.left_keys if self.build_right else p.right_keys
        probe_side = left if self.build_right else right
        probe_ex = self._exchange(probe_side, pkeys)
        probe_stats = probe_ex.partition_stats()

        # symmetric coalescing: group by COMBINED size so both sides
        # stay co-partitioned
        combined = [(b1 + b2, r1 + r2) for (b1, r1), (b2, r2)
                    in zip(build_stats, probe_stats)]
        groups = coalesce_partition_ids(combined, self.target_bytes)

        skewed = skew_split_sizes(probe_stats, self.skew_factor,
                                  self.skew_min_bytes) \
            if p.join_type in self._SKEW_SAFE else \
            [False] * len(probe_stats)

        tasks = []   # list of (probe_batch_list | None, pids)
        for g in groups:
            if len(g) == 1 and skewed[g[0]]:
                pid = g[0]
                # split the skewed probe partition by batches; each
                # slice re-reads the full build partition
                probe_batches = [b for b in probe_ex.read_reduce(pid)
                                 if b.num_rows > 0]
                nsplit = max(2, min(len(probe_batches), 4))
                chunks = [probe_batches[i::nsplit] for i in range(nsplit)]
                split_any = False
                for chunk in chunks:
                    if chunk:
                        split_any = True
                        tasks.append((chunk, [pid]))
                if not split_any:
                    tasks.append(([], [pid]))
            else:
                tasks.append((None, list(g)))

        def run_task(probe_batches, pids):
            build_batches = []
            for pid in pids:
                build_batches.extend(b for b in build_ex.read_reduce(pid)
                                     if b.num_rows > 0)
            if probe_batches is None:
                pb = []
                for pid in pids:
                    pb.extend(b for b in probe_ex.read_reduce(pid)
                              if b.num_rows > 0)
            else:
                pb = probe_batches
            if self.build_right:
                yield from join._run_partition(iter(pb),
                                               iter(build_batches))
            else:
                yield from join._run_partition(iter(build_batches),
                                               iter(pb))

        return [run_task(pb, pids) for pb, pids in tasks]
