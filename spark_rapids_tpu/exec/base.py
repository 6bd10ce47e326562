"""Physical operator base — the GpuExec role (reference: GpuExec.scala:168).

Contract: ``execute() -> List[Iterator[...]]`` (one lazy iterator per
partition).  TPU operators stream ColumnarBatch; CPU fallback operators
stream pa.Table.  ``columnar`` distinguishes them and the planner inserts
RowToColumnar/ColumnarToRow transitions exactly like
GpuTransitionOverrides (GpuTransitionOverrides.scala:40).

Metrics: every node carries leveled metrics (ESSENTIAL/MODERATE/DEBUG),
mirroring GpuMetric (GpuExec.scala:27-237).
"""
from __future__ import annotations

import logging
from typing import Dict, Iterator, List

from ..columnar.schema import Schema
from ..obs import flight as _flight
from ..obs import trace as _trace
from ..service.cancellation import cancel_checkpoint

ESSENTIAL, MODERATE, DEBUG = "ESSENTIAL", "MODERATE", "DEBUG"

# standard metric names (reference: GpuExec.scala:40-95)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
OP_TIME = "opTime"
CONCAT_TIME = "concatTime"
SORT_TIME = "sortTime"
AGG_TIME = "computeAggTime"
JOIN_TIME = "joinTime"
BUILD_TIME = "buildTime"
PARTITION_TIME = "partitionTime"
SPILL_BYTES = "spillData"
#: mesh SPMD programs whose receive region overflowed and re-ran on the
#: in-process path (data-dependent, so not an error — but it must show)
MESH_OVERFLOW_FALLBACKS = "meshOverflowFallbacks"
#: distinct devices holding a mesh exec's sharded input
MESH_INPUT_DEVICES = "meshInputDevices"


class Metric:
    __slots__ = ("name", "level", "_value", "_pending")

    def __init__(self, name: str, level: str = MODERATE):
        self.name = name
        self.level = level
        self._value = 0
        self._pending = None

    @property
    def value(self):
        # resolve deferred device counts only when the metric is read
        # (pulling them eagerly would serialize the dispatch queue)
        if self._pending:
            self._value += sum(int(p) for p in self._pending)
            self._pending = None
        return self._value

    @value.setter
    def value(self, v):
        self._value = int(v)
        self._pending = None

    def add(self, v):
        if isinstance(v, int):
            self._value += v
        else:
            if self._pending is None:
                self._pending = []
            self._pending.append(v)

    def __iadd__(self, v):
        self.add(v)
        return self

    def __repr__(self):
        return f"{self.name}={self.value}"


class MetricSet:
    def __init__(self):
        self._metrics: Dict[str, Metric] = {}

    def get(self, name: str, level: str = MODERATE) -> Metric:
        if name not in self._metrics:
            self._metrics[name] = Metric(name, level)
        return self._metrics[name]

    def __getitem__(self, name):
        return self.get(name)

    def __setitem__(self, name, value):
        # supports `metrics[X] += n` (Metric.__iadd__ returns the Metric)
        assert isinstance(value, Metric)
        self._metrics[name] = value

    def snapshot(self, level: str = DEBUG) -> Dict[str, int]:
        """Stable-key-order metric snapshot at ``level``.

        Filters BEFORE reading ``.value``: a metric excluded by level
        never resolves its deferred device counts, so an ESSENTIAL
        snapshot cannot force a device sync for MODERATE/DEBUG counters
        still pending on the dispatch queue."""
        rank = {ESSENTIAL: 0, MODERATE: 1, DEBUG: 2}
        mx = rank[level]
        out: Dict[str, int] = {}
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if rank[m.level] <= mx:
                out[name] = m.value
        return out


def note_mesh_input(node: "PhysicalPlan", sharded) -> None:
    """Record on how many distinct devices a mesh exec's re-placed
    input really landed (shard metadata only — no device sync)."""
    node.metrics[MESH_INPUT_DEVICES].value = len(
        {s.device.id for s in sharded.addressable_shards})


def note_mesh_overflow(node: "PhysicalPlan") -> None:
    """A mesh exec's SPMD program reported overflow and is about to
    re-run in process: count it on the node and say so."""
    node.metrics[MESH_OVERFLOW_FALLBACKS] += 1
    logging.getLogger("spark_rapids_tpu.exec.mesh").warning(
        "%s: receive region overflowed; re-running on the in-process "
        "path", node.name)


class timed:
    """Context manager adding elapsed ns to a metric (NvtxWithMetrics role).

    Doubles as the per-operator cancellation checkpoint: entering a
    timed region is exactly an operator boundary (one batch about to be
    processed by one node), so a cancelled/deadline-exceeded query
    unwinds here instead of running its remaining operators — the
    TaskContext.isInterrupted pattern at columnar granularity.

    Each timed region is the coarse span ``srt.exec.<node>`` (the
    operator; obs/trace.py), nesting under ``srt.query`` / the service
    attempt span and over flush, jit-build, shuffle and memory spans.
    The span's two clock reads are the metric's: one measurement feeds
    both.  The node is the thread's operator while the region is open:
    the launches inside it are counted under its name."""

    __slots__ = ("metric", "name", "_span", "_outer")

    def __init__(self, metric: Metric, node: "PhysicalPlan" = None):
        self.metric = metric
        self.name = node.name if node is not None else metric.name

    def __enter__(self):
        cancel_checkpoint()
        # flight recorder shares this operator boundary (always-on;
        # interned node/metric name only, so the record is
        # allocation-free)
        _flight.record(_flight.EV_BEGIN, self.name)
        self._span = _trace.Span("srt.exec." + self.name, "exec",
                                 {"metric": self.metric.name}, True)
        self._span.__enter__()
        self._outer = _trace.operator(self.name)
        return self

    def __exit__(self, *a):
        _trace.operator(self._outer)
        self._span.__exit__(*a)
        self.metric.add(self._span.dur_ns)
        _flight.record(_flight.EV_END, self.name)
        return False


class PhysicalPlan:
    columnar = True  # True: yields ColumnarBatch; False: pa.Table

    def __init__(self, *children: "PhysicalPlan"):
        self.children = list(children)
        self.metrics = MetricSet()

    @property
    def output_schema(self) -> Schema:
        raise NotImplementedError

    @property
    def name(self) -> str:
        return type(self).__name__

    def execute(self) -> List[Iterator]:
        raise NotImplementedError

    def execute_checkpointed(self) -> List[Iterator]:
        """execute() with a cooperative cancellation checkpoint at every
        batch hand-off of every partition (in addition to the per-
        operator checkpoints inside ``timed``).  The session's collect
        path drains through this so even plans whose operators never
        enter a timed region stay cancellable."""
        cancel_checkpoint()

        def wrap(it):
            for item in it:
                cancel_checkpoint()
                yield item
        return [wrap(it) for it in self.execute()]

    def num_partitions_hint(self) -> int:
        if self.children:
            return self.children[0].num_partitions_hint()
        return 1

    def tree_string(self, indent: int = 0, annotate=None) -> str:
        """Indented tree rendering (one node per line, preorder — the
        order node_metrics keys are emitted in, so consumers join
        positionally).

        ``annotate``: optional ``(preorder_index, node) -> str``; a
        non-empty result is appended after the node label (the plan
        verifier's verified/violation markers ride here).  Annotations
        never change line order or leading indentation, so positional
        consumers (tools/report.py) keep working."""
        if annotate is None:
            pad = "  " * indent
            s = f"{pad}{self._node_string()}"
            for c in self.children:
                s += "\n" + c.tree_string(indent + 1)
            return s
        lines: List[str] = []
        counter = [0]

        def walk(node, depth):
            idx = counter[0]
            counter[0] += 1
            line = f"{'  ' * (indent + depth)}{node._node_string()}"
            tag = annotate(idx, node)
            if tag:
                line += f"  {tag}"
            lines.append(line)
            for c in node.children:
                walk(c, depth + 1)
        walk(self, 0)
        return "\n".join(lines)

    def _node_string(self):
        return self.name

    def collect_nodes(self) -> List["PhysicalPlan"]:
        out = [self]
        for c in self.children:
            out.extend(c.collect_nodes())
        return out

    def __repr__(self):
        return self.tree_string()
