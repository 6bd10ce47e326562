"""Static warm-flush predictor: how many pending-pool flushes one warm
collect of a physical plan costs, BEFORE executing it.

``columnar/pending.py`` is the engine's cost model: every host-visible
device value stages into the pending pool, and ``FLUSH_COUNT`` ticks
once per forced (non-empty) fused flush — one tick == one device round
trip.  Smoke tests bound this at runtime; this module predicts it
statically so a planner or operator change that adds a round trip shows
up as a verifier diff (PV-FLUSH, analysis/plan_verify.py) instead of a
bench regression.

The model walks the physical tree with ``compile/lower.py`` dispatch
classifications (PROGRAM/CHAIN/BARRIER/BOUNDARY) and charges each
operator the flush its warm execute path is known to force:

* an EAGER hash join forces its phase-A probe-count barrier
  (tpu_join.py ``pending.flush()``) — one flush per join.  A carved
  superstage member running the sync-free speculative path (inner, no
  condition, non-string keys, conf on) forces none;
* an eager group-by COMPLETE/FINAL aggregate pulls the partial's group
  count to compact it (``_compact_partial``) — one flush.  No-group-key
  aggregates emit a host-known single row and pull nothing; carved
  members defer verification to the stage barrier;
* an eager sort pulls ``num_rows`` per input batch — one flush iff the
  chain below it (walked through CHAIN-classified transports) produces
  a lazy count (a filter or group-by aggregate).  Eager join outputs
  are host-counted after phase B; scans, exchanges and BARRIER nodes
  resolve their own counts;
* a shuffle exchange's map side finalizes staged buffers — one flush;
* a broadcast exchange resolves its build only when the build is
  speculative/lazily-counted, i.e. when its child region is a carved
  superstage — one flush.  Single-batch eager builds ride for free and
  eager join builds arrive host-counted;
* the collect sink: a root superstage resolves its speculative output
  in ONE barrier flush (counted unconditionally — fit flags force even
  for empty results).  An eager root instead pays the ``to_arrow``
  forcing of the staged output buffers — a flush that never fires when
  the query returns ZERO rows (nothing ever forces the pool), which is
  why the prediction is piecewise: ``expected(result_rows)``.

Assumptions (documented, asserted by the quartet cross-check): warm
caches, the serial single-partition collect regime of ci smoke runs
(per-partition flush scaling is counted once), single-batch broadcast
builds, the speculative join's eligibility test of
exec/tpu_join.py, and stream batches below
``TpuHashJoinBase._SIZED_MIN_CAPACITY``: an armed join whose partition
holds a stream batch of that capacity or more sizes its outputs and
pays the phase-A barrier of an eager join, one flush the prediction
lacks.  The plan cannot know a later join's stream capacity (it is the
earlier join's match count), so the predictor does not try.
"""
from __future__ import annotations

from typing import List, Optional

from ..exec.base import PhysicalPlan

__all__ = ["Contribution", "FlushPrediction", "predict_flushes"]


class Contribution:
    """One operator's predicted flushes, anchored like a Violation."""

    __slots__ = ("node_index", "node_name", "count", "reason",
                 "empty_discount")

    def __init__(self, node_index: int, node_name: str, count: int,
                 reason: str, empty_discount: int = 0):
        self.node_index = node_index
        self.node_name = node_name
        self.count = count
        self.reason = reason
        # flushes of this contribution that never fire when the query
        # returns zero rows (nothing forces the staged output buffers)
        self.empty_discount = empty_discount

    def __str__(self):
        tail = " (skipped on empty result)" if self.empty_discount else ""
        return (f"node {self.node_index} ({self.node_name}): "
                f"+{self.count} {self.reason}{tail}")

    def __repr__(self):
        return f"Contribution({self})"


class FlushPrediction:
    """Piecewise warm-flush budget for one physical plan."""

    def __init__(self, plan: PhysicalPlan,
                 contributions: List[Contribution]):
        self.plan = plan
        self.contributions = list(contributions)
        self.warm = sum(c.count for c in self.contributions)
        self.empty_result_discount = sum(
            c.empty_discount for c in self.contributions)

    def expected(self, result_rows: Optional[int] = None) -> int:
        """Predicted FLUSH_COUNT delta for one warm collect.

        ``result_rows`` selects the piecewise branch: a zero-row result
        never forces the final output conversion, so its flush is
        discounted.  None assumes a non-empty result."""
        if result_rows == 0:
            return self.warm - self.empty_result_discount
        return self.warm

    def by_node(self):
        out = {}
        for c in self.contributions:
            out.setdefault(c.node_index, []).append(c)
        return out

    def explain(self) -> str:
        lines = [f"predicted warm flushes: {self.warm}"
                 + (f" ({self.warm - self.empty_result_discount} on an "
                    f"empty result)" if self.empty_result_discount
                    else "")]
        lines += [f"  {c}" for c in self.contributions]
        return "\n".join(lines)

    def __repr__(self):
        return (f"FlushPrediction(warm={self.warm}, "
                f"empty_discount={self.empty_result_discount})")


# ---------------------------------------------------------------------------
# node predicates
# ---------------------------------------------------------------------------

def _cls_name(node) -> str:
    return type(node).__name__


def _is_join(node) -> bool:
    from ..exec.tpu_join import TpuHashJoinBase
    return isinstance(node, TpuHashJoinBase)


def _spec_join_eligible(node, conf) -> bool:
    """Mirror of the sync-free speculative-join gate in
    exec/tpu_join.py: only a carved member (``_superstage`` armed by
    compile/carve.py) of an inner, unconditioned, non-string-key join
    skips the phase-A flush barrier."""
    if not getattr(node, "_superstage", False):
        return False
    lg = node.logical
    if lg.join_type != "inner" or \
            getattr(lg, "condition", None) is not None:
        return False
    from ..columnar import dtypes as T
    try:
        lschema = node.children[0].output_schema
        rschema = node.children[1].output_schema
        key_dtypes = [e.bind(lschema).dtype for e in lg.left_keys] + \
                     [e.bind(rschema).dtype for e in lg.right_keys]
    except Exception:
        return False        # unbindable keys: assume the eager path
    return all(d != T.STRING for d in key_dtypes)


def _has_filter_op(node) -> bool:
    """TpuStagedCompute chains mutate the count iff they hold a filter."""
    ops = getattr(node, "ops", None) or ()
    return any(kind == "filter" for kind, _p, _s in ops)


def _lazy_count_input(node) -> bool:
    """Does ``node``'s input arrive with a device-lazy row count?

    Walks the child chain through CHAIN-classified transports
    (compile/lower.py) to the first count-determining operator.
    BARRIER and BOUNDARY nodes resolve counts themselves (coalesce
    forces, exchanges finalize, scans read host metadata); among
    PROGRAM nodes, filters and group-by aggregates emit lazy counts
    while eager joins (phase-B host capacities) and global aggregates
    (single host-known row) do not."""
    from ..compile import lower
    cur = node.children[0] if node.children else None
    while cur is not None:
        strategy = lower.classify(cur)
        if strategy == lower.CHAIN:
            cur = cur.children[0] if cur.children else None
            continue
        if strategy in (lower.BARRIER, lower.BOUNDARY):
            return False
        cname = _cls_name(cur)
        if cname == "TpuFilter":
            return True
        if cname == "TpuStagedCompute":
            if _has_filter_op(cur):
                return True
            cur = cur.children[0] if cur.children else None
            continue
        if cname == "TpuHashAggregate":
            return bool(getattr(cur, "group_exprs", None))
        if _is_join(cur):
            # eager phase B expands with host-known output capacities;
            # a speculative member join is lazy, but then this node
            # would be a member too and never reach the eager pull
            return getattr(cur, "_superstage", False)
        if cname in ("TpuProject", "TpuLocalLimit", "TpuGlobalLimit",
                     "TpuSort", "TpuSuperstage"):
            # count-preserving (or host-computable from the child's):
            # keep walking; a superstage's output count is resolved at
            # its own barrier before an eager consumer pulls it
            if cname == "TpuSuperstage":
                return False
            cur = cur.children[0] if cur.children else None
            continue
        return False        # unknown operator: stay permissive
    return False


def _chain_child_superstage(node) -> bool:
    """Is the (CHAIN-transported) child region of ``node`` a carved
    superstage?  Broadcast builds over one resolve their speculative /
    lazily-counted output at the exchange."""
    from ..compile import lower
    cur = node.children[0] if node.children else None
    while cur is not None:
        if _cls_name(cur) == "TpuSuperstage":
            return True
        if lower.classify(cur) == lower.CHAIN and cur.children:
            cur = cur.children[0]
            continue
        return False
    return False


# ---------------------------------------------------------------------------
# the predictor
# ---------------------------------------------------------------------------

def predict_flushes(plan: PhysicalPlan, conf=None) -> FlushPrediction:
    """Predict the warm per-collect ``pending.FLUSH_COUNT`` delta for a
    lowered physical plan.  Pure plan analysis — never executes, never
    touches the device; safe under JAX_PLATFORMS=cpu."""
    if conf is None:
        from ..config import get_active
        conf = get_active()
    from .plan_verify import _preorder
    nodes = _preorder(plan)
    contributions: List[Contribution] = []
    member_ids = set()
    for _i, node, _anc in nodes:
        if _cls_name(node) == "TpuSuperstage":
            member_ids.update(id(m) for m in
                              getattr(node, "members", ()) or ())

    def exchange_ancestor(anc) -> bool:
        return any(_cls_name(a) in ("TpuShuffleExchange",
                                    "TpuBroadcastExchange")
                   for a in anc)

    for i, node, anc in nodes:
        cname = _cls_name(node)
        member = id(node) in member_ids
        if cname == "TpuSuperstage":
            if not exchange_ancestor(anc):
                # consumer is the collect sink: ONE resolve barrier for
                # the stage's speculative output (fit flags force even
                # when the result is empty)
                contributions.append(Contribution(
                    i, node.name, 1,
                    "superstage collect-resolve barrier"))
            # under an exchange the stage's flush is charged to the
            # exchange's finalize/build-resolve below
        elif cname == "TpuShuffleExchange":
            contributions.append(Contribution(
                i, node.name, 1,
                "map-side finalize_staged flush"))
        elif cname == "TpuBroadcastExchange":
            if _chain_child_superstage(node):
                contributions.append(Contribution(
                    i, node.name, 1,
                    "build resolve of speculative superstage output"))
        elif _is_join(node):
            if not _spec_join_eligible(node, conf):
                contributions.append(Contribution(
                    i, node.name, 1,
                    "phase-A probe-count barrier"))
        elif cname == "TpuHashAggregate":
            if member:
                continue    # deferred verify: the stage barrier pays
            if getattr(node, "mode", None) in ("complete", "final") and \
                    getattr(node, "group_exprs", None):
                contributions.append(Contribution(
                    i, node.name, 1,
                    "group-count pull to compact the partial"))
        elif cname == "TpuSort":
            if member:
                continue    # lazy single-batch fast path
            if _lazy_count_input(node):
                contributions.append(Contribution(
                    i, node.name, 1,
                    "input num_rows pull over a lazily-counted chain"))
        elif cname == "TpuCoalesceBatches":
            if not member and _lazy_count_input(node):
                contributions.append(Contribution(
                    i, node.name, 1,
                    "host count read to pack batches"))
    if _cls_name(plan) != "TpuSuperstage":
        # eager root: the collect sink's to_arrow forces whatever the
        # tail operators staged after the last barrier — unless the
        # result is empty and nothing ever forces the pool
        contributions.append(Contribution(
            len(nodes), "collect", 1,
            "to_arrow forcing of staged output buffers",
            empty_discount=1))
    return FlushPrediction(plan, contributions)
