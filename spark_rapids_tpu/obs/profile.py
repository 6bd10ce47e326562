"""Per-dispatch device-time attribution — the stats plane's timing half.

The superstage compiler (compile/) collapsed whole exchange-delimited
regions into a handful of fused device dispatches, which made the
per-operator ``timed()`` spans blind inside exactly the regions that now
dominate runtime: one opaque span per stage, nothing per member.  This
module restores attribution WITHOUT adding dispatches or host syncs:

- every pending-pool flush (columnar/pending.py) — the unit of device
  round-trip cost — reports its wall
  duration through a module observer installed at import time;
- drain loops that own a flush barrier (the superstage drain, the
  exchange map-side finalize, the session's collect sink) declare
  themselves the ATTRIBUTION TARGET with ``attrib_scope(node)``:
  flushes forced while the scope is active accrue to that node's
  ``StageProfile`` (device-attributed wall ns + flush count);
- member-level time shares inside a fused stage are apportioned
  deterministically: a per-operator FLOP/byte intensity factor —
  MEASURED from the cost plane's live static-cost store
  (obs/costplane.py, XLA ``cost_analysis()`` per program x bucket)
  when that plane has costed the class's programs, the static
  ``_INTENSITY`` table otherwise (the deterministic fallback when the
  plane is off or cold) — weighted by each member's output rows x
  nominal row width, normalized so the shares sum to exactly 1.0;
- explicit dispatch sites (speculative join probe/redo, superstage
  chain steps, exchange splits, flushes) record bounded wall-duration
  samples per site for the per-query p50/p95 dispatch summary.

Keying: profiles live on the exec nodes themselves — plans are
per-query objects, so ``(query_id, stage_id, member_op)`` is recovered
at StatsProfile build time from (event-log query_id, node preorder
index, member position).

Hot-path discipline (this file is on the SYNC001/OBS002 lint scope):
no numpy, no device pulls, no formatted flight-record args; the flush
observer allocates nothing past a node's first-touch accumulator.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from . import compile_watch as _cwatch
from . import flight, timeline
from .registry import (STATS_ATTRIBUTED_DEVICE_SECONDS,
                       STATS_DISPATCH_SECONDS, STATS_FLUSH_SECONDS)

# dispatch-site constants (interned: flight records pass them verbatim)
SITE_FLUSH = "flush"
SITE_CHAIN_STEP = "chain_step"
SITE_SPLIT = "split"
SITE_SPEC_PROBE = "spec_probe"
SITE_SPEC_REDO = "spec_redo"

# compile-bearing windows route to the site's _cold twin so warm
# dispatch percentiles stop absorbing first-call compile walls
# (BENCH_r16 read dispatch_p95_ms = 2155 — that was XLA, not
# dispatch).  Pre-interned: the routing decision allocates nothing.
SITE_FLUSH_COLD = "flush_cold"
_COLD_SITES = {SITE_FLUSH: SITE_FLUSH_COLD,
               SITE_CHAIN_STEP: "chain_step_cold",
               SITE_SPLIT: "split_cold",
               SITE_SPEC_PROBE: "spec_probe_cold",
               SITE_SPEC_REDO: "spec_redo_cold"}
_COLD_SUFFIX = "_cold"

_TLS = threading.local()

#: per-site wall-duration samples (ns), process-wide and bounded;
#: ``begin_query()`` snapshots lengths so summaries stay per-query.
#: list.append is GIL-atomic — only first-touch takes the lock.
_DISPATCH: Dict[str, List[int]] = {}
_DISP_LOCK = threading.Lock()
_DISPATCH_CAP = 1 << 16


class StageProfile:
    """Flush-attributed device time + flush count of one exec node."""

    __slots__ = ("device_ns", "flushes")

    def __init__(self):
        self.device_ns = 0
        self.flushes = 0


def stage_profile(node) -> StageProfile:
    sp = getattr(node, "_stage_profile", None)
    if sp is None:
        sp = node._stage_profile = StageProfile()
    return sp


class attrib_scope:
    """Declare ``node`` the attribution target for flushes forced in
    this region (thread-local stack; innermost scope wins, so a nested
    exchange finalize under a collect drain attributes to the
    exchange).  ``None`` pushes are allowed and mean "unattributed"."""

    __slots__ = ("node",)

    def __init__(self, node):
        self.node = node

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        stack.append(self.node)
        return self

    def __exit__(self, *exc):
        _TLS.stack.pop()
        return False


def _note_dispatch(site: str, dur_ns: int):
    lst = _DISPATCH.get(site)
    if lst is None:
        with _DISP_LOCK:
            lst = _DISPATCH.setdefault(site, [])
    if len(lst) < _DISPATCH_CAP:
        lst.append(dur_ns)


def _cold_site(site: str) -> str:
    cold = _COLD_SITES.get(site)
    if cold is None:  # unknown caller-defined site: intern once
        with _DISP_LOCK:
            cold = _COLD_SITES.setdefault(site, site + _COLD_SUFFIX)
    return cold


#: compile_seq as of the last observed flush — a flush whose window
#: advanced it carried (or directly followed) an XLA compile and lands
#: under flush_cold.  One-element list so the benign-race update stays
#: a plain item write (the _DISPATCH discipline: no lock on this path).
_FLUSH_SEQ = [0]


def _on_flush(dur_ns: int, n_items: int):
    """pending.flush observer: attribute one fused device round trip.

    Runs once per non-empty flush (a handful per warm query): accrue to
    the innermost attribution scope, feed the dispatch summary and the
    two registry instruments, and drop one flight-recorder breadcrumb
    (constant name, plain ints — OBS002)."""
    stack = getattr(_TLS, "stack", None)
    node = stack[-1] if stack else None
    if node is not None:
        sp = stage_profile(node)
        sp.device_ns += dur_ns
        sp.flushes += 1
    seq = _cwatch.compile_seq()
    if seq != _FLUSH_SEQ[0]:
        _FLUSH_SEQ[0] = seq
        site = SITE_FLUSH_COLD
    else:
        site = SITE_FLUSH
    _note_dispatch(site, dur_ns)
    timeline.note_flush(dur_ns)
    STATS_FLUSH_SECONDS.observe(dur_ns / 1e9)
    STATS_ATTRIBUTED_DEVICE_SECONDS.labels(
        attributed="yes" if node is not None else "no").inc(dur_ns / 1e9)
    flight.record(flight.EV_STATS, site, n_items,
                  dur_ns // 1_000_000)


class _DispatchCM:
    """Wall-time one explicit dispatch site (speculative probe/redo,
    superstage chain step, exchange split) into the per-site summary
    and the ``tpu_stats_dispatch_seconds{site}`` histogram.  Windows
    that a compile landed inside (compile_seq advanced) route to the
    site's ``_cold`` twin."""

    __slots__ = ("site", "t0", "c0")

    def __init__(self, site: str):
        self.site = site

    def __enter__(self):
        self.c0 = _cwatch.compile_seq()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        site = self.site if _cwatch.compile_seq() == self.c0 \
            else _cold_site(self.site)
        _note_dispatch(site, dur)
        STATS_DISPATCH_SECONDS.labels(site=site).observe(dur / 1e9)
        return False


def dispatch(site: str) -> _DispatchCM:
    """Pooled per-(thread, site) timing CM — dispatch attribution used
    to allocate one CM object per device dispatch; hot loops now reuse
    a thread-local instance (first use per thread allocates).  Safe
    because no site self-nests on one thread: a reentered CM would
    clobber its own ``t0``."""
    cms = getattr(_TLS, "cms", None)
    if cms is None:
        cms = _TLS.cms = {}
    cm = cms.get(site)
    if cm is None:
        cm = cms[site] = _DispatchCM(site)
    return cm


def begin_query() -> Dict[str, int]:
    """Length snapshot of every site's sample list — the marker
    ``dispatch_summary`` slices from, keeping summaries per-query over
    the process-wide store."""
    with _DISP_LOCK:
        return {site: len(lst) for site, lst in _DISPATCH.items()}


def _pctl(sorted_ns: List[int], q: float) -> float:
    """Nearest-rank percentile in ms over a pre-sorted ns sample."""
    if not sorted_ns:
        return 0.0
    i = min(len(sorted_ns) - 1, int(q * (len(sorted_ns) - 1) + 0.5))
    return sorted_ns[i] / 1e6


def dispatch_summary(marker: Optional[Dict[str, int]] = None) -> Dict:
    """{site: {count, p50_ms, p95_ms}} over samples recorded since
    ``marker`` (a ``begin_query()`` snapshot), plus two roll-ups:
    "all" over the warm sites only, "cold" over the ``*_cold`` twins
    (compile-bearing windows) — so ``dispatch_p95_ms`` prices
    dispatch, not XLA's first call."""
    out: Dict = {}
    merged: List[int] = []
    merged_cold: List[int] = []
    with _DISP_LOCK:
        sites = [(s, list(lst)) for s, lst in _DISPATCH.items()]
    for site, lst in sorted(sites):
        lo = (marker or {}).get(site, 0)
        samples = sorted(lst[lo:])
        if not samples:
            continue
        (merged_cold if site.endswith(_COLD_SUFFIX)
         else merged).extend(samples)
        out[site] = {"count": len(samples),
                     "p50_ms": round(_pctl(samples, 0.5), 3),
                     "p95_ms": round(_pctl(samples, 0.95), 3)}
    if merged:
        merged.sort()
        out["all"] = {"count": len(merged),
                      "p50_ms": round(_pctl(merged, 0.5), 3),
                      "p95_ms": round(_pctl(merged, 0.95), 3)}
    if merged_cold:
        merged_cold.sort()
        out["cold"] = {"count": len(merged_cold),
                       "p50_ms": round(_pctl(merged_cold, 0.5), 3),
                       "p95_ms": round(_pctl(merged_cold, 0.95), 3)}
    return out


# ---------------------------------------------------------------------------
# member apportioning: deterministic time shares inside a fused stage
# ---------------------------------------------------------------------------

#: FALLBACK per-output-row FLOP+byte intensity by operator class,
#: used only when the cost plane (obs/costplane.py) has no live XLA
#: measurement for the class's programs (plane disabled, or nothing
#: compiled yet).  When the plane is warm, ``_intensity()`` prefers
#: ``costplane.measured_intensity()`` — (flops + bytes accessed) per
#: bucket row from the captured ``cost_analysis()`` records,
#: normalized to the fused_project program.  Coarse on purpose:
#: rows x row-width carries the data-dependent scale, this factor
#: only ranks operator classes.  Contract: both paths return a
#: strictly positive float and the static ranks below stay aligned
#: with the measured ranks (cross-checked in tests/test_costplane.py).
_INTENSITY = (
    ("sort", 8.0), ("topn", 8.0), ("join", 6.0), ("aggregate", 5.0),
    ("agg", 5.0), ("exchange", 3.0), ("filter", 1.5), ("project", 1.0),
    ("scan", 1.0), ("limit", 0.5), ("range", 0.5),
)

#: nominal row width per dtype name (values + 1 validity byte); strings
#: use a fixed nominal payload so the weight model stays deterministic
#: across speculative/exact capacities
_NOMINAL_WIDTH = {"boolean": 1, "tinyint": 1, "smallint": 2, "int": 4,
                  "bigint": 8, "float": 4, "double": 8, "date": 4,
                  "timestamp": 8, "string": 16, "null": 0}


def _intensity(name: str) -> float:
    low = name.lower()
    # measured weight first: the cost plane's live per-row XLA cost
    # for this operator class (None when the plane is off/cold — the
    # static table below is the deterministic fallback)
    try:
        from . import costplane as _costplane
        measured = _costplane.measured_intensity(low)
        if measured is not None and measured > 0.0:
            return float(measured)
    except Exception:  # noqa: BLE001 — attribution never fails a query
        pass
    for key, factor in _INTENSITY:
        if key in low:
            return factor
    return 2.0


def _nominal_row_bytes(schema) -> float:
    if schema is None or not len(schema):
        return 8.0
    total = 0.0
    for f in schema:
        total += _NOMINAL_WIDTH.get(f.dtype.name, 8) + 1
    return total


def _resolved_metric(node, metric_name: str) -> int:
    """A metric's value WITHOUT forcing a flush: deferred device counts
    still unresolved after the query's final flush are skipped rather
    than pulled (the stats plane must never add a round trip)."""
    ms = getattr(node, "metrics", None)
    if ms is None:
        return 0
    m = ms._metrics.get(metric_name)
    if m is None:
        return 0
    total = int(m._value)
    pend = m._pending
    if pend:
        for p in pend:
            staged = getattr(p, "_staged", None)
            if getattr(p, "_val", None) is not None or \
                    (staged is not None and staged.resolved):
                total += int(p)
            elif isinstance(p, int):
                total += p
    return total


def member_shares(stage) -> Dict[str, float]:
    """Deterministic per-member apportioning of a fused stage's
    attributed device time: weight_i = intensity(class) x max(output
    rows, 1) x nominal row width, normalized so the shares sum to
    exactly 1.0.  Keys are "<member-index>:<node name>" in region
    order (matching the lowering order the stage prints)."""
    weights = []
    for i, m in enumerate(stage.members):
        rows = _resolved_metric(m, "numOutputRows")
        width = _nominal_row_bytes(getattr(m, "output_schema", None))
        weights.append((f"{i}:{m.name}",
                        _intensity(m.name) * float(max(rows, 1)) * width))
    total = sum(w for _n, w in weights)
    if total <= 0.0:
        n = max(len(weights), 1)
        return {name: 1.0 / n for name, _w in weights}
    return {name: w / total for name, w in weights}


def install():
    """Install the flush observer into the pending pool (idempotent;
    called from obs/__init__ at import)."""
    from ..columnar import pending
    pending._FLUSH_OBSERVER = _on_flush


def reset_dispatches():
    """Test hook: drop all recorded dispatch samples."""
    with _DISP_LOCK:
        _DISPATCH.clear()
