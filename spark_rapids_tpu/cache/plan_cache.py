"""Fingerprint-keyed plan cache — repeat shapes skip the expensive
planner tail.

Production traffic is repeat-heavy: the same dashboard/report shapes
arrive all day with only their literals changing.  PR 15 gave every
shape a stable identity (``obs/fingerprint.py``); this module consumes
it.  Entries are keyed by the literal-normalized **logical** shape
digest — computable before any planning work — and scoped to the conf
fingerprint they were planned under.

What a hit actually replays — the certificate contract
------------------------------------------------------
A physical plan OBJECT cannot be reused across queries: its nodes
embed the query's literal values, accumulate runtime metrics, and
shuffle exchanges carry materialization state and locks.  The cache
therefore stores a shape's **analysis certificates** — the verifier
verdict (implicit: only verified plans are stored), the physical
``plan_fingerprint``, the PV-FLUSH prediction's contributions, the
planner's fallback and parallelism decisions, and the cold planner
latency.  A hit re-runs only the cheap structural pipeline
(prune → tag → CBO → convert → collapse → carve) on the INCOMING
logical plan — fresh literals are correct by construction — while the
two invariant-verifier passes (PV defaults + PV-STAGE) and the
flush-budget walk are skipped, and the stored ``FlushPrediction`` is
re-attached to the rebuilt tree so the PV-FLUSH exactness contract
holds unchanged on the cached path.

Safety net: the rebuilt plan's fingerprint must equal the stored one;
any divergence drops the entry and falls back to the full cold path
(counted as ``validation_miss``, never trusted).

Invalidation: a plan-affecting conf change under a cached shape drops
the entry (``invalidated``) and the cold path re-runs the verifier
from scratch.  Capacity: a bounded LRU (``maxEntries``), oldest-use
evicted first.

Pure host arithmetic; lock discipline: dict bookkeeping under
``_LOCK``, planning always outside it (LOCK001).
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from ..obs.fingerprint import (conf_fingerprint, logical_shape,
                               plan_fingerprint)
from ..obs import trace as _trace
from ..obs.registry import PLAN_CACHE_EVENTS

_LOCK = threading.Lock()
_ENTRIES: "OrderedDict[str, Dict]" = OrderedDict()
_ENABLED = True
_MAX_ENTRIES = 256
_HITS = 0
_MISSES = 0
_VALIDATION_MISSES = 0
_INVALIDATED = 0
_EVICTED = 0


def shape_key(logical) -> str:
    """Conf-independent cache key: digest of the literal-normalized
    logical shape text (``WHERE x > 5`` and ``WHERE x > 7`` share a
    key; any structural change moves it).  Conf scoping lives in the
    entry's stored ``conf_fp``, so a conf change is an explicit
    invalidation event rather than a silent key miss."""
    return hashlib.sha256(
        logical_shape(logical).encode()).hexdigest()[:16]


def _limits(conf) -> Tuple[bool, int]:
    from ..config import CACHE_PLAN_ENABLED, CACHE_PLAN_MAX_ENTRIES
    return (_ENABLED and bool(conf.get(CACHE_PLAN_ENABLED)),
            max(1, int(conf.get(CACHE_PLAN_MAX_ENTRIES))))


def plan_with_cache(logical, conf):
    """Plan ``logical`` under ``conf`` through the cache.  Returns
    ``(phys, planner)`` — the planner for its ``fallbacks`` /
    ``parallelism_warnings``, exactly like a direct ``Planner`` use
    (the structural pipeline runs on BOTH paths, so both are always
    populated for the actual incoming plan).

    Stamps on the returned physical root:

    - ``_plan_cache_flush_pred``: the :class:`FlushPrediction` to
      replay — stored contributions re-attached on a hit, freshly
      computed once on a miss; ``api/session.py`` prefers this over
      re-running ``predict_flushes``.
    - ``_plan_cache_status``: ``(status, planner_path_ms)`` for the
      event log and report header (absent when the cache is off): the
      look-up or planner alone; the coarse span ``srt.plan`` covers the
      whole call, shape key and conf fingerprint included.
    """
    with _trace.span("srt.plan", "planner", True) as sp:
        phys, planner = _plan_with_cache(logical, conf)
        status = getattr(phys, "_plan_cache_status", None)
        sp.set(cache=status[0] if status else "off")
    return phys, planner


def _plan_with_cache(logical, conf):
    global _HITS, _MISSES, _VALIDATION_MISSES, _INVALIDATED, _EVICTED
    from ..analysis.flush_budget import FlushPrediction, predict_flushes
    from ..plan.overrides import Planner
    enabled, max_entries = _limits(conf)
    if not enabled:
        planner = Planner(conf)
        return planner.plan(logical), planner
    key = shape_key(logical)
    cfp = conf_fingerprint(conf)
    invalidated_now = False
    with _LOCK:
        entry = _ENTRIES.get(key)
        if entry is not None and entry["conf_fp"] != cfp:
            # a plan-affecting conf moved under this shape: the stored
            # certificates no longer apply — drop them; the cold path
            # below re-runs the invariant verifier from scratch
            del _ENTRIES[key]
            _INVALIDATED += 1
            invalidated_now = True
            entry = None
        snap = dict(entry) if entry is not None else None
    if invalidated_now:
        PLAN_CACHE_EVENTS.labels(event="invalidated").inc()
    t0 = time.perf_counter()
    if snap is not None:
        planner = Planner(conf)
        phys = planner.plan(logical, skip_verify=True)
        if plan_fingerprint(phys, conf) == snap["plan_fingerprint"]:
            ms = (time.perf_counter() - t0) * 1000.0
            phys._plan_cache_flush_pred = FlushPrediction(
                phys, snap["contributions"])
            phys._plan_cache_status = ("hit", ms)
            with _LOCK:
                live = _ENTRIES.get(key)
                if live is not None:
                    live["hits"] += 1
                    live["warm_ms"] = ms
                    _ENTRIES.move_to_end(key)
                _HITS += 1
            PLAN_CACHE_EVENTS.labels(event="hit").inc()
            return phys, planner
        # the rebuilt plan diverged from its certificate — never trust
        # it: drop the entry and take the fully verified cold path
        with _LOCK:
            _ENTRIES.pop(key, None)
            _VALIDATION_MISSES += 1
        PLAN_CACHE_EVENTS.labels(event="validation_miss").inc()
        t0 = time.perf_counter()
    planner = Planner(conf)
    phys = planner.plan(logical)
    pred: Optional[FlushPrediction] = None
    try:
        pred = predict_flushes(phys, conf=conf)
    except Exception:  # noqa: BLE001 - prediction is observability
        pred = None
    ms = (time.perf_counter() - t0) * 1000.0
    phys._plan_cache_status = ("miss", ms)
    evicted = 0
    if pred is not None:
        # only shapes with an exact flush certificate are cacheable:
        # a hit MUST replay a prediction, so a shape the predictor
        # cannot cover is re-planned cold every time
        phys._plan_cache_flush_pred = pred
        entry = {
            "conf_fp": cfp,
            "plan_fingerprint": plan_fingerprint(phys, conf),
            "contributions": list(pred.contributions),
            "fallbacks": list(planner.fallbacks),
            "parallelism_warnings": list(planner.parallelism_warnings),
            "cold_ms": ms,
            "warm_ms": None,
            "hits": 0,
        }
        with _LOCK:
            _ENTRIES[key] = entry
            _ENTRIES.move_to_end(key)
            _MISSES += 1
            while len(_ENTRIES) > max_entries:
                _ENTRIES.popitem(last=False)
                _EVICTED += 1
                evicted += 1
    else:
        with _LOCK:
            _MISSES += 1
    PLAN_CACHE_EVENTS.labels(event="miss").inc()
    for _ in range(evicted):
        PLAN_CACHE_EVENTS.labels(event="evicted").inc()
    return phys, planner


def entry_for(logical, conf) -> Optional[Dict]:
    """Read-only peek for the admission scheduler: the certificate
    record cached for this logical shape under this conf, or None (no
    entry, or the conf fingerprint moved).  Never mutates LRU order or
    counters — admission-time prediction must not perturb the cache."""
    enabled, _ = _limits(conf)
    if not enabled:
        return None
    key = shape_key(logical)
    cfp = conf_fingerprint(conf)
    with _LOCK:
        e = _ENTRIES.get(key)
        if e is None or e["conf_fp"] != cfp:
            return None
        return dict(e)


def entry_count() -> int:
    """Resident shapes — the ``tpu_plan_cache_entries`` gauge."""
    with _LOCK:
        return len(_ENTRIES)


def top_entries(n: int = 5) -> List[Dict]:
    """Most-hit cached shapes, for the dashboard panel and report."""
    with _LOCK:
        snap = [(k, dict(e)) for k, e in _ENTRIES.items()]
    snap.sort(key=lambda kv: kv[1]["hits"], reverse=True)
    return [{
        "digest": k,
        "plan_fingerprint": e["plan_fingerprint"],
        "hits": e["hits"],
        "cold_ms": round(e["cold_ms"], 3),
        "warm_ms": (round(e["warm_ms"], 3)
                    if e["warm_ms"] is not None else None),
    } for k, e in snap[:max(0, n)]]


def stats_section() -> Dict:
    """The ``plan_cache`` section of ``Service.stats().snapshot()``."""
    with _LOCK:
        entries = len(_ENTRIES)
        hits, misses = _HITS, _MISSES
        vmiss, inval, evict = _VALIDATION_MISSES, _INVALIDATED, _EVICTED
    lookups = hits + misses
    return {
        "enabled": _ENABLED,
        "entries": entries,
        "max_entries": _MAX_ENTRIES,
        "hits": hits,
        "misses": misses,
        "validation_misses": vmiss,
        "invalidated": inval,
        "evicted": evict,
        "hit_pct": round(hits / lookups * 100.0, 1) if lookups else 0.0,
        "top": top_entries(5),
    }


def configure(conf) -> None:
    """Apply the ``spark.rapids.tpu.cache.plan.*`` conf group (called
    by QueryService.__init__; the flags are ALSO honored per planning
    call from the query's own conf, so a session overlay can opt out
    without touching process-wide state)."""
    global _ENABLED, _MAX_ENTRIES
    from ..config import CACHE_PLAN_ENABLED, CACHE_PLAN_MAX_ENTRIES
    _ENABLED = bool(conf.get(CACHE_PLAN_ENABLED))
    _MAX_ENTRIES = max(1, int(conf.get(CACHE_PLAN_MAX_ENTRIES)))
    evicted = 0
    with _LOCK:
        while len(_ENTRIES) > _MAX_ENTRIES:
            _ENTRIES.popitem(last=False)
            evicted += 1
    for _ in range(evicted):
        PLAN_CACHE_EVENTS.labels(event="evicted").inc()


def reset() -> None:
    """Test hook: drop all entries and counters."""
    global _HITS, _MISSES, _VALIDATION_MISSES, _INVALIDATED, _EVICTED
    with _LOCK:
        _ENTRIES.clear()
        _HITS = _MISSES = _VALIDATION_MISSES = 0
        _INVALIDATED = _EVICTED = 0
