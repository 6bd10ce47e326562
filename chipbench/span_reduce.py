"""From the engine's coarse spans to host time by layer.

The engine records a coarse span at each layer boundary (``srt.sql.*``,
``srt.plan``, ``srt.query``, ``srt.exec.<Node>``, ``srt.jit_build``,
``srt.flush``, ``srt.pull``, ``srt.obs.assemble``, ...) into a bounded
ring, always, and this module is the one place the benchmark reads it.
The contract with the engine is one call each:

- ``spark_rapids_tpu.obs.trace.coarse_spans(since_ns)`` -> a list of
  ``{"id", "parent", "name", "t0_ns", "dur_ns", "thread", "query",
  "args"}`` on the ``time.perf_counter_ns`` clock, or ``None`` when the
  ring wrapped past ``since_ns`` (it says so on stderr);
- ``spark_rapids_tpu.obs.trace.coarse_counts()`` -> ``{query number:
  {counter name: count}}`` of the last 64 queries.

An engine without them (the parent of the PR that added them) gives
``None`` everywhere here and the metric is left out of the line.

Attribution: a record of ``run["queries"]`` holds ``done`` and
``seconds`` on ``time.perf_counter``, the ring's clock, so the query's
interval is ``[done - seconds, done]``.  A span belongs to the query in
whose interval it starts.  A span's self time is its duration less what
its children (spans naming it as ``parent``; nesting is per thread) cover
of it, overlaps counted once.  ``layer_ms`` sums self time by name
prefix over the window's queries and divides by their number.

A rehearsal (``run["peaks"]`` is None: no chip) reports none of these:
its host times are another machine's, and
``tests/test_rehearsal.py`` pins the metrics a CPU run prints.
``tests/test_span_reduce.py`` drives the reduction on a CPU run itself.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

#: XLA module names of the engine's operator programs (``jax.jit`` of a
#: function named ``<operator>_<role>``)
OPERATOR_PROGRAMS = ("jit_agg_", "jit_join_", "jit_sort_", "jit_filter_",
                     "jit_hash_", "jit_scan_", "jit_staged_", "jit_fused_",
                     "jit_batch_", "jit_partition_", "jit_pending_",
                     "jit_str_", "jit_list_", "jit_mesh_", "jit_stats_")

#: the last run reduced and its window (a run has several readers)
_LAST: list = [None, None]


def query_intervals(run: dict) -> List[Tuple[int, int]]:
    return [(int((r["done"] - r["seconds"]) * 1e9), int(r["done"] * 1e9))
            for r in run["queries"]]


def self_times(spans: Sequence[dict]) -> Dict[int, int]:
    """``{span id: ns of the span no child covers}``."""
    kids: Dict[int, list] = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    out = {}
    for s in spans:
        t0, t1 = s["t0_ns"], s["t0_ns"] + s["dur_ns"]
        covered, edge = 0, t0
        for c in sorted(kids.get(s["id"], ()), key=lambda c: c["t0_ns"]):
            c0 = max(c["t0_ns"], edge)
            c1 = min(c["t0_ns"] + c["dur_ns"], t1)
            if c1 > c0:
                covered += c1 - c0
                edge = c1
        out[s["id"]] = s["dur_ns"] - covered
    return out


def in_window(spans: Sequence[dict], intervals) -> List[dict]:
    """The spans that start inside one of ``intervals``."""
    return [s for s in spans
            if any(a <= s["t0_ns"] <= b for a, b in intervals)]


def window(run: dict) -> Optional[dict]:
    """The window's spans, their self times and the window's query
    numbers; None when the engine has no ring, the ring wrapped, or the
    run is a rehearsal.  Read once per run."""
    if _LAST[0] is not run:
        _LAST[:] = [run, _window(run)]
    return _LAST[1]


def _window(run: dict) -> Optional[dict]:
    if not run.get("peaks") or not run["queries"]:
        return None
    try:
        from spark_rapids_tpu.obs import trace
    except ImportError:
        return None
    read = getattr(trace, "coarse_spans", None)
    if read is None:
        return None
    intervals = query_intervals(run)
    spans = read(min(a for a, _ in intervals))
    if spans is None:
        return None
    return reduce_spans(spans, intervals, trace.coarse_counts())


def reduce_spans(spans: Sequence[dict], intervals, counts: dict) -> dict:
    selfs = self_times(spans)
    mine = in_window(spans, intervals)
    queries = {s["query"] for s in mine if s["name"] == "srt.query"}
    return {"spans": mine, "self_ns": selfs, "n_queries": len(intervals),
            "counts": {q: counts[q] for q in queries if q in counts}}


def layer_ms(run: dict, prefixes: Sequence[str],
             self_time: bool = True) -> Optional[float]:
    """Mean ms per query of the spans whose name starts with one of
    ``prefixes``: self time, or whole durations."""
    w = window(run)
    if w is None:
        return None
    total = sum(w["self_ns"][s["id"]] if self_time else s["dur_ns"]
                for s in w["spans"] if s["name"].startswith(tuple(prefixes)))
    return total / 1e6 / w["n_queries"]


def spans_per_query(run: dict, name: str) -> Optional[float]:
    w = window(run)
    if w is None:
        return None
    return sum(1 for s in w["spans"] if s["name"] == name) / w["n_queries"]


def counts_per_query(run: dict, prefix: str) -> Optional[float]:
    """Mean per query of the engine's per-query counters named
    ``prefix*``, over the window's query numbers."""
    w = window(run)
    if w is None:
        return None
    total = sum(n for tbl in w["counts"].values() for name, n in tbl.items()
                if name.startswith(prefix))
    return total / w["n_queries"]


def query_self_share(run: dict) -> Optional[float]:
    """Largest share of a query's ``srt.query`` span that no child span
    covers, over the window: the host time in a query that no layer's
    span accounts for (the check that no layer lacks its span)."""
    w = window(run)
    if w is None:
        return None
    shares = [w["self_ns"][s["id"]] / s["dur_ns"] for s in w["spans"]
              if s["name"] == "srt.query" and s["dur_ns"]]
    return max(shares) if shares else None


def device_ms_per_query(run: dict, prefix: str) -> Optional[float]:
    """Device time of the traced pass's programs named ``prefix*`` per
    traced query, from ``run["trace"]["device_ops"]``.  That list holds
    the ten programs with the most device time: a program that falls off
    it is not counted, so this is a lower bound (0 when every such
    program is below the tenth).  None without a device trace, or when
    no program in the list carries an operator prefix at all: an engine
    that does not name its programs."""
    t = run.get("trace")
    if not t or not t.get("queries"):
        return None
    names = [name for name, _ in t["device_ops"]]
    if not any(n.startswith(OPERATOR_PROGRAMS) for n in names):
        return None
    return sum(sec for name, sec in t["device_ops"]
               if name.startswith(prefix)) * 1e3 / len(t["queries"])
