"""Device-compute cost plane — roofline attribution + padding waste.

Six planes price every *gap* around device work (compile, shuffle host
drop, spill, queueing); this one opens up the busy time itself.  The
doctor's gated verdict has been ``device_compute`` at ~50% since r12,
and ROADMAP item 4 (Pallas-native operator core) needs a measured
target list, not a hunch.  Three joined ledgers provide it:

- **static-cost store** — at every JIT-cache first call (the
  ``compile_watch.wrap_miss`` choke point: inline miss, AOT warmup and
  persistent-cache load alike) ``capture()`` runs XLA cost analysis on
  the *lowered* program (``Lowered.cost_analysis()`` — trace-only, no
  second backend compile, no device work) and stores flops / bytes
  accessed / IO working set per (program, bucket), bounded at
  ``spark.rapids.tpu.obs.cost.maxRecords``;
- **dispatch ledger** — every ``aot.note_demand`` forwards (program,
  bucket, effective rows) here; rows are read only when the host
  already knows them without a flush (the ``_rows_if_resolved``
  discipline from obs/stats.py), so padding waste = 1 - rows/capacity
  prices the AOT lattice's ``bucketRatio`` with zero round trips;
- **roofline join** — ``query_summary()`` apportions the flush-observer
  busy window (obs/timeline.py, PR 7) over the query's dispatches by
  each program's roofline time estimate max(flops/peak_flops,
  bytes/peak_bw), yielding per-program achieved FLOP/s, achieved GB/s,
  arithmetic intensity and a ``compute_bound``/``memory_bound``
  verdict against the conf-declared peaks.

The doctor (obs/doctor.py) decomposes its ``device_compute`` share
into compute_bound / memory_bound / padding_waste sub-causes from this
plane's summary; obs/profile.py replaces its hand-maintained static
``_INTENSITY`` factors with ``measured_intensity()`` when the store
has live records for an operator class.

``stable_digest()`` covers only the MODEL — version, declared peaks,
ridge intensity, verdict + waste rules — never timings or the
execution-shape-dependent program set, so it is stable across pipeline
parallelism {1,4} x superstage on/off (the plane-determinism
acceptance contract every plane pins).

Hot-path discipline (this file is on the SYNC001/OBS002 lint scope):
no numpy, no device pulls, no formatted flight-record args;
``note_dispatch`` is plain int arithmetic on an interned-key dict and
``capture`` runs at most once per (program, bucket) for the life of
the process.
"""
from __future__ import annotations

import hashlib
import json
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import flight
from . import overhead as _overhead

MODEL_VERSION = 1

#: roofline verdict constants (interned: flight/registry label values)
VERDICT_COMPUTE = "compute_bound"
VERDICT_MEMORY = "memory_bound"

#: capture-origin constants (which path paid for the first call)
ORIGIN_MISS = "miss"
ORIGIN_WARMUP = "warmup"
ORIGIN_PERSISTENT = "persistent"

#: capture-source constants: live XLA cost analysis vs the
#: deterministic static fallback (profile._INTENSITY model) used when
#: lowering is unavailable (non-jit callable, exotic kernel)
SOURCE_XLA = "xla"
SOURCE_STATIC = "static"

_ENABLED = True
#: explicit conf overrides of the roofline ceilings (FLOP/s, bytes/s);
#: None = take that ceiling from device_peaks.TABLE by device_kind
_PEAK_CONF: Tuple[Optional[float], Optional[float]] = (None, None)
#: resolved (FLOP/s, bytes/s, source) for the current _PEAK_CONF
_PEAKS: Optional[Tuple[float, float, str]] = None
_MAX_RECORDS = 256

_LOCK = threading.Lock()
_TLS = threading.local()

#: (program, bucket) -> {"flops", "bytes", "io_bytes", "origin",
#: "source"} — the bounded static-cost store.  First capture wins;
#: a later live capture upgrades a static-fallback record.
_COSTS: Dict[Tuple[str, int], Dict[str, Any]] = {}
_DROPPED = 0
#: capture attempts by source ("xla"/"static") plus skips of
#: already-costed pairs
_CAPTURES = {SOURCE_XLA: 0, SOURCE_STATIC: 0, "skipped": 0}

#: (program, bucket) -> [dispatches, rows_known_dispatches, rows_sum]
#: — process-wide dispatch ledger; ``begin_query()`` snapshots the
#: cells so summaries stay per-query.  Item updates are GIL-atomic;
#: only first-touch takes the lock (the obs/profile.py discipline).
_DISPATCH: Dict[Tuple[str, int], List[int]] = {}
_DISPATCH_DROPPED = 0

#: last query_summary() roll-up (achieved rates for the Prometheus
#: gauges + Service.stats())
_LAST: Dict[str, Any] = {}

#: the wrap_miss cache name "hash_aggregate" is shared by the three
#: aggregate program variants (grouped / whole-stage / global) — one
#: trace cache, three auditor names.  Coverage accounting maps the
#: cache onto every program it compiles (mirrors the PR 10 auditor's
#: REQUIRED_PROGRAMS naming).
_CACHE_COVERS = {
    "hash_aggregate": ("hash_aggregate_grouped",
                       "hash_aggregate_whole_stage",
                       "hash_aggregate_global"),
}

#: operator class -> the JIT caches whose measured per-row cost prices
#: it (substring match discipline identical to profile._INTENSITY, so
#: measured and static factors answer the same lookup)
_CLASS_CACHES = (
    ("sort", ("mesh_sort",)),
    ("topn", ("mesh_sort",)),
    ("join", ("join_probe", "join_spec_probe", "mesh_join")),
    ("aggregate", ("hash_aggregate", "mesh_aggregate")),
    ("agg", ("hash_aggregate", "mesh_aggregate")),
    ("exchange", ("exchange_stats",)),
    ("filter", ("fused_project",)),
    ("project", ("fused_project",)),
    ("scan", ("fused_project",)),
    ("limit", ("fused_project",)),
    ("range", ("fused_project",)),
)


# ---------------------------------------------------------------------------
# static-cost capture (JIT-cache first calls — cold path by definition)
# ---------------------------------------------------------------------------

def _leaves_of(args, kwargs) -> list:
    try:
        import jax
        return jax.tree_util.tree_leaves((args, kwargs))
    except Exception:  # noqa: BLE001 — capture never fails the call
        return []


def _has_tracer(leaves) -> bool:
    """True when the call is itself being traced (the program auditor
    runs make_jaxpr through wrapped callables) — nothing real to cost,
    and lowering tracer args would raise."""
    try:
        import jax
        return any(isinstance(leaf, jax.core.Tracer) for leaf in leaves)
    except Exception:  # noqa: BLE001
        return False


def _bucket_of(leaves) -> int:
    """Leading-dim capacity of the widest array argument — the bucket
    the program was compiled for.  Derived from the call args, so the
    attribution is identical for miss/warmup/persistent origins (the
    demand ledger's thread-local is stale during warmup)."""
    best = 0
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape and len(shape) >= 1:
            try:
                n = int(shape[0])
            except (TypeError, ValueError):
                continue
            if n > best:
                best = n
    return best


def _static_fallback(cache: str, bucket: int) -> Tuple[float, float]:
    """Deterministic (flops, bytes) estimate from the static operator-
    class intensity table (obs/profile.py) — the fallback for programs
    whose lowering refuses cost analysis.  8 flops and 16 bytes per
    row per intensity unit: coarse on purpose, it only has to rank."""
    from . import profile as _profile
    factor = float(_profile._intensity(cache))
    rows = float(max(bucket, 1))
    return factor * rows * 8.0, factor * rows * 16.0


def capture(cache: str, fn: Callable, args: tuple, kwargs: dict,
            origin: str = ORIGIN_MISS) -> bool:
    """Capture XLA static cost analysis for one freshly first-called
    program into the (program, bucket) store.  Runs on the compile
    path (seconds-scale already) — the analysis itself is a host-side
    pass over the *unoptimized lowered* HLO: no second backend
    compile, no device work, no flush.  Returns False only when the
    call must be retried later (traced args); True when handled."""
    if not _ENABLED or getattr(_TLS, "capturing", False):
        return True
    leaves = _leaves_of(args, kwargs)
    if _has_tracer(leaves):
        return False
    bucket = _bucket_of(leaves)
    key = (cache, bucket)
    with _LOCK:
        prior = _COSTS.get(key)
    if prior is not None and prior["source"] == SOURCE_XLA:
        _CAPTURES["skipped"] += 1
        return True
    _TLS.capturing = True
    try:
        flops, byts, io_bytes, source = 0.0, 0.0, 0.0, SOURCE_STATIC
        try:
            lowered = fn.lower(*args, **kwargs)
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops", 0.0) or 0.0)
            byts = float(ca.get("bytes accessed", 0.0) or 0.0)
            # per-operand + output splits ("bytes accessed0{}",
            # "bytes accessedout{}") bound the program's HBM-touched
            # working set; the allocator-truth peak stays with the
            # memplane
            io_bytes = float(sum(
                v for k, v in ca.items()
                if k.startswith("bytes accessed")
                and k != "bytes accessed"))
            source = SOURCE_XLA
        except Exception:  # noqa: BLE001 — cost capture never fails
            flops, byts = _static_fallback(cache, bucket)
            io_bytes = byts
        rec = {"flops": flops, "bytes": byts, "io_bytes": io_bytes,
               "origin": origin, "source": source}
        global _DROPPED
        with _LOCK:
            prior = _COSTS.get(key)
            if prior is not None and prior["source"] == SOURCE_XLA:
                _CAPTURES["skipped"] += 1
                return True
            if prior is None and len(_COSTS) >= _MAX_RECORDS:
                _DROPPED += 1
                return True
            _COSTS[key] = rec
        _CAPTURES[source] += 1
        flight.record(flight.EV_COST, name=cache, a=bucket,
                      b=int(flops))
        try:
            from .registry import COST_CAPTURES
            COST_CAPTURES.labels(source=source).inc()
        except Exception:  # noqa: BLE001 — metrics never fail capture
            pass
        return True
    finally:
        _TLS.capturing = False


def wrap_capture(cache: str, fn: Callable) -> Callable:
    """First-call cost capture for JIT caches that do not route
    through ``compile_watch.wrap_miss`` (the speculative join probes,
    the exchange stats sketch).  Warm calls pay one list-index check —
    the wrap_miss overhead contract."""
    done = [False]

    def _capturing(*args, **kwargs):
        out = fn(*args, **kwargs)
        if not done[0] and capture(cache, fn, args, kwargs,
                                   origin=ORIGIN_MISS):
            done[0] = True
        return out

    return _capturing


# ---------------------------------------------------------------------------
# dispatch ledger (hot path: one call per batch per program)
# ---------------------------------------------------------------------------

def rows_if_resolved(batch) -> Optional[int]:
    """The batch's host row count IF knowable without a flush: a plain
    int, an already-memoized lazy count, or a resolved staged value.
    Anything still device-pending is skipped, never pulled (the
    zero-round-trip contract every plane carries)."""
    try:
        r = batch.rows_lazy
    except Exception:  # noqa: BLE001 — shape-only callers lack rows
        return None
    if isinstance(r, int):
        return r
    v = getattr(r, "_val", None)
    if v is not None:
        return int(v)
    st = getattr(r, "_staged", None)
    if st is not None and st.resolved:
        return int(r)
    return None


def note_dispatch(cache: str, capacity: int,
                  rows: Optional[int] = None) -> None:
    """One program dispatch at a bucketed capacity (forwarded from
    ``aot.note_demand``).  ``rows`` is the effective row count when
    the host already knows it; padded-capacity waste accrues only over
    rows-known dispatches so the fraction is exact, never guessed."""
    if not _ENABLED:
        return
    _mt0 = _overhead.clock()
    key = (cache, int(capacity))
    cell = _DISPATCH.get(key)
    if cell is None:
        global _DISPATCH_DROPPED
        with _LOCK:
            if len(_DISPATCH) >= _MAX_RECORDS:
                _DISPATCH_DROPPED += 1
                return
            cell = _DISPATCH.setdefault(key, [0, 0, 0])
    cell[0] += 1
    if rows is not None:
        cell[1] += 1
        cell[2] += int(rows)
    _overhead.note(_overhead.P_COST, _mt0)


# ---------------------------------------------------------------------------
# roofline model
# ---------------------------------------------------------------------------

def peaks() -> Tuple[float, float, str]:
    """The roofline ceilings as (FLOP/s, bytes/s, source).

    Both conf keys set -> ``"conf"``.  Otherwise the published row for
    this process's device (device_peaks.TABLE keyed by ``device_kind``;
    the CPU test mesh borrows a named model constant) fills whichever
    ceiling the conf leaves open.  An accelerator missing from the
    table raises ``device_peaks.UnknownDeviceError``: a share against
    another chip's ceiling is a wrong number that looks right."""
    global _PEAKS
    if _PEAKS is None:
        flops, byts = _PEAK_CONF
        if flops is not None and byts is not None:
            _PEAKS = (flops, byts, "conf")
        else:
            import jax
            from .. import device_peaks
            row = device_peaks.lookup(jax.devices()[0])
            source = f"device_table:{row.device_kind}"
            if flops is not None or byts is not None:
                source += "+conf"
            _PEAKS = (flops if flops is not None
                      else row.bf16_tflops * 1e12,
                      byts if byts is not None else row.hbm_gbps * 1e9,
                      source)
    return _PEAKS


def ridge_intensity() -> float:
    """flops/byte at the roofline ridge: programs below it cannot
    reach peak FLOP/s no matter how good the kernel is."""
    flops, byts, _src = peaks()
    return flops / byts


def roofline_verdict(flops: float, byts: float) -> str:
    """compute_bound when arithmetic intensity clears the ridge;
    memory_bound below it (including the degenerate zero-flop
    program, which can only be waiting on bytes)."""
    if byts <= 0.0:
        return VERDICT_COMPUTE if flops > 0.0 else VERDICT_MEMORY
    return (VERDICT_COMPUTE
            if flops / byts >= ridge_intensity() else VERDICT_MEMORY)


def _t_est_s(flops: float, byts: float) -> float:
    """Roofline execution-time estimate: the binding ceiling's wall
    seconds.  Floor keeps zero-cost records from vanishing out of the
    busy apportionment."""
    peak_flops, peak_bytes, _src = peaks()
    t = max(flops / peak_flops, byts / peak_bytes)
    return t if t > 0.0 else 1e-12


# ---------------------------------------------------------------------------
# per-query window
# ---------------------------------------------------------------------------

def begin_query() -> Dict[Tuple[str, int], Tuple[int, int, int]]:
    """Snapshot the dispatch ledger so ``query_summary`` can delta a
    per-query window out of the process-wide cells (the FLUSH_COUNT
    discipline: exact when queries run serially)."""
    if not _ENABLED:
        return {}
    with _LOCK:
        return {k: (c[0], c[1], c[2]) for k, c in _DISPATCH.items()}


def query_summary(marker, busy_ms: Optional[float] = None
                  ) -> Dict[str, Any]:
    """Join the window's dispatches with the static-cost store and the
    flush-observer busy window into the per-query costplane artifact.
    Pure host arithmetic over dicts already in hand — zero flushes."""
    marker = marker or {}
    with _LOCK:
        deltas = []
        for key, cell in _DISPATCH.items():
            prev = marker.get(key, (0, 0, 0))
            d = cell[0] - prev[0]
            if d <= 0:
                continue
            deltas.append((key, d, cell[1] - prev[1],
                           cell[2] - prev[2]))
        costs = {k: dict(v) for k, v in _COSTS.items()}
    busy_s = (busy_ms or 0.0) / 1e3
    entries: List[Dict[str, Any]] = []
    uncosted = 0
    weights: List[float] = []
    for (cache, bucket), d, known, rows_sum in sorted(deltas):
        rec = costs.get((cache, bucket))
        waste = None
        if known > 0 and bucket > 0:
            waste = max(0.0, 1.0 - rows_sum / float(known * bucket))
        if rec is None:
            uncosted += d
            entries.append({
                "program": cache, "bucket": bucket, "dispatches": d,
                "flops": None, "bytes": None, "intensity": None,
                "verdict": None, "source": None, "origin": None,
                "est_share_pct": None, "achieved_gflops": None,
                "achieved_gbps": None,
                "padding_waste_pct":
                    None if waste is None else round(100.0 * waste, 3),
                "rows_known": known})
            weights.append(0.0)
            continue
        flops, byts = rec["flops"], rec["bytes"]
        entries.append({
            "program": cache, "bucket": bucket, "dispatches": d,
            "flops": flops, "bytes": byts,
            "intensity":
                round(flops / byts, 4) if byts > 0 else None,
            "verdict": roofline_verdict(flops, byts),
            "source": rec["source"], "origin": rec["origin"],
            "est_share_pct": None, "achieved_gflops": None,
            "achieved_gbps": None,
            "padding_waste_pct":
                None if waste is None else round(100.0 * waste, 3),
            "rows_known": known})
        weights.append(d * _t_est_s(flops, byts))
    wsum = sum(weights)
    compute_share = memory_share = 0.0
    total_flops = total_bytes = 0.0
    waste_w = waste_wsum = 0.0
    for e, w in zip(entries, weights):
        if e["verdict"] is None:
            continue
        share = w / wsum if wsum > 0 else 0.0
        e["est_share_pct"] = round(100.0 * share, 3)
        total_flops += e["flops"] * e["dispatches"]
        total_bytes += e["bytes"] * e["dispatches"]
        if e["verdict"] == VERDICT_COMPUTE:
            compute_share += share
        else:
            memory_share += share
        if busy_s > 0.0 and share > 0.0:
            prog_busy = busy_s * share
            e["achieved_gflops"] = round(
                e["flops"] * e["dispatches"] / prog_busy / 1e9, 3)
            e["achieved_gbps"] = round(
                e["bytes"] * e["dispatches"] / prog_busy / 1e9, 3)
        if e["padding_waste_pct"] is not None:
            waste_w += share * (e["padding_waste_pct"] / 100.0)
            waste_wsum += share
    entries.sort(key=lambda e: (-(e["est_share_pct"] or 0.0),
                                e["program"], e["bucket"]))
    if waste_wsum > 0.0:
        padding_pct = round(100.0 * waste_w / waste_wsum, 3)
    else:
        # no time-weighted evidence (nothing costed): fall back to the
        # capacity-weighted ledger view over rows-known dispatches
        cap_rows = sum(key[1] * kn for key, _d, kn, _rs in deltas)
        row_sum = sum(rs for _key, _d, _kn, rs in deltas)
        padding_pct = (round(100.0 * (1.0 - row_sum / cap_rows), 3)
                       if cap_rows > 0 else None)
    verdict = None
    comp_pct, mem_pct = 0.0, 0.0
    if compute_share > 0.0 or memory_share > 0.0:
        verdict = (VERDICT_COMPUTE if compute_share >= memory_share
                   else VERDICT_MEMORY)
        # the two shares partition the costed busy weight: round one,
        # derive the other, so the published pair sums to exactly 100
        comp_pct = round(100.0 * compute_share, 3)
        mem_pct = round(100.0 - comp_pct, 3)
    out = {
        "programs": entries,
        "busy_ms": busy_ms,
        "achieved_gflops":
            round(total_flops / busy_s / 1e9, 3) if busy_s > 0 else None,
        "achieved_gbps":
            round(total_bytes / busy_s / 1e9, 3) if busy_s > 0 else None,
        "padding_waste_pct": padding_pct,
        "verdict": verdict,
        "compute_share_pct": comp_pct,
        "memory_share_pct": mem_pct,
        "uncosted_dispatches": uncosted,
        "costed_records": len(costs),
        **_peaks_section(),
        "model_version": MODEL_VERSION,
        "digest": stable_digest(),
    }
    with _LOCK:
        _LAST.clear()
        _LAST.update({k: out[k] for k in
                      ("achieved_gflops", "achieved_gbps",
                       "padding_waste_pct", "verdict")})
    try:
        from .registry import COST_VERDICTS
        for e in entries:
            if e["verdict"] is not None:
                COST_VERDICTS.labels(verdict=e["verdict"]).inc()
    except Exception:  # noqa: BLE001 — metrics never fail the summary
        pass
    return out


# ---------------------------------------------------------------------------
# profile integration: measured per-class intensity
# ---------------------------------------------------------------------------

def _per_row_cost(caches) -> Optional[float]:
    tot, n = 0.0, 0
    for (cache, bucket), rec in _COSTS.items():
        if cache in caches and bucket > 0 \
                and rec["source"] == SOURCE_XLA:
            tot += (rec["flops"] + rec["bytes"]) / bucket
            n += 1
    return tot / n if n else None


def measured_intensity(name: str) -> Optional[float]:
    """Measured per-output-row FLOP+byte weight for an operator class,
    normalized to the project program — the live replacement for
    obs/profile.py's static ``_INTENSITY`` factors.  None when the
    class (or the project baseline) has no live capture yet; the
    caller falls back to the static table, keeping member shares
    deterministic for uncompiled members."""
    if not _ENABLED:
        return None
    low = name.lower()
    caches = None
    for key, cs in _CLASS_CACHES:
        if key in low:
            caches = cs
            break
    if caches is None:
        return None
    with _LOCK:
        cls = _per_row_cost(caches)
        base = _per_row_cost(("fused_project",))
    if cls is None or base is None or base <= 0.0:
        return None
    return cls / base


# ---------------------------------------------------------------------------
# coverage (mirrors the PR 10 auditor's REQUIRED_PROGRAMS gate)
# ---------------------------------------------------------------------------

def costed_programs() -> List[str]:
    """Auditor-named programs with at least one static-cost record
    (the shared hash_aggregate trace cache covers its three program
    variants — see _CACHE_COVERS)."""
    out = set()
    with _LOCK:
        caches = {cache for cache, _b in _COSTS}
    for cache in caches:
        out.update(_CACHE_COVERS.get(cache, (cache,)))
    return sorted(out)


def coverage_gaps(required=None) -> List[str]:
    """REQUIRED_PROGRAMS members with no captured static cost —
    the costplane twin of program_audit.coverage_gaps."""
    if required is None:
        from ..analysis import program_audit as _pa
        required = _pa.REQUIRED_PROGRAMS
    return sorted(set(required) - set(costed_programs()))


# ---------------------------------------------------------------------------
# surfaces
# ---------------------------------------------------------------------------

def _peaks_section() -> Dict[str, Any]:
    """The ceilings every cost block states, with where they came
    from (conf override or the device table row)."""
    flops, byts, source = peaks()
    return {"peak_tflops": round(flops / 1e12, 3),
            "peak_gbps": round(byts / 1e9, 3),
            "peak_source": source,
            "ridge_intensity": round(flops / byts, 3)}


def stable_digest() -> str:
    """sha256 over the timing-independent cost MODEL only: version,
    declared peak rates, ridge, verdict + waste rules.  The captured
    program set and every achieved rate are execution-shape dependent
    (superstage on/off compiles different programs) and are excluded —
    same conf x same model -> same digest across pipeline parallelism
    {1,4} x superstage on/off."""
    peak_flops, peak_bytes, _src = peaks()
    payload = {
        "model_version": MODEL_VERSION,
        "peak_flops": peak_flops,
        "peak_bytes": peak_bytes,
        "ridge_intensity": peak_flops / peak_bytes,
        "verdict_rule": "intensity_vs_ridge",
        "waste_rule": "1_minus_rows_over_capacity_rows_known_only",
    }
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def process_waste_pct() -> float:
    """Capacity-weighted padding waste over every rows-known dispatch
    since process start (the tpu_cost_padding_waste_pct gauge)."""
    with _LOCK:
        cap_rows = sum(k * c[1] for (_p, k), c in _DISPATCH.items())
        rows = sum(c[2] for c in _DISPATCH.values())
    if cap_rows <= 0:
        return 0.0
    return round(100.0 * (1.0 - rows / cap_rows), 3)


def record_count() -> int:
    with _LOCK:
        return len(_COSTS)


def dropped_count() -> int:
    with _LOCK:
        return _DROPPED + _DISPATCH_DROPPED


def last_achieved(key: str) -> float:
    with _LOCK:
        v = _LAST.get(key)
    return float(v) if isinstance(v, (int, float)) else 0.0


def static_costs() -> Dict[Tuple[str, int], Dict[str, Any]]:
    """Snapshot of the (program, bucket) static-cost store (tests,
    auditor-style coverage gates)."""
    with _LOCK:
        return {k: dict(v) for k, v in _COSTS.items()}


def stats_section() -> Dict[str, Any]:
    """Process-lifetime roll-up for Service.stats()["cost"] and the
    diagnostic bundle."""
    with _LOCK:
        records = len(_COSTS)
        dropped = _DROPPED + _DISPATCH_DROPPED
        captures = dict(_CAPTURES)
        last = dict(_LAST)
    return {
        "enabled": _ENABLED,
        "records": records,
        "dropped": dropped,
        "captures": captures,
        "programs_costed": costed_programs(),
        "padding_waste_pct": process_waste_pct(),
        **_peaks_section(),
        "last_query": last or None,
        "model_version": MODEL_VERSION,
        "digest": stable_digest(),
    }


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def enabled(conf) -> bool:
    from ..config import OBS_COST_ENABLED
    return bool(conf.get(OBS_COST_ENABLED)) and _ENABLED


def configure(conf) -> None:
    """Apply the ``spark.rapids.tpu.obs.cost.*`` conf group."""
    global _ENABLED, _PEAK_CONF, _PEAKS, _MAX_RECORDS
    from ..config import (OBS_COST_ENABLED, OBS_COST_MAX_RECORDS,
                          OBS_COST_PEAK_HBM_GBPS, OBS_COST_PEAK_TFLOPS)
    _ENABLED = bool(conf.get(OBS_COST_ENABLED))
    tflops = float(conf.get(OBS_COST_PEAK_TFLOPS))
    gbps = float(conf.get(OBS_COST_PEAK_HBM_GBPS))
    # resolved lazily (peaks()): configure must not be what first
    # touches the backend
    _PEAK_CONF = (tflops * 1e12 if tflops > 0 else None,
                  gbps * 1e9 if gbps > 0 else None)
    _PEAKS = None
    cap = int(conf.get(OBS_COST_MAX_RECORDS))
    if cap > 0:
        _MAX_RECORDS = cap


def reset() -> None:
    """Test hook: drop the cost store, dispatch ledger and counters."""
    global _DROPPED, _DISPATCH_DROPPED
    with _LOCK:
        _COSTS.clear()
        _DISPATCH.clear()
        _LAST.clear()
        _DROPPED = 0
        _DISPATCH_DROPPED = 0
        for k in _CAPTURES:
            _CAPTURES[k] = 0
