"""Exchange-boundary data statistics + the per-query StatsProfile.

The AQE re-optimization barrier (ROADMAP item 3) is exchange
materialization — the one point where a whole stage's output is known
and the plan downstream can still change.  This module collects, AT
that barrier and in the SAME dispatch window as the partition split:

- per-partition rows and (nominal-width) bytes,
- per-partition null-key counts,
- min/max of the leading key column (canonical order words, decoded
  back to values for integral keys),
- an approximate distinct-key count from an on-device HLL-style
  register sketch (scatter-max of trailing-zero ranks), and
- a skew verdict (max/median partition-row ratio vs
  ``spark.rapids.tpu.obs.stats.skewFactor``).

Zero-extra-flush contract: the sketch program is enqueued lazily right
after the split's own device work and its outputs are STAGED through
the pending pool (columnar/pending.py), so the exchange's existing
finalize flush resolves them for free; per-partition rows are read from
the split offsets the finalize already pulled.  A speculative batch
whose fit flag failed re-stages its statistics from the exact batch
BEFORE ``finalize_split`` forces the redo flush — still zero added
round trips.  ``tests/test_stats.py`` asserts the FLUSH_COUNT delta.

TPU notes: the chip cannot bitcast 64-bit types (canon.py:55), so the
sketch derives its register index from the hash's high u32 and the
rank from the low u32's lowest set bit (an exact power of two, so the
f32 log2 is exact) — no 64-bit bitcasts anywhere.  The scatter-max
runs once per map batch at register-file size, far off the
searchsorted-vs-scatter tradeoff that shapes the split itself
(shuffle/partitioners.py).

The per-query ``StatsProfile`` joins these exchange/scan entries with
the superstage time attribution (obs/profile.py) and the dispatch
p50/p95 summary; its ``stable_digest()`` covers only the
data-dependent entries (never timings), so it is sha-stable across
pipeline parallelism and superstage on/off.
"""
from __future__ import annotations

import functools
import hashlib
import itertools
import json
import logging
import os
import threading
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import flight
from . import overhead as _overhead
from . import trace as _obs_trace

_LOG = logging.getLogger("spark_rapids_tpu.obs.stats")

#: canonical-order sign flip for integral key words (kernels/canon.py)
_SIGN64 = 0x8000000000000000
#: null-key sentinel word — must match the partitioners' routing
_NULL_SENTINEL = 0x9E3779B97F4A7C15

#: flight-recorder names (interned constants; OBS002 discipline)
_EV_EXCHANGE = "exchange"
_EV_SCAN = "scan"

# False after the sketch program failed once on this backend: the
# exchange keeps rows/bytes stats and drops the sketch (observability
# never fails a query; chip_smoke.py fails if this ever reads False).
_SKETCH_OK = True
_SKETCH_LOCK = threading.Lock()


def sketch_ok() -> bool:
    """False once the sketch program has failed in this process."""
    return _SKETCH_OK


def enabled(conf=None) -> bool:
    from ..config import get_active, OBS_STATS_ENABLED
    return bool((conf or get_active()).get(OBS_STATS_ENABLED))


def sketch_registers(conf=None) -> int:
    from ..config import get_active, OBS_STATS_SKETCH_REGISTERS
    m = int((conf or get_active()).get(OBS_STATS_SKETCH_REGISTERS))
    m = max(64, m)
    return 1 << (m.bit_length() - 1)   # round down to a power of two


def sample_every(conf=None) -> int:
    """Sketch-sampling period: stage the stats program for the first
    map batch of each exchange and every Nth after; 1 means exact
    (every batch).  Rows/bytes/skew stay EXACT regardless — they come
    free from the split offsets.  The test harness forces exact mode
    via ``SPARK_RAPIDS_TPU_OBS_STATS_EXACT`` (tests/conftest.py) so
    stats digests stay deterministic under test."""
    if os.environ.get("SPARK_RAPIDS_TPU_OBS_STATS_EXACT"):
        return 1
    from ..config import get_active, OBS_STATS_SAMPLE_EVERY
    return max(1, int((conf or get_active()).get(OBS_STATS_SAMPLE_EVERY)))


# ---------------------------------------------------------------------------
# on-device sketch program (enqueued with the split; never pulled here)
# ---------------------------------------------------------------------------

@_obs_trace.launched()
@functools.partial(jax.jit, static_argnums=(5, 6))
def stats_map_sketch(h, pids, valid, word0, num_rows, nparts: int, m: int):
    """One fused stats program per map batch: HLL registers + null
    counts + key-word min/max, all per partition.

    rho = 1 + trailing-zero count of the hash's low 32 bits (the
    lowest set bit is an exact power of two, so its f32 log2 is exact);
    register index = high 32 bits masked to m (a power of two)."""
    cap = h.shape[0]
    live = jnp.arange(cap) < num_rows
    lv = live & valid
    pid_c = jnp.clip(pids, 0, nparts - 1).astype(jnp.int32)
    j = ((h >> jnp.uint64(32)).astype(jnp.uint32)
         & jnp.uint32(m - 1)).astype(jnp.int32)
    low = (h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    lowbit = low & (~low + jnp.uint32(1))
    rho = jnp.int32(1) + jnp.log2(
        jnp.maximum(lowbit, jnp.uint32(1)).astype(jnp.float32)
    ).astype(jnp.int32)
    rho = jnp.where(low == 0, jnp.int32(33), rho)
    rho = jnp.where(lv, rho, jnp.int32(0))
    regs = jnp.zeros((nparts, m), jnp.int32).at[pid_c, j].max(rho)
    nulls = jnp.zeros(nparts, jnp.int32).at[pid_c].add(
        (live & ~valid).astype(jnp.int32))
    big = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    wmin = jnp.full(nparts, big, jnp.uint64).at[pid_c].min(
        jnp.where(lv, word0, big))
    wmax = jnp.zeros(nparts, jnp.uint64).at[pid_c].max(
        jnp.where(lv, word0, jnp.uint64(0)))
    return regs, nulls, wmin, wmax


# device-compute cost plane first-call capture: exchange_stats has no
# wrap_miss site (one module-level jit, not a keyed cache), so the
# plane's own wrapper supplies the static-cost record.  The program
# auditor keeps lowering the unwrapped jit via _audit_specs below.
_stats_prog_jit = stats_map_sketch
from . import costplane as _costplane  # noqa: E402
stats_map_sketch = _costplane.wrap_capture("exchange_stats", _stats_prog_jit)


class ExchangeBatchStats:
    """Staged (unresolved) stats of one map batch: resolves for free in
    the exchange's own finalize flush."""

    __slots__ = ("regs", "nulls", "wmin", "wmax", "key_dtype")

    def __init__(self, regs, nulls, wmin, wmax, key_dtype):
        self.regs = regs
        self.nulls = nulls
        self.wmin = wmin
        self.wmax = wmax
        self.key_dtype = key_dtype

    @property
    def resolved(self) -> bool:
        return all(h.resolved for h in
                   (self.regs, self.nulls, self.wmin, self.wmax))


def _rows_if_resolved(batch) -> Optional[int]:
    """The batch's host row count IF knowable without a flush."""
    r = batch.rows_lazy
    if isinstance(r, int):
        return r
    if r._val is not None:
        return r._val
    st = r._staged
    if st is not None and st.resolved:
        return int(r)
    return None


def stage_exchange_batch(partitioner, batch, m: int, acc=None,
                         force: bool = False
                         ) -> Optional[ExchangeBatchStats]:
    """Enqueue the stats program for one map batch (hash exchanges
    only) and stage its outputs.  Lazy device work in the split's own
    dispatch window — nothing here pulls.

    When ``acc`` is passed, its sampling gate decides whether this
    batch is sketched at all (every Nth; ``sample_every``): the skip
    path costs one counter tick and none of the expression/hash/
    program staging below.  ``force`` bypasses the gate — the
    speculative-redo path uses it to replace a sketch that was already
    staged (and counted) for a batch whose table-path assumptions
    failed, keeping ``acc.sketched`` consistent."""
    global _SKETCH_OK
    from ..shuffle.partitioners import HashPartitioner
    if not _SKETCH_OK or not isinstance(partitioner, HashPartitioner) \
            or not partitioner.key_exprs or batch.capacity == 0:
        return None
    if acc is not None and not force and not acc.want_sketch():
        return None
    _mt0 = _overhead.clock()
    try:
        from ..columnar import pending
        from ..columnar.column import StringColumn
        from ..expr import core as ec
        from ..kernels import basic as bk
        from ..kernels import canon
        word_lists: List = []
        valid = None
        word0 = None
        key_dtype = None
        for e in partitioner.key_exprs:
            bound = e.bind(batch.schema)
            col = ec.eval_as_column(bound, batch)
            if isinstance(col, StringColumn):
                nr = _rows_if_resolved(batch)
                if nr is None:
                    return None   # a host count here would add a flush
            else:
                nr = batch.rows_dev
            words = canon.value_words(col, nr)
            if word0 is None:
                word0 = words[0]
                key_dtype = col.dtype
            for w in words:
                word_lists.append(jnp.where(col.validity, w,
                                            jnp.uint64(_NULL_SENTINEL)))
            valid = col.validity if valid is None \
                else (valid & col.validity)
        h = bk.hash_words(word_lists)
        pids = (h % jnp.uint64(partitioner.num_partitions)
                ).astype(jnp.int32)
        from ..compile import aot as _aot
        _aot.note_demand("exchange_stats", batch.capacity,
                         _rows_if_resolved(batch))
        regs, nulls, wmin, wmax = stats_map_sketch(
            h, pids, valid, word0, batch.rows_dev,
            partitioner.num_partitions, m)
        st = ExchangeBatchStats(
            pending.stage(regs), pending.stage(nulls),
            pending.stage(wmin), pending.stage(wmax), key_dtype)
        _overhead.note(_overhead.P_STATS, _mt0)
        return st
    except Exception:  # noqa: BLE001 — stats must never fail the query
        with _SKETCH_LOCK:
            if _SKETCH_OK:
                _SKETCH_OK = False
                _LOG.warning("exchange stats sketch failed; disabled "
                             "for this process", exc_info=True)
        _overhead.note(_overhead.P_STATS, _mt0)
        return None


# ---------------------------------------------------------------------------
# per-exchange accumulator (lives on the exec node; finalize is serial
# under the exchange's materialization lock)
# ---------------------------------------------------------------------------

class ExchangeAcc:
    def __init__(self, nparts: int, m: int, row_width: float, kind: str,
                 partitioner_name: str, every: int = 1):
        self.kind = kind
        self.partitioner = partitioner_name
        self.nparts = nparts
        self.m = m
        self.row_width = row_width
        self.sample_every = max(1, int(every))
        self._sampler = itertools.count()
        self.rows = np.zeros(nparts, np.int64)
        self.nulls = np.zeros(nparts, np.int64)
        self.regs: Optional[np.ndarray] = None
        self.wmin = np.full(nparts, np.uint64(0xFFFFFFFFFFFFFFFF),
                            np.uint64)
        self.wmax = np.zeros(nparts, np.uint64)
        self.key_dtype = None
        self.batches = 0
        self.sketched = 0

    def want_sketch(self) -> bool:
        """Sampling gate (stage_exchange_batch): sketch the first
        batch and every Nth after.  ``next`` on an itertools.count is
        a single GIL-atomic tick, so concurrent pipelined map
        producers need no lock — each staged batch draws exactly one
        ticket."""
        if self.sample_every <= 1:
            return True
        return next(self._sampler) % self.sample_every == 0

    def absorb(self, offsets: np.ndarray,
               handles: Optional[ExchangeBatchStats]):
        """Merge one finalized map batch: rows come free from the split
        offsets the finalize already pulled; sketch/null/min-max merge
        from the staged handles IF the finalize flush resolved them
        (register max / count add / word min-max are commutative, so
        accumulation order — hence pipeline parallelism — cannot change
        the result)."""
        self.batches += 1
        self.rows += np.diff(offsets).astype(np.int64)
        if handles is None or not handles.resolved:
            return
        self.sketched += 1
        self.key_dtype = handles.key_dtype
        regs = handles.regs.np
        self.regs = regs.copy() if self.regs is None \
            else np.maximum(self.regs, regs)
        self.nulls += handles.nulls.np.astype(np.int64)
        self.wmin = np.minimum(self.wmin, handles.wmin.np)
        self.wmax = np.maximum(self.wmax, handles.wmax.np)


def exchange_acc(node, nparts: int, m: int, row_width: float, kind: str,
                 partitioner_name: str,
                 every: Optional[int] = None) -> ExchangeAcc:
    acc = getattr(node, "_stats_acc", None)
    if acc is None:
        acc = node._stats_acc = ExchangeAcc(
            nparts, m, row_width, kind, partitioner_name,
            every if every is not None else sample_every())
    return acc


def hll_estimate(regs: np.ndarray) -> float:
    """Standard HLL estimator with the small-range linear-counting
    correction, over one register vector (union = elementwise max)."""
    m = int(regs.shape[0])
    alpha = 0.7213 / (1.0 + 1.079 / m)
    inv = np.power(2.0, -regs.astype(np.float64))
    est = alpha * m * m / float(inv.sum())
    zeros = int((regs == 0).sum())
    if est <= 2.5 * m and zeros:
        est = m * float(np.log(m / zeros))
    return float(est)


def _decode_word(word: int, key_dtype) -> Optional[int]:
    """Canonical order word -> key value for integral-ish keys (the
    sign-flip encoding in kernels/canon.py); None for other dtypes
    (their words are order-preserving but not trivially invertible)."""
    if key_dtype is None or not getattr(key_dtype, "is_integral", False):
        return None
    return int(np.array([np.uint64(word) ^ np.uint64(_SIGN64)],
                        np.uint64).view(np.int64)[0])


def _skew_verdict(rows: np.ndarray, factor: float) -> Dict:
    mx = int(rows.max()) if rows.size else 0
    med = float(np.median(rows)) if rows.size else 0.0
    if med > 0.0:
        ratio = mx / med
    else:
        ratio = float("inf") if mx > 0 else 1.0
    return {"max_rows": mx, "median_rows": med,
            "ratio": round(ratio, 4) if np.isfinite(ratio) else None,
            "skewed": bool(rows.size > 1 and
                           (not np.isfinite(ratio) or ratio > factor))}


def finish_exchange(node, conf=None) -> Optional[Dict]:
    """Close a shuffle exchange's accumulator into its stats entry and
    publish the registry/flight views.  Called once, at the end of the
    map-side materialization barrier."""
    acc: Optional[ExchangeAcc] = getattr(node, "_stats_acc", None)
    if acc is None:
        return None
    _mt0 = _overhead.clock()
    from ..config import get_active, OBS_STATS_SKEW_FACTOR
    from .registry import (STATS_EXCHANGES, STATS_LAST_DISTINCT_KEYS,
                           STATS_LAST_SKEW_RATIO, STATS_PARTITION_ROWS,
                           STATS_SKEWED_EXCHANGES)
    factor = float((conf or get_active()).get(OBS_STATS_SKEW_FACTOR))
    skew = _skew_verdict(acc.rows, factor)
    # exact: every finalized batch carried a resolved sketch.  Under
    # sampling (obs.stats.sampleEvery > 1) only every Nth did — the
    # sketch-derived fields then come from the sampled subset and the
    # entry says so via its "sample" block.  rows/bytes/skew are from
    # the split offsets and stay exact regardless; null counts are
    # per-row tallies that cannot be extrapolated honestly, so they
    # stay exact-mode-only.
    exact = acc.regs is not None and acc.sketched == acc.batches
    have_sketch = acc.regs is not None and acc.sketched > 0
    distinct = hll_estimate(acc.regs.max(axis=0)) if have_sketch else None
    entry = {
        "kind": acc.kind,
        "partitioner": acc.partitioner,
        "partitions": [
            {"rows": int(r),
             "bytes": int(round(r * acc.row_width)),
             "nulls": int(n) if exact else None}
            for r, n in zip(acc.rows, acc.nulls)],
        "rows": int(acc.rows.sum()),
        "est_bytes": int(round(float(acc.rows.sum()) * acc.row_width)),
        "null_count": int(acc.nulls.sum()) if exact else None,
        "key_min": _decode_word(int(acc.wmin.min()), acc.key_dtype)
        if have_sketch and acc.rows.sum() else None,
        "key_max": _decode_word(int(acc.wmax.max()), acc.key_dtype)
        if have_sketch and acc.rows.sum() else None,
        "distinct_est": round(distinct, 1) if distinct is not None
        else None,
        "skew": skew,
    }
    if have_sketch and not exact:
        entry["sample"] = {"every": acc.sample_every,
                           "sketched": acc.sketched,
                           "batches": acc.batches}
    node._stats_entry = entry
    STATS_EXCHANGES.labels(kind=acc.kind).inc()
    for r in acc.rows:
        STATS_PARTITION_ROWS.observe(float(r))
    ratio = skew["ratio"]
    STATS_LAST_SKEW_RATIO.set(ratio if ratio is not None else 0.0)
    if distinct is not None:
        STATS_LAST_DISTINCT_KEYS.set(distinct)
    if skew["skewed"]:
        STATS_SKEWED_EXCHANGES.inc()
    permille = min(int((ratio or 0.0) * 1000), 10_000_000)
    dist_i = int(distinct or 0)
    flight.record(flight.EV_STATS, _EV_EXCHANGE, permille, dist_i)
    _overhead.note(_overhead.P_STATS, _mt0)
    return entry


# ---------------------------------------------------------------------------
# scan + broadcast entries (host-side bookkeeping; zero device work)
# ---------------------------------------------------------------------------

def _row_width(schema) -> float:
    from .profile import _nominal_row_bytes
    return _nominal_row_bytes(schema)


def note_scan(node, part_rows: List[int]):
    """Per-partition output sizes of a scan (exact, from the slicing
    arithmetic the scan already does)."""
    width = _row_width(getattr(node, "output_schema", None))
    node._stats_entry = {
        "kind": "scan",
        "partitions": [{"rows": int(r),
                        "bytes": int(round(r * width))}
                       for r in part_rows],
        "rows": int(sum(part_rows)),
    }
    flight.record(flight.EV_STATS, _EV_SCAN, len(part_rows),
                  int(sum(part_rows)))


def count_scan_partitions(node, parts):
    """Wrap a scan's partition iterators to accumulate per-partition
    output rows host-side as batches stream: file scans learn their
    sizes only at read time, and the counts are host metadata on
    already-materialized batches, so this costs zero device work.
    build_profile materializes the entry from the accumulated rows."""
    rows = [0] * len(parts)
    node._stats_scan_rows = rows

    def wrap(i, it):
        for b in it:
            n = getattr(b, "num_rows", None)
            if isinstance(n, int):
                rows[i] += n
            yield b
    return [wrap(i, it) for i, it in enumerate(parts)]


def _finish_scan(node) -> Optional[Dict]:
    rows = getattr(node, "_stats_scan_rows", None)
    if rows is None:
        return None
    width = _row_width(getattr(node, "output_schema", None))
    return {
        "kind": "scan",
        "partitions": [{"rows": int(r),
                        "bytes": int(round(r * width))}
                       for r in rows],
        "rows": int(sum(rows)),
    }


def note_broadcast(node, batch):
    """Defer the broadcast's row stat to profile-build time: the
    single-batch build path costs zero round trips (exec/exchange.py)
    and forcing a count here would break that.  Unconditional (no conf
    gate): build threads have no reliable ambient conf, so the
    session's conf decides at build_profile time instead."""
    node._stats_broadcast = batch


def _finish_broadcast(node) -> Optional[Dict]:
    batch = getattr(node, "_stats_broadcast", None)
    if batch is None:
        return None
    rows = _rows_if_resolved(batch)
    width = _row_width(getattr(node, "output_schema", None))
    from .registry import STATS_EXCHANGES
    STATS_EXCHANGES.labels(kind="broadcast").inc()
    return {
        "kind": "broadcast",
        "partitions": [{"rows": int(rows) if rows is not None else None,
                        "bytes": int(round(rows * width))
                        if rows is not None else None}],
        "rows": int(rows) if rows is not None else None,
    }


# ---------------------------------------------------------------------------
# the per-query artifact
# ---------------------------------------------------------------------------

class StatsProfile:
    """Per-query stats artifact: exchange/scan data statistics,
    superstage time attribution, and the dispatch-duration summary.
    Persisted in the event-log record (tools/report.py --stats) and on
    ``session.last_stats_profile``."""

    VERSION = 1

    def __init__(self, data: Dict):
        self.data = data

    def to_dict(self) -> Dict:
        return self.data

    def get(self, key, default=None):
        return self.data.get(key, default)

    def __getitem__(self, key):
        return self.data[key]

    def stable_digest(self) -> str:
        """sha256 over the DATA-dependent entries only (shuffle
        exchanges + scans; no timings, no flush counts), so the digest
        is stable across pipeline parallelism and superstage on/off —
        the determinism surface tests/test_stats.py pins.  Broadcast
        entries are excluded: their row stat is read best-effort from
        whatever the query's own flushes happened to resolve (the
        zero-round-trip contract forbids forcing it), which is
        execution-shape dependent.  ``node_index`` is dropped too —
        preorder positions shift when the carve pass wraps regions,
        without changing any data statistic."""

        def _strip(e):
            return {k: v for k, v in e.items() if k != "node_index"}
        det = {"exchanges": [_strip(e)
                             for e in self.data.get("exchanges", [])
                             if e.get("kind") != "broadcast"],
               "scans": [_strip(e) for e in self.data.get("scans", [])]}
        blob = json.dumps(det, sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()


def build_profile(phys, query_id=None, flushes: Optional[int] = None,
                  dispatch_marker: Optional[Dict[str, int]] = None
                  ) -> StatsProfile:
    """Harvest the per-node stats state of an executed plan into one
    StatsProfile.  Read-only over resolved values: never forces a
    flush (the profile is built AFTER the query's flush window) —
    and, since r17, after the query's recorded wall clock stops: the
    session defers this call to event-log write time."""
    from . import profile as _profile
    _mt0 = _overhead.clock()
    exchanges: List[Dict] = []
    scans: List[Dict] = []
    stages: List[Dict] = []
    for idx, node in enumerate(phys.collect_nodes()):
        entry = getattr(node, "_stats_entry", None)
        if entry is None and getattr(node, "_stats_broadcast", None) \
                is not None:
            entry = _finish_broadcast(node)
        if entry is None:
            entry = _finish_scan(node)
        if entry is not None:
            e = dict(entry)
            e["node_index"] = idx
            e["node"] = node.name
            (scans if e["kind"] == "scan" else exchanges).append(e)
        if getattr(node, "lowering", None) is not None and \
                getattr(node, "members", None):
            sp = getattr(node, "_stage_profile", None)
            shares = _profile.member_shares(node)
            device_ns = sp.device_ns if sp is not None else 0
            stages.append({
                "node_index": idx,
                "node": node.name,
                "members": [f"{i}:{m.name}"
                            for i, m in enumerate(node.members)],
                "device_ms": round(device_ns / 1e6, 3),
                "flushes": sp.flushes if sp is not None else 0,
                "member_share": shares,
                "member_device_ms": {
                    k: round(v * device_ns / 1e6, 3)
                    for k, v in shares.items()},
            })
    prof = StatsProfile({
        "version": StatsProfile.VERSION,
        "query_id": query_id,
        "flushes": flushes,
        "exchanges": exchanges,
        "scans": scans,
        "superstages": stages,
        "dispatches": _profile.dispatch_summary(dispatch_marker),
    })
    _overhead.note(_overhead.P_STATS, _mt0)
    return prof


# ---------------------------------------------------------------------------
# program audit registration (analysis/program_audit.py): exact=False —
# the stats program intentionally uses float log2 for the distinct-
# count sketch; it produces observability estimates, never query data.
# ---------------------------------------------------------------------------

def _audit_specs():
    from ..analysis.program_audit import AuditSpec

    def _build():
        import jax
        import numpy as np
        cap = 128
        args = (jax.ShapeDtypeStruct((cap,), np.uint64),
                jax.ShapeDtypeStruct((cap,), np.int32),
                jax.ShapeDtypeStruct((cap,), np.bool_),
                jax.ShapeDtypeStruct((cap,), np.uint64),
                jax.ShapeDtypeStruct((), np.int32),
                4, 64)
        return _stats_prog_jit, args, {"static_argnums": (5, 6)}

    return [AuditSpec(
        "exchange_stats", "exchange_stats", _build, exact=False,
        notes="exchange-boundary stats sketch (float log2 is "
              "intentional: estimates, not query data)",
        budgets={"gather": 4, "scatter": 8, "transpose": 2, "sort": 2})]
