"""Process-wide metrics registry — counters, gauges, fixed-bucket
histograms (the GpuMetric -> Spark-SQL-UI role lifted to a serving
process: one registry every subsystem writes into, scraped as a whole).

Instruments are get-or-create by name (re-registering returns the
existing family), optionally labeled, and cheap on the hot path: a
counter ``inc`` is one lock-free float add under a per-child lock;
gauges for arena/queue state are *collect-time callbacks* so the memory
and service layers pay nothing per operation.  ``snapshot()`` returns a
plain dict for tests; ``obs.prom`` renders the Prometheus text format.

Stdlib-only; the default instrument callbacks lazy-import engine layers
at collect time to stay import-cycle-free.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

COUNTER, GAUGE, HISTOGRAM = "counter", "gauge", "histogram"

#: wait-time buckets (seconds) shared by the semaphore/queue histograms
WAIT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class _Child:
    """One sample series (a family's instance for one label set)."""
    __slots__ = ("labels", "_lock", "_value", "_fn",
                 "buckets", "_bucket_counts", "_sum", "_count")

    def __init__(self, labels: Tuple[Tuple[str, str], ...],
                 buckets: Optional[Sequence[float]] = None):
        self.labels = labels
        self._lock = threading.Lock()
        self._value = 0.0
        self._fn: Optional[Callable[[], float]] = None
        self.buckets = tuple(buckets) if buckets is not None else None
        if self.buckets is not None:
            self._bucket_counts = [0] * (len(self.buckets) + 1)  # +Inf
            self._sum = 0.0
            self._count = 0

    # -- counter/gauge -----------------------------------------------------
    def inc(self, by: float = 1.0):
        with self._lock:
            self._value += by

    def dec(self, by: float = 1.0):
        with self._lock:
            self._value -= by

    def set(self, v: float):
        with self._lock:
            self._value = float(v)

    def set_function(self, fn: Callable[[], float]):
        """Collect-time callback: the series' value is ``fn()`` at each
        scrape/snapshot instead of a stored number (zero hot-path
        cost for state another subsystem already tracks)."""
        self._fn = fn

    @property
    def value(self) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                return 0.0
        with self._lock:
            return self._value

    # -- histogram ---------------------------------------------------------
    def observe(self, v: float):
        with self._lock:
            i = 0
            for b in self.buckets:
                if v <= b:
                    break
                i += 1
            self._bucket_counts[i] += 1
            self._sum += v
            self._count += 1

    def hist_snapshot(self) -> Dict:
        """Cumulative bucket counts keyed by upper bound + sum/count."""
        with self._lock:
            counts = list(self._bucket_counts)
            total, s = self._count, self._sum
        cum, out = 0, {}
        for b, c in zip(self.buckets, counts):
            cum += c
            out[b] = cum
        out["+Inf"] = total
        return {"buckets": out, "sum": s, "count": total}


class Family:
    """A named metric family: type + help + labeled children."""

    def __init__(self, name: str, typ: str, help: str = "",
                 label_names: Sequence[str] = (),
                 buckets: Optional[Sequence[float]] = None):
        self.name = name
        self.type = typ
        self.help = help
        self.label_names = tuple(label_names)
        self._buckets = tuple(buckets) if buckets is not None else None
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], _Child] = {}

    def labels(self, **kv) -> _Child:
        assert set(kv) == set(self.label_names), \
            f"{self.name}: expected labels {self.label_names}, got {kv}"
        key = tuple(str(kv[n]) for n in self.label_names)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    child = _Child(tuple(zip(self.label_names, key)),
                                   self._buckets)
                    self._children[key] = child
        return child

    def _default(self) -> _Child:
        assert not self.label_names, \
            f"{self.name} is labeled; use .labels(...)"
        return self.labels()

    # unlabeled families delegate straight to their single child
    def inc(self, by: float = 1.0):
        self._default().inc(by)

    def dec(self, by: float = 1.0):
        self._default().dec(by)

    def set(self, v: float):
        self._default().set(v)

    def set_function(self, fn: Callable[[], float]):
        self._default().set_function(fn)

    def observe(self, v: float):
        self._default().observe(v)

    def hist_snapshot(self) -> Dict:
        return self._default().hist_snapshot()

    @property
    def value(self) -> float:
        return self._default().value

    def children(self) -> List[_Child]:
        with self._lock:
            return [self._children[k]
                    for k in sorted(self._children)]


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, Family] = {}

    def _get_or_create(self, name: str, typ: str, help: str,
                       label_names: Sequence[str],
                       buckets: Optional[Sequence[float]] = None) -> Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = Family(name, typ, help, label_names, buckets)
                self._families[name] = fam
            else:
                assert fam.type == typ, \
                    f"{name} re-registered as {typ}, was {fam.type}"
            return fam

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Family:
        return self._get_or_create(name, COUNTER, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = (),
              fn: Optional[Callable[[], float]] = None) -> Family:
        fam = self._get_or_create(name, GAUGE, help, labels)
        if fn is not None:
            fam.set_function(fn)
        return fam

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = WAIT_BUCKETS,
                  labels: Sequence[str] = ()) -> Family:
        return self._get_or_create(name, HISTOGRAM, help, labels, buckets)

    def families(self) -> List[Family]:
        with self._lock:
            return [self._families[n] for n in sorted(self._families)]

    def snapshot(self) -> Dict:
        """Deterministic plain-dict view (sorted names/labels) for
        tests and the report tool."""
        out: Dict = {}
        for fam in self.families():
            if fam.type == HISTOGRAM:
                if fam.label_names:
                    out[fam.name] = {
                        _label_key(c.labels): c.hist_snapshot()
                        for c in fam.children()}
                else:
                    out[fam.name] = fam._default().hist_snapshot()
            elif fam.label_names:
                out[fam.name] = {_label_key(c.labels): c.value
                                 for c in fam.children()}
            else:
                out[fam.name] = fam.value
        return out


def _label_key(labels: Tuple[Tuple[str, str], ...]) -> str:
    return ",".join(f"{k}={v}" for k, v in labels)


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _REGISTRY


# ---------------------------------------------------------------------------
# Default engine instruments.  Gauges over state other layers already
# track are collect-time callbacks (lazy imports: no cycle, no hot-path
# cost); counters the layers push into are bound here once so call
# sites skip label resolution.
# ---------------------------------------------------------------------------

def _catalog():
    from ..memory.catalog import BufferCatalog
    return BufferCatalog.get()


ARENA_DEVICE_BYTES = _REGISTRY.gauge(
    "tpu_arena_device_bytes",
    "Logical live bytes on the device tier of the buffer catalog",
    fn=lambda: _catalog().device_bytes)
ARENA_DEVICE_PEAK_BYTES = _REGISTRY.gauge(
    "tpu_arena_device_peak_bytes",
    "High-water mark of device-tier live bytes since catalog reset",
    fn=lambda: _catalog().device_peak_bytes)
ARENA_DEVICE_LIMIT_BYTES = _REGISTRY.gauge(
    "tpu_arena_device_limit_bytes",
    "Device-tier byte budget enforced by the arena",
    fn=lambda: _catalog().device_limit)
ARENA_HOST_BYTES = _REGISTRY.gauge(
    "tpu_arena_host_bytes",
    "Bytes of spilled buffers on the host tier",
    fn=lambda: _catalog().host_bytes)
ARENA_DISK_BYTES = _REGISTRY.gauge(
    "tpu_arena_disk_bytes",
    "Bytes of spilled buffers on the disk tier",
    fn=lambda: _catalog().disk_bytes)

SPILL_BYTES = _REGISTRY.counter(
    "tpu_spill_bytes_total",
    "Bytes moved down the spill tiers since catalog reset",
    labels=("direction",))
SPILL_BYTES.labels(direction="device_to_host").set_function(
    lambda: _catalog().spilled_device_to_host)
SPILL_BYTES.labels(direction="host_to_disk").set_function(
    lambda: _catalog().spilled_host_to_disk)

SEM_WAIT_SECONDS = _REGISTRY.histogram(
    "tpu_semaphore_wait_seconds",
    "Time tasks spent blocked on the device semaphore "
    "(only blocked acquires observe; immediate grants are free)")

QUEUE_WAIT_SECONDS = _REGISTRY.histogram(
    "tpu_service_queue_wait_seconds",
    "Admission-to-start wait of service queries")

SERVICE_QUEUE_DEPTH = _REGISTRY.gauge(
    "tpu_service_queue_depth",
    "Queries waiting in the service admission queue")
SERVICE_QUEUED_BYTES = _REGISTRY.gauge(
    "tpu_service_queued_bytes",
    "Estimated bytes of queries waiting in the admission queue")
SERVICE_INFLIGHT = _REGISTRY.gauge(
    "tpu_service_inflight_queries",
    "Queries admitted and not yet finished")

SERVICE_EVENTS = _REGISTRY.counter(
    "tpu_service_queries_total",
    "Service lifecycle transitions (submitted/admitted/shed/completed/"
    "failed/cancelled/deadline_exceeded/retries)",
    labels=("event",))

COMPILE_CACHE = _REGISTRY.counter(
    "tpu_compile_cache_requests_total",
    "Engine JIT compile-cache lookups by cache and outcome",
    labels=("cache", "outcome"))

AOT_BUCKET_DEMAND = _REGISTRY.counter(
    "tpu_aot_bucket_demand_total",
    "JIT-cache lookups by (program, capacity bucket, outcome) — the "
    "demand mix the admission-aware warmup daemon pre-compiles "
    "against (compile/aot.py; bucket cardinality is bounded by the "
    "geometric lattice)",
    labels=("cache", "bucket", "outcome"))

AOT_WARMUP_COMPILES = _REGISTRY.counter(
    "tpu_aot_warmup_compiles_total",
    "Background warmup compiles by program: (program, bucket) pairs "
    "pre-compiled off the query critical path by the service warmup "
    "daemon (service/warmup.py), attributed to the 'warmup' "
    "pseudo-victim by obs/compile_watch.py",
    labels=("program",))

AOT_HINT_COMPILES = _REGISTRY.counter(
    "tpu_aot_hint_warmup_compiles_total",
    "Background warmup compiles whose (program, bucket) pair arrived "
    "ONLY through a predictive-scheduler pre-warm hint "
    "(service/scheduler.py -> service/warmup.py note_hint) — never "
    "organically demanded before the compile; counted separately "
    "from the admission-driven tpu_aot_warmup_compiles_total",
    labels=("program",))

COMPILE_PERSISTENT_HITS = _REGISTRY.counter(
    "tpu_compile_persistent_hits_total",
    "First calls satisfied by the persistent executable cache "
    "(compile/aot.py manifest + JAX persistent compilation cache): "
    "the program was compiled by an earlier process run and "
    "deserialized here, so it is NOT counted in tpu_compile_seconds",
    labels=("cache",))

COMPILE_SUPERSTAGES = _REGISTRY.counter(
    "tpu_compile_superstages_total",
    "Superstage compiler carve outcomes: carved (region wrapped), "
    "ejected (unfusable member split a region), fallback (stage setup "
    "failed, re-ran with per-operator dispatch), spec_redo (a member's "
    "speculative fit flag failed and the exact path recomputed)",
    labels=("event",))

COMPILE_SUPERSTAGE_FLUSHES = _REGISTRY.counter(
    "tpu_compile_superstage_flushes_total",
    "Host round trips (pending-pool flushes) observed while draining "
    "superstage output partitions — the quantity the compiler exists "
    "to minimize (approximate under concurrent queries: the flush "
    "counter is process-wide)")

SHUFFLE_BYTES = _REGISTRY.counter(
    "tpu_shuffle_bytes_total",
    "Shuffle bytes moved through the map-output catalog",
    labels=("direction",))
SHUFFLE_WRITE_BYTES = SHUFFLE_BYTES.labels(direction="write")
SHUFFLE_READ_BYTES = SHUFFLE_BYTES.labels(direction="read")


# -- shuffle-transport observability plane (obs/netplane.py) ----------------
# Fetch/RTT buckets sized to a LAN TCP hop: sub-ms loopback to tens of
# seconds for a stalled peer.
_NET_BUCKETS = (0.0005, 0.001, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


def _netplane_mod():
    from . import netplane
    return netplane


SHUFFLE_HOST_DROP_SECONDS = _REGISTRY.counter(
    "tpu_shuffle_host_drop_seconds_total",
    "Measured host-drop phase time of shuffle exchanges: serialize "
    "(device->host pull into the staged block), wire (TCP transfer "
    "incl. bounce hop), deserialize (host->device upload on read); "
    "dwell is derived per query as the lifecycle remainder "
    "(obs/netplane.py)",
    labels=("phase",))
SHUFFLE_FETCH_SECONDS = _REGISTRY.histogram(
    "tpu_shuffle_fetch_seconds",
    "Remote shuffle fetch latency by peer (metadata request to last "
    "table landed, shuffle/iterator.py)",
    buckets=_NET_BUCKETS,
    labels=("peer",))
SHUFFLE_CONN_EVENTS = _REGISTRY.counter(
    "tpu_shuffle_conn_events_total",
    "Shuffle connection-pool transitions (shuffle/tcp.py): dial = new "
    "socket, reuse = pooled socket served a request, reset = "
    "connection torn down with pending transactions errored",
    labels=("event",))
SHUFFLE_BOUNCE_DWELL_SECONDS = _REGISTRY.histogram(
    "tpu_shuffle_bounce_dwell_seconds",
    "Bounce-buffer hold time, acquire to release (shuffle/bounce.py)",
    buckets=_NET_BUCKETS)
SHUFFLE_BOUNCE_FREE = _REGISTRY.gauge(
    "tpu_shuffle_bounce_free",
    "Free bounce buffers across live shuffle servers",
    fn=lambda: _netplane_mod().bounce_free())
SHUFFLE_BOUNCE_TOTAL = _REGISTRY.gauge(
    "tpu_shuffle_bounce_total",
    "Total bounce buffers across live shuffle servers",
    fn=lambda: _netplane_mod().bounce_total())
SHUFFLE_PENDING_FETCHES = _REGISTRY.gauge(
    "tpu_shuffle_pending_fetches",
    "Shuffle fetches issued and not yet completed or errored — a "
    "nonzero steady state means waiters are stuck on a torn-down "
    "connection (shuffle/client.py)",
    fn=lambda: _netplane_mod().pending_fetches())
SHUFFLE_EDGES_TRACKED = _REGISTRY.gauge(
    "tpu_shuffle_edges_tracked",
    "Distinct (shuffle, map, reduce) edges held in the bounded "
    "transfer matrix",
    fn=lambda: _netplane_mod().edges_tracked())
SHUFFLE_EDGES_EVICTED = _REGISTRY.counter(
    "tpu_shuffle_edges_evicted_total",
    "Edge records dropped because the transfer matrix hit "
    "spark.rapids.tpu.obs.net.maxEdges")
SHUFFLE_PEER_RTT_SECONDS = _REGISTRY.histogram(
    "tpu_shuffle_peer_rtt_seconds",
    "Heartbeat round-trip time by executor peer "
    "(shuffle/heartbeat.py)",
    buckets=_NET_BUCKETS,
    labels=("peer",))
SHUFFLE_COMPRESSION_BYTES = _REGISTRY.counter(
    "tpu_shuffle_compression_bytes_total",
    "Shuffle codec traffic by codec and side: raw = uncompressed "
    "payload, compressed = encoded payload (ratio = raw/compressed; "
    "shuffle/compression.py)",
    labels=("codec", "direction"))


# -- HBM memory observability plane (obs/memplane.py) -----------------------
#: provenance sites a registration can be attributed to (mirrors
#: memplane.SITES; a fixed tuple here keeps the gauge children stable)
MEM_SITES = ("superstage", "exchange", "broadcast", "scan_cache",
             "stream_state", "operator", "other")
# Tier-move buckets: a device->host pull of one batch is ~1-100ms, a
# compressed disk write of a big sorted run can take seconds.
_MEM_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.075, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _memplane_mod():
    from . import memplane
    return memplane


MEM_SPILL_SECONDS = _REGISTRY.histogram(
    "tpu_mem_spill_seconds",
    "Wall duration of each buffer-catalog tier move by direction: "
    "device_to_host serialize, host_to_disk write, unspill = the "
    "whole read-back path incl. a disk hop when present "
    "(obs/memplane.py spill ledger)",
    buckets=_MEM_BUCKETS,
    labels=("direction",))
MEM_SPILL_SKIPPED = _REGISTRY.counter(
    "tpu_mem_spill_skipped_total",
    "spill_device_to_fit calls that could not free the requested "
    "bytes because only pinned (refcount>0) entries remained on the "
    "device tier — OOM forensics: 'nothing spillable' vs 'spill too "
    "slow'",
    labels=("reason",))
MEM_LEAKED_TOTAL = _REGISTRY.counter(
    "tpu_mem_leaked_entries_total",
    "Catalog entries still owned by a query at its terminal state "
    "outside the expected survivor set (scan cache, live shuffle "
    "materializations); each is reported with its registration "
    "call-site tag in the event log and diag bundle")
MEM_LIVE_BYTES = _REGISTRY.gauge(
    "tpu_mem_live_bytes",
    "Live device-tier bytes by provenance site; the sites sum to "
    "tpu_arena_device_bytes at every scrape (obs/memplane.py)",
    labels=("site",))
for _site in MEM_SITES:
    MEM_LIVE_BYTES.labels(site=_site).set_function(
        lambda s=_site: _memplane_mod().live_site_bytes(s))
MEM_HEADROOM_BYTES = _REGISTRY.gauge(
    "tpu_mem_headroom_bytes",
    "Admission headroom forecast: free device bytes plus spillable-"
    "at-zero-refcount bytes (obs/memplane.py headroom())",
    fn=lambda: _memplane_mod().headroom()["headroom_bytes"])
MEM_PINNED_BYTES = _REGISTRY.gauge(
    "tpu_mem_pinned_bytes",
    "Device-tier bytes pinned by refcount>0 entries (unspillable)",
    fn=lambda: _memplane_mod().headroom()["pinned_bytes"])
MEM_SPILLABLE_BYTES = _REGISTRY.gauge(
    "tpu_mem_spillable_bytes",
    "Device-tier bytes in refcount==0 entries (reclaimable by a "
    "synchronous spill)",
    fn=lambda: _memplane_mod().headroom()["spillable_bytes"])
MEM_LEDGER_DROPPED = _REGISTRY.counter(
    "tpu_mem_ledger_dropped_total",
    "Spill-ledger records dropped past "
    "spark.rapids.tpu.obs.mem.maxLedger (fixed memory)")
MEM_LEDGER_DROPPED.set_function(
    lambda: _memplane_mod().ledger_dropped())


def _pipeline_mod():
    from ..exec import pipeline
    return pipeline


PIPELINE_QUEUE_DEPTH = _REGISTRY.gauge(
    "tpu_pipeline_queue_depth",
    "Prefetched batches buffered across all live morsel-pipeline drains",
    fn=lambda: _pipeline_mod().buffered_items())
PIPELINE_BUFFERED_BYTES = _REGISTRY.gauge(
    "tpu_pipeline_buffered_bytes",
    "Bytes of prefetched batches buffered across all live pipeline "
    "drains (bounded by exec.pipelineBufferBytes per drain)",
    fn=lambda: _pipeline_mod().buffered_bytes())
PIPELINE_WORKERS_BUSY = _REGISTRY.gauge(
    "tpu_pipeline_workers_busy",
    "Pipeline-pool workers currently serving a drain",
    fn=lambda: _pipeline_mod().busy_workers())
PIPELINE_WORKER_BUSY_SECONDS = _REGISTRY.histogram(
    "tpu_pipeline_worker_busy_seconds",
    "Per-batch produce time on pipeline producers (partition pull + "
    "sink, device dispatch under the semaphore)")
PIPELINE_OVERLAP_RATIO = _REGISTRY.gauge(
    "tpu_pipeline_overlap_ratio",
    "Summed produce time / wall time of the last finished parallel "
    "drain (>1 means host staging overlapped device compute)")
PIPELINE_BATCHES = _REGISTRY.counter(
    "tpu_pipeline_batches_total",
    "Batches produced through drain_parallel, by producer "
    "(worker = pool thread, inline = consumer-assist)",
    labels=("source",))
PIPELINE_DRAINS = _REGISTRY.counter(
    "tpu_pipeline_drains_total",
    "drain_parallel invocations by mode (parallel vs serial fallback)",
    labels=("mode",))


# -- runtime stats plane (obs/stats.py + obs/profile.py) --------------------
# Buckets span 1ms-10s: a fused flush waits for all queued device work,
# so its duration ranges from a bare transfer to a whole stage.
_DISPATCH_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.075, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
#: per-partition row-count buckets for the exchange skew histogram
_PARTITION_ROW_BUCKETS = (0.0, 1.0, 100.0, 1_000.0, 10_000.0,
                          100_000.0, 1_000_000.0, 10_000_000.0,
                          100_000_000.0)

STATS_FLUSH_SECONDS = _REGISTRY.histogram(
    "tpu_stats_flush_seconds",
    "Wall duration of each fused pending-pool flush (one device round "
    "trip; columnar/pending.py) as observed by the stats plane",
    buckets=_DISPATCH_BUCKETS)
STATS_ATTRIBUTED_DEVICE_SECONDS = _REGISTRY.counter(
    "tpu_stats_attributed_device_seconds_total",
    "Flush wall time accrued by attribution target (attributed=yes: a "
    "superstage/exchange/collect scope owned the flush; no: the flush "
    "fired outside any declared scope)",
    labels=("attributed",))
STATS_DISPATCH_SECONDS = _REGISTRY.histogram(
    "tpu_stats_dispatch_seconds",
    "Wall duration of explicit dispatch sites the stats plane times "
    "(flush, superstage chain_step, exchange split, speculative join "
    "spec_probe/spec_redo)",
    buckets=_DISPATCH_BUCKETS,
    labels=("site",))
STATS_EXCHANGES = _REGISTRY.counter(
    "tpu_stats_exchanges_total",
    "Exchange materializations the stats plane profiled, by kind "
    "(shuffle/broadcast) — each contributes per-partition rows/bytes, "
    "null counts, min/max and an HLL distinct-key estimate",
    labels=("kind",))
STATS_SKEWED_EXCHANGES = _REGISTRY.counter(
    "tpu_stats_skewed_exchanges_total",
    "Exchanges whose max/median partition-row ratio exceeded "
    "spark.rapids.tpu.obs.stats.skewFactor")
STATS_LAST_SKEW_RATIO = _REGISTRY.gauge(
    "tpu_stats_last_skew_ratio",
    "max/median partition-row ratio of the most recently profiled "
    "shuffle exchange (1.0 = perfectly balanced)")
STATS_LAST_DISTINCT_KEYS = _REGISTRY.gauge(
    "tpu_stats_last_distinct_keys",
    "HLL distinct-key estimate of the most recently profiled hash "
    "exchange")
STATS_PARTITION_ROWS = _REGISTRY.histogram(
    "tpu_stats_partition_rows",
    "Rows per reduce partition across profiled shuffle exchanges",
    buckets=_PARTITION_ROW_BUCKETS)


# -- serving-grade performance plane (obs/timeline, compile_watch, slo) -----
# Compile buckets span the real range: a warm-trace re-jit is ~10ms, a
# cold XLA compile of a fused superstage is seconds to minutes.
_COMPILE_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)

COMPILE_SECONDS = _REGISTRY.histogram(
    "tpu_compile_seconds",
    "Wall duration of each compile-cache miss's first call (jit trace "
    "+ XLA compile) by cache — the inline-compile cost ROADMAP item "
    "4's AOT cache exists to remove (obs/compile_watch.py)",
    buckets=_COMPILE_BUCKETS,
    labels=("cache",))

DEVICE_BUSY_SECONDS = _REGISTRY.counter(
    "tpu_device_busy_seconds_total",
    "Device-busy wall time by device id: fused pending-pool flush "
    "windows on the dispatch device, plus mesh SPMD dispatch windows "
    "attributed to every participating device (obs/timeline.py)",
    labels=("device",))

#: idle-gap taxonomy of the utilization timeline (docs/observability.md;
#: shuffle_host = active shuffle host-drop work windows from
#: obs/netplane.py and mem_spill = active tier-move work windows from
#: obs/memplane.py, both classified ahead of the generic drain causes)
TIMELINE_GAP_CAUSES = ("inline_compile", "sem_wait", "admission_queue",
                       "shuffle_host", "mem_spill", "host_staging",
                       "pipeline_starvation", "idle")


def _timeline_mod():
    from . import timeline
    return timeline


DEVICE_UTIL_PCT = _REGISTRY.gauge(
    "tpu_device_util_pct",
    "Process-wide device utilization percent: merged busy intervals / "
    "wall window since the first observed dispatch (obs/timeline.py)",
    fn=lambda: _timeline_mod().process_util_pct())
DEVICE_IDLE_PCT = _REGISTRY.gauge(
    "tpu_device_idle_pct",
    "Idle share of the process wall window by attributed cause; busy "
    "pct + all idle-cause pcts sum to 100 (obs/timeline.py)",
    labels=("cause",))
for _cause in TIMELINE_GAP_CAUSES:
    DEVICE_IDLE_PCT.labels(cause=_cause).set_function(
        lambda c=_cause: _timeline_mod().process_gap_pct(c))

DOCTOR_VERDICTS = _REGISTRY.counter(
    "tpu_doctor_verdicts_total",
    "Primary-bottleneck verdicts issued by the cross-plane query "
    "doctor (obs/doctor.py), by cause; one increment per diagnosed "
    "query, exactly one cause each — the cause set is device_compute "
    "plus the TIMELINE_GAP_CAUSES taxonomy",
    labels=("cause",))

def _costplane_mod():
    from . import costplane
    return costplane


COST_CAPTURES = _REGISTRY.counter(
    "tpu_cost_captures_total",
    "Static-cost captures by the device-compute cost plane "
    "(obs/costplane.py) at JIT-cache first calls, by source: live XLA "
    "cost analysis (xla) vs the deterministic static-intensity "
    "fallback (static)",
    labels=("source",))
COST_RECORDS = _REGISTRY.gauge(
    "tpu_cost_records",
    "Retained (program, bucket) static-cost records in the bounded "
    "store (spark.rapids.tpu.obs.cost.maxRecords)",
    fn=lambda: float(_costplane_mod().record_count()))
COST_RECORDS_DROPPED = _REGISTRY.gauge(
    "tpu_cost_records_dropped",
    "Static-cost records and dispatch-ledger keys dropped at the "
    "maxRecords bound (fixed memory — the flight-recorder discipline)",
    fn=lambda: float(_costplane_mod().dropped_count()))
COST_PADDING_WASTE_PCT = _REGISTRY.gauge(
    "tpu_cost_padding_waste_pct",
    "Capacity-weighted padded-compute waste percent over every "
    "rows-known dispatch since process start: 100 * (1 - effective "
    "rows / padded bucket capacity) — the price of the AOT lattice's "
    "bucketRatio (obs/costplane.py)",
    fn=lambda: float(_costplane_mod().process_waste_pct()))
COST_VERDICTS = _REGISTRY.counter(
    "tpu_cost_roofline_verdicts_total",
    "Per-program roofline verdicts issued at query end by the "
    "device-compute cost plane: compute_bound when arithmetic "
    "intensity clears the conf-declared ridge, memory_bound below it",
    labels=("verdict",))
COST_ACHIEVED_GFLOPS = _REGISTRY.gauge(
    "tpu_cost_achieved_gflops",
    "Last query's achieved GFLOP/s: total captured static flops "
    "dispatched / flush-observer busy window (obs/costplane.py)",
    fn=lambda: _costplane_mod().last_achieved("achieved_gflops"))
COST_ACHIEVED_GBPS = _REGISTRY.gauge(
    "tpu_cost_achieved_gbps",
    "Last query's achieved GB/s: total captured static bytes "
    "accessed dispatched / flush-observer busy window "
    "(obs/costplane.py)",
    fn=lambda: _costplane_mod().last_achieved("achieved_gbps"))

SLO_LATENCY_SECONDS = _REGISTRY.histogram(
    "tpu_slo_latency_seconds",
    "Per-tenant service latency by phase: end_to_end (queue wait + "
    "execution), queue_wait, exec (obs/slo.py)",
    labels=("tenant", "phase"))
SLO_BREACHES = _REGISTRY.counter(
    "tpu_slo_breaches_total",
    "Queries past spark.rapids.tpu.obs.slo.targetMs by tenant, each "
    "attributed to exactly one cause (shed/predicted_breach/deadline/"
    "inline_compile/slow_exec; predicted_breach = the admission "
    "scheduler shed the query BEFORE it burned device time)",
    labels=("tenant", "cause"))
SLO_BURN_MS = _REGISTRY.counter(
    "tpu_slo_burn_ms_total",
    "Cumulative ms of SLO overshoot per tenant (the error-budget burn "
    "counter: breach count says how often, burn says how badly)",
    labels=("tenant",))


# -- longitudinal fleet plane (obs/history.py + obs/anomaly.py) -------------
# Write buckets sized to a host JSONL append: single-digit µs for the
# in-memory enqueue, tens of µs to low ms for the fsync-free file write.
_HISTORY_WRITE_BUCKETS = (0.00001, 0.00005, 0.0001, 0.00025, 0.0005,
                          0.001, 0.0025, 0.005, 0.01, 0.05, 0.1)


def _anomaly_mod():
    from . import anomaly
    return anomaly


HISTORY_ROWS = _REGISTRY.counter(
    "tpu_history_rows_total",
    "Query-history rows appended by the persistent history store "
    "(obs/history.py), by terminal outcome — one row per terminal "
    "query when the plane is enabled",
    labels=("outcome",))
HISTORY_DROPPED = _REGISTRY.counter(
    "tpu_history_dropped_total",
    "History rows dropped because the bounded writer queue was full "
    "(the store never blocks or fails the query path)")
HISTORY_WRITE_SECONDS = _REGISTRY.histogram(
    "tpu_history_write_seconds",
    "Wall duration of each background JSONL row append (serialize + "
    "write + rotation check; obs/history.py writer thread — off the "
    "query path by construction)",
    buckets=_HISTORY_WRITE_BUCKETS)

ANOMALY_CHECKS = _REGISTRY.counter(
    "tpu_anomaly_checks_total",
    "Per-(fingerprint, key) EWMA folds performed by the online "
    "anomaly sentinel (obs/anomaly.py) — one per gated key per "
    "history row once the store is enabled")
ANOMALY_EVENTS = _REGISTRY.counter(
    "tpu_anomaly_events_total",
    "Anomaly lifecycle events by kind: breach = K consecutive "
    "sigma-outliers opened an anomaly, recovery = K consecutive "
    "in-band runs closed it (obs/anomaly.py)",
    labels=("kind",))
ANOMALY_ACTIVE = _REGISTRY.gauge(
    "tpu_anomaly_active",
    "Currently open (breached, not yet recovered) anomalies across "
    "all fingerprints and keys",
    fn=lambda: float(_anomaly_mod().active_count()))
ANOMALY_FP = _REGISTRY.counter(
    "tpu_anomaly_fp_total",
    "Anomaly breach-opens that closed again without a confirmed level "
    "shift (the recovery arrived from the frozen baseline, not a "
    "re-baselining) — transient false positives; on stationary soak "
    "traffic their rate over breaches is the sentinel's "
    "false-positive accounting (obs/anomaly.py, gated by the soak "
    "bench key anomaly_fp_rate)")


# -- soak plane: burn-rate monitors (obs/burn.py) + load harness
#    (service/soak.py) -------------------------------------------------------

def _burn_mod():
    from . import burn
    return burn


def _soak_mod():
    from ..service import soak
    return soak


BURN_RATE = _REGISTRY.gauge(
    "tpu_burn_rate",
    "Multi-window SLO burn rate per tenant (obs/burn.py): fraction of "
    "the obs.burn.budgetPct error budget consumed inside the window "
    "over the fraction allowed — 1.0 burns the budget exactly as fast "
    "as permitted, >1 is an incident.  window=fast catches spikes, "
    "window=slow confirms sustained burn (the SRE multi-window "
    "alerting shape)",
    labels=("tenant", "window"))
BURN_STEADY_STATE = _REGISTRY.gauge(
    "tpu_burn_steady_state",
    "1 while the EWMA-slope steady-state detector declares the "
    "service stationary (obs/burn.py); drops to 0 when a fault or "
    "load shift breaks the latency slope streak")
BURN_LEAK_DRIFT_BYTES = _REGISTRY.gauge(
    "tpu_burn_leak_drift_bytes",
    "Leak-drift regression over the sampled memplane live-bytes "
    "floor: min of the newest half of samples minus min of the oldest "
    "half (obs/burn.py) — exactly 0 on a clean soak run, gated exact "
    "by ci/perf_gate.py",
    fn=lambda: float(_burn_mod().leak_drift_bytes()))
SOAK_QPS = _REGISTRY.gauge(
    "tpu_soak_qps",
    "Achieved completions/second of the live (or last) soak run "
    "(service/soak.py harness state)",
    fn=lambda: float(_soak_mod().stats_section()["qps_actual"]))
SOAK_INFLIGHT = _REGISTRY.gauge(
    "tpu_soak_inflight",
    "Queries submitted by the soak harness and not yet terminal",
    fn=lambda: float(_soak_mod().stats_section()["inflight"]))
SOAK_SUBMITTED = _REGISTRY.gauge(
    "tpu_soak_submitted_total",
    "Soak-harness submissions accepted by the service this run",
    fn=lambda: float(_soak_mod().stats_section()["submitted"]))
SOAK_COMPLETED = _REGISTRY.gauge(
    "tpu_soak_completed_total",
    "Soak-harness queries completed this run",
    fn=lambda: float(_soak_mod().stats_section()["completed"]))
SOAK_SHED = _REGISTRY.gauge(
    "tpu_soak_shed_total",
    "Soak-harness submissions shed by admission control this run",
    fn=lambda: float(_soak_mod().stats_section()["shed"]))
SOAK_ACTIVE_FAULTS = _REGISTRY.gauge(
    "tpu_soak_active_faults",
    "Injected fault windows currently open (service/faults.py)",
    fn=lambda: float(len(_soak_mod().stats_section()["active_faults"])))


# -- observability self-metering (obs/overhead.py) --------------------------

def _overhead_mod():
    from . import overhead
    return overhead


OBS_SELF_SECONDS = _REGISTRY.counter(
    "tpu_obs_self_seconds_total",
    "Host time the observability layer spent inside its own hot-path "
    "entry points, by plane (obs/overhead.py self-meter): stats "
    "staging, timeline note_flush, netplane put/get accounting, "
    "memplane register/sweep, costplane dispatch accounting, history "
    "row build, doctor assembly.  Collect-time callbacks over "
    "preallocated ns counters — scrapes pay the read, the record path "
    "pays two clock reads and two list writes.  The flight recorder "
    "is exempt by construction",
    labels=("plane",))
for _plane in ("stats", "timeline", "net", "mem", "cost", "history",
               "doctor", "burn"):
    OBS_SELF_SECONDS.labels(plane=_plane).set_function(
        lambda p=_plane: _overhead_mod().plane_seconds(p))


# -- plan cache + predictive scheduler (cache/plan_cache.py,
#    service/scheduler.py) --------------------------------------------------

def _plan_cache_mod():
    from ..cache import plan_cache
    return plan_cache


PLAN_CACHE_EVENTS = _REGISTRY.counter(
    "tpu_plan_cache_events_total",
    "Fingerprint-keyed plan-cache lifecycle events "
    "(cache/plan_cache.py): hit = repeat logical shape replayed its "
    "stored certificates (verify + PV-FLUSH skipped), miss = cold "
    "plan + store, validation_miss = rebuilt plan's fingerprint "
    "diverged from the stored one (fell back to the cold path), "
    "invalidated = conf-fingerprint change dropped the entry, "
    "evicted = LRU bound pushed the entry out",
    labels=("event",))
PLAN_CACHE_ENTRIES = _REGISTRY.gauge(
    "tpu_plan_cache_entries",
    "Plan shapes currently resident in the bounded plan cache",
    fn=lambda: float(_plan_cache_mod().entry_count()))
SCHED_PREDICTIONS = _REGISTRY.counter(
    "tpu_sched_predictions_total",
    "Admission-time exec_ms predictions by the predictive scheduler "
    "(service/scheduler.py), by source: baseline = a frozen EWMA "
    "baseline for the query's fingerprint existed, none = no cache "
    "entry or no frozen baseline yet (query admitted unranked)",
    labels=("source",))


def compile_cache_event(cache: str, hit: bool, dur_ns: int = 0,
                        signature=None):
    """One compile-cache lookup (called from the exec/kernels JIT
    caches; compile paths, not per-batch hot paths).  A miss whose
    compile duration is already known may pass ``dur_ns``/``signature``
    to feed the compile-telemetry plane directly; callers that only
    learn the duration at the jitted callable's first invocation use
    ``compile_watch.wrap_miss`` instead."""
    COMPILE_CACHE.labels(cache=cache,
                         outcome="hit" if hit else "miss").inc()
    if dur_ns > 0:
        from . import compile_watch
        compile_watch.note_compile(cache, dur_ns, signature)


def superstage_event(event: str, n: int = 1):
    """One superstage compiler event (carve/eject/fallback/spec_redo —
    plan-time and stage-setup paths, not per-batch hot paths)."""
    COMPILE_SUPERSTAGES.labels(event=event).inc(n)
