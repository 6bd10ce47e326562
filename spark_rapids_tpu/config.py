"""Typed configuration registry — the RapidsConf role.

Reference analogue: sql-plugin/.../RapidsConf.scala:116,288 — a registry of
typed ``ConfEntry``s under ``spark.rapids.*`` with docs, defaults and
converters, able to self-generate docs (RapidsConf.help/main,
RapidsConf.scala:1229).  Here the namespace is ``spark.rapids.tpu.*`` and
entries drive the same behaviors: enable/disable per-op replacement,
batch-size goals, memory pool fractions, shuffle transport selection,
explain verbosity, test-mode assertions.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class ConfEntry:
    key: str
    converter: Callable[[str], Any]
    default: Any
    doc: str
    internal: bool = False

    def get(self, conf: "TpuConf") -> Any:
        raw = conf._settings.get(self.key)
        if raw is None:
            return self.default
        if isinstance(raw, str):
            return self.converter(raw)
        return raw


_REGISTRY: Dict[str, ConfEntry] = {}


def _register(entry: ConfEntry) -> ConfEntry:
    assert entry.key not in _REGISTRY, f"duplicate conf {entry.key}"
    _REGISTRY[entry.key] = entry
    return entry


def _bool(v: str) -> bool:
    return str(v).strip().lower() in ("true", "1", "yes")


def conf_bool(key, default, doc, internal=False):
    return _register(ConfEntry(key, _bool, default, doc, internal))


def conf_int(key, default, doc, internal=False):
    return _register(ConfEntry(key, int, default, doc, internal))


def conf_float(key, default, doc, internal=False):
    return _register(ConfEntry(key, float, default, doc, internal))


def conf_str(key, default, doc, internal=False):
    return _register(ConfEntry(key, str, default, doc, internal))


def conf_bytes(key, default, doc, internal=False):
    def parse(v):
        s = str(v).strip().lower()
        mult = 1
        for suffix, m in (("k", 2**10), ("m", 2**20), ("g", 2**30),
                          ("t", 2**40)):
            if s.endswith(suffix + "b"):
                s, mult = s[:-2], m
                break
            if s.endswith(suffix):
                s, mult = s[:-1], m
                break
        return int(float(s) * mult)
    return _register(ConfEntry(key, parse, default, doc, internal))


# ---------------------------------------------------------------------------
# Entries (parity with the reference's major spark.rapids.* groups,
# RapidsConf.scala — same knobs, TPU names)
# ---------------------------------------------------------------------------

SQL_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.enabled", True,
    "Master enable for plan acceleration (reference: spark.rapids.sql.enabled)")
EXPLAIN = conf_str(
    "spark.rapids.tpu.sql.explain", "NONE",
    "NONE/NOT_ON_TPU/ALL: log why operators did or didn't go to the TPU "
    "(reference: spark.rapids.sql.explain)")
PLAN_VERIFY = conf_bool(
    "spark.rapids.tpu.sql.planVerify", False,
    "Run the static plan-invariant verifier on every physical plan "
    "before execution: schema propagation, dtype supportability, "
    "partitioning/distribution contracts, and cancellation-checkpoint "
    "coverage.  Violations raise PlanVerificationError listing every "
    "failure with an annotated plan tree.  Forced on under pytest; "
    "default OFF in production to keep planning latency flat "
    "(reference: the tagging/validation passes of GpuOverrides)")
PLAN_VERIFY_FLUSH_BUDGET = conf_int(
    "spark.rapids.tpu.sql.planVerify.flushBudget", 0,
    "When > 0, the PV-FLUSH verifier pass fails any plan whose "
    "statically predicted warm flush count (analysis/flush_budget.py) "
    "exceeds this many device round trips per collect.  0 keeps the "
    "pass advisory: the prediction is still computed and surfaced "
    "(tools/report.py, bench predicted_flushes) but never fails "
    "verification")
AUDIT_ENABLED = conf_bool(
    "spark.rapids.tpu.analysis.audit.enabled", True,
    "Enable the jaxpr program auditor (analysis/program_audit.py): "
    "ci/audit.py and bench coverage reporting abstractly trace every "
    "registered jitted program and enforce device-purity rules "
    "AUD001-AUD004 (no host callbacks, no float primitives in exact "
    "programs, no data-dependent shapes, fusion-breaker budgets).  "
    "Disabling skips the audit sweep; it never affects query "
    "execution")
RESIDENCY_GUARD = conf_bool(
    "spark.rapids.tpu.analysis.residency.transferGuard", False,
    "Wrap engine execution (the session collect drain and every "
    "pipeline pool worker) in a scoped "
    "jax.transfer_guard_device_to_host('disallow') so any device->host "
    "transfer outside a residency.declared_transfer(site=...) region "
    "fails loudly instead of silently costing a dispatch-queue sync "
    "(analysis/residency.py).  The tier-1 test harness forces this on "
    "via SPARK_RAPIDS_TPU_FORCE_TRANSFER_GUARD=1 (set the env var to "
    "0 to switch the forced mode off); production default is off "
    "because the guard adds a thread-local context flip per drain")
RESIDENCY_IN_EVENT_LOG = conf_bool(
    "spark.rapids.tpu.analysis.residency.inEventLog", True,
    "Record the per-query declared-transfer counts (total plus the "
    "per-site breakdown from the residency registry) on the event-log "
    "record next to flushes and host_drop_tax_ms, so the doctor can "
    "cite which declared site owns the host_staging share.  Counting "
    "is a lock-guarded integer bump per declared region and is always "
    "on; this conf only controls the event-log field")
BATCH_SIZE_ROWS = conf_int(
    "spark.rapids.tpu.sql.batchSizeRows", 1 << 20,
    "Target rows per columnar batch (coalesce goal; reference: "
    "spark.rapids.sql.batchSizeBytes)")
BATCH_SIZE_BYTES = conf_bytes(
    "spark.rapids.tpu.sql.batchSizeBytes", 512 * 2**20,
    "Target bytes per columnar batch for coalescing")
ALLUXIO_PATHS_TO_REPLACE = conf_str(
    "spark.rapids.tpu.alluxio.pathsToReplace", "",
    "Semicolon-separated 'scheme://from->scheme://to' rules applied to "
    "scan paths before reading, so queries planned against one store "
    "transparently read a faster mirror (reference: "
    "spark.rapids.alluxio.pathsToReplace, RapidsConf.scala:1072)")
PYTHON_USE_WORKERS = conf_bool(
    "spark.rapids.tpu.python.useWorkerProcesses", True,
    "Run pandas UDFs in persistent out-of-process Python workers over "
    "Arrow IPC with pipelined batch streaming (reference: "
    "GpuArrowEvalPythonExec + BatchQueue); functions that cannot "
    "pickle fall back in-process")
PYTHON_WORKERS = conf_int(
    "spark.rapids.tpu.python.concurrentPythonWorkers", 2,
    "Max concurrently leased Python worker processes (reference: "
    "spark.rapids.python.concurrentPythonWorkers / "
    "PythonWorkerSemaphore)")
SORT_OOC_CHUNK_ROWS = conf_int(
    "spark.rapids.tpu.sql.sort.outOfCore.chunkRows", 1 << 22,
    "Out-of-core sort merge emits chunks of at most about this many "
    "rows; a partition with more buffered rows than this merges via "
    "range-sliced spillable runs instead of one concat "
    "(reference: GpuSortExec.scala:219 out-of-core mode)")
JOIN_GATHER_CHUNK_ROWS = conf_int(
    "spark.rapids.tpu.sql.join.gather.chunkRows", 1 << 22,
    "Join output rows gathered per expansion chunk; a (stream batch, "
    "build) pair whose match total exceeds this expands incrementally "
    "— splitting even one probe row's matches across chunks — so no "
    "single output allocation exceeds the budget; a join with a "
    "residual condition decides at most this many candidate pairs a "
    "launch (reference: JoinGatherer.scala bounded gather)")
SORT_OOC_SAMPLES = conf_int(
    "spark.rapids.tpu.sql.sort.outOfCore.samplesPerRun", 256,
    "Sorted-run key samples kept per run for choosing merge range "
    "boundaries (slack per run-boundary is ~run_rows/samples)",
    internal=True)
CONCURRENT_TPU_TASKS = conf_int(
    "spark.rapids.tpu.sql.concurrentTpuTasks", 2,
    "Max concurrent tasks admitted to the device (reference: "
    "spark.rapids.sql.concurrentGpuTasks / GpuSemaphore)")
SCAN_CACHE = conf_bool(
    "spark.rapids.tpu.io.deviceScanCache.enabled", True,
    "Keep uploaded file-scan batches device-resident across queries, "
    "keyed on (files, mtimes, columns, pushed filters, batching). "
    "HBM residency makes repeat scans of the same tables skip decode "
    "AND host->device transfer (ParquetCachedBatchSerializer role, "
    "applied at the scan). Entries are dropped LRU past deviceScanCache.bytes "
    "and on real device-OOM pressure")

SCAN_CACHE_BYTES = conf_bytes(
    "spark.rapids.tpu.io.deviceScanCache.bytes", 6 << 30,
    "Device-byte budget for the scan cache (LRU beyond it)")

SCAN_PREFETCH = conf_bool(
    "spark.rapids.tpu.sql.reader.prefetch.enabled", True,
    "Decode scan files on background producer threads ahead of "
    "consumption (bounded to 2 host tables per partition) so scan I/O "
    "overlaps device compute; uploads are admitted under the device "
    "semaphore (reference: the multithreaded cloud reader + "
    "GpuSemaphore)")
MAX_READER_BATCH_ROWS = conf_int(
    "spark.rapids.tpu.sql.reader.batchSizeRows", 1 << 20,
    "Soft cap on rows per scan batch (reference: "
    "spark.rapids.sql.reader.batchSizeRows)")
HBM_POOL_FRACTION = conf_float(
    "spark.rapids.tpu.memory.pool.fraction", 0.9,
    "Fraction of device HBM managed by the arena (reference: "
    "spark.rapids.memory.gpu.allocFraction)")
HBM_RESERVE = conf_bytes(
    "spark.rapids.tpu.memory.reserve", 1 << 30,
    "HBM held back from the pool for XLA scratch (reference: "
    "spark.rapids.memory.gpu.reserve)")
HOST_SPILL_LIMIT = conf_bytes(
    "spark.rapids.tpu.memory.host.spillStorageSize", 8 * 2**30,
    "Bytes of host memory for spilled buffers before disk "
    "(reference: spark.rapids.memory.host.spillStorageSize)")
SPILL_DIR = conf_str(
    "spark.rapids.tpu.memory.spill.dir", "/tmp/spark_rapids_tpu_spill",
    "Directory for disk-tier spill files (reference: RapidsDiskStore)")
MEMORY_DEBUG = conf_bool(
    "spark.rapids.tpu.memory.debug", False,
    "Log arena allocations (reference: spark.rapids.memory.gpu.debug)")
SHUFFLE_TRANSPORT = conf_str(
    "spark.rapids.tpu.shuffle.transport", "local",
    "Shuffle transport: local | mesh (ICI collectives) "
    "(reference: spark.rapids.shuffle.transport.enabled / UCX)")
SHUFFLE_PARTITIONS = conf_int(
    "spark.rapids.tpu.sql.shuffle.partitions", 8,
    "Default partition count for exchanges (spark.sql.shuffle.partitions)")
SHUFFLE_MAP_STAGING_BYTES = conf_bytes(
    "spark.rapids.tpu.shuffle.mapStagingBytes", 2 * 2**30,
    "Device bytes of map-side shuffle input allowed to stage between "
    "fused flushes.  Staging many map partitions before one flush "
    "amortizes dispatch, but an unbounded stage could exhaust HBM on "
    "shuffles larger than device memory; past this budget the exchange "
    "flushes and finalizes what is staged so the catalog can spill it. "
    "Applies to hash exchanges; RANGE exchanges (global sort) first "
    "materialize the input for bound sampling and are not covered "
    "(reference role: the bounded batch iteration in "
    "GpuShuffleExchangeExec.scala:176)")
SHUFFLE_COMPRESS = conf_str(
    "spark.rapids.tpu.shuffle.compression.codec", "none",
    "none|zlib|lz4|tplz codec for shuffle buffers; tplz is the native "
    "C++ LZ block codec (the nvcomp-LZ4 role; reference: "
    "spark.rapids.shuffle.compression.codec)")
VARIABLE_FLOAT_AGG = conf_bool(
    "spark.rapids.tpu.sql.variableFloatAgg.enabled", False,
    "Allow float/double aggregations (sum/avg/min/max) to accumulate in "
    "f32 on device.  TPUs have no 64-bit float ALU — XLA emulates f64 at "
    "4-6x cost — so f32 accumulation is the TPU-native fast path; results "
    "can differ from the CPU oracle in low-order bits.  Default OFF to "
    "match the reference (spark.rapids.sql.variableFloatAgg.enabled "
    "defaults false, RapidsConf.scala:556-562): exact results unless the "
    "user opts in.  When enabled, inputs whose f32 cast would overflow "
    "are detected on device and re-run on the exact path.")
EXACT_DOUBLE = conf_bool(
    "spark.rapids.tpu.sql.exactDouble.enabled", False,
    "Store DOUBLE columns as IEEE-754 bit patterns in int64 and route "
    "arithmetic/comparison/aggregation through the exact softfloat "
    "kernels (kernels/binary64.py).  The chip has no f64 ALU — XLA's "
    "emulated f64 is an f32 pair (~48-bit precision, ~1e+/-38 range), "
    "so values like 1e300 cannot even round-trip device memory without "
    "this mode.  Wired surfaces: scan/literal/cast sources, +,-,*,/, "
    "abs, negate, comparisons, sort/group/join keys, sum/min/max/avg. "
    "Other DOUBLE ops raise loudly.  (Reference contract: bit-for-bit "
    "DOUBLE, GpuCast.scala / arithmetic.scala.)")
AGG_TABLE_SIZE = conf_int(
    "spark.rapids.tpu.sql.agg.tableSize", 4096,
    "Bucket-table size for the sort-free small-domain group-by fast path "
    "(kernels/aggregate.py table_bucket).  Integer-family key sets "
    "whose combined cardinality range fits are aggregated via "
    "small-output scatters with no sort, in batches of at least this "
    "capacity; a device-side fit flag reruns non-fitting batches on the "
    "general sort path.")
AGG_COMPACT_ROWS = conf_int(
    "spark.rapids.tpu.sql.agg.speculativeCompactRows", 1 << 16,
    "Sort-path group-by outputs are speculatively compacted on device "
    "to this capacity (a fit flag verifies group count <= cap at the "
    "consumer's flush barrier; the rare wider batch is recomputed "
    "uncompacted).  Without it a 4M-row batch aggregating to 1k groups "
    "hands a 4M-capacity batch to the exchange/join, and every "
    "downstream program pays full-width work for dead rows.")
INCOMPATIBLE_OPS = conf_bool(
    "spark.rapids.tpu.sql.incompatibleOps.enabled", False,
    "Allow ops whose results can differ from CPU in corner cases "
    "(reference: spark.rapids.sql.incompatibleOps.enabled)")
HAS_NANS = conf_bool(
    "spark.rapids.tpu.sql.hasNans", True,
    "Assume float data may contain NaNs (reference: spark.rapids.sql.hasNans)")
ANSI_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.ansi.enabled", False,
    "ANSI mode: overflow/invalid-cast raise instead of null/wrap")
TEST_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.test.enabled", False,
    "Test mode: assert everything that should run on TPU did "
    "(reference: spark.rapids.sql.test.enabled)")
TEST_ALLOWED_NON_TPU = conf_str(
    "spark.rapids.tpu.sql.test.allowedNonTpu", "",
    "Comma-separated op names permitted to fall back in test mode "
    "(reference: spark.rapids.sql.test.allowedNonGpu)")
CBO_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.optimizer.enabled", False,
    "Cost-based fallback optimizer (reference: "
    "spark.rapids.sql.optimizer.enabled)")
ADAPTIVE_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.adaptive.enabled", True,
    "Adaptive query execution: re-plan exchanges/joins from materialized "
    "shuffle statistics (reference: AQE handling in GpuOverrides/"
    "GpuTransitionOverrides + GpuCustomShuffleReaderExec)")
ADAPTIVE_TARGET_PARTITION_BYTES = conf_bytes(
    "spark.rapids.tpu.sql.adaptive.targetPartitionBytes", 64 << 20,
    "Advisory post-shuffle partition size: adjacent small reduce "
    "partitions are coalesced up to this (the "
    "spark.sql.adaptive.advisoryPartitionSizeInBytes role)")
ADAPTIVE_BROADCAST_BYTES = conf_bytes(
    "spark.rapids.tpu.sql.adaptive.autoBroadcastJoinBytes", 32 << 20,
    "Runtime broadcast threshold: a shuffled join whose materialized "
    "build side is under this skips the probe-side shuffle entirely "
    "(AQE shuffled-hash-join -> broadcast conversion)")
ADAPTIVE_SKEW_FACTOR = conf_float(
    "spark.rapids.tpu.sql.adaptive.skewedPartitionFactor", 5.0,
    "A probe partition is skewed when its bytes exceed this multiple of "
    "the median partition size (spark.sql.adaptive.skewJoin role)")
ADAPTIVE_SKEW_MIN_BYTES = conf_bytes(
    "spark.rapids.tpu.sql.adaptive.skewedPartitionThresholdBytes", 16 << 20,
    "Minimum bytes before a partition can be considered skewed")
METRICS_LEVEL = conf_str(
    "spark.rapids.tpu.sql.metrics.level", "MODERATE",
    "ESSENTIAL/MODERATE/DEBUG metric collection level "
    "(reference: spark.rapids.sql.metrics.level)")
DECIMAL_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.decimalType.enabled", True,
    "Enable decimal64 acceleration (reference: "
    "spark.rapids.sql.decimalType.enabled)")
CAST_STRING_TO_FLOAT = conf_bool(
    "spark.rapids.tpu.sql.castStringToFloat.enabled", False,
    "Enable string->float cast (tiny rounding diffs vs CPU; reference: "
    "spark.rapids.sql.castStringToFloat.enabled)")
FORMAT_PARQUET_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.format.parquet.enabled", True,
    "Enable Parquet scan/write acceleration")
FORMAT_CSV_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.format.csv.enabled", True,
    "Enable CSV scan acceleration")
FORMAT_ORC_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.format.orc.enabled", True,
    "Enable ORC scan/write acceleration")
PARQUET_READER_TYPE = conf_str(
    "spark.rapids.tpu.sql.format.parquet.reader.type", "AUTO",
    "AUTO/PERFILE/MULTITHREADED/COALESCING (reference: "
    "spark.rapids.sql.format.parquet.reader.type)")
MULTITHREAD_READ_THREADS = conf_int(
    "spark.rapids.tpu.sql.format.parquet.multiThreadedRead.numThreads", 4,
    "Prefetch threads for the multithreaded reader (reference: "
    "spark.rapids.sql.format.parquet.multiThreadedRead.numThreads)")
UDF_COMPILER_ENABLED = conf_bool(
    "spark.rapids.tpu.sql.udfCompiler.enabled", True,
    "Compile Python UDF bytecode to native expressions when possible "
    "(reference: com.nvidia.spark.udf.Plugin)")
EVENT_LOG_PATH = conf_str(
    "spark.rapids.tpu.eventLog.path", "",
    "Append per-query JSON event records here; consumed by the "
    "qualification/profiling tools (reference: Spark event logs + tools/)")
EVENT_LOG_ROTATE_BYTES = conf_bytes(
    "spark.rapids.tpu.eventLog.rotation.maxBytes", 0,
    "Rotate the event log (rename to <path>.N, start fresh) when it "
    "would exceed this many bytes, so long service runs don't grow one "
    "unbounded JSONL file.  0 disables rotation.  Env override: "
    "SPARK_RAPIDS_TPU_EVENT_LOG_MAX_BYTES")
EVENT_LOG_FLUSH_PER_RECORD = conf_bool(
    "spark.rapids.tpu.eventLog.flushPerRecord", True,
    "Flush the event log after every record (durability for crash "
    "forensics); false trades durability for fewer syscalls on "
    "high-QPS services.  Env override: SPARK_RAPIDS_TPU_EVENT_LOG_FLUSH")
OBS_TRACE_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.trace.enabled", False,
    "Record the fine level of hierarchical engine spans (service -> "
    "exec node -> shuffle/memory; the NvtxRange role) as Chrome trace "
    "events in an in-process buffer.  The coarse srt.* spans at layer "
    "boundaries are recorded whatever this says (obs/trace.py); "
    "disabled, a fine site costs one flag read")
OBS_TRACE_PATH = conf_str(
    "spark.rapids.tpu.obs.trace.path", "",
    "Write the Chrome trace-event JSON (Perfetto/chrome://tracing "
    "loadable) here when the session or the service closes, or on "
    "trace.flush(), when tracing is enabled; never per query")
OBS_TRACE_MAX_SPANS = conf_int(
    "spark.rapids.tpu.obs.trace.maxBufferedSpans", 100000,
    "Bound on buffered spans; past it new spans are dropped (and "
    "counted) instead of growing host memory without limit")
SHIM_PROVIDER_OVERRIDE = conf_str(
    "spark.rapids.tpu.shims-provider-override", "",
    "Force a specific compat shim (reference: "
    "spark.rapids.shims-provider-override)")
SHUFFLE_MODE = conf_str(
    "spark.rapids.tpu.shuffle.mode", "inprocess",
    "Distributed exchange strategy: 'inprocess' (catalog-backed shuffle "
    "manager) or 'mesh' (aggregations lower to ONE SPMD program over the "
    "jax.sharding.Mesh: hash-routed lax.all_to_all over ICI in place of "
    "the transport; reference: RapidsShuffleManager over UCX)")
PROFILE_TRACE_DIR = conf_str(
    "spark.rapids.tpu.profile.traceDir", "",
    "Capture an XLA/jax profiler trace (xprof / trace-viewer format) "
    "of each query execution into this directory (reference: NVTX "
    "ranges + Nsight, docs/dev/nvtx_profiling.md)")
SERVICE_WORKERS = conf_int(
    "spark.rapids.tpu.service.workerThreads", 4,
    "Executor threads of the in-process query service; each runs one "
    "admitted query at a time (device concurrency is still bounded "
    "separately by concurrentTpuTasks / the DeviceSemaphore)")
SERVICE_MAX_QUEUE_DEPTH = conf_int(
    "spark.rapids.tpu.service.admission.maxQueueDepth", 64,
    "Bounded admission queue: submissions beyond this many waiting "
    "queries are shed with ServiceOverloaded (load shedding keeps "
    "client latency bounded instead of queueing without limit)")
SERVICE_MAX_QUEUED_BYTES = conf_bytes(
    "spark.rapids.tpu.service.admission.maxQueuedBytes", 4 << 30,
    "Shed submissions once the estimated bytes of queued queries "
    "(client-provided est_bytes) exceed this; 0 disables the byte "
    "bound and sheds on depth only")
SERVICE_DEFAULT_DEADLINE_MS = conf_int(
    "spark.rapids.tpu.service.defaultDeadlineMs", 0,
    "Deadline applied to queries submitted without one, in ms from "
    "admission; past it the query is cooperatively cancelled at the "
    "next operator checkpoint. 0 = no default deadline")
SERVICE_RETRY_MAX_ATTEMPTS = conf_int(
    "spark.rapids.tpu.service.retry.maxAttempts", 3,
    "Total attempts per query for retryable failures (device OOM, "
    "shuffle fetch failure) before the error is surfaced (reference: "
    "the bounded spill-and-retry of DeviceMemoryEventHandler and "
    "Spark's stage-retry on FetchFailedException)")
SERVICE_RETRY_BACKOFF_MS = conf_int(
    "spark.rapids.tpu.service.retry.initialBackoffMs", 50,
    "Backoff before the first retry; grows by backoffMultiplier per "
    "attempt. Sleeps are interruptible by cancellation")
SERVICE_RETRY_BACKOFF_MULT = conf_float(
    "spark.rapids.tpu.service.retry.backoffMultiplier", 2.0,
    "Exponential backoff multiplier between retry attempts")
SERVICE_RETRY_BATCH_DECAY = conf_float(
    "spark.rapids.tpu.service.retry.batchSizeDecay", 0.5,
    "Each retry scales the query's batch-size goals (batchSizeRows/"
    "Bytes, reader batch rows) by this factor so a memory-pressured "
    "query re-runs at a smaller device footprint")
OBS_FLIGHT_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.flightRecorder.enabled", True,
    "Always-on flight recorder: every engine thread keeps a bounded "
    "ring of compact structured events (span boundaries, retries, "
    "spill/unspill, semaphore, shuffle fetch, admission transitions) "
    "recorded unconditionally with no allocation or locking on the hot "
    "path; the recent tail lands in failure diagnostic bundles even "
    "with tracing disabled (the airplane-black-box counterpart to "
    "obs.trace.*)")
OBS_FLIGHT_CAPACITY = conf_int(
    "spark.rapids.tpu.obs.flightRecorder.capacityPerThread", 512,
    "Event slots preallocated per thread ring; past it the recorder "
    "overwrites oldest (fixed memory, recent history only).  Applies "
    "to rings created after the change")
OBS_WATCHDOG_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.watchdog.enabled", True,
    "Service stall watchdog: a daemon that flags RUNNING queries whose "
    "worker thread records no flight-recorder events for "
    "watchdog.stallSeconds while holding an inflight slot (and "
    "typically the device semaphore), then captures thread stacks, the "
    "arena map, shuffle state and queue depths into a diagnostic "
    "bundle and logs a 'watchdog' service event (once per query)")
OBS_WATCHDOG_INTERVAL_MS = conf_int(
    "spark.rapids.tpu.obs.watchdog.intervalMs", 1000,
    "Watchdog poll period; each poll reads one per-thread event-count "
    "map — nothing on any query hot path")
OBS_WATCHDOG_STALL_S = conf_int(
    "spark.rapids.tpu.obs.watchdog.stallSeconds", 120,
    "A RUNNING query with no flight-recorder progress for this long is "
    "declared stalled and triggers the watchdog")
OBS_WATCHDOG_REFIRE_S = conf_float(
    "spark.rapids.tpu.obs.watchdog.refireSeconds", 0.0,
    "Rate-limited periodic re-fire for a query that STAYS stalled: "
    "after the first trigger the watchdog fires again (fresh stacks + "
    "diag bundle + event) every this many seconds while the stall "
    "persists, so a soak-length hang keeps producing evidence instead "
    "of going silent after one bundle.  0 keeps the legacy "
    "once-per-query behavior")
OBS_DIAG_DIR = conf_str(
    "spark.rapids.tpu.obs.diagnostics.dir", "",
    "Directory for automatic failure diagnostic bundles: on query "
    "failure, device OOM, deadline expiry, cancellation, or watchdog "
    "trigger the service writes one JSON bundle (flight-recorder tail, "
    "all thread stacks, metrics snapshot, arena map, plan tree with "
    "verifier verdicts, conf dump with secrets redacted) named "
    "diag-<utc>-<query_id>-<trigger>.json; render with tools/"
    "diagnose.py.  Empty disables bundle capture")
OBS_DIAG_MAX_BUNDLES = conf_int(
    "spark.rapids.tpu.obs.diagnostics.maxBundles", 20,
    "Rotation bound on the diagnostics dir: after each write the "
    "oldest diag-*.json beyond this many are deleted")
OBS_STATS_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.stats.enabled", True,
    "Runtime stats plane (obs/stats.py + obs/profile.py): per-dispatch "
    "device-time attribution under superstage fusion plus exchange-"
    "boundary data statistics (per-partition rows/bytes/null counts/"
    "key min-max, an on-device HLL distinct-key sketch, and a skew "
    "verdict), assembled into a per-query StatsProfile persisted with "
    "the event log and exported as tpu_stats_* metrics.  All device-"
    "side collection rides dispatches the query already makes: the "
    "plane adds ZERO pending-pool flushes (tests/test_stats.py asserts "
    "the FLUSH_COUNT delta)")
OBS_STATS_SKETCH_REGISTERS = conf_int(
    "spark.rapids.tpu.obs.stats.sketchRegisters", 512,
    "Register count (m) of the HLL-style distinct-key sketch computed "
    "in the same dispatch window as each hash-exchange split.  Rounded "
    "down to a power of two, minimum 64; relative error is about "
    "1.04/sqrt(m) (~4.6% at the default 512)")
OBS_STATS_SKEW_FACTOR = conf_float(
    "spark.rapids.tpu.obs.stats.skewFactor", 4.0,
    "An exchange is flagged skewed when its largest partition holds "
    "more than this multiple of the median partition's rows (the AQE "
    "skew-join threshold role; ROADMAP item 3 consumes the verdict)")
OBS_STATS_IN_EVENT_LOG = conf_bool(
    "spark.rapids.tpu.obs.stats.profileInEventLog", True,
    "Persist the per-query StatsProfile artifact inside the engine "
    "event-log record (tools/report.py --stats renders it); off keeps "
    "the profile reachable only via session.last_stats_profile")
OBS_STATS_SAMPLE_EVERY = conf_int(
    "spark.rapids.tpu.obs.stats.sampleEvery", 4,
    "Sampling rate of the per-map-batch exchange stats sketch (HLL "
    "distinct / null counts / key min-max): only every Nth staged map "
    "batch per exchange runs the sketch program.  Per-partition rows, "
    "bytes and the skew verdict stay EXACT regardless (they come from "
    "the split offsets the finalize flush already pulls); sampled "
    "sketch verdicts are labeled with their rate in the entry's "
    "'sample' block.  1 forces exact mode (every batch sketched) — "
    "the test harness forces it via SPARK_RAPIDS_TPU_OBS_STATS_EXACT "
    "so digest-stability assertions see exact entries")
OBS_OVERHEAD_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.overhead.enabled", True,
    "Observability self-metering (obs/overhead.py): per-plane host-"
    "time meter bracketing each plane's hot-path entry points "
    "(interned plane ids, preallocated ns counters, zero allocation "
    "on record), exported as tpu_obs_self_seconds_total{plane} and "
    "the stats()['obs_overhead'] section so the observability tax is "
    "attributed per plane, not just measured as one on-vs-off delta. "
    "The flight recorder is exempt by construction")
OBS_TIMELINE_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.timeline.enabled", True,
    "Device-utilization timeline (obs/timeline.py): accumulate the "
    "busy interval of every fused pending-pool flush and mesh SPMD "
    "dispatch into a bounded per-process store, reconstruct device "
    "busy/idle, classify idle gaps by cause (inline compile, "
    "semaphore wait, admission queue, pipeline starvation, host "
    "staging) from flight-recorder evidence, and report per-query + "
    "process device_util_pct.  Fed by observers the stats plane "
    "already runs: zero extra flushes, one bounded append per flush")
OBS_TIMELINE_MAX_INTERVALS = conf_int(
    "spark.rapids.tpu.obs.timeline.maxIntervals", 1 << 16,
    "Bound on buffered busy intervals in the utilization timeline; "
    "past it new intervals are dropped and counted (fixed memory — "
    "the flight-recorder discipline).  Applies on the next reset")
OBS_COMPILE_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.compile.enabled", True,
    "Compile telemetry (obs/compile_watch.py): time the first call of "
    "every compile-cache miss across the seven engine JIT caches, "
    "recording duration, cache name, shape/dtype signature and an "
    "inline-vs-warm flag (inline = a query context was blocked on "
    "it), exported as the tpu_compile_seconds histogram and the "
    "top-N slowest-compiles table in Service.stats().  The direct "
    "measurement the AOT shape-bucketed compile cache (ROADMAP item "
    "4) is built and judged against")
OBS_COMPILE_TOP_N = conf_int(
    "spark.rapids.tpu.obs.compile.topN", 20,
    "Rows of the slowest-compiles table in Service.stats() (the "
    "bounded record store keeps the slowest 256 compiles)")
OBS_SLO_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.slo.enabled", True,
    "Per-tenant SLO latency plane (obs/slo.py): end-to-end latency "
    "histograms labeled by tenant with admission wait and execution "
    "recorded separately, p50/p95/p99 in Prometheus and "
    "Service.stats(), and breach/burn accounting against "
    "obs.slo.targetMs with every breach attributed to exactly one "
    "cause (shed / deadline / inline_compile / slow_exec)")
OBS_SLO_TARGET_MS = conf_float(
    "spark.rapids.tpu.obs.slo.targetMs", 0.0,
    "End-to-end latency SLO per query in ms (queue wait + execution). "
    "A served query past it is a breach attributed to one cause; shed "
    "and deadline-cancelled queries always breach.  The burn counter "
    "accumulates overshoot ms per tenant.  0 disables breach/burn "
    "accounting (latency histograms still record)")
OBS_NET_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.net.enabled", True,
    "Shuffle-transport observability plane (obs/netplane.py): per-edge "
    "(shuffle, map partition -> reduce partition) transfer matrix, "
    "host-drop tax accounting splitting every exchange into serialize/"
    "dwell/wire/deserialize phases (rolled up per query as "
    "host_drop_tax_ms and fed to the utilization timeline as the "
    "shuffle_host gap cause), connection-pool and bounce-buffer state, "
    "and cross-boundary (query_id, span_id) trace correlation over "
    "the shuffle wire.  Host-side timestamps only: zero extra device "
    "flushes by construction")
OBS_NET_MAX_EDGES = conf_int(
    "spark.rapids.tpu.obs.net.maxEdges", 1 << 16,
    "Bound on distinct (shuffle, map, reduce) edges held in the "
    "transfer matrix; past it new edges are dropped and counted in "
    "tpu_shuffle_edges_evicted_total (fixed memory — the "
    "flight-recorder discipline)")
OBS_NET_MAX_INTERVALS = conf_int(
    "spark.rapids.tpu.obs.net.maxIntervals", 1 << 16,
    "Bound on buffered host-drop work windows (the shuffle_host "
    "timeline evidence) and per-block edge-log entries; past it new "
    "records are dropped, keeping netplane memory fixed")
OBS_MEM_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.mem.enabled", True,
    "HBM memory observability plane (obs/memplane.py): allocation "
    "provenance on every BufferCatalog registration (owner query_id, "
    "operator, site) with per-owner live-byte decomposition summing "
    "exactly to device_bytes and peak attribution, a spill ledger "
    "pricing every tier move (victim, owner, trigger reason, victim "
    "rank, serialize/deserialize duration — fed to the utilization "
    "timeline as the mem_spill gap cause), retention/leak detection "
    "at query terminal states, and headroom forecasting for the "
    "admission path.  Host-side timestamps only: zero extra device "
    "flushes by construction")
OBS_MEM_MAX_LEDGER = conf_int(
    "spark.rapids.tpu.obs.mem.maxLedger", 1 << 16,
    "Bound on retained spill-ledger records and on buffered spill "
    "work windows (the mem_spill timeline evidence); past it new "
    "records are dropped and counted in tpu_mem_ledger_dropped_total "
    "(fixed memory — the flight-recorder discipline)")
OBS_DOCTOR_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.doctor.enabled", True,
    "Cross-plane query doctor (obs/doctor.py): joins the per-query "
    "plane artifacts (utilization-gap taxonomy, inline_compile_ms, "
    "shuffle host-drop tax, memplane spill ledger, predicted-vs-"
    "observed flushes, StatsProfile digest) into one QueryDiagnosis "
    "with exactly one primary bottleneck, contribution shares summing "
    "to 100, Amdahl-modeled headroom per candidate fix, and a ranked "
    "mapping onto ROADMAP items 1-4.  Surfaced on "
    "session.last_query_diagnosis, the event-log record, "
    "Service.stats() and tpu_doctor_verdicts_total.  Pure post-query "
    "host arithmetic over already-collected summaries: zero extra "
    "device flushes by construction")
OBS_COST_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.cost.enabled", True,
    "Device-compute cost plane (obs/costplane.py): captures XLA "
    "static cost analysis (flops, bytes accessed, IO working set) per "
    "(program, bucket) at every JIT-cache first call — inline miss, "
    "AOT warmup and persistent-cache load alike — into a bounded "
    "static-cost store, records effective rows vs padded bucket "
    "capacity on every dispatch, and at query end joins the static "
    "costs with the flush-observer busy window into per-program "
    "achieved FLOP/s, achieved GB/s, arithmetic intensity, a roofline "
    "verdict (compute_bound/memory_bound) against the conf-declared "
    "peak rates, and a padding-waste fraction pricing the AOT "
    "lattice's bucketRatio.  Feeds the doctor's device_compute "
    "sub-cause decomposition.  Host-side trace analysis only: zero "
    "extra device flushes and zero extra backend compiles by "
    "construction")
OBS_COST_PEAK_TFLOPS = conf_float(
    "spark.rapids.tpu.obs.cost.peakTeraflops", 0.0,
    "Explicit override of the peak dense compute rate of one chip in "
    "TFLOP/s — the roofline ceiling achieved FLOP/s is scored "
    "against.  0 (default) takes the published figure for the "
    "running device_kind from spark_rapids_tpu/device_peaks.py (TPU "
    "v5e: 197 bf16 TFLOP/s); a device missing from that table is an "
    "error, not a default, and the CPU test mesh uses a named model "
    "constant, not a measurement.  With peakHbmGBps it fixes the "
    "ridge intensity that splits compute_bound from memory_bound "
    "verdicts; every cost block names its peak_source")
OBS_COST_PEAK_HBM_GBPS = conf_float(
    "spark.rapids.tpu.obs.cost.peakHbmGBps", 0.0,
    "Explicit override of the peak HBM bandwidth of one chip in GB/s "
    "— the roofline memory ceiling.  0 (default) takes the published "
    "figure for the running device_kind from "
    "spark_rapids_tpu/device_peaks.py (TPU v5e: 819 GB/s).  Programs "
    "whose arithmetic intensity (flops per byte accessed) falls "
    "below peakTeraflops*1e3/peakHbmGBps are verdicted memory_bound")
OBS_COST_MAX_RECORDS = conf_int(
    "spark.rapids.tpu.obs.cost.maxRecords", 256,
    "Bound on retained (program, bucket) static-cost records and on "
    "dispatch-ledger keys; past it new entries are dropped and "
    "counted in tpu_cost_records_dropped (fixed memory — the "
    "flight-recorder discipline)")
OBS_HISTORY_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.history.enabled", True,
    "Persistent query-history store (obs/history.py): one compact row "
    "per terminal query — plan fingerprint, tenant, outcome, latency "
    "phases, predicted/observed flushes, device_util_pct + gap "
    "breakdown, host_drop_tax_ms, spill/compile/roofline keys and the "
    "doctor verdict — appended to JSONL segments off the query path "
    "through a bounded writer queue (full queue drops the row and "
    "counts it in tpu_history_dropped_total; a history failure never "
    "fails a query).  The longitudinal substrate the anomaly "
    "sentinel, fleet dashboard and tools/history.py CLI read.  "
    "Host-side arithmetic over already-stamped QueryMetrics: zero "
    "extra device flushes by construction")
OBS_HISTORY_DIR = conf_str(
    "spark.rapids.tpu.obs.history.dir", "",
    "Directory for the history store's history-*.jsonl segments.  "
    "Empty (the default) keeps the store in-memory only: fleet "
    "aggregates, the sentinel and the dashboard all still work for "
    "the life of the process, but nothing persists across restarts")
OBS_HISTORY_MAX_SEGMENT_BYTES = conf_bytes(
    "spark.rapids.tpu.obs.history.rotation.maxBytes", 4 * 1024 * 1024,
    "Size-based segment rotation: when the active history segment "
    "exceeds this many bytes the writer seals it and opens a new one "
    "(0 disables size rotation)")
OBS_HISTORY_MAX_SEGMENT_AGE_S = conf_int(
    "spark.rapids.tpu.obs.history.rotation.maxAgeSeconds", 0,
    "Age-based segment rotation: a segment whose first row is older "
    "than this many seconds relative to the row being appended is "
    "sealed first (0 disables age rotation).  Ages compare the rows' "
    "own submitted_ts stamps — the writer never reads a wall clock")
OBS_HISTORY_MAX_SEGMENTS = conf_int(
    "spark.rapids.tpu.obs.history.retention.maxSegments", 8,
    "Retention bound on sealed history segments: after each rotation "
    "the oldest segments beyond this count are deleted, keeping the "
    "store's disk footprint fixed")
OBS_HISTORY_QUEUE_DEPTH = conf_int(
    "spark.rapids.tpu.obs.history.queueDepth", 1024,
    "Bound on rows buffered between the terminal-state hook and the "
    "background writer thread; a full queue drops the new row (never "
    "blocks the query path) and increments tpu_history_dropped_total",
    internal=True)
OBS_HISTORY_MAX_FINGERPRINTS = conf_int(
    "spark.rapids.tpu.obs.history.maxFingerprints", 1024,
    "Bound on distinct plan fingerprints held in the in-memory fleet "
    "aggregates (and per-fingerprint EWMA state in the anomaly "
    "sentinel); past it rows still persist to JSONL but new "
    "fingerprints are not aggregated (fixed memory — the "
    "flight-recorder discipline)",
    internal=True)
OBS_ANOMALY_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.anomaly.enabled", True,
    "Online anomaly sentinel (obs/anomaly.py): folds every history "
    "row into per-(fingerprint, key) EWMA mean/variance state and on "
    "sustained breach — breachRuns consecutive sigma-outliers after a "
    "warmupMinRuns warm-up — emits an anomaly event to the event log, "
    "the tpu_anomaly_* Prometheus families, a rate-limited diag "
    "bundle and the doctor's trend section.  Band/direction semantics "
    "are shared with the offline perf gate (analysis/bands.py).  "
    "Pure host arithmetic over history rows: zero extra device "
    "flushes by construction")
OBS_ANOMALY_EWMA_ALPHA = conf_float(
    "spark.rapids.tpu.obs.anomaly.ewmaAlpha", 0.15,
    "Smoothing factor of the per-(fingerprint, key) EWMA mean/"
    "variance: higher tracks drift faster but is noisier; 0.15 "
    "weights roughly the last ~13 runs")
OBS_ANOMALY_WARMUP_MIN_RUNS = conf_int(
    "spark.rapids.tpu.obs.anomaly.warmupMinRuns", 8,
    "Runs of a fingerprint folded before its EWMA state may flag "
    "outliers (and before the trend baseline is frozen): fresh plans "
    "never alarm on compile-warmup noise")
OBS_ANOMALY_BREACH_RUNS = conf_int(
    "spark.rapids.tpu.obs.anomaly.breachRuns", 3,
    "Consecutive sigma-outlier runs (same fingerprint, key, "
    "direction) required before an anomaly event fires; the same "
    "count of consecutive in-band runs recovers it")
OBS_ANOMALY_SIGMA = conf_float(
    "spark.rapids.tpu.obs.anomaly.sigma", 3.0,
    "Outlier threshold in EWMA standard deviations; a run is an "
    "outlier only when it is ALSO outside the key's perf-gate band "
    "(analysis/bands.py), so tight-variance fingerprints do not alarm "
    "on noise within the documented tolerance")
OBS_ANOMALY_BUNDLE_INTERVAL_S = conf_float(
    "spark.rapids.tpu.obs.anomaly.bundleIntervalSeconds", 300.0,
    "Rate limit on anomaly-triggered diagnostics bundles: at most one "
    "bundle per this many seconds process-wide (0 disables anomaly "
    "bundles); breach events and Prometheus counters are never "
    "rate-limited")
OBS_BURN_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.burn.enabled", True,
    "Longitudinal burn-rate plane (obs/burn.py): folds every terminal "
    "history row into per-tenant fast/slow SLO burn-rate windows, an "
    "EWMA-slope steady-state detector and a sampled memplane "
    "leak-drift regression — the live monitors of a soak run "
    "(service/soak.py).  Pure host arithmetic over rows the history "
    "store already built: zero extra device flushes; self-cost billed "
    "to the overhead meter's 'burn' plane")
OBS_BURN_FAST_WINDOW_S = conf_float(
    "spark.rapids.tpu.obs.burn.fastWindowSeconds", 60.0,
    "Span of the fast burn-rate window (incident detection: a "
    "burn rate >> 1 here means the error budget is being consumed "
    "far faster than allowed).  Keyed on the rows' own submit "
    "timestamps, so the math replays identically from history "
    "segments")
OBS_BURN_SLOW_WINDOW_S = conf_float(
    "spark.rapids.tpu.obs.burn.slowWindowSeconds", 600.0,
    "Span of the slow burn-rate window (sustained-burn confirmation; "
    "the SRE multi-window pattern pages only when BOTH windows burn)")
OBS_BURN_BUDGET_PCT = conf_float(
    "spark.rapids.tpu.obs.burn.budgetPct", 1.0,
    "Error budget as a percent of queries allowed to breach the "
    "obs.slo.targetMs target (shed/failed queries always count as "
    "breaches); burn rate 1.0 = consuming the budget exactly as fast "
    "as allowed")
OBS_BURN_EWMA_ALPHA = conf_float(
    "spark.rapids.tpu.obs.burn.ewmaAlpha", 0.2,
    "Smoothing factor of the steady-state detector's end-to-end "
    "latency EWMA")
OBS_BURN_STEADY_SLOPE_PCT = conf_float(
    "spark.rapids.tpu.obs.burn.steadySlopePct", 5.0,
    "Per-fold relative EWMA slope (percent) under which a fold counts "
    "toward the steady-state streak; a fold above it breaks the "
    "streak (and drops an established steady state — counted as a "
    "loss, e.g. across an injected fault)")
OBS_BURN_STEADY_RUNS = conf_int(
    "spark.rapids.tpu.obs.burn.steadyRuns", 8,
    "Consecutive in-slope folds required before the run is declared "
    "stationary (stamped with the qualifying row's timestamp)")
OBS_BURN_MEM_SAMPLES = conf_int(
    "spark.rapids.tpu.obs.burn.memSamples", 512,
    "Bound on buffered memplane live-bytes samples for the leak-drift "
    "regression (oldest dropped past it — fixed memory); drift "
    "compares the min of the newest half against the min of the "
    "oldest half, so a clean run reads exactly 0 bytes",
    internal=True)
OBS_DASHBOARD_ENABLED = conf_bool(
    "spark.rapids.tpu.obs.dashboard.enabled", True,
    "Fleet dashboard (obs/dashboard.py): a self-contained HTML view — "
    "top fingerprints by volume/latency/SLO burn, active anomalies, "
    "doctor verdict mix, per-tenant table — served at /dashboard "
    "beside the Prometheus text endpoint and renderable offline via "
    "tools/history.py")
OBS_DASHBOARD_REFRESH_S = conf_float(
    "spark.rapids.tpu.obs.dashboard.refreshSeconds", 5.0,
    "Meta auto-refresh interval of the served /dashboard page, so it "
    "works as a live soak console; 0 renders a static page (offline "
    "rendering via tools/history.py is always static)")
SUPERSTAGE = conf_bool(
    "spark.rapids.tpu.sql.superstage", True,
    "Superstage compiler (compile/): a planner post-pass after the "
    "plan-invariant verifier carves the physical plan into maximal "
    "exchange-delimited superstages (scan->project->filter->partial-agg"
    "->shuffle-split, join->agg->topn) and lowers each to ONE traced "
    "XLA program where possible, with intermediates staying device-"
    "resident between stages: inner-join probes run the speculative "
    "unique-match path, aggregates hand fit flags to the stage "
    "barrier, and the whole map side of an exchange resolves in a "
    "single fused flush.  Per-node fallback ejects an unfusable "
    "operator into its own dispatch instead of failing the stage; "
    "off restores one-dispatch-per-operator execution bit-identically")
SUPERSTAGE_MIN_OPS = conf_int(
    "spark.rapids.tpu.sql.superstage.minOps", 2,
    "Minimum member operators before a carved region is wrapped in a "
    "TpuSuperstage (singleton regions gain nothing over the "
    "per-operator fused paths)", internal=True)
AOT_ENABLED = conf_bool(
    "spark.rapids.tpu.compile.aot.enabled", True,
    "AOT compile subsystem (compile/aot.py): shape-bucket batch "
    "capacities onto a small geometric lattice so the seven engine "
    "JIT caches share executables across queries instead of "
    "compiling per exact shape.  Padded rows carry validity, so "
    "bucketed execution is bit-identical to unbucketed.  Also "
    "enables the per-(program, bucket) demand ledger the warmup "
    "daemon and the compile report read")
AOT_BUCKET_RATIO = conf_int(
    "spark.rapids.tpu.compile.aot.bucketRatio", 2,
    "Growth factor between adjacent capacity buckets in the shape "
    "lattice (power of two).  2 reproduces the classic pow2 padding; "
    "4 quarters the number of distinct shapes each program compiles "
    "for, trading up to 4x padding waste for executable reuse")
AOT_CACHE_DIR = conf_str(
    "spark.rapids.tpu.compile.aot.cacheDir", "",
    "Directory for the AOT manifest.  When set, compile/aot.py keeps "
    "a manifest here keyed by (program id, bucket, dtype tuple, conf "
    "fingerprint) so first-calls satisfied by the JAX persistent "
    "compilation cache are counted as persistent hits, not new "
    "compiles.  It does NOT place the XLA cache itself: that "
    "directory is JAX_COMPILATION_CACHE_DIR when set, else the fixed "
    "<checkout>/.jax_cache (compile/xla_cache.py), and the manifest "
    "only vouches for the XLA cache directory it was written "
    "against.  Empty = no manifest")
AOT_PERSIST_EVERY_PROGRAM = conf_bool(
    "spark.rapids.tpu.compile.aot.xlaCache.enabled", True,
    "With aot.cacheDir set, drop the JAX persistent compilation "
    "cache's min-compile-time and min-entry-size thresholds to zero "
    "so every engine program persists — what lets a manifest entry "
    "from an earlier run count the first call as a cache load.  Off "
    "keeps the manifest bookkeeping without touching the JAX cache "
    "config (no persistent-hit claims) — the escape hatch for "
    "platforms where cross-process executable deserialization "
    "misbehaves")
AOT_WARMUP_ENABLED = conf_bool(
    "spark.rapids.tpu.compile.aot.warmup.enabled", True,
    "Admission-aware warmup daemon (service/warmup.py): a "
    "QueryService background thread that observes the admission "
    "queue's (program, bucket) demand mix and pre-compiles "
    "likely-missing buckets off the query critical path.  Warmup "
    "compiles are attributed to the dedicated 'warmup' origin by "
    "obs/compile_watch.py — never to a tenant query's "
    "inline_compile_ms")
AOT_WARMUP_INTERVAL_MS = conf_int(
    "spark.rapids.tpu.compile.aot.warmup.intervalMs", 500,
    "Fallback wakeup period of the warmup daemon between admission "
    "signals (each admission also wakes it immediately)",
    internal=True)
AOT_WARMUP_MAX_PER_CYCLE = conf_int(
    "spark.rapids.tpu.compile.aot.warmup.maxCompilesPerCycle", 4,
    "Bound on background compiles per warmup sweep, so a cold "
    "process warms incrementally instead of monopolizing the device "
    "semaphore with dummy-batch executions", internal=True)
PIPELINE_ENABLED = conf_bool(
    "spark.rapids.tpu.exec.pipeline.enabled", True,
    "Morsel-parallel partition drains (exec/pipeline.py): the shuffle "
    "map-side materialization, the broadcast build and the session "
    "collect loop pull partition iterators on a bounded per-process "
    "worker pool with per-partition prefetch, so host-side staging "
    "(arrow conversion, partition-split prep, spill/unspill) overlaps "
    "in-flight device compute.  Results are reassembled in "
    "deterministic partition order, so output is bit-identical to the "
    "serial drains.  Off = the pre-pipeline one-thread-per-query "
    "behavior")
PIPELINE_PARALLELISM = conf_int(
    "spark.rapids.tpu.exec.pipelineParallelism", 0,
    "Worker threads in the per-process pipeline pool (the bound on "
    "concurrent partition pulls; the device itself is still gated by "
    "sql.concurrentTpuTasks through the DeviceSemaphore, which "
    "pipeline workers hold only around device dispatch).  0 = auto: "
    "min(4, cpu count).  1 degenerates every drain to the serial path")
PIPELINE_PREFETCH_DEPTH = conf_int(
    "spark.rapids.tpu.exec.pipelinePrefetchDepth", 2,
    "Batches each pipeline worker may buffer ahead of the consumer per "
    "partition; past it the producer parks until the consumer catches "
    "up (per-partition backpressure on top of the global "
    "pipelineBufferBytes budget)")
PIPELINE_BUFFER_BYTES = conf_bytes(
    "spark.rapids.tpu.exec.pipelineBufferBytes", 1 << 30,
    "Per-drain byte budget for buffered prefetched batches "
    "(backpressure: producers park past it, except the head partition "
    "when it has nothing queued — the liveness bypass that keeps the "
    "budget deadlock-free).  Spill-aware: at drain start the budget is "
    "additionally capped at half the free device tier, so prefetch "
    "never plans to out-buffer what the arena could hold without "
    "forced spilling")
CACHE_PLAN_ENABLED = conf_bool(
    "spark.rapids.tpu.cache.plan.enabled", True,
    "Fingerprint-keyed plan cache (cache/plan_cache.py): repeat query "
    "shapes — keyed by a literal-normalized logical-plan digest scoped "
    "to the plan-affecting conf fingerprint — skip the planner's "
    "analysis passes (CBO costing, the six-pass plan verifier, the "
    "PV-FLUSH budget prediction) by replaying the certificates "
    "recorded when the shape was first verified.  Hits are validated "
    "against the stored physical plan_fingerprint; a conf-fingerprint "
    "change invalidates the entry and re-runs the full verifier.  The "
    "cached path is sha-identical to the cold path with PV-FLUSH "
    "predictions still exact")
CACHE_PLAN_MAX_ENTRIES = conf_int(
    "spark.rapids.tpu.cache.plan.maxEntries", 256,
    "Bound on cached plan shapes (LRU eviction past it).  Each entry "
    "holds the shape's analysis certificates (verification verdict, "
    "plan fingerprint, flush-budget contributions), not the physical "
    "tree itself, so entries are small")
SERVICE_SCHED_ENABLED = conf_bool(
    "spark.rapids.tpu.service.sched.enabled", True,
    "Predictive admission scheduler (service/scheduler.py): predicts "
    "each submitted query's exec_ms from its plan fingerprint's "
    "frozen EWMA baseline (obs/anomaly.py), reorders the per-tenant "
    "admission queue so queries predicted to finish inside the SLO "
    "target run ahead of predicted breaches, and hands predicted "
    "(program, bucket) pairs to the AOT warmup daemon as pre-warm "
    "hints.  Queries without a frozen baseline keep plain FIFO order "
    "and are never shed predictively")
SERVICE_SCHED_PREDICT_SHED = conf_bool(
    "spark.rapids.tpu.service.sched.predictShed.enabled", True,
    "Shed queries predicted to breach BEFORE they burn device time: "
    "when the fingerprint's conservative predicted floor (baseline "
    "mean minus two EWMA sigmas) already exceeds the latency budget "
    "(the tighter of the query deadline and obs.slo.targetMs) by "
    "sched.shedMarginPct, submit fails with PredictedBreach and the "
    "SLO plane records the dedicated predicted_breach cause — "
    "distinct from queue-overload load shedding.  No-op without a "
    "frozen baseline or a latency budget (zero false sheds on "
    "never-seen or in-band work)")
SERVICE_SCHED_SHED_MARGIN_PCT = conf_float(
    "spark.rapids.tpu.service.sched.shedMarginPct", 20.0,
    "Safety margin for predictive shedding: the predicted floor must "
    "exceed the latency budget by this percentage before a query is "
    "shed as predicted_breach — absorbs baseline noise so in-band "
    "workloads are never falsely shed")


class TpuConf:
    """Immutable-ish view over a settings dict; re-read per query plan like

    the reference (GpuOverrides.scala:3105 constructs RapidsConf per apply)."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings = dict(settings or {})

    def get(self, entry: ConfEntry):
        return entry.get(self)

    def get_key(self, key: str):
        if key in _REGISTRY:
            return _REGISTRY[key].get(self)
        return self._settings.get(key)

    def set(self, key: str, value) -> "TpuConf":
        s = dict(self._settings)
        s[key] = value
        return TpuConf(s)

    def with_overrides(self, overrides: Dict[str, Any]) -> "TpuConf":
        s = dict(self._settings)
        s.update(overrides)
        return TpuConf(s)

    @property
    def is_sql_enabled(self):
        return self.get(SQL_ENABLED)

    @property
    def allowed_non_tpu(self) -> List[str]:
        raw = self.get(TEST_ALLOWED_NON_TPU)
        return [s.strip() for s in raw.split(",") if s.strip()]


def all_entries() -> List[ConfEntry]:
    return sorted(_REGISTRY.values(), key=lambda e: e.key)


def generate_docs() -> str:
    """Self-generated config docs (reference: RapidsConf.help -> configs.md)."""
    lines = ["# spark_rapids_tpu configuration", "",
             "| Key | Default | Description |", "|---|---|---|"]
    for e in all_entries():
        if e.internal:
            continue
        lines.append(f"| `{e.key}` | `{e.default}` | {e.doc} |")
    return "\n".join(lines) + "\n"


# Active conf: thread-local with a process-global fallback.  Query
# threads (service workers, concurrent client sessions) each activate
# their own conf without clobbering one another; helper threads that
# never activated one (scan-prefetch producers, shuffle servers) read
# the process-global, which tracks the most recent activation.
_ACTIVE_GLOBAL = TpuConf()
_ACTIVE_LOCK = threading.Lock()
_ACTIVE_TLS = threading.local()


def get_active() -> TpuConf:
    conf = getattr(_ACTIVE_TLS, "conf", None)
    return conf if conf is not None else _ACTIVE_GLOBAL


def set_active(conf: TpuConf, thread_only: bool = False):
    """Activate ``conf`` for the calling thread (and, unless
    ``thread_only``, as the process-global fallback for threads that
    never activate one themselves)."""
    global _ACTIVE_GLOBAL
    _ACTIVE_TLS.conf = conf
    if not thread_only:
        with _ACTIVE_LOCK:
            _ACTIVE_GLOBAL = conf


def clear_thread_active():
    """Drop this thread's conf override (falls back to the global)."""
    _ACTIVE_TLS.conf = None
