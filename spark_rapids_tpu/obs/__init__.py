"""Observability subsystem: span tracing, process-wide metrics, and
the always-on flight recorder with automatic failure diagnostics.

Layers, mirroring the reference plugin's observability story
(SURVEY.md §tools) plus the black-box additions:

- ``obs.trace``   — hierarchical span tracer (the NvtxRange role):
  thread-local nested spans with query_id attribution, exported as
  Chrome trace-event JSON loadable in Perfetto/chrome://tracing.
  Opt-in (near-zero cost disabled).
- ``obs.registry``— process-wide metrics registry (counters, gauges,
  fixed-bucket histograms): arena bytes, semaphore/queue waits, spill
  bytes, compile-cache hits, shuffle bytes.
- ``obs.prom``    — Prometheus text-format exposition over the registry
  (``QueryService.metrics_text()`` / scrape handler).
- ``obs.flight``  — always-on flight recorder: per-thread bounded rings
  of compact structured events recorded unconditionally (preallocated
  slots, no allocation/locking on the hot path, overwrite-oldest) at
  the same boundaries the tracer instruments.
- ``obs.watchdog``— service stall watchdog: flags RUNNING queries with
  no flight-recorder progress and captures the evidence.
- ``obs.profile`` — runtime stats plane, timing half: flush-level
  device-time attribution (which exec node owned each fused device
  round trip), deterministic per-member time shares inside fused
  superstages, and per-site dispatch duration summaries.
- ``obs.stats``   — runtime stats plane, data half: exchange-boundary
  per-partition rows/bytes/null/min-max statistics, an on-device
  HLL-style distinct-key sketch computed in the split's own dispatch
  window (zero extra flushes), skew verdicts, and the per-query
  ``StatsProfile`` artifact (imported lazily by exec/ and api/).
- ``obs.diagnostics`` — one-JSON-file incident bundles (flight tail,
  thread stacks, metrics, arena map, plan verdicts, redacted conf)
  written automatically on failure/OOM/deadline/watchdog; rendered by
  ``tools/diagnose.py``.
- ``obs.timeline`` — device-utilization timeline: busy/idle intervals
  reconstructed from flush/mesh dispatch windows, idle gaps classified
  by cause (staging, inline compile, semaphore, admission,
  starvation), per-device busy counters.
- ``obs.compile_watch`` — compile telemetry: every compile-cache
  miss's duration, signature and inline-vs-warm flag, across all
  seven engine JIT caches.
- ``obs.slo`` — per-tenant SLO latency accounting: p50/p95/p99,
  breach/burn counters with single-cause attribution.
- ``obs.netplane`` — shuffle-transport plane: bounded per-edge
  transfer matrix, host-drop tax accounting (serialize/dwell/wire/
  deserialize phase split per exchange, ``shuffle_host`` timeline gap
  cause), connection-pool/bounce-buffer state and cross-boundary
  (query_id, span_id) trace correlation over the shuffle wire.
- ``obs.memplane`` — HBM memory plane: allocation provenance (owner
  query/site/op decomposition of live device bytes, exact to
  ``device_bytes``, with peak attribution), the priced spill ledger
  (victim/owner/reason/rank/duration per tier move, ``mem_spill``
  timeline gap cause), retention/leak detection at query terminal
  states, and the admission headroom forecast.

- ``obs.costplane`` — device-compute cost plane: XLA static cost
  analysis (flops / bytes accessed / IO working set) captured per
  (program, bucket) at every JIT-cache first call, joined at query
  end with the flush-observer busy window into per-program achieved
  FLOP/s, achieved GB/s, arithmetic intensity and a roofline verdict
  (``compute_bound``/``memory_bound``) against conf-declared peaks —
  plus padding-waste accounting (effective rows vs padded bucket
  capacity per dispatch) pricing the AOT lattice's ``bucketRatio``.

- ``obs.doctor`` — cross-plane query doctor: joins the per-query
  artifacts of every plane above into one ``QueryDiagnosis`` —
  exactly one primary bottleneck with priority-ordered evidence,
  contribution shares summing to 100 (the PR 8 gap taxonomy plus the
  busy share as ``device_compute``), Amdahl-modeled headroom per
  candidate fix, and a ranked mapping onto ROADMAP items 1-4.

- ``obs.overhead`` — observability self-metering: a per-plane host-
  time meter (interned plane ids, preallocated ns counters, zero
  allocation on record) bracketing each plane's hot-path entry
  points, exported as ``tpu_obs_self_seconds_total{plane}`` and the
  ``stats()["obs_overhead"]`` section so the tax every plane above
  levies is attributed, not just measured as one on-vs-off delta.

The per-query report generator that joins the event log with these
streams lives in ``tools/report.py`` (the SQL-UI stand-in).
"""
from . import (trace, registry, prom, flight, timeline,     # noqa: F401
               compile_watch, slo, profile, netplane,       # noqa: F401
               memplane, costplane, doctor, overhead)       # noqa: F401
from .registry import get_registry  # noqa: F401
from .trace import span             # noqa: F401

# install the pending-pool flush observer (idempotent module hook)
profile.install()
