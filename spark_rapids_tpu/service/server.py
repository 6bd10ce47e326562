"""QueryService — in-process multi-tenant query serving front-end.

Shape: many client threads submit queries against ONE TPU-backed engine;
a bounded fair admission queue (queue.py) hands them to a small pool of
worker threads; each worker plans and executes with a per-query conf
overlay under a per-query CancelToken (cancellation.py), retrying
device-OOM / shuffle-fetch failures with exponential backoff and batch
degradation (retry.py); every lifecycle transition emits a structured
event-log line keyed by a stable query_id (metrics.py + tools/events).

This lifts the reference's per-task mechanisms (GpuSemaphore admission,
DeviceMemoryEventHandler spill-and-retry, FetchFailed stage re-run) into
the serving subsystem an inference-style front-end needs; later scaling
PRs (multi-process serving, replica routing) plug in above this.
"""
from __future__ import annotations

import itertools
import threading
import time
import uuid
from typing import Dict, List, Optional

from ..api.session import TpuSession
from ..config import (TpuConf, set_active, EVENT_LOG_PATH,
                      SERVICE_WORKERS, SERVICE_MAX_QUEUE_DEPTH,
                      SERVICE_MAX_QUEUED_BYTES, SERVICE_DEFAULT_DEADLINE_MS,
                      OBS_WATCHDOG_ENABLED, OBS_WATCHDOG_INTERVAL_MS,
                      OBS_WATCHDOG_STALL_S, OBS_WATCHDOG_REFIRE_S,
                      OBS_DIAG_DIR,
                      OBS_DIAG_MAX_BUNDLES, AOT_WARMUP_ENABLED,
                      AOT_WARMUP_INTERVAL_MS, AOT_WARMUP_MAX_PER_CYCLE)
from ..cache import plan_cache as _plan_cache
from ..compile import aot as _aot
from ..obs import anomaly as _anomaly
from ..obs import burn as _burn
from ..obs import compile_watch as _cwatch
from ..obs import dashboard as _dashboard
from ..obs import history as _history
from ..obs import costplane as _costplane
from ..obs import doctor as _doctor
from ..obs import flight as _flight
from ..obs import memplane as _memplane
from ..obs import netplane as _netplane
from ..obs import overhead as _overhead
from ..obs import slo as _slo
from ..obs import timeline as _timeline
from ..obs import trace as _trace
from ..obs.registry import (QUEUE_WAIT_SECONDS, SERVICE_INFLIGHT,
                            SERVICE_QUEUE_DEPTH, SERVICE_QUEUED_BYTES)
from ..plan import logical as L
from .cancellation import CancelToken, query_context
from .errors import QueryCancelledError, ServiceOverloaded
from .metrics import QueryMetrics, ServiceStats
from .queue import FairQueryQueue
from .retry import RetryPolicy
from .scheduler import AdmissionScheduler, PredictedBreach

QUEUED, RUNNING, DONE, FAILED, CANCELLED = (
    "QUEUED", "RUNNING", "DONE", "FAILED", "CANCELLED")


def _pipeline_stats() -> Dict:
    """Pipeline-pool occupancy for ``stats().snapshot()`` (lazy import:
    the service must not pull exec/ at module load)."""
    try:
        from ..exec.pipeline import pool_stats
        return pool_stats()
    except Exception:
        return {}


def _soak_stats() -> Dict:
    """Live soak-harness counters for ``stats().snapshot()`` (lazy
    import: service/soak.py imports QueryService, so the module-load
    direction must stay soak -> server only)."""
    try:
        from .soak import stats_section
        return stats_section()
    except Exception:
        return {}


class QueryHandle:
    """Client-side future for one submitted query."""

    def __init__(self, service: "QueryService", query_id: str,
                 logical: L.LogicalPlan, tenant: str, priority: int,
                 est_bytes: int, token: CancelToken,
                 conf_overrides: Optional[Dict] = None):
        self._service = service
        self.query_id = query_id
        self.logical = logical
        self.tenant = tenant
        self.priority = priority
        self.est_bytes = est_bytes
        self.token = token
        self.conf_overrides = dict(conf_overrides or {})
        self.metrics = QueryMetrics(query_id, tenant, priority, est_bytes)
        self.status = QUEUED
        self._done = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        # observability side-car state: the worker thread running this
        # query (stall-watchdog progress key) and the last planned
        # physical tree (diagnostic-bundle plan section)
        self._worker_ident: Optional[int] = None
        self._last_phys = None
        # admission-scheduler rank tier (queue.py _insert_ranked);
        # None = unranked (scheduler off or no prediction)
        self._sched_rank: Optional[int] = None

    # -- client API --------------------------------------------------------
    def result(self, timeout: Optional[float] = None):
        """Block for the outcome: the pa.Table on success, raises the
        query's error (QueryCancelledError on cancel/deadline) on
        failure, TimeoutError if not done within ``timeout``."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"query {self.query_id} not done within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self, reason: str = "cancelled") -> bool:
        """Request cooperative cancellation.  A still-queued query is
        finalized immediately; a running one unwinds at its next
        checkpoint.  Returns False if the query already finished."""
        if self._done.is_set():
            return False
        self.token.cancel(reason)
        self._service._cancel_queued(self)
        return True

    # -- service side ------------------------------------------------------
    def _finish(self, status: str, result=None,
                error: Optional[BaseException] = None):
        self.status = status
        self._result = result
        self._error = error
        self._done.set()


class QueryService:
    """In-process concurrent query service over one engine session."""

    def __init__(self, session: Optional[TpuSession] = None,
                 num_workers: Optional[int] = None):
        self.session = session or TpuSession.active()
        conf = self.session.conf
        self.num_workers = int(num_workers or conf.get(SERVICE_WORKERS))
        self.queue = FairQueryQueue(
            max_depth=conf.get(SERVICE_MAX_QUEUE_DEPTH),
            max_bytes=conf.get(SERVICE_MAX_QUEUED_BYTES))
        self.retry = RetryPolicy.from_conf(conf)
        self._stats = ServiceStats()
        from ..tools.events import QueryEventLogger
        self._events = QueryEventLogger(conf.get(EVENT_LOG_PATH) or None)
        self._default_deadline_ms = conf.get(SERVICE_DEFAULT_DEADLINE_MS)
        self._seq = itertools.count(1)
        self._inflight: Dict[str, QueryHandle] = {}
        self._inflight_lock = threading.Lock()
        self._workers: List[threading.Thread] = []
        self._shutdown = False
        self._start_lock = threading.Lock()
        self._scrape_server = None
        # failure diagnostics: bundle directory ("" disables) + rotation
        self._diag_dir = conf.get(OBS_DIAG_DIR) or ""
        self._diag_max = conf.get(OBS_DIAG_MAX_BUNDLES)
        self._last_shed_bundle_mono = 0.0
        # stall watchdog (daemon; started/stopped with the service)
        from ..obs.watchdog import Watchdog
        self._watchdog_enabled = bool(conf.get(OBS_WATCHDOG_ENABLED))
        self.watchdog = Watchdog(
            self,
            interval_s=conf.get(OBS_WATCHDOG_INTERVAL_MS) / 1000.0,
            stall_s=float(conf.get(OBS_WATCHDOG_STALL_S)),
            refire_s=float(conf.get(OBS_WATCHDOG_REFIRE_S)))
        # queue/inflight gauges read live service state at collect time
        # (scrapes pay the cost, the submit/run hot path pays nothing)
        SERVICE_QUEUE_DEPTH.set_function(lambda: self.queue.depth)
        SERVICE_QUEUED_BYTES.set_function(
            lambda: self.queue.stats().get("queued_bytes", 0))
        SERVICE_INFLIGHT.set_function(lambda: len(self._inflight))
        # serving-grade performance plane: conf the three obs planes
        # (process-wide, like the registry — last service wins)
        _slo.configure(conf)
        _cwatch.configure(conf)
        _timeline.configure(conf)
        _netplane.configure(conf)
        _memplane.configure(conf)
        _costplane.configure(conf)
        _doctor.configure(conf)
        _overhead.configure(conf)
        _aot.configure(conf)
        # longitudinal fleet planes: the persistent history store and
        # the online anomaly sentinel it feeds (process-wide, last
        # service wins, like every other plane)
        _history.configure(conf)
        _anomaly.configure(conf)
        _burn.configure(conf)
        _dashboard.configure(conf)
        # plan cache + predictive admission scheduler (cache/
        # plan_cache.py, service/scheduler.py): repeat shapes skip the
        # planner tail; learned baselines rank/shed at admission
        _plan_cache.configure(conf)
        self.scheduler = AdmissionScheduler(conf)
        # admission-aware AOT warmup daemon (service/warmup.py): watches
        # the (program, bucket) demand ledger and pre-compiles missing
        # bucket executables off the query path
        from .warmup import WarmupDaemon
        self._warmup_enabled = bool(conf.get(AOT_WARMUP_ENABLED))
        self.warmup = WarmupDaemon(
            interval_ms=conf.get(AOT_WARMUP_INTERVAL_MS),
            max_per_cycle=conf.get(AOT_WARMUP_MAX_PER_CYCLE))
        # stats().snapshot() carries the live obs sections alongside the
        # lifecycle counters (the monitoring one-stop view)
        self._stats.set_extras(lambda: {
            "watchdog": self.watchdog.state(),
            "flight_recorder": _flight.occupancy(),
            "pipeline": _pipeline_stats(),
            "slo": _slo.stats_section(),
            "compile": _cwatch.stats_section(),
            "timeline": _timeline.process_summary(),
            "shuffle": _netplane.stats_section(),
            "memory": _memplane.stats_section(),
            "cost": _costplane.stats_section(),
            "doctor": _doctor.stats_section(),
            "aot": _aot.stats_section(),
            "warmup": self.warmup.state(),
            "history": _history.stats_section(),
            "anomaly": _anomaly.stats_section(),
            "burn": _burn.stats_section(),
            "soak": _soak_stats(),
            "plan_cache": _plan_cache.stats_section(),
            "scheduler": self.scheduler.stats_section(),
            "obs_overhead": _overhead.stats_section(),
        })

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "QueryService":
        with self._start_lock:
            if self._workers:
                return self
            for i in range(self.num_workers):
                t = threading.Thread(target=self._worker_loop, daemon=True,
                                     name=f"tpu-query-service-{i}")
                t.start()
                self._workers.append(t)
            if self._watchdog_enabled:
                self.watchdog.start()
            if self._warmup_enabled:
                self.warmup.start()
        return self

    def shutdown(self, wait: bool = True, timeout: Optional[float] = None,
                 cancel_running: bool = False):
        """Stop admitting.  Queued work drains (workers exit once the
        queue is empty); ``cancel_running`` additionally cancels every
        in-flight query at its next checkpoint."""
        self._shutdown = True
        self.queue.close()
        if cancel_running:
            with self._inflight_lock:
                handles = list(self._inflight.values())
            for h in handles:
                h.cancel("cancelled")
        if wait:
            deadline = (time.monotonic() + timeout) if timeout else None
            for t in self._workers:
                left = None if deadline is None else \
                    max(0.0, deadline - time.monotonic())
                t.join(left)
        self.watchdog.stop()
        self.warmup.stop()
        _history.stop()
        # the span file is written here, once (no-op when fine tracing
        # is off or has no path), not after every query
        _trace.flush()
        if self._scrape_server is not None:
            # hardened lifecycle: stop() joins the serving thread and
            # closes the socket so a successor service can rebind the
            # same port immediately
            stop = getattr(self._scrape_server, "stop",
                           self._scrape_server.shutdown)
            stop()
            self._scrape_server = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(wait=True, timeout=30.0, cancel_running=True)
        return False

    # -- submission --------------------------------------------------------
    def _to_logical(self, query) -> L.LogicalPlan:
        if isinstance(query, L.LogicalPlan):
            return query
        if isinstance(query, str):
            return self.session.sql(query)._plan
        plan = getattr(query, "_plan", None)   # DataFrame
        if isinstance(plan, L.LogicalPlan):
            return plan
        raise TypeError(f"cannot submit {type(query)}: expected a "
                        "DataFrame, LogicalPlan or SQL string")

    def submit(self, query, tenant: str = "default", priority: int = 0,
               deadline_ms: Optional[float] = None,
               conf: Optional[Dict] = None,
               est_bytes: int = 0) -> QueryHandle:
        """Admit a query or raise ServiceOverloaded (load shedding).

        ``deadline_ms`` counts from submission (queue wait included —
        the serving-level definition); falls back to the
        service.defaultDeadlineMs knob.  ``conf`` is a per-query conf
        overlay applied on top of the session conf for this query only.
        """
        if self._shutdown:
            raise ServiceOverloaded("service is shut down")
        self.start()
        logical = self._to_logical(query)
        self._stats.inc("submitted")
        query_id = f"q{next(self._seq):06d}-{uuid.uuid4().hex[:8]}"
        ms = deadline_ms if deadline_ms is not None else \
            (self._default_deadline_ms or None)
        deadline = (time.monotonic() + ms / 1000.0) if ms else None
        token = CancelToken(query_id, deadline)
        handle = QueryHandle(self, query_id, logical, tenant, priority,
                             est_bytes, token, conf)
        # predictive admission assessment (service/scheduler.py): rank
        # the query against its fingerprint's learned exec_ms baseline
        # and shed a certain breach BEFORE it burns device time
        decision = None
        if self.scheduler.enabled:
            sched_conf = self.session.conf.with_overrides(conf or {})
            decision = self.scheduler.assess(logical, sched_conf, ms)
            handle._sched_rank = decision.rank
            if decision.predicted_ms is not None:
                handle.metrics.predicted_exec_ms = decision.predicted_ms
            if decision.shed_reason:
                self._stats.inc("shed")
                handle.metrics.outcome = "shed"
                handle.metrics.error = decision.shed_reason
                _slo.record(handle.metrics)
                self._record_terminal(handle.metrics, handle)
                e = PredictedBreach(decision.shed_reason,
                                    decision.predicted_ms or 0.0,
                                    decision.budget_ms or 0.0)
                handle._finish(FAILED, error=e)
                _flight.record(_flight.EV_STATE, "shed",
                               query_id=query_id)
                bundle = self._maybe_shed_bundle(handle, e)
                self._events.log_service_event(
                    "shed", query_id, tenant=tenant, priority=priority,
                    reason=decision.shed_reason,
                    predicted_exec_ms=round(decision.predicted_ms or 0.0,
                                            3),
                    budget_ms=round(decision.budget_ms or 0.0, 3),
                    diag_bundle=bundle)
                raise e
        # register BEFORE offering: a fast worker may finish (and
        # _forget) the query before submit() returns
        with self._inflight_lock:
            self._inflight[query_id] = handle
        try:
            self.queue.offer(handle)
        except ServiceOverloaded as e:
            self._forget(handle)
            self._stats.inc("shed")
            handle.metrics.outcome = "shed"
            _slo.record(handle.metrics)
            self._record_terminal(handle.metrics, handle)
            handle._finish(FAILED, error=e)
            _flight.record(_flight.EV_STATE, "shed", query_id=query_id)
            bundle = self._maybe_shed_bundle(handle, e)
            self._events.log_service_event(
                "shed", query_id, tenant=tenant, priority=priority,
                queue_depth=e.queue_depth, queued_bytes=e.queued_bytes,
                reason=str(e), diag_bundle=bundle)
            raise
        self._stats.inc("admitted")
        _flight.record(_flight.EV_STATE, "admitted", query_id=query_id)
        # admission-time headroom forecast (obs/memplane.py): device
        # bytes the arena could still grant vs what this query claims it
        # needs — the event-log row operators grep when deciding whether
        # an admission preceded a spill storm
        hr = _memplane.headroom()
        self._events.log_service_event(
            "admitted", query_id, tenant=tenant, priority=priority,
            est_bytes=est_bytes, queue_depth=self.queue.depth,
            deadline_ms=ms,
            headroom_bytes=hr["headroom_bytes"],
            device_bytes=hr["device_bytes"],
            spillable_bytes=hr["spillable_bytes"],
            forecast_fits=(est_bytes <= hr["headroom_bytes"]
                           + hr["spillable_bytes"]))
        self.warmup.note_admission(query_id)
        if decision is not None:
            # predicted shape-buckets → pre-warm hints: AOT compiles
            # for the repeat traffic land before the traffic does
            for prog, bucket in decision.hints:
                self.warmup.note_hint(prog, bucket)
        return handle

    def _cancel_queued(self, handle: QueryHandle):
        """Finalize a cancel() on a query that has not started yet."""
        if self.queue.remove(handle):
            self._finalize_cancel(handle)

    # -- execution ---------------------------------------------------------
    def _worker_loop(self):
        while True:
            handle = self.queue.take(timeout=0.2)
            if handle is None:
                if self._shutdown:
                    return
                continue
            try:
                self._run_one(handle)
            except BaseException as e:  # noqa: BLE001 - last-resort guard
                if not handle.done():
                    handle.metrics.outcome = "failed"
                    handle.metrics.error = repr(e)
                    _slo.record(handle.metrics)
                    self._record_terminal(handle.metrics, handle)
                    handle._finish(FAILED, error=e)
                self._forget(handle)

    def _run_one(self, handle: QueryHandle):
        m = handle.metrics
        # progress key for the stall watchdog: this worker's flight ring
        handle._worker_ident = threading.get_ident()
        m.queue_wait_ms = (time.time() - m.submitted_ts) * 1000.0
        QUEUE_WAIT_SECONDS.observe(m.queue_wait_ms / 1e3)
        # retroactive coarse span: the admission-to-start wait, on the
        # worker thread's track just before the attempt spans
        wait_ns = int(m.queue_wait_ms * 1e6)
        _trace.emit("srt.queue_wait", "service",
                    time.perf_counter_ns() - wait_ns, wait_ns, True,
                    query_id=handle.query_id)
        if handle.token.cancelled:
            self._finalize_cancel(handle)
            return
        handle.status = RUNNING
        _flight.record(_flight.EV_STATE, "running",
                       query_id=handle.query_id)
        base_conf = self.session.conf.with_overrides(handle.conf_overrides)
        attempt = 0
        while True:
            m.attempts = attempt + 1
            try:
                table = self._execute_attempt(handle, base_conf, attempt)
            except QueryCancelledError:
                self._cleanup_failed_attempt(handle)
                self._finalize_cancel(handle)
                return
            except Exception as e:  # noqa: BLE001 - classified below
                self._cleanup_failed_attempt(handle)
                retryable = self.retry.is_retryable(e)
                if retryable and attempt + 1 < self.retry.max_attempts \
                        and not handle.token.cancelled:
                    attempt += 1
                    m.retries += 1
                    self._stats.inc("retries")
                    _flight.record(_flight.EV_RETRY,
                                   self.retry.classify(e), a=attempt,
                                   query_id=handle.query_id)
                    backoff = self.retry.backoff_s(attempt)
                    self._events.log_service_event(
                        "retry", handle.query_id, tenant=handle.tenant,
                        attempt=attempt, reason=self.retry.classify(e),
                        error=repr(e), backoff_ms=round(backoff * 1e3, 1),
                        conf_overlay=self.retry.overlay(attempt, base_conf))
                    if handle.token.wait_cancelled(backoff):
                        self._finalize_cancel(handle)
                        return
                    continue
                m.outcome = "failed"
                m.error = repr(e)
                self._stats.inc("failed")
                _slo.record(m)
                self._record_terminal(m, handle)
                handle._finish(FAILED, error=e)
                _flight.record(_flight.EV_STATE, "failed",
                               query_id=handle.query_id)
                reason = self.retry.classify(e)
                bundle = self._write_diag_bundle(
                    "oom" if reason == "device_oom" else "failed",
                    handle, e)
                self._emit_outcome(
                    "failed", handle, reason=reason, retryable=retryable,
                    diag_bundle=bundle)
                self._forget(handle)
                return
            m.outcome = "completed"
            self._stats.inc("completed")
            _slo.record(m)
            self._record_terminal(m, handle)
            handle._finish(DONE, result=table)
            _flight.record(_flight.EV_STATE, "completed",
                           query_id=handle.query_id)
            self._emit_outcome("completed", handle, rows=table.num_rows)
            self._forget(handle)
            return

    def _execute_attempt(self, handle: QueryHandle, base_conf: TpuConf,
                         attempt: int):
        """One planning+execution attempt under the query's context,
        with the retry overlay for this attempt applied."""
        m = handle.metrics
        conf = base_conf.with_overrides(self.retry.overlay(attempt,
                                                           base_conf))
        with _trace.span("srt.attempt", "service", True,
                         query_id=handle.query_id, tenant=handle.tenant,
                         attempt=attempt), \
                query_context(handle.token) as token:
            token.observed.clear()
            token.check()
            # thread-only: the worker's conf must not leak into other
            # client threads' get_active()
            set_active(conf, thread_only=True)
            t0 = time.perf_counter()
            # plan through the fingerprint-keyed cache: a repeat shape
            # replays its stored certificates (verify + PV-FLUSH
            # skipped, prediction re-attached) instead of the full
            # planner tail
            phys, planner = _plan_cache.plan_with_cache(
                handle.logical, conf)
            handle._last_phys = phys
            table = self.session.execute_physical(
                phys, conf=conf, fallbacks=planner.fallbacks)
            m.execute_ms += (time.perf_counter() - t0) * 1000.0
            m.sem_wait_ms += token.observed.get("sem_wait_ms", 0.0)
            m.inline_compile_ms += token.observed.get(
                "inline_compile_ms", 0.0)
            m.host_drop_tax_ms += token.observed.get(
                "host_drop_tax_ms", 0.0)
            m.spill_bytes += int(token.observed.get("spill_bytes", 0))
            m.spill_ms += float(token.observed.get("spill_ms", 0.0))
            m.unspill_count += int(token.observed.get("unspill_count", 0))
            m.leaked_entries += int(
                token.observed.get("leaked_entries", 0))
            return table

    def _emit_outcome(self, kind: str, handle: QueryHandle, **fields):
        """Outcome event line = full metrics record + extra fields."""
        rec = handle.metrics.to_record()
        rec.pop("query_id", None)       # passed positionally below
        rec.update(fields)
        self._events.log_service_event(kind, handle.query_id, **rec)

    def _record_terminal(self, m, handle: Optional[QueryHandle] = None):
        """Fold one terminal query into the longitudinal planes: the
        history row (obs/history.py) and, through it, the anomaly
        sentinel (obs/anomaly.py).  The sentinel's lifecycle events
        get their side effects here — an ``anomaly`` event-log line
        each, plus a rate-limited diag bundle on breach.  Runs on the
        terminal transition path and must never raise."""
        try:
            self.scheduler.observe(m)
        except Exception:
            pass
        try:
            row = _history.record(m)
            if row is None:
                return
            _burn.fold(row)
            for ev in _anomaly.fold(row):
                fields = dict(ev)
                kind = fields.pop("kind", "breach")
                bundle = None
                if kind == "breach" and self._diag_dir \
                        and _anomaly.should_bundle():
                    bundle = self._write_diag_bundle("anomaly", handle,
                                                     None)
                self._events.log_service_event(
                    "anomaly", m.query_id, anomaly_kind=kind,
                    diag_bundle=bundle, **fields)
        except Exception:
            pass

    # -- cleanup / finalization -------------------------------------------
    def _cleanup_failed_attempt(self, handle: QueryHandle):
        """Release everything a dead attempt may still hold: this
        thread's semaphore permits, the query's shuffle map outputs,
        and any catalog buffers still registered to it (unregister of
        an already-released id is a no-op)."""
        from ..memory.arena import DeviceManager
        from ..memory.catalog import BufferCatalog
        from ..shuffle.manager import ShuffleManager
        DeviceManager.get().semaphore.release_all()
        mgr = ShuffleManager._instance
        for sid in handle.token.pop_owned_shuffles():
            if mgr is not None:
                mgr.cleanup(sid)
        cat = BufferCatalog.get()
        for bid in handle.token.pop_owned_buffers():
            cat.unregister(bid)

    def _finalize_cancel(self, handle: QueryHandle):
        reason = handle.token.reason or "cancelled"
        m = handle.metrics
        m.outcome = "cancelled"
        m.error = reason
        self._stats.inc("cancelled")
        _slo.record(m)
        self._record_terminal(m, handle)
        if reason == "deadline":
            self._stats.inc("deadline_exceeded")
        err = QueryCancelledError(reason, handle.query_id)
        handle._finish(CANCELLED, error=err)
        _flight.record(_flight.EV_STATE, "cancelled",
                       query_id=handle.query_id)
        bundle = self._write_diag_bundle(
            "deadline" if reason == "deadline" else "cancelled",
            handle, err)
        self._emit_outcome("cancelled", handle, reason=reason,
                           diag_bundle=bundle)
        self._forget(handle)

    # -- failure diagnostics ----------------------------------------------
    def _write_diag_bundle(self, trigger: str, handle: Optional[QueryHandle],
                           error: Optional[BaseException]) -> Optional[str]:
        """Capture one diagnostic bundle (obs/diagnostics.py) into the
        conf'd directory.  Returns the bundle path, or None when
        diagnostics are disabled or capture failed — this runs on a
        failing query's unwind path and must never raise."""
        if not self._diag_dir:
            return None
        from ..obs import diagnostics as _diag
        return _diag.capture(trigger, self._diag_dir, self._diag_max,
                             handle=handle, error=error, service=self)

    def _maybe_shed_bundle(self, handle: QueryHandle,
                           error: BaseException) -> Optional[str]:
        """Shed is the overload path: a bundle per shed submission would
        turn one incident into thousands of files, so shed bundles are
        rate-limited to one per 10s (the event-log line still records
        every shed)."""
        if not self._diag_dir:
            return None
        now = time.monotonic()
        if now - self._last_shed_bundle_mono < 10.0:
            return None
        self._last_shed_bundle_mono = now
        return self._write_diag_bundle("shed", handle, error)

    def _inflight_items(self) -> List:
        """(query_id, handle) snapshot for the stall watchdog."""
        with self._inflight_lock:
            return list(self._inflight.items())

    def _forget(self, handle: QueryHandle):
        with self._inflight_lock:
            self._inflight.pop(handle.query_id, None)

    # -- introspection -----------------------------------------------------
    def stats(self) -> "ServiceStats":
        """The service's lifecycle counters (public accessor; the
        counter object itself stays private so callers observe through
        ``snapshot()``/the registry rather than mutating it).
        ``stats().snapshot()`` additionally carries the live
        ``watchdog`` state and ``flight_recorder`` occupancy
        sections."""
        return self._stats

    def snapshot(self) -> Dict:
        """Service counters + queue state (monitoring endpoint shape)."""
        out = self._stats.snapshot()
        out.update(self.queue.stats())
        with self._inflight_lock:
            out["inflight"] = len(self._inflight)
        return out

    def metrics_text(self) -> str:
        """Process metrics registry (arena, semaphore/queue waits,
        compile caches, shuffle bytes, service lifecycle counters) in
        Prometheus text exposition format."""
        from ..obs.prom import render_text
        return render_text()

    def start_metrics_server(self, port: int = 0,
                             host: str = "127.0.0.1") -> int:
        """Start (once) a daemon-thread ``/metrics`` scrape endpoint;
        returns the bound port."""
        if self._scrape_server is None:
            from ..obs.prom import serve_scrapes
            self._scrape_server, port = serve_scrapes(port=port, host=host)
            self._scrape_port = port
        return self._scrape_port


# back-compat alias: a submitted query is the "request"
QueryRequest = QueryHandle
