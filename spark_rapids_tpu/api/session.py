"""TpuSession — the user entry point.

Role parity: in the reference, users keep their SparkSession and the
plugin hooks in via ``spark.plugins=com.nvidia.spark.SQLPlugin``
(Plugin.scala:57).  Standalone, TpuSession plays both roles: it owns the
conf, initializes the device (executor-plugin init, Plugin.scala:175 ->
GpuDeviceManager), and runs the planner on every action.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa

from ..config import TpuConf, set_active, SQL_ENABLED
from ..columnar.schema import Schema
from ..memory.arena import DeviceManager
from ..obs import trace as _obs_trace
from ..plan import logical as L
from ..plan.overrides import Planner


class TpuSessionBuilder:
    def __init__(self):
        self._conf: Dict[str, object] = {}

    def config(self, key: str, value) -> "TpuSessionBuilder":
        self._conf[key] = value
        return self

    def get_or_create(self) -> "TpuSession":
        return TpuSession(TpuConf(self._conf))


class TpuSession:
    # Active-session registry: per-thread with a lock-guarded global
    # fallback, so concurrent client threads each see the session THEY
    # activated (and its conf) rather than whichever thread activated
    # last.  The conf registry (config.set_active) follows the same
    # thread-local-with-global-fallback discipline.
    _active: Optional["TpuSession"] = None
    _active_tls = threading.local()
    _active_lock = threading.Lock()

    def __init__(self, conf: Optional[TpuConf] = None):
        self.conf = conf or TpuConf()
        set_active(self.conf)
        # persistent XLA compile cache: kernels compile per (schema,
        # capacity bucket), so cross-process reuse pays off at once;
        # compile/xla_cache.py is the only place that picks the directory
        from ..compile import xla_cache as _xla_cache
        _xla_cache.enable()
        _obs_trace.configure(self.conf)
        from ..obs import flight as _obs_flight
        _obs_flight.configure(self.conf)
        from ..obs import overhead as _obs_overhead
        _obs_overhead.configure(self.conf)
        from ..compile import aot as _aot
        _aot.configure(self.conf)
        with TpuSession._active_lock:
            # device (re)init mutates process-wide state (catalog,
            # semaphore); serialize concurrent session construction
            DeviceManager.initialize(self.conf)
            TpuSession._active = self
        TpuSession._active_tls.session = self
        self._last_planner: Optional[Planner] = None
        self._views: dict = {}
        self._logger_lock = threading.Lock()
        # plan-cache disposition of the most recent collect:
        # ("hit"|"miss", planner_path_ms) or None (cache off)
        self.last_query_plan_cache = None

    builder = TpuSessionBuilder

    def close(self) -> None:
        """End of the session's work: write the span file when fine
        tracing has a path (``obs/trace.py``; nothing is written per
        query).  The process-wide device state stays up for the next
        session."""
        _obs_trace.flush()

    @classmethod
    def active(cls) -> "TpuSession":
        s = getattr(cls._active_tls, "session", None)
        if s is not None:
            return s
        with cls._active_lock:
            if cls._active is not None:
                return cls._active
        return TpuSession()   # constructor registers itself

    # -- conf ----------------------------------------------------------------
    def set_conf(self, key: str, value):
        self.conf = self.conf.set(key, value)
        set_active(self.conf)

    def get_conf(self, key: str):
        return self.conf.get_key(key)

    # -- data sources --------------------------------------------------------
    def create_dataframe(self, data, schema: Optional[Schema] = None,
                         num_partitions: int = 1):
        from .dataframe import DataFrame
        if isinstance(data, pa.Table):
            table = data
        elif isinstance(data, dict):
            if schema is None:
                # pyarrow inference handles date/datetime/decimal object
                # arrays that numpy would stringify
                table = pa.table({k: pa.array(v) for k, v in data.items()})
            else:
                from ..columnar.arrow import schema_to_arrow
                target = schema_to_arrow(schema)
                table = pa.table(
                    {f.name: pa.array(data[f.name], type=target.field(
                        f.name).type) for f in schema})
        elif isinstance(data, list):
            # list of tuples + schema
            assert schema is not None, "list data requires a schema"
            cols = {f.name: [row[i] for row in data]
                    for i, f in enumerate(schema)}
            from ..columnar.batch import ColumnarBatch
            from ..columnar.arrow import to_arrow
            batch = ColumnarBatch.from_pydict(cols, schema=schema)
            table = to_arrow(batch)
        else:
            raise TypeError(f"cannot create dataframe from {type(data)}")
        return DataFrame(L.LocalRelation(table, num_partitions), self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1):
        from .dataframe import DataFrame
        if end is None:
            start, end = 0, start
        return DataFrame(L.Range(start, end, step, num_partitions), self)

    @property
    def read(self):
        from .reader import DataFrameReader
        return DataFrameReader(self)

    # -- SQL -----------------------------------------------------------------
    def sql(self, query: str):
        """Parse + lower a SQL query against registered temp views.

        Reference role: Spark's own parser/analyzer feed the plugin its
        plans; standalone, api/sql.py supplies that front end."""
        from .dataframe import DataFrame
        from .sql import sql_to_plan
        _obs_trace.begin_query(parsed=True)
        plan = sql_to_plan(query, self, self._views)
        return DataFrame(plan, self)

    def register_table(self, name: str, df) -> None:
        self._views[name.lower()] = df._plan

    def drop_temp_view(self, name: str) -> None:
        self._views.pop(name.lower(), None)

    # -- execution -----------------------------------------------------------
    def _plan(self, logical: L.LogicalPlan, conf: Optional[TpuConf] = None):
        # plan through the fingerprint-keyed cache (cache/plan_cache.py)
        # so repeat shapes skip the planner tail in standalone sessions
        # exactly as they do under the query service
        from ..cache import plan_cache as _plan_cache
        phys, planner = _plan_cache.plan_with_cache(
            logical, conf or self.conf)
        self._last_planner = planner
        return phys

    def execute_to_arrow(self, logical: L.LogicalPlan) -> pa.Table:
        """Run a logical plan and collect everything as one arrow table."""
        import time as _time
        from ..columnar.arrow import to_arrow, schema_to_arrow
        from ..config import PROFILE_TRACE_DIR
        _obs_trace.begin_query()
        trace_dir = self.conf.get(PROFILE_TRACE_DIR)
        if trace_dir:
            # xprof trace of the whole query — the NVTX+Nsight role
            # (SURVEY.md §5); view with tensorboard / xprof
            import jax
            with jax.profiler.trace(trace_dir):
                return self._execute_to_arrow_inner(logical)
        return self._execute_to_arrow_inner(logical)

    def _execute_to_arrow_inner(self, logical: L.LogicalPlan) -> pa.Table:
        phys = self._plan(logical)
        return self.execute_physical(phys)

    def execute_physical(self, phys, conf: Optional[TpuConf] = None,
                         fallbacks: Optional[List[str]] = None) -> pa.Table:
        """Run an ALREADY-PLANNED physical tree and collect one arrow
        table (the distributed runner plans once, attaches executor
        contexts to exchange nodes, then executes that exact tree).

        ``conf``/``fallbacks`` override the session's own for callers
        that planned with an overlay (the query service executes many
        queries with per-query confs on worker threads; passing them
        explicitly keeps this method thread-safe against session-level
        mutation).  Execution drains through cancellation checkpoints
        and surfaces per-query semaphore-wait and spill-bytes metrics
        in the event log.  The whole collect is the coarse span
        ``srt.query`` (operator, flush, pull and memory spans nest under
        it); the span file is written by ``close()``, not here."""
        with _obs_trace.span("srt.query", "engine", True, root=phys.name):
            return self._execute_physical_traced(phys, conf, fallbacks)

    def _execute_physical_traced(self, phys, conf: Optional[TpuConf] = None,
                                 fallbacks: Optional[List[str]] = None
                                 ) -> pa.Table:
        import time as _time
        from ..columnar.arrow import to_arrow, schema_to_arrow
        from ..columnar.arrow import stage_batch
        from ..memory.arena import DeviceManager
        from ..memory.catalog import BufferCatalog
        from ..service.cancellation import current_token, observe
        conf = conf or self.conf
        # the executing query's conf is the ambient conf for THIS
        # thread for the duration of the drain: with several live
        # sessions, "last constructed wins" would hand exec-layer
        # get_active() callers (shuffle staging budget, stats plane)
        # another session's settings
        set_active(conf, thread_only=True)
        if fallbacks is None:
            fallbacks = self._last_planner.fallbacks \
                if self._last_planner else []
        t0 = _time.perf_counter()
        self.last_physical_plan = phys
        # static PV-FLUSH prediction, computed BEFORE any execution so
        # the predicted-vs-observed comparison below cannot be informed
        # by the run it predicts.  A predictor gap must never block a
        # query: the comparison is observability, the exactness contract
        # is enforced by ci/compile_smoke.py + tests/test_audit.py.
        _flush_pred = None
        try:
            # a plan that came through the plan cache carries its
            # prediction already (replayed from the stored certificate
            # on a hit, computed once at store time on a miss) — the
            # PV-FLUSH exactness contract holds on both paths
            _flush_pred = getattr(phys, "_plan_cache_flush_pred", None)
            if _flush_pred is None:
                from ..analysis.flush_budget import predict_flushes
                _flush_pred = predict_flushes(phys, conf=conf)
        except Exception:  # noqa: BLE001 - observability only
            pass
        sem = DeviceManager.get().semaphore
        sem.pop_wait_ns()                     # reset this thread's counter
        cat = BufferCatalog.get()
        spill0 = cat.spilled_device_to_host + cat.spilled_host_to_disk
        # device round trips this query (process-wide counter delta:
        # concurrent peers' flushes land in whichever query's window
        # they fall — exact when queries run serially, which is how the
        # flush budget is benchmarked)
        from ..analysis import residency as _residency
        from ..columnar import pending
        from ..obs import compile_watch as _cwatch
        from ..obs import costplane as _costplane
        from ..obs import doctor as _doctor
        from ..obs import memplane as _memplane
        from ..obs import netplane as _netplane
        from ..obs import overhead as _overhead
        from ..obs import profile as _profile
        from ..obs import stats as _stats
        from ..obs import timeline as _timeline
        flushes0 = pending.FLUSH_COUNT
        # declared device->host transfers this query (same counter-delta
        # discipline; analysis/residency.py) — the runtime half of the
        # residency contract
        res_marker = _residency.snapshot()
        # self-meter window (obs/overhead.py): per-plane observability
        # self-cost accrued inside this query, same process-wide
        # counter-delta discipline as FLUSH_COUNT
        obs_marker = _overhead.snapshot()
        disp_marker = _profile.begin_query()
        np_marker = _netplane.begin_query()
        mem_marker = _memplane.begin_query()
        cost_marker = _costplane.begin_query()
        # performance-plane windows: compile ns + busy intervals are
        # process-wide counters deltaed around this execution (the
        # FLUSH_COUNT discipline — exact when queries run serially)
        compile0 = _cwatch.total_ns()
        cw_marker = _cwatch.begin_query()
        tl_marker = _timeline.begin_query()
        # collect-sink flushes belong to the root-most fused superstage
        # when the plan has one (obs/profile.py attribution scopes)
        _attrib = next((n for n in phys.collect_nodes()
                        if getattr(n, "lowering", None) is not None),
                       phys)
        token = current_token()
        try:
            # drain all partitions first (device work + staged pulls),
            # then one fused flush serves every batch's counts/buffers
            # (columnar/pending).  The drain is morsel-parallel
            # (exec/pipeline.py): partitions are pulled + resolved on
            # the pipeline pool, reassembled here in partition order —
            # same items, same order as the serial loop it replaced
            from ..columnar.batch import resolve_speculative
            from ..exec.pipeline import drain_parallel

            def _resolve(item):
                if isinstance(item, pa.Table):
                    return item
                # stage output buffers BEFORE the fit-flag check: the
                # flush the verification forces then carries the values
                # too, so a fully speculative chain (superstage join ->
                # agg -> sort -> limit) collects in ONE round trip
                with _profile.attrib_scope(_attrib):
                    stage_batch(item)
                    fixed = resolve_speculative(item)
                    if fixed is not item:
                        stage_batch(fixed)
                return fixed
            # the scoped transfer guard (analysis/residency.py): any
            # device->host pull on this thread that is not inside a
            # declared_transfer region fails loudly.  Pool workers arm
            # the same guard per-thread in _ParallelDrain._serve.
            with _residency.guard_scope(conf):
                items = [item for _pid, item in drain_parallel(
                    phys.execute_checkpointed(), sink=_resolve,
                    token=token, label="collect")]
                tables: List[pa.Table] = []
                for item in items:
                    if isinstance(item, pa.Table):
                        t = item
                    else:
                        with _residency.declared_transfer(
                                site="collect_sink"):
                            t = to_arrow(item)
                    if t.num_rows:
                        tables.append(t)
        finally:
            # end-of-query shuffle release (ContextCleaner role): map
            # outputs are per-query; holding them across a long sweep
            # exhausts the real allocator.  Under a query context only
            # THIS query's shuffles are dropped (concurrent peers may
            # still be draining theirs); distributed-attached exchanges
            # keep their executor-context outputs (peers may still
            # fetch).
            from ..shuffle.manager import ShuffleManager
            mgr = ShuffleManager._instance
            if mgr is not None:
                if token is not None:
                    for sid in token.pop_owned_shuffles():
                        mgr.cleanup(sid)
                else:
                    mgr.clear_all()
        sem_wait_ms = sem.pop_wait_ns() / 1e6
        spill_bytes = (cat.spilled_device_to_host +
                       cat.spilled_host_to_disk) - spill0
        observe("sem_wait_ms", sem_wait_ms)
        observe("spill_bytes", spill_bytes)
        flushes = pending.FLUSH_COUNT - flushes0
        self.last_query_flushes = flushes
        observe("flushes", flushes)
        declared_total, declared_sites = _residency.delta(res_marker)
        self.last_query_declared_transfers = declared_sites
        observe("declared_transfers", declared_total)
        # compile telemetry: compiles that landed in this query's window
        # (engine path; the service separately harvests the token's
        # inline_compile_ms observed at compile time)
        inline_compile_ms = (_cwatch.total_ns() - compile0) / 1e6
        self.last_query_inline_compile_ms = inline_compile_ms
        # device-utilization lane for this query's window
        tl = _timeline.query_summary(tl_marker)
        self.last_query_timeline = tl
        # shuffle host-drop roll-up for this query's window (same
        # process-wide-counter-delta discipline as FLUSH_COUNT); the
        # edge heat rows + per-peer fetch aggregate ride the record so
        # tools/report.py --shuffle renders offline
        net = _netplane.query_summary(np_marker)
        net["top_edges"] = _netplane.query_edges(np_marker, limit=8)
        peers = _netplane.fetch_peer_stats()
        if peers:
            net["fetch_peers"] = peers
        self.last_query_netplane = net
        # the service harvests this into the completed-outcome record
        # (service/metrics.py), like sem_wait_ms above
        observe("host_drop_tax_ms", net["host_drop_tax_ms"])
        # retention check (obs/memplane.py): anything still owned by
        # this query past the shuffle release above that is not an
        # expected survivor (scan cache, shuffle materializations a
        # live reader may still fetch) leaked its registration
        leaks = []
        if token is not None and _memplane.is_enabled():
            from ..shuffle.manager import live_spill_buffer_ids
            leaks = _memplane.leak_check(
                token.query_id, survivors=live_spill_buffer_ids())
        # memory roll-up for this query's window: peak + owner set at
        # peak, per-direction spill totals, the ledger slice
        mem = _memplane.query_summary(mem_marker)
        if leaks:
            mem["leaks"] = leaks
        self.last_query_memplane = mem
        observe("spill_ms", mem["spill_ms"])
        observe("unspill_count", mem["unspill_count"])
        observe("leaked_entries", mem["leaked_entries"])
        result_rows = sum(t.num_rows for t in tables)
        predicted_flushes = None
        if _flush_pred is not None:
            predicted_flushes = _flush_pred.expected(result_rows)
        self.last_query_predicted_flushes = predicted_flushes
        # device-compute cost roll-up (obs/costplane.py): joins the
        # static XLA costs already captured at compile time with this
        # window's dispatch ledger and the timeline busy span — pure
        # host arithmetic, after the final flush, zero extra round trips
        cost = None
        if _costplane.enabled(conf):
            try:
                cost = _costplane.query_summary(
                    cost_marker, busy_ms=float(tl["busy_ms"]))
            except Exception:  # noqa: BLE001 — cost never fails a query
                import logging
                logging.getLogger("spark_rapids_tpu.obs.costplane").warning(
                    "cost summary failed", exc_info=True)
        self.last_query_costplane = cost
        extra = {"sem_wait_ms": round(sem_wait_ms, 3),
                 "spill_bytes": int(spill_bytes),
                 "flushes": int(flushes),
                 "predicted_flushes": predicted_flushes,
                 "declared_transfers": int(declared_total),
                 "declared_transfer_sites": dict(declared_sites),
                 "inline_compile_ms": round(inline_compile_ms, 3),
                 "device_busy_ms": tl["busy_ms"],
                 "device_util_pct": tl["util_pct"],
                 "util_gap_breakdown": tl["gaps"],
                 "host_drop_tax_ms": net["host_drop_tax_ms"],
                 "shuffle_netplane": net,
                 "peak_device_bytes": mem["peak_device_bytes"],
                 "spill_ms": mem["spill_ms"],
                 "unspill_count": mem["unspill_count"],
                 "leaked_entries": mem["leaked_entries"],
                 "memplane": mem}
        from ..config import RESIDENCY_IN_EVENT_LOG
        if not conf.get(RESIDENCY_IN_EVENT_LOG):
            extra.pop("declared_transfers")
            extra.pop("declared_transfer_sites")
        if cost is not None:
            extra["costplane"] = cost
        # plan-cache disposition (cache/plan_cache.py): stamped on the
        # physical root by plan_with_cache — hit/miss plus the wall ms
        # the planner path actually took for THIS query
        pc_status = getattr(phys, "_plan_cache_status", None)
        self.last_query_plan_cache = pc_status
        if pc_status is not None:
            extra["plan_cache"] = pc_status[0]
            extra["planner_path_ms"] = round(pc_status[1], 3)
        compiles = _cwatch.records_since(cw_marker)
        if compiles:
            extra["compiles"] = [
                {"cache": r["cache"], "dur_ms": r["dur_ms"],
                 "inline": r["inline"], "signature": r["signature"],
                 # AOT dimensions (compile/aot.py): which capacity
                 # bucket the compile was for and who paid for it
                 # (inline/warm/warmup/persistent)
                 "origin": r.get("origin", "inline"),
                 "bucket": r.get("bucket")}
                for r in compiles]
        # the recorded wall clock STOPS here: everything below is
        # observability artifact assembly (StatsProfile, the doctor
        # verdict, the fingerprint/history deposit) deferred to
        # event-log write time — it runs off the measured query path,
        # each piece billed to its plane by obs/overhead.py, and the
        # event-log wall_ms no longer pays for its own reporting
        wall_ms = (_time.perf_counter() - t0) * 1000
        # span srt.obs.assemble: what collect() still does between the
        # answer being ready and returning it
        with _obs_trace.span("srt.obs.assemble", "engine", True):
            # per-query StatsProfile (obs/stats.py): read-only over resolved
            # values — built AFTER the final flush, never adds a round trip
            self.last_stats_profile = None
            if _stats.enabled(conf):
                from ..config import OBS_STATS_IN_EVENT_LOG
                try:
                    prof = _stats.build_profile(
                        phys,
                        query_id=token.query_id if token is not None else None,
                        flushes=int(flushes), dispatch_marker=disp_marker)
                    self.last_stats_profile = prof
                    if conf.get(OBS_STATS_IN_EVENT_LOG):
                        extra["stats_profile"] = prof.to_dict()
                except Exception:  # noqa: BLE001 — stats never fail a query
                    import logging
                    logging.getLogger("spark_rapids_tpu.obs.stats").warning(
                        "stats profile build failed", exc_info=True)
            # cross-plane query doctor (obs/doctor.py): joins the summaries
            # gathered above into one primary-bottleneck verdict — pure
            # host arithmetic over dicts already in hand, after the final
            # flush, so the FLUSH_COUNT delta above is unchanged
            self.last_query_diagnosis = None
            if _doctor.enabled(conf):
                try:
                    diag = _doctor.diagnose(
                        tl, inline_compile_ms=inline_compile_ms,
                        netplane=net, memplane=mem, flushes=int(flushes),
                        predicted_flushes=predicted_flushes,
                        declared_transfers=declared_sites,
                        sem_wait_ms=sem_wait_ms,
                        stats_profile=self.last_stats_profile,
                        query_id=token.query_id if token is not None
                        else None,
                        compiles=extra.get("compiles"),
                        costplane=cost)
                    self.last_query_diagnosis = diag
                    extra["doctor"] = diag.to_dict()
                except Exception:  # noqa: BLE001 — doctor never fails a query
                    import logging
                    logging.getLogger("spark_rapids_tpu.obs.doctor").warning(
                        "query diagnosis failed", exc_info=True)
            # longitudinal fleet plane: the stable plan fingerprint groups
            # this query with every recurrence of its shape
            # (obs/fingerprint.py), and the engine-side artifacts are
            # deposited for the history store's terminal join keyed by the
            # same query_id the service folds at the terminal transition
            # (obs/history.py).  Pure host arithmetic after the final
            # flush: the FLUSH_COUNT delta above is unchanged.
            self.last_query_fingerprint = None
            try:
                from ..obs import fingerprint as _fingerprint
                from ..obs import history as _qhistory
                fp = _fingerprint.plan_fingerprint(phys, conf)
                self.last_query_fingerprint = fp
                extra["plan_fingerprint"] = fp
                if token is not None and _qhistory.enabled():
                    art = {
                        "fingerprint": fp,
                        "flushes": int(flushes),
                        "flushes_predicted": predicted_flushes,
                        "device_util_pct": tl["util_pct"],
                        "gaps": tl["gaps"],
                    }
                    if cost is not None:
                        art["roofline_verdict"] = cost.get("verdict")
                        art["achieved_GBps"] = cost.get("achieved_gbps")
                        art["padding_waste_pct"] = \
                            cost.get("padding_waste_pct")
                    if self.last_query_diagnosis is not None:
                        d = self.last_query_diagnosis.to_dict()
                        art["doctor_cause"] = d.get("primary_cause")
                        art["doctor_share_pct"] = d.get("primary_share_pct")
                    _qhistory.note_query(token.query_id, art)
            except Exception:  # noqa: BLE001 — fleet plane never fails a query
                import logging
                logging.getLogger("spark_rapids_tpu.obs.history").warning(
                    "fingerprint/history deposit failed", exc_info=True)
            # the self-meter's verdict on everything the planes above spent
            # inside this query (including the deferred assembly just run)
            if _overhead.is_enabled():
                obs_self = _overhead.delta_ms(obs_marker)
                extra["obs_self"] = {
                    "total_ms": round(sum(obs_self.values()), 3),
                    "planes": obs_self}
            self._log_query(phys, wall_ms, conf=conf, fallbacks=fallbacks,
                            extra=extra)
        target = schema_to_arrow(phys.output_schema) if len(
            phys.output_schema) else None
        if not tables:
            return target.empty_table() if target is not None else \
                pa.table({})
        out = pa.concat_tables(tables, promote_options="permissive")
        if target is not None and out.schema != target:
            import pyarrow.compute as pc
            out = pa.Table.from_arrays(
                [pc.cast(out.column(i).combine_chunks(), f.type, safe=False)
                 for i, f in enumerate(target)], schema=target)
        return out

    def _log_query(self, phys, wall_ms: float,
                   conf: Optional[TpuConf] = None,
                   fallbacks: Optional[List[str]] = None,
                   extra: Optional[Dict] = None):
        from ..config import EVENT_LOG_PATH, METRICS_LEVEL
        from ..service.cancellation import current_token
        from ..tools.events import QueryEventLogger
        conf = conf or self.conf
        path = conf.get(EVENT_LOG_PATH)
        with self._logger_lock:
            if not hasattr(self, "_event_logger") or \
                    (self._event_logger.path or "") != (path or ""):
                self._event_logger = QueryEventLogger(path or None)
            logger = self._event_logger
        # a service-managed query logs under its stable service query_id
        # so admission / retry / outcome lines join with engine metrics
        token = current_token()
        self.last_query_event = logger.log_query(
            phys, wall_ms,
            fallbacks if fallbacks is not None else (
                self._last_planner.fallbacks if self._last_planner else []),
            dict(conf._settings),
            metrics_level=conf.get(METRICS_LEVEL),
            query_id=token.query_id if token is not None else None,
            extra=extra)

    def explain(self, logical: L.LogicalPlan) -> str:
        """Planner explain: physical tree + fallback reasons."""
        phys = self._plan(logical)
        text = phys.tree_string()
        if self._last_planner.fallbacks:
            text += "\n-- CPU fallbacks --\n" + "\n".join(
                self._last_planner.fallbacks)
        return text
