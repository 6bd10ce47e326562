"""Benchmark driver: TPU engine vs CPU oracle engine on a representative

SQL workload (scan -> filter -> project -> hash-aggregate -> join), the
shape of the reference's headline mortgage-ETL / TPC queries
(BASELINE.md).  The aggregate output (~1000 groups) is joined against a
small dimension table, so the headline number exercises the join +
exchange machinery, not just filter/project/agg.  Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

value        = TPU engine throughput (M rows/s through the pipeline)
vs_baseline  = TPU time / CPU-engine time speedup (the reference's
               headline metric is end-to-end speedup vs CPU Spark;
               our CPU engine is the stand-in oracle)

Float mode: the HEADLINE numbers are the DEFAULT configuration
(variableFloatAgg off — exact-results parity with the reference's
default).  The opt-in f32-accumulation fast path is reported in the
secondary keys (variable_Mrows_s / variable_vs_baseline).

History note: rounds r01-r05 were measured on a backend that no
longer exists (their BENCH_r files are deleted); not measured on the
current machine.  r04's headline reported the VARIABLE float mode and
r05 switched it to the exact-mode default, so the r04 -> r05 move was a
headline *definition* change, not a regression.

Pipeline split: since r06 the engine drains partitions morsel-parallel
(spark.rapids.tpu.exec.pipeline.*, exec/pipeline.py).  The headline
runs with the pipeline ON (parallelism/prefetch pinned to 4, like the
batch-size tuning above — the auto default is min(4, cpu) and bench
hosts vary); pipeline_off_Mrows_s re-measures exact mode with the
pipeline disabled so each BENCH_r shows the on/off delta.  Output is
bit-identical either way (tests/test_pipeline.py).

Superstage split: since r06 the planner carves exchange-delimited
regions into one-dispatch superstages (spark.rapids.tpu.sql.superstage,
compile/).  superstage_off_Mrows_s re-measures exact mode with carving
disabled, and the flushes / superstage_off_flushes keys report the warm
per-query device round trips under each mode (the cost model the
compiler optimizes).  Output is bit-identical either way
(tests/test_compile.py).

Stats split: since r07 the runtime stats plane (obs/stats.py,
spark.rapids.tpu.obs.stats.*) is ON in the headline configuration —
it is designed to add zero device flushes, so its cost is pure host
work.  stats_off_Mrows_s re-measures the exact headline with stats
collection disabled and stats_overhead_pct reports the on/off overhead
(budget: <= 2%, asserted by ci/stats_smoke.py with a loose bound).
dispatch_p50_ms / dispatch_p95_ms are the warm query's device-dispatch
duration percentiles from the StatsProfile's "all" roll-up.

Memory split: since r11 the memory plane (obs/memplane.py,
spark.rapids.tpu.obs.mem.*) prices every tier move the catalog makes.
peak_device_bytes is the headline session's device-byte peak (set by
the cold warmup run — warm reruns free their buffers and do not
advance it), spill_ms the active spill time inside the warm window, and
spill_tax_pct the share of the headline wall spent moving buffers
between tiers (spill + unspill) — 0.0 on a bench host whose budget
fits the working set, which is itself the claim the key documents.

Fleet split: since r15 the service stage runs with a history dir
configured (obs/history.py, obs/anomaly.py), so the burst prices the
fleet longitudinal plane: history_rows must equal the submission
count exactly (gated "exact" — any drop or double-count is a
regression), anomaly_checks counts the sentinel's EWMA folds, and
history_write_p99_us bounds the background writer's append latency
(the plane's only I/O, strictly off the query path).

Obs tax split: since r17 the observability layer meters ITSELF
(obs/overhead.py).  all_planes_off_Mrows_s re-measures the exact
headline with every obs conf disabled, all_planes_on_vs_off is the
off/on time ratio the perf gate bounds at >= 0.98 (the <= 2% total
overhead budget) — measured as an interleaved on/off pair of fresh
runs so run-order drift cannot masquerade as tax — and obs_self_ms
is the self-meter's per-plane attribution of one warm headline query
— where the tax lives, not just what it sums to.  Results are
identical planes-on vs planes-off (tests/test_obs_overhead.py pins
the arrow sha), so the ratio prices pure host-side bookkeeping.
"""
import json
import sys
import time

import numpy as np


def build_df(session, n_rows: int, num_partitions: int):
    rng = np.random.default_rng(7)
    from spark_rapids_tpu.api import functions as F
    data = {
        "k": rng.integers(0, 1000, n_rows).astype(np.int64),
        "a": rng.integers(-100_000, 100_000, n_rows).astype(np.int64),
        "x": rng.random(n_rows),
        "y": rng.random(n_rows),
    }
    df = session.create_dataframe(data, num_partitions=num_partitions)
    # small dimension side: one row per group key, joined post-agg
    dim = session.create_dataframe({
        "dk": np.arange(1000, dtype=np.int64),
        "w": rng.random(1000),
    }, num_partitions=1)
    agg = (df.filter((F.col("x") > 0.1) & (F.col("a") % 7 != 0))
             .with_column("z", F.col("x") * F.col("y") + F.col("a"))
             .group_by("k")
             .agg(F.sum("z").alias("sz"), F.count().alias("c"),
                  F.max("x").alias("mx")))
    joined = (agg.join(dim, agg["k"] == dim["dk"], "inner")
                 .select(F.col("k"), F.col("sz"), F.col("c"),
                         (F.col("mx") * F.col("w")).alias("mw")))
    return joined


def run_engine(enabled: bool, n_rows: int, num_partitions: int,
               repeats: int, variable_float: bool = True,
               pipeline: bool = True, superstage: bool = True,
               stats: bool = True, obs_planes: bool = True):
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.obs import memplane as _memplane
    # tuned like the reference's benchmark guides tune Spark: large
    # scan batches keep the per-batch fixed costs (dispatch + transfer
    # round trips) amortized on the accelerator
    conf = {
        "spark.rapids.tpu.sql.enabled": enabled,
        "spark.rapids.tpu.sql.batchSizeRows": 1 << 22,
        "spark.rapids.tpu.sql.reader.batchSizeRows": 1 << 22,
        # f32 accumulation opt-in for the variable-mode measurement
        # (defaults off to match the reference's exact-results default;
        # the EXACT-mode number is measured separately and reported in
        # the same line as exact_vs_baseline)
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": variable_float,
        # morsel pipeline pinned (not auto) so the measurement does not
        # depend on the bench host's core count; pipeline=False is the
        # pipeline_off_Mrows_s measurement
        "spark.rapids.tpu.exec.pipeline.enabled": pipeline,
        "spark.rapids.tpu.exec.pipelineParallelism": 4,
        "spark.rapids.tpu.exec.pipelinePrefetchDepth": 4,
        # superstage carving (compile/): superstage=False is the
        # superstage_off measurement of the same exact-mode query
        "spark.rapids.tpu.sql.superstage": superstage,
        # runtime stats plane (obs/stats.py): stats=False is the
        # stats_off measurement behind stats_overhead_pct
        "spark.rapids.tpu.obs.stats.enabled": stats,
    }
    if not obs_planes:
        # observability tax measurement: EVERY obs conf off — the
        # all_planes_on_vs_off denominator.  Results must be identical
        # to the planes-on run (tests/test_obs_overhead.py pins the
        # arrow sha), so the ratio prices pure host-side bookkeeping
        conf.update({
            "spark.rapids.tpu.obs.trace.enabled": False,
            "spark.rapids.tpu.obs.flightRecorder.enabled": False,
            "spark.rapids.tpu.obs.stats.enabled": False,
            "spark.rapids.tpu.obs.timeline.enabled": False,
            "spark.rapids.tpu.obs.compile.enabled": False,
            "spark.rapids.tpu.obs.slo.enabled": False,
            "spark.rapids.tpu.obs.net.enabled": False,
            "spark.rapids.tpu.obs.mem.enabled": False,
            "spark.rapids.tpu.obs.cost.enabled": False,
            "spark.rapids.tpu.obs.doctor.enabled": False,
            "spark.rapids.tpu.obs.history.enabled": False,
            "spark.rapids.tpu.obs.anomaly.enabled": False,
            "spark.rapids.tpu.obs.overhead.enabled": False,
        })
    s = TpuSession(TpuConf(conf))
    # build the query ONCE: the measurement is query execution over
    # loaded data (the reference's benchmark shape), not datagen/upload
    df = build_df(s, n_rows, num_partitions)
    # cold run: compile cache + device-resident input warmup.  For the
    # FIRST engine run in the process this is the true cold-start cost
    # (every jit cache empty) — cold_exact_Mrows_s / cold_vs_warm_ratio
    # report it for the headline config
    t0 = time.perf_counter()
    df.to_arrow()
    cold_t = time.perf_counter() - t0
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = df.to_arrow()
        dt = time.perf_counter() - t0
        best = min(best, dt)
    assert out.num_rows > 0
    # warm per-query device round trips (api/session.py counts the
    # pending-pool flush delta around each execution) — the flushes
    # column every BENCH_r now reports alongside throughput
    flushes = getattr(s, "last_query_flushes", None)
    prof = getattr(s, "last_stats_profile", None)
    # performance plane (obs/timeline.py, obs/compile_watch.py): the
    # warm query's device-utilization lane + inline-compile ms
    perf = {"timeline": getattr(s, "last_query_timeline", None),
            "inline_compile_ms": getattr(
                s, "last_query_inline_compile_ms", None),
            "netplane": getattr(s, "last_query_netplane", None),
            # memory plane (obs/memplane.py): the same warm query's
            # spill-pricing roll-up, plus the session's device-byte
            # peak (warm reruns do not advance the peak themselves —
            # the cold warmup run is what set it)
            "memplane": getattr(s, "last_query_memplane", None),
            "mem_peak_bytes": _memplane.stats_section()["peak"]["bytes"],
            # static PV-FLUSH prediction for the same warm query
            # (analysis/flush_budget.py — must equal `flushes`)
            "predicted_flushes": getattr(
                s, "last_query_predicted_flushes", None),
            # per-site declared-transfer counts of the same warm query
            # (analysis/residency.py registry — the event-log field
            # the doctor joins against host_staging)
            "declared_transfer_sites": dict(getattr(
                s, "last_query_declared_transfers", None) or {}),
            # device-compute cost roll-up (obs/costplane.py): the
            # warm query's roofline verdict, achieved rates and the
            # padding-waste tax of the AOT bucket lattice
            "costplane": getattr(s, "last_query_costplane", None),
            # cross-plane doctor verdict for the same warm query
            # (obs/doctor.py)
            "diagnosis": getattr(s, "last_query_diagnosis", None),
            # per-plane obs self-cost of the same warm query (the
            # obs_self block obs/overhead.py puts on the event record)
            "obs_self": (getattr(s, "last_query_event", None)
                         or {}).get("obs_self"),
            "cold_s": cold_t}
    return best, flushes, (prof.to_dict() if prof is not None
                           else None), perf


def audited_programs():
    """Run the jaxpr program audit (analysis/program_audit.py) and
    return the audited program names — the bench record documents WHICH
    device programs the numbers were measured over, statically vetted
    (no host callbacks / float surprises / data-dependent shapes).
    Mesh programs need >= 2 devices to build; on a single-device bench
    host the rest are still audited."""
    try:
        import jax
        from spark_rapids_tpu.analysis.program_audit import (audit_all,
                                                             collect_specs)
        specs = collect_specs()
        if jax.local_device_count() < 2:
            specs = [s for s in specs if not s.name.startswith("mesh_")]
        report = audit_all(specs)
        if not report.ok:
            return {"findings": [str(f) for f in report.findings]}
        return sorted(report.audited)
    except Exception:  # noqa: BLE001 - reporting only, never gate bench
        return None


def undeclared_transfers():
    """Static residency verdict for the measured build
    (analysis/residency.py): RES findings the interprocedural escape
    analysis proves on the execution spine, plus declared-site registry
    coverage gaps and parse errors.  Must be 0 — the perf baseline
    gates it exact, so a change that reintroduces a hidden device->host
    sync fails the perf gate, not a profiling session."""
    try:
        import os
        from spark_rapids_tpu.analysis import residency
        root = os.path.dirname(os.path.abspath(__file__))
        report = residency.analyze_project(root)
        gaps = residency.coverage_gaps(root)
        return len(report.findings) + len(report.errors) + len(gaps)
    except Exception:  # noqa: BLE001 - reporting only, never gate bench
        return None


def _aot_warmup_total():
    """Compiles the warmup daemon absorbed (compile/aot.py) — nonzero
    once the service stage has run with warmup enabled."""
    try:
        from spark_rapids_tpu.compile import aot
        return aot.warmup_total()
    except Exception:  # noqa: BLE001 - reporting only, never gate bench
        return None


def compile_cache_hit_pct():
    """Process-wide engine JIT cache hit rate (registry counter
    tpu_compile_cache_requests_total over every cache) — after a full
    bench run this is the share of compile-cache lookups the shape
    bucketing (compile/aot.py) kept on the hit path."""
    from spark_rapids_tpu.obs.registry import COMPILE_CACHE
    hits = misses = 0.0
    for c in COMPILE_CACHE.children():
        lab = dict(c.labels)
        if lab.get("outcome") == "hit":
            hits += c.value
        elif lab.get("outcome") == "miss":
            misses += c.value
    total = hits + misses
    return round(hits / total * 100, 2) if total else None


def planner_cold_ms():
    """The true cold planner-path latency: the first-in-process
    planning of the headline shape (the first ``run_engine`` call's
    plan-cache miss — every rule table, verifier pass and fingerprint
    walk first-touch included).  This is what a fresh serving
    process's first query of a shape pays; the certificate-replay hit
    latency (``planner_path_ms_warm``) is what every repeat pays.
    Must be read right after the FIRST engine run: later sessions'
    conf changes invalidate the entry and re-store it with a
    warm-process miss latency."""
    from spark_rapids_tpu.cache import plan_cache
    top = plan_cache.stats_section().get("top") or []
    return top[0]["cold_ms"] if top else None


def measure_service_p99(n_rows: int = 200_000, submissions: int = 8,
                        cold_ms: float = None):
    """Tenant p99 through the serving front-end (service/server.py):
    submit a small burst as tenant "bench" and read the SLO plane's
    reservoir percentile from stats().  Small rows on purpose — this
    measures the serving overhead distribution, not throughput.

    The same burst prices the fleet plane (obs/history.py,
    obs/anomaly.py): the service runs with a history dir configured,
    so every terminal query folds one JSONL row through the bounded
    background writer and the sentinel.  history_rows must equal the
    submission count exactly (nothing dropped, nothing double-counted),
    anomaly_checks counts the sentinel's per-(fingerprint, key) folds,
    and history_write_p99_us is the background append p99 — the
    off-query-path budget the perf gate bounds.

    Since r16 the burst ALSO prices the plan cache + predictive
    scheduler (cache/plan_cache.py, service/scheduler.py): the warmup
    ``to_arrow`` is the one plan-cache miss of the measured window,
    every service repeat replays the stored certificate, so
    plan_cache_hit_pct / planner_path_ms_warm come straight from the
    cache ledger (planner_path_ms_cold is the process-cold miss
    snapshot passed in as ``cold_ms`` — see :func:`planner_cold_ms`).
    The burst's ``submissions`` folds freeze the shape's exec_ms
    baseline (warmupMinRuns default 8), so the trailing predicted
    submissions carry exec_ms predictions and predicted_exec_err_pct
    is the scheduler's honesty window mean over them."""
    import tempfile
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.cache import plan_cache as _plan_cache
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.obs import anomaly as _anomaly
    from spark_rapids_tpu.obs import history as _history
    from spark_rapids_tpu.service.server import QueryService
    hist_dir = tempfile.mkdtemp(prefix="bench_history_")
    s = TpuSession(TpuConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.obs.history.dir": hist_dir,
    }))
    df = build_df(s, n_rows, 2)
    # warm the compile caches AND seed the plan cache: with the ledger
    # reset first, this is the measured cold planner pass (the one
    # miss); every service submission below replays the certificate
    _plan_cache.reset()
    df.to_arrow()
    predicted_extra = 2
    with QueryService(session=s, num_workers=2) as svc:
        # only the measured burst below lands in the fleet counters
        _history.reset()
        _anomaly.reset()
        handles = [svc.submit(df, tenant="bench")
                   for _ in range(submissions)]
        for h in handles:
            h.result(timeout=120)
        # the burst's folds froze the shape's exec_ms baseline — these
        # trailing submissions are assessed WITH a prediction, and
        # their completion folds |predicted - actual| into the
        # scheduler's honesty window (predicted_exec_err_pct)
        for _ in range(predicted_extra):
            svc.submit(df, tenant="bench").result(timeout=120)
        snap = svc.stats().snapshot()
    # read fleet counters AFTER shutdown: stop() drains the writer
    # queue, so write_p99_us covers every appended row
    hist = _history.stats_section()
    anom = _anomaly.stats_section()
    pc = _plan_cache.stats_section()
    top = (pc.get("top") or [{}])[0]
    pred_err = snap.get("scheduler", {}).get("pred_err_pct", {})
    return {
        "service_p99_ms": snap.get("slo", {}).get("tenants", {}).get(
            "bench", {}).get("p99_ms"),
        "history_rows": hist.get("rows"),
        "history_write_p99_us": hist.get("write_p99_us"),
        "anomaly_checks": anom.get("checks"),
        "plan_cache_hit_pct": pc.get("hit_pct"),
        "planner_path_ms_cold": (cold_ms if cold_ms is not None
                                 else top.get("cold_ms")),
        "planner_path_ms_warm": top.get("warm_ms"),
        "predicted_exec_err_pct": pred_err.get("mean"),
    }


def measure_soak(total_queries: int = 80, qps: float = 10.0,
                 rows: int = 4096):
    """Sustained mixed-traffic stage (service/soak.py): drive the
    repeat-heavy fingerprint mix through the service at open-loop QPS
    with ONE seeded worker-kill fault, and read the soak plane's six
    gated keys from the report.  Quota-driven (total_queries) rather
    than wall-driven so the stage is seconds-scale and deterministic
    in shape; the fault lands at 2s — late enough for a measured
    pre-fault p99, early enough that every run exercises the kill ->
    recovery -> re-convergence path.  leak_drift_bytes is the
    pool-idle-floor regression over the run and MUST be exactly 0
    (scale-invariant in the perf gate); anomaly_fp_rate is the
    sentinel's false-positive share over the stationary traffic."""
    import tempfile
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.obs import anomaly as _anomaly
    from spark_rapids_tpu.obs import history as _history
    from spark_rapids_tpu.service.soak import SoakConfig, run_soak
    hist_dir = tempfile.mkdtemp(prefix="bench_soak_history_")
    s = TpuSession(TpuConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.obs.history.dir": hist_dir,
    }))
    _history.reset()
    _anomaly.reset()
    cfg = SoakConfig(
        duration_s=60.0, total_queries=total_queries, qps=qps,
        rows=rows, partitions=2, seed=42,
        faults=((2.0, "kill_pipeline_worker"),), num_workers=2)
    report = run_soak(s, cfg).to_dict()
    anom = report.get("anomaly") or {}
    return {
        "sustained_Mrows_s": round(
            (report["totals"].get("sustained_rows_s") or 0.0) / 1e6, 4),
        "soak_p99_ms": report["latency"]["p99_ms"],
        "shed_rate_pct": report["shed_rate_pct"],
        "leak_drift_bytes": report["leak_drift_bytes"],
        "anomaly_fp_rate": anom.get("fp_rate_pct", 0.0),
        "fault_recovery_ratio": report["fault_recovery_ratio"],
    }


def main():
    # 64M rows: fixed dispatch/flush overhead amortizes and the
    # measurement approaches the engines' sustained throughput
    n_rows = int(sys.argv[1]) if len(sys.argv) > 1 else 64_000_000
    parts = 4
    repeats = 3
    # headline: the DEFAULT conf (exact float aggregation) — the 8-bit
    # chunk-lane / two-stage-u32 exact table path (exec/tpu_aggregate)
    tpu_exact_t, tpu_flushes, tpu_prof, tpu_perf = run_engine(
        True, n_rows, parts, repeats, variable_float=False)
    # per-plane self-cost of the LAST warm headline query (the
    # per-query obs_self block from obs/overhead.py on the event-log
    # record) — warmup compiles never pollute it, so this is the
    # steady-state per-query observability tax in ms
    obs_self_ms = (tpu_perf.get("obs_self") or {}).get("planes") or {}
    cold_exact_t = tpu_perf["cold_s"]
    # the first engine run's plan-cache miss recorded the TRUE cold
    # planner path (process-cold first-touch); snapshot it before the
    # next session's conf invalidates the entry
    planner_cold = planner_cold_ms()
    # stats-off runs ADJACENT to the headline: the on/off overhead is a
    # fixed ~10-15ms of host work per query, so at small n the pair
    # must share process cache state or session-order drift swamps it
    tpu_nostats_t, _, _, _ = run_engine(True, n_rows, parts, repeats,
                                        variable_float=False, stats=False)
    # ALL planes off, measured as an interleaved on/off pair of fresh
    # runs of the same query: the aggregate observability tax the r17
    # diet budgets at <= 2% (all_planes_on_vs_off gated >= 0.98) is
    # ~1%, so run-order drift (growing compile caches, host thermal
    # state) would swamp a single distant on/off comparison.  Each leg
    # is best-of-`repeats`; the ratio takes the best leg per mode
    # across both rounds
    tpu_onadj_t = float("inf")
    tpu_noobs_t = float("inf")
    for _ in range(2):
        t_on, _, _, _ = run_engine(True, n_rows, parts, repeats,
                                   variable_float=False)
        tpu_onadj_t = min(tpu_onadj_t, t_on)
        t_off, _, _, _ = run_engine(True, n_rows, parts, repeats,
                                    variable_float=False,
                                    obs_planes=False)
        tpu_noobs_t = min(tpu_noobs_t, t_off)
    tpu_off_t, _, _, _ = run_engine(True, n_rows, parts, repeats,
                                    variable_float=False, pipeline=False)
    tpu_nostage_t, nostage_flushes, _, _ = run_engine(
        True, n_rows, parts, repeats, variable_float=False,
        superstage=False)
    tpu_var_t, _, _, _ = run_engine(True, n_rows, parts, repeats,
                                    variable_float=True)
    cpu_t, _, _, _ = run_engine(False, n_rows, parts, repeats)
    svc_keys = measure_service_p99(cold_ms=planner_cold)
    service_p99 = svc_keys["service_p99_ms"]
    soak_keys = measure_soak()
    disp = (tpu_prof or {}).get("dispatches", {}).get("all", {})
    diag = tpu_perf.get("diagnosis")
    tl = tpu_perf.get("timeline") or {}
    net = tpu_perf.get("netplane") or {}
    mem = tpu_perf.get("memplane") or {}
    cost = tpu_perf.get("costplane") or {}
    tier_ms = (mem.get("spill_ms") or 0.0) + (mem.get("unspill_ms")
                                              or 0.0)
    print(json.dumps({
        "metric": "sql_pipeline_throughput",
        "value": round(n_rows / tpu_exact_t / 1e6, 3),
        "unit": "Mrows/s",
        "vs_baseline": round(cpu_t / tpu_exact_t, 3),
        "float_mode": "exact",
        # opt-in f32-accumulation fast path (variableFloatAgg=true)
        "variable_Mrows_s": round(n_rows / tpu_var_t / 1e6, 3),
        "variable_vs_baseline": round(cpu_t / tpu_var_t, 3),
        "exact_Mrows_s": round(n_rows / tpu_exact_t / 1e6, 3),
        "exact_vs_baseline": round(cpu_t / tpu_exact_t, 3),
        # AOT compile service (compile/aot.py + service/warmup.py):
        # cold-start throughput of the headline config (first execution
        # in the process, every jit cache empty), how much slower cold
        # is than warm, the process-wide JIT cache hit share after the
        # full run, and how many compiles the admission-aware warmup
        # daemon absorbed off the query path during the service stage
        "cold_exact_Mrows_s": round(n_rows / cold_exact_t / 1e6, 3),
        "cold_vs_warm_ratio": round(cold_exact_t / tpu_exact_t, 3),
        "compile_cache_hit_pct": compile_cache_hit_pct(),
        "warmup_compiles": _aot_warmup_total(),
        # exact mode with the morsel pipeline disabled: the on/off
        # delta of intra-query pipelined drains (exec/pipeline.py)
        "pipeline_off_Mrows_s": round(n_rows / tpu_off_t / 1e6, 3),
        "pipeline_on_vs_off": round(tpu_off_t / tpu_exact_t, 3),
        # exact mode with superstage carving disabled (compile/): the
        # on/off split of one-dispatch-per-stage execution, plus the
        # warm per-query device round trips under each mode
        "superstage_off_Mrows_s": round(n_rows / tpu_nostage_t / 1e6, 3),
        "superstage_on_vs_off": round(tpu_nostage_t / tpu_exact_t, 3),
        "flushes": tpu_flushes,
        "superstage_off_flushes": nostage_flushes,
        # static PV-FLUSH prediction for the warm headline query — the
        # cross-checked dispatch model (analysis/flush_budget.py)
        "predicted_flushes": tpu_perf.get("predicted_flushes"),
        # device residency (analysis/residency.py): the warm headline
        # query's per-site declared-transfer counts, and the static
        # escape analysis verdict over the execution spine — MUST be 0
        # (gated exact by PERF_BASELINE, so a reintroduced hidden sync
        # fails ci/perf_gate.py rather than a profiling session)
        "declared_transfer_sites": tpu_perf.get("declared_transfer_sites"),
        "undeclared_transfers": undeclared_transfers(),
        # device programs statically vetted by the jaxpr auditor
        "audited_programs": audited_programs(),
        # runtime stats plane (obs/stats.py): on/off overhead of the
        # exact headline (the plane adds zero flushes, so this is pure
        # host-side cost; budget <= 2%) + the warm query's dispatch
        # duration percentiles from the StatsProfile
        "stats_off_Mrows_s": round(n_rows / tpu_nostats_t / 1e6, 3),
        "stats_overhead_pct": round(
            (tpu_exact_t - tpu_nostats_t) / tpu_nostats_t * 100, 2),
        # observability tax diet (obs/overhead.py): the exact headline
        # re-measured with EVERY obs conf off, the on/off time ratio
        # the perf gate bounds at >= 0.98 (<= ~2% total overhead), and
        # the self-meter's per-plane attribution of the planes-on
        # window (host ms billed to each plane's record paths)
        "all_planes_off_Mrows_s": round(n_rows / tpu_noobs_t / 1e6, 3),
        "all_planes_on_vs_off": round(tpu_noobs_t / tpu_onadj_t, 3),
        "obs_self_ms": obs_self_ms,
        "dispatch_p50_ms": disp.get("p50_ms"),
        "dispatch_p95_ms": disp.get("p95_ms"),
        # serving-grade performance plane (obs/timeline, compile_watch,
        # slo): the warm query's device utilization + WHY idle time
        # exists, the inline-compile ms that landed in its window
        # (~0 warm — the cold cost lives in tpu_compile_seconds), and
        # the tenant p99 through the service front-end
        "device_util_pct": tl.get("util_pct"),
        "util_gap_breakdown": tl.get("gaps"),
        "inline_compile_ms": round(
            tpu_perf.get("inline_compile_ms") or 0.0, 3),
        "service_p99_ms": service_p99,
        # shuffle transport plane (obs/netplane.py): the warm query's
        # host-drop tax (active serialize+wire+deserialize ms — the
        # baseline ROADMAP item 2's ICI shuffle must beat), wire
        # throughput and the worst per-shuffle edge skew
        "host_drop_tax_ms": net.get("host_drop_tax_ms"),
        "shuffle_wire_MBps": net.get("wire_MBps"),
        "shuffle_edge_skew": net.get("edge_skew"),
        # memory plane (obs/memplane.py): the warm headline query's
        # device-byte peak and the share of its wall spent moving
        # buffers between tiers (spill + unspill active ms)
        "peak_device_bytes": tpu_perf.get("mem_peak_bytes"),
        "spill_ms": mem.get("spill_ms"),
        "spill_tax_pct": round(tier_ms / (tpu_exact_t * 1000) * 100, 2),
        # device-compute cost plane (obs/costplane.py): the warm
        # headline query's achieved HBM bandwidth against the
        # conf-declared peak, the padding-waste share of its padded
        # bucket dispatches (the bucketRatio tax), and the roofline
        # verdict the doctor's device_compute sub-split is built on
        "achieved_GBps": cost.get("achieved_gbps"),
        "padding_waste_pct": cost.get("padding_waste_pct"),
        "roofline_verdict": cost.get("verdict"),
        # cross-plane query doctor (obs/doctor.py): the warm headline
        # query's primary-bottleneck verdict and the Amdahl speedup
        # bound for eliminating it — the one-line answer the seven
        # plane keys above feed
        "doctor_primary_cause": (diag.primary_cause
                                 if diag is not None else None),
        "doctor_primary_share_pct": (diag.primary_share_pct
                                     if diag is not None else None),
        "doctor_headroom_x": (diag.headroom[0]["bound_x"]
                              if diag is not None and diag.headroom
                              else None),
        # fleet longitudinal plane (obs/history.py, obs/anomaly.py):
        # the service burst's history-row count (must equal the
        # submission count exactly — zero drops), the sentinel's
        # per-(fingerprint, key) fold count, and the background
        # writer's append p99 (the off-query-path budget)
        "history_rows": svc_keys["history_rows"],
        "anomaly_checks": svc_keys["anomaly_checks"],
        "history_write_p99_us": svc_keys["history_write_p99_us"],
        # plan cache + predictive scheduler (cache/plan_cache.py,
        # service/scheduler.py): repeat hit rate through the service
        # burst, the process-cold planner path (what a fresh serving
        # process's first query of the shape pays) vs the
        # certificate-replay warm path every repeat pays, and the
        # scheduler's predicted-vs-actual exec_ms honesty mean
        "plan_cache_hit_pct": svc_keys["plan_cache_hit_pct"],
        "planner_path_ms_cold": svc_keys["planner_path_ms_cold"],
        "planner_path_ms_warm": svc_keys["planner_path_ms_warm"],
        "predicted_exec_err_pct": svc_keys["predicted_exec_err_pct"],
        # soak plane (service/soak.py, obs/burn.py, service/faults.py):
        # sustained mixed-traffic throughput and p99 through the
        # service under ONE seeded worker-kill fault, the open-loop
        # shed share, the pool-idle-floor memory drift over the run
        # (gated exact 0 — a nonzero value IS a leak), the anomaly
        # sentinel's false-positive share over stationary traffic, and
        # the fraction of injected fault windows whose p99 recovered
        "sustained_Mrows_s": soak_keys["sustained_Mrows_s"],
        "soak_p99_ms": soak_keys["soak_p99_ms"],
        "shed_rate_pct": soak_keys["shed_rate_pct"],
        "leak_drift_bytes": soak_keys["leak_drift_bytes"],
        "anomaly_fp_rate": soak_keys["anomaly_fp_rate"],
        "fault_recovery_ratio": soak_keys["fault_recovery_ratio"],
    }))


if __name__ == "__main__":
    main()
