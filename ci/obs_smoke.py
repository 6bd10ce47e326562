"""CI smoke for the observability subsystem: run a traced query through
the service, then assert (1) the Chrome trace JSON parses and carries
nested engine/exec spans, (2) the Prometheus snapshot covers the arena
and semaphore series, (3) the report tool renders the per-query story,
(4) a forced query failure produces a diagnostic bundle — flight tail,
thread stacks, arena map — that tools/diagnose.py renders, and the
failure event-log record links it, (5) a multi-partition shuffle
populates the transport plane (obs/netplane.py): nonzero edge matrix,
host-drop phases summing to the exchange wall, and a real TCP fetch
whose client/server spans join on span_id in the same trace, (6) the
memory plane (obs/memplane.py) attributes every live device byte to an
owner, prices forced tier moves in a ledger whose totals equal the
catalog's own spill counters, and surfaces it all through
Service.stats(), Prometheus, the event log, and the report tool, (7)
the device-compute cost plane (obs/costplane.py) costs the workload's
own programs, splits the roofline shares to 100 within 1e-6, prices
padding waste >0 under a forced non-power-of-two batch, decomposes
the doctor's device_compute share exactly, and adds zero device
flushes against a cost-off run of the same query, (8) the fleet plane
(obs/fingerprint, obs/history, obs/anomaly, obs/dashboard) writes one
history row per terminal query of a two-tenant repeated mix, flags an
injected sleep-shim slowdown on exactly the shimmed plan fingerprint
across the event log, Prometheus, the doctor trend and the dashboard,
reads the same story back through tools/history.py, and adds zero
device flushes against a fleet-off run of the same query, (9) the
observability tax diet (obs/overhead.py): the same query with EVERY
obs conf disabled returns an identical arrow table with the same warm
flush delta, the self-meter attributes the planes-on window per plane
with shares summing to its own total, the per-query event record
carries the ``obs_self`` block, and the metered self-cost stays
within a loose bound of the measured on-vs-off wall delta (the exact
>= 0.98 budget is gated by bench.py + ci/perf_gate.py).
"""
import json
import os
import sys
import tempfile
import time

import jax

jax.config.update("jax_platforms", "cpu")

from spark_rapids_tpu.api import TpuSession, functions as F  # noqa: E402
from spark_rapids_tpu.config import TpuConf  # noqa: E402
from spark_rapids_tpu.service.server import QueryService  # noqa: E402


def main():
    td = tempfile.mkdtemp(prefix="obs_smoke_")
    trace_path = os.path.join(td, "trace.json")
    log_path = os.path.join(td, "events.jsonl")
    diag_dir = os.path.join(td, "diag")
    s = TpuSession(TpuConf({
        "spark.rapids.tpu.eventLog.path": log_path,
        "spark.rapids.tpu.obs.trace.enabled": True,
        "spark.rapids.tpu.obs.trace.path": trace_path,
        "spark.rapids.tpu.obs.diagnostics.dir": diag_dir,
        "spark.rapids.tpu.service.retry.maxAttempts": 2,
        "spark.rapids.tpu.service.retry.initialBackoffMs": 5,
    }))
    df = s.create_dataframe(
        {"k": [i % 7 for i in range(2000)],
         "v": [float(i) for i in range(2000)]})
    s.register_table("obs_smoke", df)
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.udf import pandas_udf

    def _doomed(series):
        raise RuntimeError("RESOURCE_EXHAUSTED: obs_smoke forced OOM")
    doomed = pandas_udf(_doomed, return_type=T.INT64)
    failing = s.range(0, 64, num_partitions=2) \
        .select(doomed(F.col("id")).alias("id"))

    with QueryService(s, num_workers=2) as svc:
        for _ in range(3):
            svc.submit(
                "SELECT k, SUM(v), COUNT(v) FROM obs_smoke GROUP BY k"
            ).result(120)
        # a multi-partition aggregate: the group-by exchange gives the
        # transport plane real map->reduce traffic to account for
        shuf_df = s.range(0, 4096, num_partitions=4) \
            .select((F.col("id") % 13).alias("k"),
                    F.col("id").alias("v")) \
            .group_by("k").agg(F.sum("v").alias("sv"))
        h_shuf = svc.submit(shuf_df, tenant="shuffle")
        h_shuf.result(120)
        # cross-boundary correlation: one real TCP fetch inside the
        # traced process, so the client's shuffle_fetch span and the
        # server's serve spans land in the same Perfetto trace
        import numpy as np
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.shuffle import (MapOutputTracker,
                                              ShuffleExecutorContext)
        from spark_rapids_tpu.shuffle.tcp import TcpTransport
        ta, tb = TcpTransport("exec-a"), TcpTransport("exec-b")
        ta.add_peer("exec-b", tb.address)
        tb.add_peer("exec-a", ta.address)
        trk = MapOutputTracker()
        ex_a = ShuffleExecutorContext("exec-a", ta, trk,
                                      bounce_buffer_size=4096,
                                      num_bounce_buffers=2)
        ex_b = ShuffleExecutorContext("exec-b", tb, trk,
                                      bounce_buffer_size=4096,
                                      num_bounce_buffers=2)
        ex_a.write_map_output(97, 0, {0: [ColumnarBatch.from_pydict({
            "k": np.arange(64, dtype=np.int64),
            "v": np.arange(64, dtype=np.float64)})]})
        fetched = list(ex_b.read_partition(97, 0, timeout_s=30.0))
        assert sum(len(b.to_pydict()["k"]) for b in fetched) == 64
        ta.close()
        tb.close()
        # one forced failure: every retry attempt OOMs
        h_fail = svc.submit(failing, tenant="doomed")
        try:
            h_fail.result(120)
            raise AssertionError("forced-failure query succeeded")
        except RuntimeError:
            pass
        metrics = svc.metrics_text()
        snap = svc.stats().snapshot()
        assert snap["flight_recorder"]["events_recorded"] > 0, snap
        assert snap["watchdog"]["enabled"], snap

    # 0. performance plane (obs/timeline, compile_watch, slo): this is
    #    a fresh process, so the aggregate query's first run was a COLD
    #    compile under an active query context — inline by definition
    tl = snap["timeline"]
    assert tl["busy_ms"] > 0, tl
    total_share = tl["util_pct"] + sum(tl["gaps"].values())
    assert abs(total_share - 100.0) < 0.1, (total_share, tl)
    comp = snap["compile"]
    assert comp["top"], comp
    assert all(r["dur_ms"] > 0 for r in comp["top"]), comp["top"]
    assert any(r["inline"] for r in comp["top"]), comp["top"]
    assert comp["inline_compile_ms"] > 0, comp
    slo = snap["slo"]
    t_default = slo["tenants"]["default"]
    assert t_default["count"] == 3, t_default
    assert t_default["p99_ms"] >= t_default["p50_ms"] > 0, t_default
    # the victim query's event-log record carries the same compile cost
    from spark_rapids_tpu.tools.events import read_event_log as _rel
    completed = [r for r in _rel(log_path, events="completed")]
    assert completed and all("queue_wait_ms" in r and "execute_ms" in r
                             for r in completed), completed
    assert any(r.get("inline_compile_ms", 0) > 0
               for r in completed), completed
    print(f"perf plane OK: busy_ms={tl['busy_ms']}, "
          f"util={tl['util_pct']}%, compiles={comp['compiles']}, "
          f"default p99={t_default['p99_ms']}ms")

    # 1. trace JSON parses and has the span hierarchy
    doc = json.load(open(trace_path))
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert events, "no spans recorded"
    cats = {e["cat"] for e in events}
    assert {"engine", "exec"} <= cats, cats
    names = {e["name"] for e in events}
    assert "srt.query" in names and "srt.attempt" in names, names
    qids = {e["args"].get("query_id") for e in events
            if e["name"] == "srt.attempt"}
    # 3 healthy + the shuffle aggregate + the forced failure
    assert len(qids) == 5, qids
    # the TCP fetch's client/server halves join on span_id
    fetch_ids = {e["args"].get("span_id") for e in events
                 if e["name"] == "shuffle_fetch"}
    serve_ids = {e["args"].get("span_id") for e in events
                 if e["name"].startswith("shuffle_serve")}
    assert fetch_ids and fetch_ids & serve_ids, (fetch_ids, serve_ids)
    assert any(e["name"] == "exchange_map_side" for e in events), names
    print(f"trace OK: {len(events)} spans, cats={sorted(cats)}, "
          f"joined fetch spans={len(fetch_ids & serve_ids)}")

    # 2. Prometheus exposition covers arena + semaphore + queue series
    for series in ("tpu_arena_device_bytes", "tpu_arena_device_peak_bytes",
                   "tpu_semaphore_wait_seconds_bucket",
                   "tpu_service_queue_wait_seconds_count",
                   "tpu_compile_cache_requests_total",
                   "tpu_compile_seconds_bucket",
                   "tpu_device_busy_seconds_total",
                   "tpu_device_util_pct",
                   "tpu_device_idle_pct",
                   "tpu_slo_latency_seconds_bucket",
                   "tpu_shuffle_host_drop_seconds_total",
                   "tpu_shuffle_fetch_seconds_bucket",
                   "tpu_shuffle_conn_events_total",
                   "tpu_shuffle_edges_tracked",
                   "tpu_shuffle_pending_fetches",
                   'tpu_mem_live_bytes{site="exchange"}',
                   "tpu_mem_headroom_bytes",
                   "tpu_mem_pinned_bytes",
                   "tpu_mem_spillable_bytes",
                   "tpu_mem_leaked_entries_total",
                   "tpu_cost_records",
                   "tpu_cost_padding_waste_pct",
                   "tpu_cost_captures_total",
                   'tpu_service_queries_total{event="completed"}'):
        assert series in metrics, f"missing series {series}"
    print("prometheus OK:", len(metrics.splitlines()), "lines")

    # 2b. shuffle transport plane (obs/netplane.py): the edge matrix
    #     saw the exchange, the four-phase host-drop split sums to the
    #     exchange wall, and the TCP fetch left pool + peer evidence
    net = snap["shuffle"]
    assert net["enabled"], net
    assert net["edges_tracked"] > 0 and net["top_edges"], net
    ph = net["host_drop"]["phases_ms"]
    wall = net["host_drop"]["exchange_wall_ms"]
    assert wall > 0, net["host_drop"]
    assert abs(sum(ph.values()) - wall) <= max(wall * 0.01, 0.02), \
        (ph, wall)
    assert net["wire_bytes"] > 0 and ph["wire"] > 0, net
    assert net["connections"]["dial"] >= 1, net["connections"]
    assert net["fetch_peers"].get("exec-a", {}).get("count", 0) >= 1, \
        net["fetch_peers"]
    assert net["pending_fetches"] == 0, net
    # the shuffle query's event-log records carry the same roll-up:
    # the engine record the full netplane dict, the service's
    # completed-outcome record the host_drop_tax_ms scalar
    engine = [r for r in _rel(log_path)
              if r.get("query_id") == h_shuf.query_id]
    assert engine, h_shuf.query_id
    sn = engine[0]["shuffle_netplane"]
    assert sn["edges"] > 0 and sn["blocks"] > 0, sn
    assert engine[0]["host_drop_tax_ms"] == sn["host_drop_tax_ms"] > 0
    assert abs(sum(sn["phases_ms"].values()) - sn["exchange_wall_ms"]) \
        <= max(sn["exchange_wall_ms"] * 0.01, 0.02), sn
    shuf_rec = [r for r in completed if r["query_id"] == h_shuf.query_id]
    assert shuf_rec and shuf_rec[0]["host_drop_tax_ms"] > 0, shuf_rec
    print(f"shuffle plane OK: edges={net['edges_tracked']}, "
          f"host_drop_tax_ms={net['host_drop']['host_drop_tax_ms']}, "
          f"wire_bytes={net['wire_bytes']}")

    # 2c. memory plane (obs/memplane.py): the service snapshot carries
    #     the memory section, the engine record the full per-query
    #     roll-up (registrations attributed by site with zero leaks),
    #     and every admission logged a headroom forecast
    mem = snap["memory"]
    assert mem["enabled"], mem
    assert mem["spill_skipped"] >= 0 and "headroom" in mem, mem
    assert mem["headroom"]["device_limit"] > 0, mem["headroom"]
    em = engine[0]["memplane"]
    assert em["registered"]["count"] > 0, em
    assert any(r["site"] == "exchange"
               for r in em["registered"]["by_site"]), em["registered"]
    assert em["peak_device_bytes"] > 0 and em["peak_advanced"], em
    assert sum(em["peak_by_site"].values()) == em["peak_device_bytes"]
    assert engine[0]["peak_device_bytes"] == em["peak_device_bytes"]
    assert em["leaked_entries"] == 0, em
    assert all("spill_ms" in r and "unspill_count" in r
               for r in completed), completed
    admitted = [r for r in _rel(log_path, events="admitted")]
    assert admitted and all(
        "headroom_bytes" in r and "forecast_fits" in r
        for r in admitted), admitted
    # forced tier moves on a deliberately tiny budget: the priced
    # ledger must balance against the catalog's own spill counters
    from spark_rapids_tpu.columnar.batch import ColumnarBatch as _CB
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.memory.spillable import SpillableBatch
    from spark_rapids_tpu.obs import memplane as _memplane
    from spark_rapids_tpu.service.cancellation import (CancelToken,
                                                       query_context)
    cat = BufferCatalog.reset(spill_dir=os.path.join(td, "spill"),
                              device_limit=16 * 1024)
    with query_context(CancelToken("mem-smoke", None)):
        handles = [SpillableBatch(_CB.from_pydict(
            {"a": list(range(512))}), op="SmokeOp", site="operator")
            for _ in range(3)]
    view = _memplane.owners()
    assert view["device_bytes"] == cat.device_bytes > 0, view
    assert sum(r["bytes"] for r in view["owners"]) == cat.device_bytes
    assert all(r["query_id"] == "mem-smoke" for r in view["owners"])
    cat.spill_device_to_fit(cat.device_limit, reason="pressure")
    rows = _memplane.ledger()
    assert rows, "forced budget produced no ledger records"
    d2h = sum(r["nbytes"] for r in rows
              if r["direction"] == "device_to_host")
    assert d2h == cat.spilled_device_to_host > 0, (d2h, rows)
    # the histogram family only emits buckets once a spill is priced —
    # so this series is asserted here, after the forced tier moves
    from spark_rapids_tpu.obs.prom import render_text
    from spark_rapids_tpu.obs.registry import get_registry
    assert "tpu_mem_spill_seconds_bucket" in render_text(get_registry())
    for h in handles:
        h.close()
    assert _memplane.leak_check("mem-smoke") == []
    BufferCatalog.reset()          # restore default budgets
    print(f"memory plane OK: peak={em['peak_device_bytes']}B, "
          f"admissions forecast={len(admitted)}, "
          f"ledger d2h={d2h}B")

    # 2e. device-compute cost plane (obs/costplane.py): the warm query
    #     joins static XLA costs with the dispatch ledger, the roofline
    #     split partitions the busy share, a non-power-of-two batch
    #     (1300 rows on a power-of-two bucket lattice) prices padding
    #     waste, the doctor sub-verdict sums exactly, and the plane
    #     adds ZERO device flushes against a cost-off run
    from spark_rapids_tpu.columnar import pending as _pending

    def _cost_query(sess):
        cdf = sess.range(0, 1300, 1, 2)
        cdf = cdf.with_column("k", cdf["id"] % 13)
        return cdf.group_by("k").agg(F.sum("id").alias("sv"))

    cs = TpuSession(TpuConf({}))
    cq = _cost_query(cs)
    cq.collect()                      # warm: programs compiled + costed
    f0 = _pending.FLUSH_COUNT
    cq.collect()
    on_flushes = _pending.FLUSH_COUNT - f0
    cost = cs.last_query_costplane
    assert cost and cost["costed_records"] > 0, cost
    assert cost["programs"], cost
    share_sum = cost["compute_share_pct"] + cost["memory_share_pct"]
    assert abs(share_sum - 100.0) < 1e-6, cost
    assert (cost["padding_waste_pct"] or 0) > 0, cost
    diag = cs.last_query_diagnosis
    sub = diag.data.get("device_compute_breakdown")
    assert sub is not None, diag.data
    assert abs(sum(sub.values()) -
               diag.data["shares"]["device_compute"]) < 1e-9, \
        (sub, diag.data["shares"])
    offs = TpuSession(TpuConf(
        {"spark.rapids.tpu.obs.cost.enabled": False}))
    oq = _cost_query(offs)
    oq.collect()
    f0 = _pending.FLUSH_COUNT
    oq.collect()
    off_flushes = _pending.FLUSH_COUNT - f0
    assert on_flushes == off_flushes, (on_flushes, off_flushes)
    assert offs.last_query_costplane is None
    print(f"cost plane OK: records={cost['costed_records']}, "
          f"verdict={cost['verdict']}, "
          f"padding_waste={cost['padding_waste_pct']}%, "
          f"flushes on/off={on_flushes}/{off_flushes}")

    # 3. report tool renders the joined story
    from spark_rapids_tpu.tools.report import main as report_main
    assert report_main([log_path, "--trace", trace_path, "--shuffle",
                        "--memory", "--cost",
                        "--html", os.path.join(td, "report.html")]) == 0
    html = open(os.path.join(td, "report.html")).read()
    assert "plan + time shares" in html
    assert "shuffle transport (netplane)" in html
    assert "top edges (map" in html      # "->" is HTML-escaped
    assert "HBM memory (memplane)" in html
    assert "peak_device_bytes=" in html
    assert "device-compute cost (roofline)" in html
    print("report OK")

    # 4. the forced failure produced one diagnostic bundle with the
    #    flight tail + thread stacks + arena map, linked from the event
    #    log, and diagnose renders it
    from spark_rapids_tpu.tools.diagnose import main as diagnose_main
    from spark_rapids_tpu.tools.events import read_event_log
    bundles = sorted(os.path.join(diag_dir, n)
                     for n in os.listdir(diag_dir)
                     if n.startswith("diag-") and n.endswith(".json"))
    assert len(bundles) == 1, bundles
    bundle = json.load(open(bundles[0]))
    assert bundle["trigger"] == "oom", bundle["trigger"]
    assert bundle["flight"]["query_events"], "empty flight tail"
    assert bundle["threads"], "no thread stacks"
    assert "stats" in bundle["arena"], bundle["arena"]
    failed = [r for r in read_event_log(log_path, events="failed")
              if r["query_id"] == h_fail.query_id]
    assert failed and failed[0]["diag_bundle"] == bundles[0], failed
    assert diagnose_main([bundles[0], "--no-stacks"]) == 0
    print("diagnostics OK:", os.path.basename(bundles[0]))

    # 5. fleet plane (obs/fingerprint, history, anomaly, dashboard): a
    #    repeated query mix on two tenants writes one history row per
    #    terminal query, a sleep-shimmed slowdown injected into ONE
    #    plan's UDF drifts exactly that fingerprint — the sentinel
    #    breaches it (and no other) into the event log, Prometheus,
    #    the doctor trend and the dashboard — and the offline
    #    tools/history.py CLI reads the same story back from disk
    import time as _time_mod
    import urllib.request
    from spark_rapids_tpu.obs import anomaly as _anomaly
    from spark_rapids_tpu.obs import history as _histplane
    hist_dir = os.path.join(td, "history")
    fleet_log = os.path.join(td, "fleet_events.jsonl")
    fleet_diag = os.path.join(td, "fleet_diag")
    _histplane.reset()
    _anomaly.reset()
    fs = TpuSession(TpuConf({
        "spark.rapids.tpu.obs.history.dir": hist_dir,
        "spark.rapids.tpu.eventLog.path": fleet_log,
        "spark.rapids.tpu.obs.diagnostics.dir": fleet_diag,
        "spark.rapids.tpu.obs.anomaly.warmupMinRuns": 5,
        "spark.rapids.tpu.obs.anomaly.breachRuns": 3,
        "spark.rapids.tpu.obs.anomaly.sigma": 2.0,
    }))
    fast_df = fs.range(0, 256, num_partitions=2) \
        .select((F.col("id") % 5).alias("k")) \
        .group_by("k").agg(F.count("k").alias("c"))
    shim = {"sleep_s": 0.05}

    def _shimmed(series):
        _time_mod.sleep(shim["sleep_s"])
        return series
    shim_udf = pandas_udf(_shimmed, return_type=T.INT64)
    shim_df = fs.range(0, 32, num_partitions=1) \
        .select(shim_udf(F.col("id")).alias("id"))
    fast_df.collect()        # warm the compiles OUTSIDE the service:
    shim_df.collect()        # cold-compile wall must not skew the
    _histplane.reset()       # sentinel's warm-up baseline
    _anomaly.reset()
    with QueryService(fs, num_workers=1) as fsvc:
        fp_fast = fp_shim = None
        for i in range(6):            # warm-up: both plans healthy
            fsvc.submit(fast_df,
                        tenant="red" if i % 2 else "blue").result(120)
            fp_fast = fs.last_query_fingerprint
            fsvc.submit(shim_df, tenant="red").result(120)
            fp_shim = fs.last_query_fingerprint
        shim["sleep_s"] = 0.5         # the injected regression
        for _ in range(4):
            fsvc.submit(fast_df, tenant="blue").result(120)
            fsvc.submit(shim_df, tenant="red").result(120)
        fleet_snap = fsvc.stats().snapshot()
        fleet_metrics = fsvc.metrics_text()
        port = fsvc.start_metrics_server()
        dash = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/dashboard", timeout=10) \
            .read().decode()
    assert fp_fast and fp_shim and fp_fast != fp_shim
    # one history row per terminal query, none dropped
    h = fleet_snap["history"]
    assert h["rows"] == 20, h
    assert h["dropped"] == 0 and h["segments"] >= 1, h
    aggs = _histplane.fleet_aggregates()
    assert aggs[fp_fast]["count"] == 10 and aggs[fp_shim]["count"] == 10
    assert set(aggs[fp_fast]["tenants"]) == {"red", "blue"}, aggs
    # the sentinel breached exactly the shimmed fingerprint
    assert fleet_snap["anomaly"]["active"] >= 1, fleet_snap["anomaly"]
    anomalies = _rel(fleet_log, events="anomaly")
    assert anomalies, "no anomaly events logged"
    breached = {r["fingerprint"] for r in anomalies
                if r["anomaly_kind"] == "breach"}
    assert breached == {fp_shim}, (breached, fp_shim, fp_fast)
    breach = [r for r in anomalies if r["anomaly_kind"] == "breach"][0]
    assert breach["key"] == "exec_ms" and breach["drift_pct"] > 100
    assert breach["diag_bundle"] and os.path.exists(
        breach["diag_bundle"]), breach
    assert 'tpu_anomaly_events_total{kind="breach"}' in fleet_metrics
    assert "tpu_anomaly_active" in fleet_metrics
    assert "tpu_history_rows_total" in fleet_metrics
    # the doctor trend section carries the drift for that fingerprint
    trend = fleet_snap["doctor"]["trend"]
    assert "exec_ms" in trend[fp_shim]["active"], trend[fp_shim]
    drift = trend[fp_shim]["drift"]["exec_ms"]
    assert drift["last"] > 2 * drift["baseline"], drift
    assert not trend.get(fp_fast, {}).get("active"), trend
    # the dashboard served beside /metrics shows the breach
    assert fp_shim in dash and "Active anomalies" in dash
    # the offline CLI reads the same story back from the segments
    from spark_rapids_tpu.tools.history import main as history_main
    assert history_main(["summary", hist_dir]) == 0
    assert history_main(["trend", hist_dir, "--fingerprint", fp_shim,
                         "--key", "exec_ms"]) == 0
    assert history_main(["compare", hist_dir, "--fingerprint",
                         fp_shim]) == 0
    from spark_rapids_tpu.tools.history import (compare_windows,
                                                load_rows)
    disk_rows = load_rows(hist_dir)
    assert len(disk_rows) == 20, len(disk_rows)
    delta = compare_windows(load_rows(hist_dir, fingerprint=fp_shim),
                            keys=("exec_ms",))
    assert delta["keys"]["exec_ms"]["delta_pct"] > 100, delta
    # zero extra device flushes: history+anomaly on vs off, same query
    def _fleet_flush_delta(conf):
        zs = TpuSession(conf)
        zq = zs.range(0, 64, num_partitions=2) \
            .select((F.col("id") % 7).alias("k")) \
            .group_by("k").agg(F.count("k").alias("c"))
        zq.collect()
        f0 = _pending.FLUSH_COUNT
        zq.collect()
        return _pending.FLUSH_COUNT - f0
    on_f = _fleet_flush_delta(TpuConf({}))
    off_f = _fleet_flush_delta(TpuConf({
        "spark.rapids.tpu.obs.history.enabled": False,
        "spark.rapids.tpu.obs.anomaly.enabled": False}))
    assert on_f == off_f, (on_f, off_f)
    print(f"fleet plane OK: rows={h['rows']}, "
          f"breached={sorted(breached)}, "
          f"drift={breach['drift_pct']}%, "
          f"flushes on/off={on_f}/{off_f}")
    # (9) observability tax diet (obs/overhead.py): planes-on vs
    # planes-off on the same query — identical results, identical warm
    # flush delta, per-plane self-cost attribution that sums to its
    # own total and stays within a loose bound of the measured wall
    # delta (CI hosts are too noisy to pin the 2% budget — bench.py's
    # all_planes_on_vs_off key and the perf gate own the exact bound)
    from spark_rapids_tpu.obs import overhead as _overhead
    all_planes_off = {
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
    }

    def _diet_run(conf):
        ds = TpuSession(conf)
        dq = ds.range(0, 2048, num_partitions=2) \
            .select((F.col("id") % 11).alias("k"),
                    F.col("id").alias("v")) \
            .group_by("k").agg(F.sum("v").alias("sv")).sort("k")
        dq.to_arrow()                           # warm
        f0 = _pending.FLUSH_COUNT
        t0 = time.perf_counter()
        tbl = dq.to_arrow()
        wall_s = time.perf_counter() - t0
        return tbl, _pending.FLUSH_COUNT - f0, wall_s, \
            ds.last_query_event
    _overhead.configure(TpuConf({}))
    _overhead.reset()
    ns0 = _overhead.snapshot()
    on_tbl, diet_on_f, on_wall, on_rec = _diet_run(TpuConf({}))
    self_ms = _overhead.delta_ms(ns0)
    off_tbl, diet_off_f, off_wall, off_rec = _diet_run(
        TpuConf(all_planes_off))
    assert on_tbl.equals(off_tbl), "planes-on/off results diverged"
    assert diet_on_f == diet_off_f, (diet_on_f, diet_off_f)
    obs_self = (on_rec or {}).get("obs_self")
    assert obs_self and set(obs_self["planes"]) == \
        set(_overhead.PLANES), obs_self
    assert abs(obs_self["total_ms"]
               - sum(obs_self["planes"].values())) < 0.01, obs_self
    assert "obs_self" not in (off_rec or {})     # meter off: no block
    total_self_ms = sum(self_ms.values())
    delta_ms = max(on_wall - off_wall, 0.0) * 1e3
    # loose tolerance: the attributed shares explain the measured
    # on-vs-off delta to within CI noise (they can never dwarf it)
    assert total_self_ms <= delta_ms + 50.0, (total_self_ms, delta_ms)
    _overhead.configure(TpuConf({}))             # restore default-on
    print(f"obs tax diet OK: flushes on/off={diet_on_f}/{diet_off_f}, "
          f"self={total_self_ms:.3f}ms vs delta={delta_ms:.3f}ms, "
          f"planes={ {k: v for k, v in self_ms.items() if v} }")
    print("obs smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
