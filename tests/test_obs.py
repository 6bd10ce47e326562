"""Observability tests: span tracer, metrics registry, Prometheus
exposition, and the query report generator."""
import json
import threading
import urllib.request

import pytest

from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import trace, registry
from spark_rapids_tpu.obs.prom import render_text, serve_scrapes
from spark_rapids_tpu.obs.registry import MetricsRegistry, get_registry

from data_gen import IntGen, KeyGen, gen_df


@pytest.fixture(autouse=True)
def _trace_off_after():
    # before too: under xdist the test that ran before on this worker
    # may be another file's and have left coarse spans in the ring
    trace.reset()
    yield
    trace.disable()
    trace.reset()


class TestSpanTracer:
    def test_disabled_span_is_shared_noop(self):
        assert trace.span("a") is trace.span("b", "kernel", x=1)
        assert trace.get_tracer().num_spans() == 0

    def test_coarse_span_records_with_tracing_disabled(self):
        # what the kernel-entry decorator used to cover: a region that
        # is instrumented whether or not fine tracing is on.  A coarse
        # span lands in the ring and leaves the fine buffer alone.
        def f(x):
            with trace.span("srt.exec.f", "exec", coarse=True, rows=x):
                with trace.span("fine_inner", "kernel"):
                    return x + 1
        assert f(1) == 2
        assert trace.get_tracer().num_spans() == 0
        (only,) = trace.coarse_spans()
        assert only["name"] == "srt.exec.f" and only["parent"] == 0
        assert only["args"] == {"rows": 1} and only["dur_ns"] >= 0

    def test_spans_record_and_nest(self):
        trace.enable()
        with trace.span("outer", "engine"):
            with trace.span("inner", "kernel", k="v"):
                pass
        tr = trace.get_tracer()
        assert tr.num_spans() == 2
        doc = tr.to_chrome_trace()
        by_name = {e["name"]: e for e in doc["traceEvents"]
                   if e.get("ph") == "X"}
        assert by_name["inner"]["args"]["depth"] == \
            by_name["outer"]["args"]["depth"] + 1
        assert by_name["inner"]["args"]["parent"] == \
            by_name["outer"]["args"]["id"]
        assert trace.coarse_spans() == []       # fine spans stay fine
        assert by_name["inner"]["args"]["k"] == "v"
        # inner fully contained in outer on the timeline
        o, i = by_name["outer"], by_name["inner"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3

    def test_span_records_error_type(self):
        trace.enable()
        with pytest.raises(ValueError):
            with trace.span("boom"):
                raise ValueError("x")
        doc = trace.get_tracer().to_chrome_trace()
        ev = [e for e in doc["traceEvents"] if e.get("name") == "boom"][0]
        assert ev["args"]["error"] == "ValueError"

    def test_query_id_attribution(self):
        from spark_rapids_tpu.service.cancellation import (CancelToken,
                                                           query_context)
        trace.enable()
        with query_context(CancelToken("q42")):
            with trace.span("work"):
                pass
        doc = trace.get_tracer().to_chrome_trace()
        ev = [e for e in doc["traceEvents"] if e.get("name") == "work"][0]
        assert ev["args"]["query_id"] == "q42"

    def test_emit_retroactive(self):
        import time
        trace.enable()
        t0 = time.perf_counter_ns()
        trace.emit("waited", "memory", t0, 5_000_000, note="x")
        doc = trace.get_tracer().to_chrome_trace()
        ev = [e for e in doc["traceEvents"] if e.get("name") == "waited"][0]
        assert ev["dur"] == pytest.approx(5000.0)  # µs

    def test_bounded_buffer_counts_drops(self):
        trace.enable(max_spans=3)
        for i in range(5):
            with trace.span(f"s{i}"):
                pass
        tr = trace.get_tracer()
        assert tr.num_spans() == 3
        assert tr.dropped == 2
        assert tr.to_chrome_trace()["otherData"]["dropped_spans"] == 2

    def test_write_and_reload_chrome_json(self, tmp_path):
        trace.enable()
        with trace.span("x"):
            pass
        path = str(tmp_path / "t.json")
        out = trace.flush(path)
        assert out == path
        doc = json.load(open(path))
        assert doc["displayTimeUnit"] == "ms"
        phs = {e["ph"] for e in doc["traceEvents"]}
        assert phs <= {"X", "M"}
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                assert {"name", "cat", "ts", "dur", "pid",
                        "tid"} <= set(e)

    def test_flush_without_path_is_noop(self):
        trace.enable()
        assert trace.flush() is None

    def test_session_conf_end_to_end(self, tmp_path):
        path = str(tmp_path / "trace.json")
        s = TpuSession(TpuConf({
            "spark.rapids.tpu.obs.trace.enabled": True,
            "spark.rapids.tpu.obs.trace.path": path,
        }))
        df = gen_df(s, {"k": KeyGen(), "v": IntGen()}, 200)
        df.group_by("k").agg(F.sum("v").alias("s")).collect()
        s.close()           # the span file is written at close
        doc = json.load(open(path))
        names = {e["name"] for e in doc["traceEvents"]}
        assert "srt.query" in names
        cats = {e.get("cat") for e in doc["traceEvents"]
                if e.get("ph") == "X"}
        # engine (query) + exec (operators) at minimum; kernels when the
        # plan dispatches them
        assert {"engine", "exec"} <= cats


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", "help")
        c.inc()
        c.inc(2)
        g = reg.gauge("g", "help")
        g.set(5)
        g.dec(1.5)
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(10.0)
        snap = reg.snapshot()
        assert snap["c_total"] == 3
        assert snap["g"] == 3.5
        hs = snap["h_seconds"]
        assert hs["count"] == 3
        assert hs["buckets"][0.1] == 1
        assert hs["buckets"][1.0] == 2          # cumulative
        assert hs["buckets"]["+Inf"] == 3

    def test_labels_and_deterministic_snapshot(self):
        reg = MetricsRegistry()
        c = reg.counter("ops_total", labels=("kind",))
        c.labels(kind="b").inc(2)
        c.labels(kind="a").inc(1)
        snap = reg.snapshot()
        assert list(snap["ops_total"]) == ["kind=a", "kind=b"]
        # get-or-create returns the same family
        assert reg.counter("ops_total", labels=("kind",)) is c

    def test_gauge_callback(self):
        reg = MetricsRegistry()
        state = {"v": 7}
        reg.gauge("cb", fn=lambda: state["v"])
        assert reg.snapshot()["cb"] == 7
        state["v"] = 9
        assert reg.snapshot()["cb"] == 9

    def test_default_instruments_registered(self):
        snap = get_registry().snapshot()
        for name in ("tpu_arena_device_bytes", "tpu_arena_device_peak_bytes",
                     "tpu_semaphore_wait_seconds",
                     "tpu_service_queue_wait_seconds",
                     "tpu_compile_cache_requests_total",
                     "tpu_shuffle_bytes_total"):
            assert name in snap, name

    def test_arena_peak_gauge_tracks_catalog(self):
        from spark_rapids_tpu.memory.catalog import BufferCatalog
        cat = BufferCatalog.get()
        base = cat.device_peak_bytes
        bid = cat.register(object(), 1234)
        try:
            assert registry.ARENA_DEVICE_PEAK_BYTES.value >= base + 1234
            assert cat.stats()["device_peak_bytes"] == cat.device_peak_bytes
        finally:
            cat.unregister(bid)


class TestPromExposition:
    def test_render_text_format(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "a help").inc(2)
        reg.gauge("b", 'hel"p\nnl').set(1.5)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0),
                          labels=("op",))
        h.labels(op="x").observe(0.5)
        txt = render_text(reg)
        lines = txt.splitlines()
        assert "# TYPE a_total counter" in lines
        assert "a_total 2" in lines
        assert "# TYPE lat_seconds histogram" in lines
        assert 'lat_seconds_bucket{op="x",le="0.1"} 0' in lines
        assert 'lat_seconds_bucket{op="x",le="1"} 1' in lines
        assert 'lat_seconds_bucket{op="x",le="+Inf"} 1' in lines
        assert 'lat_seconds_count{op="x"} 1' in lines
        # +Inf bucket must equal _count (prometheus invariant)
        assert txt.endswith("\n")

    def test_scrape_endpoint(self):
        server, port = serve_scrapes(port=0)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5).read()
            assert b"tpu_arena_device_bytes" in body
        finally:
            server.shutdown()

    def test_service_metrics_text_covers_series(self, tmp_path):
        from spark_rapids_tpu.service.server import QueryService
        s = TpuSession(TpuConf({}))
        df = gen_df(s, {"k": KeyGen(), "v": IntGen()}, 200)
        s.register_table("obs_t", df)
        with QueryService(s, num_workers=1) as svc:
            svc.submit("SELECT k, SUM(v) FROM obs_t GROUP BY k").result(60)
            txt = svc.metrics_text()
            stats = svc.stats().snapshot()
        for series in ("tpu_arena_device_bytes",
                       "tpu_semaphore_wait_seconds",
                       "tpu_service_queue_wait_seconds",
                       "tpu_compile_cache_requests_total",
                       "tpu_service_queries_total"):
            assert series in txt, series
        assert 'tpu_service_queries_total{event="completed"}' in txt
        assert stats["completed"] >= 1
        # queue-wait histogram observed the query
        hist = get_registry().snapshot()["tpu_service_queue_wait_seconds"]
        assert hist["count"] >= 1


class TestMetricSetDeterminism:
    def test_snapshot_sorted_and_level_filtered(self):
        from spark_rapids_tpu.exec.base import (MetricSet, ESSENTIAL,
                                                DEBUG, MODERATE)
        ms = MetricSet()
        ms.get("zeta", ESSENTIAL).add(1)
        ms.get("alpha", ESSENTIAL).add(2)
        ms.get("mid", MODERATE).add(3)
        assert list(ms.snapshot(DEBUG)) == ["alpha", "mid", "zeta"]
        assert list(ms.snapshot(ESSENTIAL)) == ["alpha", "zeta"]

    def test_essential_snapshot_skips_deferred_device_reads(self):
        from spark_rapids_tpu.exec.base import (MetricSet, ESSENTIAL,
                                                MODERATE)

        class Exploding:
            def __int__(self):
                raise AssertionError("deferred value was forced")

        ms = MetricSet()
        ms.get("wall", ESSENTIAL).add(5)
        ms.get("deviceRows", MODERATE).add(Exploding())
        # ESSENTIAL snapshot must not resolve the MODERATE metric's
        # pending device value (no device sync)
        snap = ms.snapshot(ESSENTIAL)
        assert snap == {"wall": 5}


class TestTimedSpans:
    def test_timed_emits_exec_span_with_node_name(self):
        from spark_rapids_tpu.exec.base import Metric, timed

        class FakeNode:
            name = "TpuFakeOp"

        trace.enable()
        with timed(Metric("opTime"), FakeNode()):
            pass
        doc = trace.get_tracer().to_chrome_trace()
        evs = [e for e in doc["traceEvents"]
               if e.get("name") == "srt.exec.TpuFakeOp"]
        assert evs and evs[0]["cat"] == "exec"
        assert evs[0]["args"]["metric"] == "opTime"

    def test_timed_without_tracing_writes_the_ring_only(self):
        from spark_rapids_tpu.exec.base import Metric, timed
        m = Metric("opTime")
        with timed(m):
            pass
        assert m.value > 0
        assert trace.get_tracer().num_spans() == 0
        (only,) = trace.coarse_spans()
        assert only["name"] == "srt.exec.opTime"
        assert only["dur_ns"] == m.value


class TestReportTool:
    def _make_log(self, tmp_path):
        log = str(tmp_path / "events.jsonl")
        s = TpuSession(TpuConf({"spark.rapids.tpu.eventLog.path": log}))
        df = gen_df(s, {"k": KeyGen(), "v": IntGen()}, 300)
        df.group_by("k").agg(F.sum("v").alias("s")).collect()
        return log

    def test_report_text(self, tmp_path, capsys):
        from spark_rapids_tpu.tools.report import main
        log = self._make_log(tmp_path)
        assert main([log]) == 0
        out = capsys.readouterr().out
        assert "plan + time shares" in out
        assert "TpuHashAggregate" in out
        assert "%" in out

    def test_report_html(self, tmp_path):
        from spark_rapids_tpu.tools.report import main
        log = self._make_log(tmp_path)
        html_path = str(tmp_path / "report.html")
        assert main([log, "--html", html_path]) == 0
        html = open(html_path).read()
        assert html.startswith("<!DOCTYPE html>")
        assert "TpuHashAggregate" in html

    def test_report_joins_trace(self, tmp_path, capsys):
        from spark_rapids_tpu.tools.report import main
        tp = str(tmp_path / "trace.json")
        log = str(tmp_path / "events.jsonl")
        s = TpuSession(TpuConf({
            "spark.rapids.tpu.eventLog.path": log,
            "spark.rapids.tpu.obs.trace.enabled": True,
            "spark.rapids.tpu.obs.trace.path": tp,
        }))
        df = gen_df(s, {"k": KeyGen(), "v": IntGen()}, 300)
        df.group_by("k").agg(F.sum("v").alias("s")).collect()
        s.close()
        assert main([log, "--trace", tp]) == 0
        out = capsys.readouterr().out
        assert "critical-path spans" in out
        assert "query" in out

    def test_plan_time_shares_sum_to_one(self, tmp_path):
        from spark_rapids_tpu.tools.report import plan_time_shares
        from spark_rapids_tpu.tools.events import read_event_log
        log = self._make_log(tmp_path)
        rec = read_event_log(log)[0]
        rows = plan_time_shares(rec)
        assert rows
        assert sum(r["share"] for r in rows) == pytest.approx(1.0)
