"""Device-compute cost plane tests (obs/costplane.py): static XLA
cost capture at every compile origin, the dispatch-ledger join into
per-program achieved rates and roofline verdicts, padding-waste
arithmetic, the doctor's exact device_compute sub-split, digest
stability across pipeline parallelism {1,4} x superstage on/off, the
REQUIRED_PROGRAMS coverage gate (mirroring the jaxpr auditor), the
measured-vs-static profile intensity cross-check, and the
zero-extra-flush + disabled-plane + lint-scope acceptance contracts.
"""
import json
import os

import jax
import numpy as np
import pytest

from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.columnar import pending
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import costplane, doctor

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "lint_fixtures")


@pytest.fixture(scope="module", autouse=True)
def _seed_store():
    # capture runs ONCE per (program, bucket) for the life of the
    # process, but the engine's JIT caches stay warm across tests —
    # so seed the process-lifetime store while this module still owns
    # cold caches (a later reset() could never get the records back)
    costplane.configure(TpuConf({}))
    s = TpuSession(TpuConf({}))
    _agg_join_df(s).collect()
    yield


@pytest.fixture(autouse=True)
def _cost_guard():
    # snapshot/restore instead of reset(): unit tests may freely
    # reset or fill the bounded store without starving the e2e tests
    # that rely on the seeded process-lifetime records
    costplane.configure(TpuConf({}))
    with costplane._LOCK:
        saved = (dict(costplane._COSTS),
                 {k: list(v) for k, v in costplane._DISPATCH.items()},
                 dict(costplane._CAPTURES),
                 costplane._DROPPED, costplane._DISPATCH_DROPPED,
                 dict(costplane._LAST))
    yield
    costplane.configure(TpuConf({}))
    with costplane._LOCK:
        costplane._COSTS.clear()
        costplane._COSTS.update(saved[0])
        costplane._DISPATCH.clear()
        costplane._DISPATCH.update(saved[1])
        costplane._CAPTURES.clear()
        costplane._CAPTURES.update(saved[2])
        costplane._DROPPED = saved[3]
        costplane._DISPATCH_DROPPED = saved[4]
        costplane._LAST.clear()
        costplane._LAST.update(saved[5])


def _agg_join_df(sess, n=50_000, groups=31):
    df = sess.range(0, n, 1, 4)
    df = df.with_column("k", df["id"] % groups)
    dim = sess.range(0, groups, 1, 1).with_column("v", F.col("id") * 2)
    j = df.join(dim.with_column_renamed("id", "k2"),
                df["k"] == F.col("k2"), "inner")
    return j.group_by("k").agg(F.sum("v").alias("sv"))


def _jit_add():
    return jax.jit(lambda x: x + 1)


def _args(n=1024):
    return (np.zeros((n,), dtype=np.int64),), {}


# ---------------------------------------------------------------------------
# 1. static-cost capture
# ---------------------------------------------------------------------------

class TestCapture:
    def test_capture_stores_xla_record_at_bucket(self):
        costplane.reset()
        args, kwargs = _args(1024)
        assert costplane.capture("prog_a", _jit_add(), args, kwargs)
        costs = costplane.static_costs()
        rec = costs[("prog_a", 1024)]
        assert rec["source"] == costplane.SOURCE_XLA
        assert rec["flops"] > 0 and rec["bytes"] > 0
        assert rec["io_bytes"] > 0
        assert rec["origin"] == costplane.ORIGIN_MISS

    def test_capture_records_origin(self):
        costplane.reset()
        args, kwargs = _args(64)
        assert costplane.capture("prog_w", _jit_add(), args, kwargs,
                                 origin=costplane.ORIGIN_WARMUP)
        rec = costplane.static_costs()[("prog_w", 64)]
        assert rec["origin"] == costplane.ORIGIN_WARMUP

    def test_capture_returns_false_on_tracer_args(self):
        # the program auditor traces make_jaxpr THROUGH wrapped
        # callables: capture must defer (False), not store garbage
        costplane.reset()
        seen = []

        def probe(x):
            seen.append(costplane.capture(
                "prog_t", _jit_add(), (x,), {}))
            return x + 1
        jax.make_jaxpr(probe)(np.zeros((8,), dtype=np.int64))
        assert seen == [False]
        assert ("prog_t", 8) not in costplane.static_costs()

    def test_wrap_capture_fires_once_and_preserves_result(self):
        costplane.reset()
        fn = costplane.wrap_capture("prog_wrap", _jit_add())
        x = np.arange(16, dtype=np.int64)
        out = fn(x)
        np.testing.assert_array_equal(np.asarray(out), x + 1)
        fn(x)
        assert costplane.record_count() == 1

    def test_wrap_capture_retries_after_traced_first_call(self):
        # first call under make_jaxpr defers; the next REAL call must
        # still capture (the done flag is only set on success)
        costplane.reset()
        fn = costplane.wrap_capture("prog_retry", _jit_add())
        jax.make_jaxpr(lambda x: fn(x))(np.zeros((8,), dtype=np.int64))
        assert ("prog_retry", 8) not in costplane.static_costs()
        fn(np.zeros((8,), dtype=np.int64))
        assert ("prog_retry", 8) in costplane.static_costs()

    def test_static_fallback_upgrades_to_xla(self):
        costplane.reset()

        class _NoLower:
            pass
        assert costplane.capture("prog_up", _NoLower(), *(_args(32)))
        assert costplane.static_costs()[("prog_up", 32)]["source"] \
            == costplane.SOURCE_STATIC
        assert costplane.capture("prog_up", _jit_add(), *(_args(32)))
        assert costplane.static_costs()[("prog_up", 32)]["source"] \
            == costplane.SOURCE_XLA

    def test_store_is_bounded_and_counts_drops(self):
        costplane.reset()
        limit = costplane._MAX_RECORDS
        for i in range(limit + 5):
            costplane.capture(f"prog_{i}", _jit_add(), *(_args(16)))
        assert costplane.record_count() == limit
        assert costplane.dropped_count() == 5


# ---------------------------------------------------------------------------
# 2. dispatch join + roofline model
# ---------------------------------------------------------------------------

class TestRoofline:
    def test_ridge_is_peak_ratio(self):
        flops, byts, _source = costplane.peaks()
        assert costplane.ridge_intensity() == pytest.approx(flops / byts)

    def test_verdict_boundary_at_ridge(self):
        ridge = costplane.ridge_intensity()
        byts = float(2 ** 20)       # power of two: ridge*b/b is exact
        assert costplane.roofline_verdict(ridge * byts, byts) \
            == costplane.VERDICT_COMPUTE
        assert costplane.roofline_verdict(ridge * byts * 0.999, byts) \
            == costplane.VERDICT_MEMORY

    def test_summary_joins_costs_with_window_dispatches(self):
        costplane.reset()
        costplane.capture("prog_j", _jit_add(), *(_args(1024)))
        marker = costplane.begin_query()
        costplane.note_dispatch("prog_j", 1024, rows=512)
        costplane.note_dispatch("prog_j", 1024, rows=512)
        out = costplane.query_summary(marker, busy_ms=0.01)
        (e,) = out["programs"]
        assert e["program"] == "prog_j" and e["bucket"] == 1024
        assert e["dispatches"] == 2 and e["source"] == "xla"
        assert e["est_share_pct"] == pytest.approx(100.0)
        # published rates round to 3 decimals, hence the abs tolerance
        assert e["achieved_gflops"] == pytest.approx(
            e["flops"] * 2 / 1e-5 / 1e9, abs=1e-3)
        assert out["verdict"] == e["verdict"]
        assert out["compute_share_pct"] + out["memory_share_pct"] \
            == pytest.approx(100.0, abs=1e-9)

    def test_busy_apportioned_by_dispatch_weighted_t_est(self):
        costplane.reset()
        costplane.capture("prog_small", _jit_add(), *(_args(64)))
        costplane.capture("prog_big", _jit_add(), *(_args(65536)))
        marker = costplane.begin_query()
        costplane.note_dispatch("prog_small", 64)
        costplane.note_dispatch("prog_big", 65536)
        out = costplane.query_summary(marker, busy_ms=100.0)
        by = {e["program"]: e for e in out["programs"]}
        # the big program's t_est dominates, so it owns more busy share
        assert by["prog_big"]["est_share_pct"] > \
            by["prog_small"]["est_share_pct"]
        assert sum(e["est_share_pct"] for e in out["programs"]) \
            == pytest.approx(100.0, abs=0.01)

    def test_uncosted_dispatches_are_counted_not_invented(self):
        costplane.reset()
        marker = costplane.begin_query()
        costplane.note_dispatch("prog_mystery", 2048)
        out = costplane.query_summary(marker, busy_ms=5.0)
        assert out["uncosted_dispatches"] == 1
        (e,) = out["programs"]
        assert e["flops"] is None and e["verdict"] is None
        assert out["verdict"] is None

    def test_summary_windows_are_disjoint(self):
        costplane.reset()
        costplane.capture("prog_win", _jit_add(), *(_args(128)))
        m1 = costplane.begin_query()
        costplane.note_dispatch("prog_win", 128, rows=100)
        costplane.query_summary(m1, busy_ms=1.0)
        m2 = costplane.begin_query()
        out2 = costplane.query_summary(m2, busy_ms=1.0)
        assert out2["programs"] == []


# ---------------------------------------------------------------------------
# 3. padding-waste arithmetic
# ---------------------------------------------------------------------------

class TestPaddingWaste:
    def test_waste_is_exact_over_rows_known_dispatches(self):
        costplane.reset()
        costplane.capture("prog_p", _jit_add(), *(_args(1024)))
        marker = costplane.begin_query()
        costplane.note_dispatch("prog_p", 1024, rows=512)
        costplane.note_dispatch("prog_p", 1024, rows=256)
        out = costplane.query_summary(marker, busy_ms=4.0)
        # (512 + 256) effective rows over 2 x 1024 padded capacity
        (e,) = out["programs"]
        assert e["padding_waste_pct"] == pytest.approx(62.5)
        assert out["padding_waste_pct"] == pytest.approx(62.5)

    def test_waste_none_when_rows_unknown(self):
        costplane.reset()
        costplane.capture("prog_u", _jit_add(), *(_args(512)))
        marker = costplane.begin_query()
        costplane.note_dispatch("prog_u", 512)        # rows unknowable
        out = costplane.query_summary(marker, busy_ms=4.0)
        (e,) = out["programs"]
        assert e["padding_waste_pct"] is None
        assert out["padding_waste_pct"] is None

    def test_rows_if_resolved_never_flushes(self):
        class _Lazy:
            _val = None
            _staged = None
        class _B:
            rows_lazy = _Lazy()
        assert costplane.rows_if_resolved(_B()) is None
        class _B2:
            rows_lazy = 37
        assert costplane.rows_if_resolved(_B2()) == 37


# ---------------------------------------------------------------------------
# 4. doctor sub-verdict decomposition
# ---------------------------------------------------------------------------

class TestDoctorBreakdown:
    def _cp(self, comp, mem, waste):
        return {"costed_records": 3, "compute_share_pct": comp,
                "memory_share_pct": mem, "padding_waste_pct": waste}

    def test_breakdown_sums_exactly_to_share(self):
        for share in (25.235, 12.697, 99.999, 0.001):
            sub = doctor._device_compute_breakdown(
                share, self._cp(37.5, 62.5, 26.718))
            assert sum(sub.values()) == pytest.approx(
                round(share, 3), abs=1e-12), (share, sub)

    def test_breakdown_padding_then_roofline_split(self):
        sub = doctor._device_compute_breakdown(
            50.0, self._cp(60.0, 40.0, 20.0))
        assert sub["padding_waste"] == pytest.approx(10.0)
        assert sub["compute_bound"] == pytest.approx(24.0)
        assert sub["memory_bound"] == pytest.approx(16.0)

    def test_breakdown_absent_without_costplane(self):
        assert doctor._device_compute_breakdown(40.0, None) is None
        assert doctor._device_compute_breakdown(
            40.0, {"costed_records": 0}) is None

    def test_diagnose_attaches_breakdown_and_evidence(self):
        from spark_rapids_tpu.obs.registry import TIMELINE_GAP_CAUSES
        gaps = {c: 0.0 for c in TIMELINE_GAP_CAUSES}
        gaps["host_staging"] = 60.0
        tl = {"busy_ms": 40.0, "window_ms": 100.0, "util_pct": 40.0,
              "gaps": gaps}
        cp = dict(self._cp(0.0, 100.0, 25.0), verdict="memory_bound",
                  achieved_gflops=81.2, achieved_gbps=15.7)
        d = doctor.diagnose(tl, costplane=cp)
        sub = d.data["device_compute_breakdown"]
        assert sum(sub.values()) == pytest.approx(
            d.data["shares"]["device_compute"], abs=1e-12)
        (ev,) = [c["evidence"] for c in d.headroom
                 if c["cause"] == "device_compute"]
        assert "roofline[memory_bound" in ev
        assert "padding_waste=25.0%" in ev

    def test_diagnose_without_costplane_keeps_old_shape(self):
        from spark_rapids_tpu.obs.registry import TIMELINE_GAP_CAUSES
        gaps = {c: 0.0 for c in TIMELINE_GAP_CAUSES}
        tl = {"busy_ms": 40.0, "window_ms": 100.0, "util_pct": 100.0,
              "gaps": gaps}
        d = doctor.diagnose(tl)
        assert "device_compute_breakdown" not in d.data


# ---------------------------------------------------------------------------
# 5. coverage: every REQUIRED_PROGRAMS member costable (auditor mirror)
# ---------------------------------------------------------------------------

class TestCoverage:
    def test_every_required_program_captures_a_static_cost(self):
        from spark_rapids_tpu.analysis import program_audit as PA
        costplane.reset()
        specs = {s.name: s for s in PA.collect_specs()}
        assert set(specs) >= set(PA.REQUIRED_PROGRAMS)
        for name in sorted(PA.REQUIRED_PROGRAMS):
            fn, args, kwargs = specs[name].build()
            jfn = fn if hasattr(fn, "lower") else jax.jit(fn, **kwargs)
            assert costplane.capture(name, jfn, args, {}), name
        assert costplane.coverage_gaps() == [], costplane.coverage_gaps()
        assert set(costplane.costed_programs()) \
            >= set(PA.REQUIRED_PROGRAMS)

    def test_quartet_covers_trace_cache_names(self):
        # the end-to-end path (seeded by the module fixture): the
        # shared hash_aggregate trace cache covers all three
        # auditor-named aggregate variants
        costed = set(costplane.costed_programs())
        assert {"fused_project", "hash_aggregate_grouped",
                "hash_aggregate_whole_stage",
                "hash_aggregate_global"} <= costed, costed


# ---------------------------------------------------------------------------
# 6. measured-vs-static profile intensity cross-check
# ---------------------------------------------------------------------------

class TestMeasuredIntensity:
    def test_measured_ranks_agree_with_static_partial_order(self):
        from spark_rapids_tpu.obs import profile
        measured = {c: costplane.measured_intensity(c)
                    for c in ("project", "join", "aggregate",
                              "exchange")}
        static = {c: next(f for k, f in profile._INTENSITY if k in c)
                  for c in ("project", "join", "aggregate", "exchange")}
        assert all(v is not None and v > 0 for v in measured.values())
        # the baseline class IS the normalization anchor
        assert measured["project"] == pytest.approx(1.0)
        # both tables rank heavy relational classes above the
        # project baseline and the exchange sketch above it too
        for table in (measured, static):
            assert table["join"] > table["project"]
            assert table["aggregate"] > table["exchange"] \
                > table["project"]

    def test_profile_intensity_prefers_measured_then_falls_back(self):
        from spark_rapids_tpu.obs import profile
        assert profile._intensity("aggregate") == pytest.approx(
            costplane.measured_intensity("aggregate"))
        # classes with no live capture still use the static factors
        assert costplane.measured_intensity("sort") is None
        assert profile._intensity("sort") == 8.0
        assert profile._intensity("unknown_operator") == 2.0


# ---------------------------------------------------------------------------
# 7. end-to-end acceptance contracts
# ---------------------------------------------------------------------------

class TestEndToEnd:
    def test_session_surfaces_costplane(self):
        s = TpuSession(TpuConf({}))
        df = _agg_join_df(s)
        df.collect()
        df.collect()
        cost = s.last_query_costplane
        assert cost is not None and cost["costed_records"] > 0
        assert cost["programs"]
        assert all(e["source"] == "xla" for e in cost["programs"]
                   if e["flops"] is not None)
        assert cost["verdict"] in (costplane.VERDICT_COMPUTE,
                                   costplane.VERDICT_MEMORY)
        assert cost["compute_share_pct"] + cost["memory_share_pct"] \
            == pytest.approx(100.0, abs=1e-6)
        assert (cost["padding_waste_pct"] or 0) > 0
        sub = s.last_query_diagnosis.data["device_compute_breakdown"]
        assert sum(sub.values()) == pytest.approx(
            s.last_query_diagnosis.data["shares"]["device_compute"],
            abs=1e-12)

    def test_costplane_adds_zero_flushes(self):
        def measure(enabled):
            s = TpuSession(TpuConf({
                "spark.rapids.tpu.obs.cost.enabled": enabled}))
            df = _agg_join_df(s)
            df.collect()                       # warm
            f0 = pending.FLUSH_COUNT
            df.collect()
            return pending.FLUSH_COUNT - f0, s.last_query_costplane
        flushes_on, cost_on = measure(True)
        flushes_off, cost_off = measure(False)
        assert cost_on is not None and cost_off is None
        # the acceptance contract: an EXACT device round-trip match
        assert flushes_on == flushes_off

    def test_digest_stable_across_parallelism_and_superstage(self):
        digests = {}
        for par in (1, 4):
            for stage in (True, False):
                s = TpuSession(TpuConf({
                    "spark.rapids.tpu.exec.pipelineParallelism": par,
                    "spark.rapids.tpu.sql.superstage": stage}))
                df = _agg_join_df(s)
                df.collect()
                df.collect()
                cost = s.last_query_costplane
                assert cost is not None
                assert cost["compute_share_pct"] \
                    + cost["memory_share_pct"] == pytest.approx(
                        100.0, abs=1e-6)
                digests[(par, stage)] = cost["digest"]
        # model-only digest: execution config must not move it
        assert len(set(digests.values())) == 1, digests

    def test_disabled_plane_is_a_noop(self, tmp_path):
        log = str(tmp_path / "events.jsonl")
        costplane.reset()
        s = TpuSession(TpuConf({
            "spark.rapids.tpu.eventLog.path": log,
            "spark.rapids.tpu.obs.cost.enabled": False}))
        _agg_join_df(s).collect()
        assert s.last_query_costplane is None
        with open(log) as f:
            recs = [json.loads(line) for line in f]
        assert all("costplane" not in r for r in recs)

    def test_conf_overrides_peaks_and_bound(self):
        costplane.configure(TpuConf({
            "spark.rapids.tpu.obs.cost.peakTeraflops": 100.0,
            "spark.rapids.tpu.obs.cost.peakHbmGBps": 500.0,
            "spark.rapids.tpu.obs.cost.maxRecords": 4}))
        try:
            assert costplane.ridge_intensity() == pytest.approx(
                100.0e12 / 500.0e9)
            costplane.reset()       # guard fixture restores the store
            for i in range(6):
                costplane.capture(f"prog_{i}", _jit_add(), *(_args(16)))
            assert costplane.record_count() == 4
            assert costplane.dropped_count() == 2
        finally:
            costplane.configure(TpuConf({}))


# ---------------------------------------------------------------------------
# 8. surfaces: event log, Prometheus, stats, report
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_event_log_record_carries_costplane(self, tmp_path):
        log = str(tmp_path / "events.jsonl")
        s = TpuSession(TpuConf({"spark.rapids.tpu.eventLog.path": log}))
        _agg_join_df(s).collect()
        with open(log) as f:
            (rec,) = [json.loads(line) for line in f]
        cost = rec["costplane"]
        assert cost["costed_records"] > 0 and cost["programs"]
        assert rec["doctor"]["device_compute_breakdown"]

    def test_prometheus_families_present(self):
        from spark_rapids_tpu.obs.prom import render_text
        s = TpuSession(TpuConf({}))
        _agg_join_df(s).collect()
        text = render_text()
        for fam in ("tpu_cost_records", "tpu_cost_records_dropped",
                    "tpu_cost_padding_waste_pct",
                    "tpu_cost_captures_total",
                    "tpu_cost_roofline_verdicts_total",
                    "tpu_cost_achieved_gflops",
                    "tpu_cost_achieved_gbps"):
            assert fam in text, fam

    def test_stats_section_shape(self):
        costplane.reset()
        sec = costplane.stats_section()
        assert sec["enabled"] is True
        assert sec["records"] == 0
        assert set(sec["captures"]) == {"xla", "static", "skipped"}
        assert sec["ridge_intensity"] > 0
        assert sec["digest"] == costplane.stable_digest()

    def test_report_cost_section_renders(self, tmp_path, capsys):
        from spark_rapids_tpu.tools import report
        log = str(tmp_path / "events.jsonl")
        s = TpuSession(TpuConf({"spark.rapids.tpu.eventLog.path": log}))
        _agg_join_df(s).collect()
        rc = report.main([log, "--cost"])
        out = capsys.readouterr().out
        assert rc in (0, None)
        assert "device-compute cost (roofline)" in out
        assert "padding waste" in out
        assert "doctor device_compute=" in out

    def test_report_cost_placeholder_on_pre_r14_record(self):
        from spark_rapids_tpu.tools.report import cost_lines
        (line,) = cost_lines({"query_id": "old"})
        assert "no costplane recorded" in line

    def test_report_all_flag_turns_every_section_on(self, tmp_path,
                                                    capsys):
        from spark_rapids_tpu.tools import report
        log = str(tmp_path / "events.jsonl")
        s = TpuSession(TpuConf({"spark.rapids.tpu.eventLog.path": log}))
        _agg_join_df(s).collect()
        rc = report.main([log, "--all"])
        out = capsys.readouterr().out
        assert rc in (0, None)
        assert "device-compute cost (roofline)" in out
        assert "HBM memory (memplane)" in out
        assert "query doctor (cross-plane verdict)" in out
        assert "shuffle transport (netplane)" in out


# ---------------------------------------------------------------------------
# 9. lint scope: the plane's own file obeys the hot-path rules
# ---------------------------------------------------------------------------

class TestLintScope:
    def test_costplane_in_all_three_scopes(self):
        from spark_rapids_tpu.analysis import lint as AL
        rel = "spark_rapids_tpu/obs/costplane.py"
        scopes = AL._scopes_for(rel)
        assert {AL.SYNC001, AL.OBS002, AL.HYG002} <= scopes

    def test_seeded_fixture_trips_all_three_rules(self):
        from spark_rapids_tpu.analysis import lint as AL
        path = os.path.join(FIXTURES, "costplane_sync.py")
        with open(path) as f:
            findings = AL.lint_source(f.read(), path)
        rules = [f.rule for f in findings]
        assert rules.count(AL.SYNC001) >= 3
        assert AL.OBS002 in rules
        assert AL.HYG002 in rules

    def test_shipped_module_lints_clean(self):
        from spark_rapids_tpu.analysis import lint as AL
        rel = "spark_rapids_tpu/obs/costplane.py"
        path = os.path.join(REPO_ROOT, rel)
        with open(path) as f:
            findings = AL.lint_source(f.read(), rel,
                                      scopes=AL._scopes_for(rel))
        assert findings == [], AL.format_findings(findings)
