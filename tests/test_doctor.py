"""Cross-plane query doctor tests (obs/doctor.py): exactly-one primary
bottleneck with contribution shares summing to 100, Amdahl headroom
bounds consistent with the timeline's gap shares, the ranked ROADMAP
mapping, digest stability across pipeline parallelism {1,4} x
superstage on/off, the event-log / Prometheus / stats / report
surfaces, the bench-record adapter behind ci/perf_gate.py, and the
zero-extra-flush + disabled-plane acceptance contracts."""
import json
import os

import pytest

from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.columnar import pending
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import doctor
from spark_rapids_tpu.obs.prom import render_text
from spark_rapids_tpu.obs.registry import TIMELINE_GAP_CAUSES

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _doctor_reset():
    doctor.configure(TpuConf({}))
    doctor.reset()
    yield
    doctor.configure(TpuConf({}))
    doctor.reset()


def _tl(util, **gaps):
    g = {c: 0.0 for c in TIMELINE_GAP_CAUSES}
    g.update(gaps)
    return {"busy_ms": util, "window_ms": 100.0, "util_pct": util,
            "gaps": g}


def _agg_join_df(sess, n=50_000, groups=31):
    df = sess.range(0, n, 1, 4)
    df = df.with_column("k", df["id"] % groups)
    dim = sess.range(0, groups, 1, 1).with_column("v", F.col("id") * 2)
    j = df.join(dim.with_column_renamed("id", "k2"),
                df["k"] == F.col("k2"), "inner")
    return j.group_by("k").agg(F.sum("v").alias("sv"))


# ---------------------------------------------------------------------------
# 1. verdict model
# ---------------------------------------------------------------------------

class TestVerdictModel:
    def test_exactly_one_primary_and_sum_to_100(self):
        d = doctor.diagnose(_tl(40.0, shuffle_host=23.655,
                                mem_spill=10.0, host_staging=21.345,
                                inline_compile=5.0))
        assert d.primary_cause == "device_compute"
        # a partition of the window: exactly 100 to float epsilon
        assert sum(d.data["shares"].values()) == pytest.approx(
            100.0, abs=1e-6)
        # exactly ONE cause carries the primary verdict
        top = [c for c, v in d.data["shares"].items()
               if v == max(d.data["shares"].values())]
        assert d.primary_cause in top

    def test_amdahl_bound_matches_gap_share(self):
        # the ISSUE's worked example: a 23.655% shuffle_host share
        # bounds speedup at 1/(1-0.23655) = 1.31x
        d = doctor.diagnose(_tl(40.0, shuffle_host=23.655,
                                mem_spill=10.0, host_staging=26.345))
        by = {c["cause"]: c for c in d.headroom}
        assert by["shuffle_host"]["bound_x"] == pytest.approx(1.31,
                                                              abs=0.005)
        # the bound rule holds for EVERY candidate, which is what
        # makes the headroom table consistent with the gap shares
        for c in d.headroom:
            assert c["bound_x"] == pytest.approx(
                1.0 / (1.0 - c["share_pct"] / 100.0), rel=1e-3)

    def test_deterministic_tie_break_by_taxonomy_order(self):
        # two equal shares: device_compute outranks host_staging in
        # the fixed priority order, never dict order
        d = doctor.diagnose(_tl(50.0, host_staging=50.0))
        assert d.primary_cause == "device_compute"
        d2 = doctor.diagnose(_tl(0.0, shuffle_host=50.0, mem_spill=50.0))
        assert d2.primary_cause == "shuffle_host"

    def test_roadmap_mapping_is_ranked_and_complete(self):
        d = doctor.diagnose(_tl(10.0, shuffle_host=40.0,
                                inline_compile=30.0, mem_spill=20.0))
        assert d.primary_cause == "shuffle_host"
        # ranked by share, every candidate mapped onto items 1-4
        shares = [c["share_pct"] for c in d.headroom]
        assert shares == sorted(shares, reverse=True)
        for c in d.headroom:
            assert c["roadmap_item"] in (1, 2, 3, 4)
            assert c["fix"]
        assert d.headroom[0]["roadmap_item"] == 1       # ICI shuffle
        by = {c["cause"]: c["roadmap_item"] for c in d.headroom}
        assert by["inline_compile"] == 3 and by["mem_spill"] == 2

    def test_rounding_residue_folded_to_exactly_100(self):
        # 3-decimal timeline rounding leaves a residue; the doctor
        # folds it into the largest component
        d = doctor.diagnose(_tl(33.333, host_staging=33.333,
                                shuffle_host=33.333))
        assert sum(d.data["shares"].values()) == pytest.approx(
            100.0, abs=1e-9)

    def test_empty_window_degrades_to_host_staging(self):
        d = doctor.diagnose(_tl(0.0))
        assert d.primary_cause == "host_staging"
        assert sum(d.data["shares"].values()) == pytest.approx(100.0)

    def test_evidence_cites_owning_plane(self):
        d = doctor.diagnose(
            _tl(30.0, shuffle_host=40.0, mem_spill=20.0,
                inline_compile=10.0),
            inline_compile_ms=12.5,
            netplane={"host_drop_tax_ms": 8.1, "edge_skew": 1.4,
                      "edges": 3},
            memplane={"spill_ms": 6.0, "peak_device_bytes": 4096,
                      "spill": {"device_to_host": {"count": 2}}},
            flushes=3, predicted_flushes=3)
        by = {c["cause"]: c["evidence"] for c in d.headroom}
        assert "host_drop_tax_ms=8.1" in by["shuffle_host"]
        assert "spill_ms=6.0" in by["mem_spill"]
        assert "2 tier moves" in by["mem_spill"]
        assert "inline_compile_ms=12.5" in by["inline_compile"]
        assert "flushes=3" in by["device_compute"]

    def test_verdict_line_names_bound_and_roadmap_item(self):
        d = doctor.diagnose(_tl(20.0, shuffle_host=23.655,
                                host_staging=56.345))
        line = d.verdict_line()
        assert "host_staging" in line and "ROADMAP item 4" in line

    def test_verdict_counter_and_stats_section(self):
        doctor.diagnose(_tl(10.0, shuffle_host=90.0))
        doctor.diagnose(_tl(10.0, shuffle_host=90.0))
        doctor.diagnose(_tl(90.0, shuffle_host=10.0))
        sec = doctor.stats_section()
        assert sec["verdicts"]["shuffle_host"] == 2
        assert sec["verdicts"]["device_compute"] == 1
        assert sec["last"]["primary_cause"] == "device_compute"
        text = render_text()
        assert 'tpu_doctor_verdicts_total{cause="shuffle_host"}' in text


# ---------------------------------------------------------------------------
# 2. bench-record adapter (the perf gate's verdict printer)
# ---------------------------------------------------------------------------

class TestBenchAdapter:
    def test_diagnose_bench_on_current_round(self):
        from spark_rapids_tpu.analysis import regression as R
        rec = R.load_round(os.path.join(REPO_ROOT,
                                        "BENCH_r12.json")).keys
        d = doctor.diagnose_bench(rec)
        assert d is not None
        assert sum(d.data["shares"].values()) == pytest.approx(100.0)
        assert d.primary_cause == rec["doctor_primary_cause"]

    def test_diagnose_bench_none_on_pre_timeline_round(self):
        from spark_rapids_tpu.analysis import regression as R
        # synthetic record with the pre-timeline key set (the real
        # early rounds are gone with the backend they were taken on)
        rec = R.load_round(os.path.join(
            REPO_ROOT, "tests", "data", "BENCH_r01.json")).keys
        assert doctor.diagnose_bench(rec) is None


# ---------------------------------------------------------------------------
# 3. end-to-end acceptance contracts
# ---------------------------------------------------------------------------

class TestEndToEnd:
    def test_session_surfaces_one_verdict(self):
        s = TpuSession(TpuConf({}))
        df = _agg_join_df(s)
        df.collect()
        df.collect()
        d = s.last_query_diagnosis
        assert d is not None
        assert d.primary_cause in d.data["shares"]
        assert sum(d.data["shares"].values()) == pytest.approx(
            100.0, abs=1e-6)
        # headroom bounds consistent with the timeline's gap shares:
        # every gap cause with a nonzero share appears with exactly
        # the Amdahl bound of (approximately) that share
        tl = s.last_query_timeline
        by = {c["cause"]: c for c in d.headroom}
        for cause, share in tl["gaps"].items():
            if share <= 0:
                continue
            cand = by[cause]
            assert cand["share_pct"] == pytest.approx(share, abs=0.01)
            assert cand["bound_x"] == pytest.approx(
                1.0 / (1.0 - cand["share_pct"] / 100.0), rel=1e-3)

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
                d = s.last_query_diagnosis
                assert d is not None
                # exactly-one primary, sum-to-100: per-config
                assert d.primary_cause in d.data["shares"]
                assert sum(d.data["shares"].values()) == pytest.approx(
                    100.0, abs=1e-6)
                digests[(par, stage)] = d.stable_digest()
        # the cause+headroom digest (verdict model keyed by the
        # query's data identity) must not move with execution config
        assert len(set(digests.values())) == 1, digests

    def test_doctor_adds_zero_flushes(self):
        def measure(enabled):
            s = TpuSession(TpuConf({
                "spark.rapids.tpu.obs.doctor.enabled": enabled}))
            df = _agg_join_df(s)
            df.collect()                       # warm
            f0 = pending.FLUSH_COUNT
            df.collect()
            return pending.FLUSH_COUNT - f0, s.last_query_diagnosis
        flushes_on, diag_on = measure(True)
        flushes_off, diag_off = measure(False)
        assert diag_on is not None and diag_off is None
        # the acceptance contract: an EXACT device round-trip match
        assert flushes_on == flushes_off

    def test_disabled_plane_is_a_noop(self, tmp_path):
        log = str(tmp_path / "events.jsonl")
        doctor.reset()
        s = TpuSession(TpuConf({
            "spark.rapids.tpu.eventLog.path": log,
            "spark.rapids.tpu.obs.doctor.enabled": False}))
        _agg_join_df(s).collect()
        assert s.last_query_diagnosis is None
        assert doctor.stats_section()["verdicts"] == {}
        recs = [json.loads(ln) for ln in open(log)]
        assert all("doctor" not in r for r in recs)

    def test_event_log_and_report_carry_verdict(self, tmp_path):
        from spark_rapids_tpu.tools.report import (doctor_lines,
                                                   load_query_stories,
                                                   render_report)
        log = str(tmp_path / "events.jsonl")
        s = TpuSession(TpuConf({"spark.rapids.tpu.eventLog.path": log}))
        df = _agg_join_df(s)
        df.collect()
        df.collect()
        recs = [json.loads(ln) for ln in open(log)]
        # the LAST record is the query last_query_diagnosis describes
        # (the first collect's verdict may differ: it paid the compiles)
        doc = [r["doctor"] for r in recs if "doctor" in r][-1]
        assert doc["primary_cause"] == \
            s.last_query_diagnosis.primary_cause
        assert sum(doc["shares"].values()) == pytest.approx(
            100.0, abs=1e-6)
        stories = load_query_stories(log)
        txt = render_report(stories, show_doctor=True)
        assert "query doctor (cross-plane verdict)" in txt
        assert "primary bottleneck" in txt
        assert "Amdahl" in txt

    def test_service_stats_carry_doctor_section(self):
        from spark_rapids_tpu.service import QueryService
        s = TpuSession(TpuConf({}))
        with QueryService(s, num_workers=1) as svc:
            h = svc.submit(s.range(0, 100, num_partitions=1),
                           tenant="doc")
            h.result(timeout=120)
            snap = svc.stats().snapshot()
        assert "doctor" in snap
        assert snap["doctor"]["enabled"] is True
        assert sum(snap["doctor"]["verdicts"].values()) >= 1


# ---------------------------------------------------------------------------
# 4. TPC-DS quartet (the acceptance sweep; mirrored in
#    ci/compile_smoke.py for the CI gate)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tpcds_quartet_one_verdict_each(tmp_path):
    from benchmarks import tpcds
    data_dir = str(tmp_path / "tpcds")
    tpcds.generate(data_dir, scale=0.002, seed=11)
    s = TpuSession(TpuConf({}))
    tpcds.register(s, data_dir)
    for q in ("q3", "q42", "q52", "q96"):
        df = s.sql(tpcds.QUERIES[q])
        df.collect()
        df.collect()
        d = s.last_query_diagnosis
        assert d is not None, q
        assert sum(d.data["shares"].values()) == pytest.approx(
            100.0, abs=1e-6), q
        tl = s.last_query_timeline
        by = {c["cause"]: c for c in d.headroom}
        for cause, share in tl["gaps"].items():
            if share <= 0:
                continue
            assert by[cause]["bound_x"] == pytest.approx(
                1.0 / (1.0 - by[cause]["share_pct"] / 100.0),
                rel=1e-3), (q, cause)
