"""AOT compile service tests (compile/aot.py + service/warmup.py).

Five surfaces:

1. Bucket-lattice unit contract — geometric growth, ratio validation,
   ``ratio=2`` reproducing the classic pow2 padding bit-for-bit.
2. Demand ledger + warmup registry — first-seen miss/hit derivation,
   warmup converting misses to hits, warmer variant bounding,
   candidate cross product, failure isolation.
3. Warmup attribution (the PR 13 bugfix regression) — a compile under
   an ACTIVE CancelToken but inside ``warmup_scope()`` lands on the
   ``warmup`` pseudo-victim: no ``inline_compile_ms`` on the token,
   excluded from the timeline's inline_compile evidence, segregated
   warmup_ns.
4. Persistence — manifest roundtrip, run-id discrimination,
   conf-fingerprint sensitivity, and the cross-process subprocess
   test: a child against a seeded cache dir records ZERO new compiles
   (tpu_compile_seconds untouched) while loading persistently.
5. Mask-correctness — bucketed execution (ratio 4) is sha-identical
   to unbucketed across pipelineParallelism {1,4} x superstage on/off.
"""
import hashlib
import json
import os
import subprocess
import sys
import time

import pytest

from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.columnar import column
from spark_rapids_tpu.compile import aot
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import compile_watch, timeline
from spark_rapids_tpu.service.cancellation import CancelToken, \
    query_context
from spark_rapids_tpu.service.warmup import WarmupDaemon

MS = 1_000_000

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _aot_reset():
    """Isolate the process-wide AOT state (and the planes it feeds)."""
    aot.reset()
    compile_watch.reset()
    timeline.reset()
    yield
    aot.reset()
    compile_watch.reset()
    timeline.reset()
    default = TpuConf({})
    compile_watch.configure(default)
    timeline.configure(default)


# ---------------------------------------------------------------------------
# bucket lattice
# ---------------------------------------------------------------------------

class TestBucketLattice:
    def test_geometric_growth(self):
        lat = aot.BucketLattice(128, 4)
        assert lat.bucket(1) == 128
        assert lat.bucket(128) == 128
        assert lat.bucket(129) == 512
        assert lat.bucket(513) == 2048
        assert lat.points_up_to(600) == [128, 512, 2048]

    def test_ratio_two_reproduces_pow2_padding(self):
        lat = aot.BucketLattice(column.MIN_CAPACITY, 2)
        for n in (1, 7, 128, 129, 1000, 4096, 4097, 1 << 20):
            assert lat.bucket(n) == column.bucket_capacity(n), n

    @pytest.mark.parametrize("ratio", [0, 1, 3, 6, -2])
    def test_ratio_must_be_power_of_two(self, ratio):
        with pytest.raises(ValueError):
            aot.BucketLattice(128, ratio)

    def test_min_rows_validated(self):
        with pytest.raises(ValueError):
            aot.BucketLattice(0, 2)

    def test_configure_installs_column_hook(self):
        aot.configure(TpuConf(
            {"spark.rapids.tpu.compile.aot.bucketRatio": 4}))
        assert column.bucket_capacity(column.MIN_CAPACITY + 1) == \
            column.MIN_CAPACITY * 4
        aot.configure(TpuConf(
            {"spark.rapids.tpu.compile.aot.enabled": False}))
        assert column.bucket_capacity(column.MIN_CAPACITY + 1) == \
            column.MIN_CAPACITY * 2


# ---------------------------------------------------------------------------
# demand ledger
# ---------------------------------------------------------------------------

class TestDemandLedger:
    def setup_method(self):
        aot.configure(TpuConf({}))

    def test_first_demand_is_miss_then_hits(self):
        aot.note_demand("fused_project", 1024)
        aot.note_demand("fused_project", 1024)
        aot.note_demand("fused_project", 1024)
        snap = aot.demand_snapshot()
        assert snap["fused_project|1024"] == [2, 1]

    def test_distinct_buckets_miss_independently(self):
        aot.note_demand("fused_project", 1024)
        aot.note_demand("fused_project", 4096)
        snap = aot.demand_snapshot()
        assert snap["fused_project|1024"] == [0, 1]
        assert snap["fused_project|4096"] == [0, 1]
        assert aot.demanded_buckets() == [1024, 4096]

    def test_warmup_converts_future_miss_to_hit(self):
        aot.note_demand("staged_compute", 2048)   # discovers the bucket
        aot.register_warmer("fused_project", lambda b: None)
        assert aot.warm_missing(8) == 1
        aot.note_demand("fused_project", 2048)    # first tenant demand
        snap = aot.demand_snapshot()
        assert snap["fused_project|2048"] == [1, 0]   # hit, not miss

    def test_last_demand_is_per_cache_thread_local(self):
        aot.note_demand("fused_project", 1024)
        assert aot.last_demand("fused_project") == 1024
        assert aot.last_demand("staged_compute") is None

    def test_disabled_records_nothing(self):
        aot.configure(TpuConf(
            {"spark.rapids.tpu.compile.aot.enabled": False}))
        aot.note_demand("fused_project", 1024)
        assert aot.demand_snapshot() == {}


# ---------------------------------------------------------------------------
# warmup registry + daemon
# ---------------------------------------------------------------------------

class TestWarmupRegistry:
    def setup_method(self):
        aot.configure(TpuConf({}))

    def test_unknown_program_rejected(self):
        with pytest.raises(ValueError):
            aot.register_warmer("not_a_program", lambda b: None)

    def test_variants_bounded_oldest_evicted(self):
        for i in range(12):
            aot.register_warmer("fused_project", lambda b: None,
                                variant=f"v{i}")
        sec = aot.stats_section()
        assert sec["warmers"]["fused_project"] == 8
        aot.note_demand("fused_project", 1024)
        cands = aot.warm_candidates()
        variants = {v for (_p, v, _b) in cands}
        assert variants == {f"v{i}" for i in range(4, 12)}

    def test_candidates_are_cross_product_minus_warmed(self):
        aot.note_demand("fused_project", 1024)
        aot.note_demand("fused_project", 4096)
        aot.register_warmer("fused_project", lambda b: None)
        aot.register_warmer("staged_compute", lambda b: None)
        assert len(aot.warm_candidates()) == 4
        assert aot.warm_missing(2) == 2
        assert len(aot.warm_candidates()) == 2
        assert aot.warm_missing(8) == 2
        assert aot.warm_candidates() == []

    def test_failing_warmer_marked_and_counted_not_retried(self):
        calls = []

        def boom(bucket):
            calls.append(bucket)
            raise RuntimeError("warm failed")

        aot.note_demand("staged_compute", 1024)
        aot.register_warmer("staged_compute", boom)
        assert aot.warm_missing(8) == 0
        assert aot.warm_missing(8) == 0          # no retry storm
        assert calls == [1024]
        assert aot.stats_section()["warmup_failed"] == 1

    def test_daemon_sweeps_on_admission_signal(self):
        warmed = []
        aot.note_demand("fused_project", 1024)
        aot.register_warmer("fused_project", warmed.append)
        d = WarmupDaemon(interval_ms=5_000, max_per_cycle=4)
        d.start()
        try:
            d.note_admission("q-1")
            deadline = time.monotonic() + 5.0
            while not warmed and time.monotonic() < deadline:
                time.sleep(0.01)
            assert warmed == [1024]
            st = d.state()
            assert st["running"] and st["compiled"] == 1
            assert st["admissions_observed"] == 1
        finally:
            d.stop()
        assert not d.running()


# ---------------------------------------------------------------------------
# warmup attribution (the PR 13 bugfix)
# ---------------------------------------------------------------------------

class TestWarmupAttribution:
    def setup_method(self):
        aot.configure(TpuConf({}))

    def test_warmup_scope_outranks_active_cancel_token(self):
        """Regression: a first call under an ACTIVE CancelToken used to
        charge that query's inline_compile_ms even when the compile was
        a background warmup.  The warmup scope must win."""
        tok = CancelToken("q-victim")
        wrapped = compile_watch.wrap_miss(
            "fused_project", lambda: time.sleep(0.01), "sig")
        with query_context(tok):
            with aot.warmup_scope():
                wrapped()
        rec = compile_watch.records_since(0)[0]
        assert rec["origin"] == "warmup"
        assert not rec["inline"] and rec["query_id"] is None
        assert "inline_compile_ms" not in tok.observed
        assert compile_watch.inline_ns() == 0
        assert compile_watch.total_ns() == 0      # session deltas clean
        assert compile_watch.warmup_ns() > 0

    def test_inline_origin_without_warmup_scope(self):
        tok = CancelToken("q-inline")
        wrapped = compile_watch.wrap_miss(
            "fused_project", lambda: time.sleep(0.005), "sig")
        with query_context(tok):
            wrapped()
        rec = compile_watch.records_since(0)[0]
        assert rec["origin"] == "inline" and rec["inline"]
        assert tok.observed["inline_compile_ms"] > 0

    def test_compile_record_carries_demand_bucket(self):
        aot.note_demand("fused_project", 4096)
        compile_watch.note_compile("fused_project", 5 * MS, "sig")
        rec = compile_watch.records_since(0)[0]
        assert rec["bucket"] == 4096

    def test_timeline_classifies_warmup_window_as_idle(self):
        """A warmup compile's window is NOT inline_compile evidence:
        in a process summary the gap stays idle."""
        now = time.perf_counter_ns()
        t0 = now - 20 * MS
        timeline._INTERVALS.append((t0, t0 + 5 * MS))
        compile_watch._RECORDS.append({
            "cache": "ut", "dur_ms": 4.0, "signature": "",
            "inline": False, "origin": "warmup", "bucket": 1024,
            "query_id": None, "end_ns": t0 + 9 * MS})
        s = timeline._summarize(0, t0, now, is_query=False)
        assert s["gaps"]["inline_compile"] == 0.0
        assert s["gaps"]["idle"] == pytest.approx(75.0, abs=0.1)

    def test_timeline_pre_r13_record_still_compile_evidence(self):
        """Placeholder tolerance: records without an origin key (pre-r13
        event logs) keep classifying as compile evidence."""
        now = time.perf_counter_ns()
        t0 = now - 20 * MS
        timeline._INTERVALS.append((t0, t0 + 5 * MS))
        compile_watch._RECORDS.append({
            "cache": "ut", "dur_ms": 4.0, "signature": "",
            "inline": True, "query_id": None, "end_ns": t0 + 9 * MS})
        s = timeline._summarize(0, t0, now, is_query=True)
        assert s["gaps"]["inline_compile"] == pytest.approx(20.0, abs=0.1)


# ---------------------------------------------------------------------------
# persistence: manifest + fingerprint
# ---------------------------------------------------------------------------

class TestManifest:
    def test_roundtrip_and_run_discrimination(self, tmp_path,
                                              monkeypatch):
        conf = TpuConf({
            "spark.rapids.tpu.compile.aot.cacheDir": str(tmp_path),
            # keep the in-process jax compilation cache untouched
            # (conftest disables it on the CPU test mesh)
            "spark.rapids.tpu.compile.aot.xlaCache.enabled": False,
        })
        aot.configure(conf)
        key = aot.first_call_key("fused_project", "sig-a")
        assert key is not None
        aot.manifest_add(key, "fused_project", "sig-a", 1024, 12.5)
        assert aot.manifest_entries() == 1
        # same run -> never a persistent hit, even when wired
        monkeypatch.setattr(aot, "_PERSIST_ALL", True)
        assert not aot.persistent_ready(key)
        # simulate a later process: reload manifest under a fresh run id
        monkeypatch.setattr(aot, "_RUN_ID", "another-run")
        aot._load_manifest()
        assert aot.persistent_ready(key)
        # unwired XLA cache -> bookkeeping only, no persistent claims
        monkeypatch.setattr(aot, "_PERSIST_ALL", False)
        assert not aot.persistent_ready(key)

    def test_first_call_key_none_without_cache_dir(self):
        aot.configure(TpuConf({}))
        assert aot.first_call_key("fused_project", "sig") is None

    def test_wrap_miss_routes_persistent_hit(self, tmp_path,
                                             monkeypatch):
        aot.configure(TpuConf({
            "spark.rapids.tpu.compile.aot.cacheDir": str(tmp_path),
            "spark.rapids.tpu.compile.aot.xlaCache.enabled": False,
        }))
        key = aot.manifest_key("fused_project", "sig-p")
        aot.manifest_add(key, "fused_project", "sig-p", 1024, 3.0)
        monkeypatch.setattr(aot, "_PERSIST_ALL", True)
        monkeypatch.setattr(aot, "_RUN_ID", "later-run")
        aot._load_manifest()
        wrapped = compile_watch.wrap_miss(
            "fused_project", lambda: None, "sig-p")
        wrapped()
        assert compile_watch.persistent_hits() == 1
        assert compile_watch.total_ns() == 0     # no compile counted
        rec = compile_watch.records_since(0)[0]
        assert rec["origin"] == "persistent"

    def test_conf_fingerprint_sensitivity(self):
        fp_default = aot.conf_fingerprint(TpuConf({}))
        # program-affecting conf changes the fingerprint
        fp_batch = aot.conf_fingerprint(TpuConf(
            {"spark.rapids.tpu.sql.batchSizeRows": 12345}))
        assert fp_batch != fp_default
        # obs/service/aot-bookkeeping groups are excluded
        fp_obs = aot.conf_fingerprint(TpuConf(
            {"spark.rapids.tpu.obs.stats.enabled": False}))
        fp_dir = aot.conf_fingerprint(TpuConf(
            {"spark.rapids.tpu.compile.aot.cacheDir": "/elsewhere"}))
        assert fp_obs == fp_default
        assert fp_dir == fp_default


# ---------------------------------------------------------------------------
# auditor coverage over the bucketed program registry
# ---------------------------------------------------------------------------

class TestAuditorCoverage:
    def test_required_programs_match_bucketed_registry(self):
        from spark_rapids_tpu.analysis.program_audit import \
            REQUIRED_PROGRAMS
        assert frozenset(REQUIRED_PROGRAMS) == aot.BUCKETED_PROGRAMS

    def test_aot_coverage_gaps_empty_and_planted_gap_trips(self):
        from spark_rapids_tpu.analysis import program_audit as PA
        specs = PA.collect_specs()
        assert PA.aot_coverage_gaps(specs) == []
        planted = [s for s in specs if s.name != "join_probe"]
        assert PA.aot_coverage_gaps(planted) == ["join_probe"]


# ---------------------------------------------------------------------------
# lint scope: the AOT modules carry the plane discipline
# ---------------------------------------------------------------------------

class TestLintScope:
    def test_scopes_cover_aot_and_warmup(self):
        from spark_rapids_tpu.analysis import lint
        for rel in ("spark_rapids_tpu/compile/aot.py",
                    "spark_rapids_tpu/service/warmup.py"):
            scopes = lint._scopes_for(rel)
            assert {lint.SYNC001, lint.OBS002, lint.HYG002} <= scopes, rel

    def test_seeded_fixture_trips_all_three_rules(self):
        from spark_rapids_tpu.analysis import lint
        path = os.path.join(REPO_ROOT, "tests", "lint_fixtures",
                            "aot_sync.py")
        with open(path, "r", encoding="utf-8") as f:
            findings = lint.lint_source(f.read(), path)
        rules = [f.rule for f in findings]
        assert rules.count(lint.SYNC001) >= 3
        assert lint.OBS002 in rules
        assert lint.HYG002 in rules

    def test_shipped_modules_lint_clean(self):
        from spark_rapids_tpu.analysis import lint
        for rel in ("spark_rapids_tpu/compile/aot.py",
                    "spark_rapids_tpu/service/warmup.py"):
            path = os.path.join(REPO_ROOT, rel)
            with open(path, "r", encoding="utf-8") as f:
                findings = lint.lint_source(
                    f.read(), rel, scopes=lint._scopes_for(rel))
            assert findings == [], rel


# ---------------------------------------------------------------------------
# mask-correctness: bucketed == unbucketed, bit for bit
# ---------------------------------------------------------------------------

def _result_sha(conf_extra):
    from harness import with_tpu_session

    def fn(s):
        df = (s.create_dataframe(
                {"k": [i % 13 for i in range(5000)],
                 "v": [i * 3 + 1 for i in range(5000)]},
                num_partitions=3)
              .filter(F.col("v") % 5 != 0)
              .group_by("k").agg(F.sum("v").alias("sv"),
                                 F.count("v").alias("cv")))
        rows = sorted(df.collect())
        return hashlib.sha256(repr(rows).encode()).hexdigest()

    settings = {"spark.rapids.tpu.sql.batchSizeRows": 700}
    settings.update(conf_extra)
    return with_tpu_session(fn, settings)


class TestBucketedShaIdentical:
    @pytest.mark.parametrize("parallelism", [1, 4])
    @pytest.mark.parametrize("superstage", [True, False])
    def test_ratio4_matches_unbucketed(self, parallelism, superstage):
        base = {
            "spark.rapids.tpu.exec.pipelineParallelism": parallelism,
            "spark.rapids.tpu.sql.superstage": superstage,
        }
        unbucketed = _result_sha(
            {**base, "spark.rapids.tpu.compile.aot.enabled": False})
        aot.reset()
        bucketed = _result_sha(
            {**base, "spark.rapids.tpu.compile.aot.bucketRatio": 4})
        assert bucketed == unbucketed


# ---------------------------------------------------------------------------
# cross-process persistent reuse (subprocess against a seeded dir)
# ---------------------------------------------------------------------------

_CHILD_SRC = r"""
import json, os, sys
sys.path.insert(0, os.path.join(sys.argv[1], "benchmarks"))
import tpcds
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import compile_watch
from spark_rapids_tpu.columnar import pending

cache_dir, data_dir = sys.argv[2], sys.argv[3]
s = TpuSession(TpuConf({
    "spark.rapids.tpu.sql.enabled": True,
    "spark.rapids.tpu.compile.aot.cacheDir": cache_dir,
}))
tpcds.register(s, data_dir)
rows = sorted(s.sql(tpcds.QUERIES["q3"]).collect())
import hashlib
sha = hashlib.sha256(repr(rows).encode()).hexdigest()
recs = compile_watch.records_since(0)
print(json.dumps({
    "sha": sha,
    "compiles": sum(1 for r in recs if r.get("origin") != "persistent"),
    "persistent_hits": compile_watch.persistent_hits(),
    "flushes": pending.FLUSH_COUNT,
}))
"""


@pytest.mark.slow
class TestPersistentCacheAcrossProcesses:
    def test_child_against_seeded_dir_compiles_nothing(self, tmp_path):
        """Child A seeds the cache dir cold; child B re-runs q3 in a
        fresh process and must satisfy every first-call from the
        persistent cache: zero new compile records (the
        tpu_compile_seconds count stays untouched), >0 persistent
        hits, sha-identical results."""
        data_dir = str(tmp_path / "sf")
        sys.path.insert(0, os.path.join(REPO_ROOT, "benchmarks"))
        import tpcds
        tpcds.generate(data_dir, scale=0.002, seed=11)
        cache_dir = str(tmp_path / "aot_cache")
        # the children's XLA cache goes where the environment says
        # (compile/xla_cache.py); aot.cacheDir holds the manifest
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=cache_dir)

        def run_child():
            out = subprocess.run(
                [sys.executable, "-c", _CHILD_SRC, REPO_ROOT,
                 cache_dir, data_dir],
                capture_output=True, text=True, env=env, timeout=300,
                cwd=REPO_ROOT)
            assert out.returncode == 0, out.stderr[-2000:]
            return json.loads(out.stdout.strip().splitlines()[-1])

        cold = run_child()
        assert cold["compiles"] > 0          # child A really compiled
        assert os.path.exists(os.path.join(cache_dir,
                                           "aot_manifest.json"))
        warm = run_child()
        assert warm["sha"] == cold["sha"]
        assert warm["compiles"] == 0, warm
        assert warm["persistent_hits"] > 0
