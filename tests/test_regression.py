"""Performance regression sentinel tests (analysis/regression.py +
ci/perf_gate.py): the dual-shape bench-record parser, the longitudinal
ledger over the REAL in-repo BENCH_r*.json files (placeholder rows for
the r01-r05 key gaps, no crash), the committed PERF_BASELINE.json's
consistency with the round that seeded it, noise-aware compare
semantics (regression / improvement / exact / skipped), the seeded
perf-gate fixtures (a -20% record must trip the gate, a +50% record
must pass and suggest a baseline bump), and the lint-scope extension
over the two new modules."""
import importlib.util
import json
import os

import pytest

from spark_rapids_tpu.analysis import lint as AL
from spark_rapids_tpu.analysis import regression as R

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")
BASELINE = os.path.join(REPO_ROOT, "PERF_BASELINE.json")


def _gate():
    spec = importlib.util.spec_from_file_location(
        "ci_perf_gate", os.path.join(REPO_ROOT, "ci", "perf_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# 1. dual-shape parser
# ---------------------------------------------------------------------------

class TestParser:
    def test_wrapper_shape(self):
        rec = R.parse_record({"n": 9, "cmd": "python bench.py", "rc": 0,
                              "tail": "...", "parsed": {"value": 1.5}})
        assert rec == {"value": 1.5}

    def test_bare_shape(self):
        assert R.parse_record({"value": 2.0, "flushes": 2}) == \
            {"value": 2.0, "flushes": 2}

    def test_wrapper_without_parsed_falls_back_to_tail(self):
        tail = ('warmup noise\n{"value": 3.25, "flushes": 2}\n')
        rec = R.parse_record({"n": 7, "cmd": "x", "rc": 0, "tail": tail})
        assert rec == {"value": 3.25, "flushes": 2}

    def test_garbage_returns_none_not_raise(self):
        assert R.parse_record(None) is None
        assert R.parse_record("not json") is None
        assert R.parse_record(42) is None
        assert R.parse_record({"cmd": "x", "rc": 1, "tail": "boom"}) \
            is None


# ---------------------------------------------------------------------------
# 2. longitudinal ledger over the REAL in-repo files
# ---------------------------------------------------------------------------

#: a synthetic record carrying only the pre-timeline key set (the
#: real r01-r05 were taken on a backend that no longer exists and are
#: deleted); it stands in for "a round that predates every later key"
PRE_TIMELINE_DIR = os.path.join(REPO_ROOT, "tests", "data")


def _history_with_early_round():
    return sorted(R.load_history(PRE_TIMELINE_DIR)
                  + R.load_history(REPO_ROOT), key=lambda r: r.round)


class TestHistory:
    def test_loads_every_committed_round_sorted(self):
        rounds = R.load_history(REPO_ROOT)
        ns = [r.round for r in rounds]
        assert ns == sorted(ns)
        assert 11 in ns and 12 in ns
        # r01-r05 are deleted and r06-r10 were never recorded:
        # absent, not crashing
        assert not any(n in ns for n in range(1, 11))

    def test_early_rounds_degrade_to_placeholders(self):
        rounds = {r.round: r for r in _history_with_early_round()}
        r01 = rounds[1]
        # pre-r06 rounds lack every post-r05 key: .get degrades to
        # None placeholders, never KeyError
        for key in ("flushes", "device_util_pct", "util_gap_breakdown",
                    "host_drop_tax_ms", "peak_device_bytes"):
            assert r01.get(key) is None, key
        assert r01.get("value") is not None
        # the newest round carries the full gated key set (the four
        # cold-path keys exist only from r13 on, the three roofline
        # keys from r14, the three fleet keys from r15, the four
        # plan-cache/scheduler keys from r16, the obs-tax key from
        # r17, the residency key from r18, the six soak keys from
        # r19)
        newest = rounds[max(rounds)]
        for key, _d, _b in R.GATE_KEYS:
            assert newest.get(key) is not None, key

    def test_history_table_has_placeholder_rows(self):
        rounds = _history_with_early_round()
        table = R.history_table(rounds, keys=["value", "flushes"])
        assert len(table) == len(rounds)
        by_round = {row["round"]: row for row in table}
        assert by_round[1]["flushes"] is None      # placeholder
        assert by_round[12]["flushes"] is not None
        # every row has every requested column
        assert all(set(row) == {"round", "value", "flushes"}
                   for row in table)


# ---------------------------------------------------------------------------
# 3. baseline + compare semantics
# ---------------------------------------------------------------------------

class TestCompare:
    BASE = {"version": 1, "round": 12, "keys": {
        "value": {"value": 2.0, "direction": "higher", "band_pct": 30.0},
        "spill_ms": {"value": 10.0, "direction": "lower",
                     "band_pct": 50.0},
        "flushes": {"value": 2, "direction": "exact"},
    }}

    def test_within_band_ok(self):
        deltas = R.compare({"value": 1.8, "spill_ms": 12.0,
                            "flushes": 2}, self.BASE)
        assert all(d.status == "ok" for d in deltas)

    def test_regression_each_direction(self):
        deltas = {d.key: d for d in R.compare(
            {"value": 1.2, "spill_ms": 16.0, "flushes": 3}, self.BASE)}
        assert deltas["value"].status == "regression"       # -40%
        assert deltas["spill_ms"].status == "regression"    # +60%
        assert deltas["flushes"].status == "regression"     # exact
        assert R.regressions(list(deltas.values()))

    def test_improvement_each_direction(self):
        deltas = {d.key: d for d in R.compare(
            {"value": 3.0, "spill_ms": 2.0, "flushes": 2}, self.BASE)}
        assert deltas["value"].status == "improvement"
        assert deltas["spill_ms"].status == "improvement"
        assert deltas["flushes"].status == "ok"   # exact never improves

    def test_missing_key_skipped_not_failed(self):
        deltas = {d.key: d for d in R.compare({"value": 2.0}, self.BASE)}
        assert deltas["spill_ms"].status == "skipped"
        assert deltas["flushes"].status == "skipped"
        assert not R.regressions(list(deltas.values()))

    def test_zero_baseline_tax_respects_abs_floor(self):
        # a tax that measured 0.0 in the baseline round would gate at
        # 0*(1+band) == 0 without the floor: any jitter would fail CI
        base = {"version": 1, "round": 12, "keys": {
            "spill_ms": {"value": 0.0, "direction": "lower",
                         "band_pct": 150.0, "abs_floor": 5.0}}}
        ok = R.compare({"spill_ms": 3.0}, base)[0]
        bad = R.compare({"spill_ms": 7.5}, base)[0]
        assert ok.status == "ok"
        assert bad.status == "regression"
        # make_baseline seeds the floor for every lower-direction key
        seeded = R.make_baseline({"spill_ms": 0.0}, round_n=12)
        assert seeded["keys"]["spill_ms"]["abs_floor"] == \
            R.ABS_FLOORS["spill_ms"]

    def test_seeded_record_scales_only_throughput(self):
        rec = R.seeded_record(self.BASE, 0.8)
        assert rec["value"] == pytest.approx(1.6)
        assert rec["spill_ms"] == 10.0          # tax key: untouched
        assert rec["flushes"] == 2              # exact key: untouched


# ---------------------------------------------------------------------------
# 4. the committed baseline matches the round that seeded it
# ---------------------------------------------------------------------------

class TestCommittedBaseline:
    def test_baseline_values_equal_r19(self):
        base = R.load_baseline(BASELINE)
        assert base["round"] == 19
        r19 = R.load_round(os.path.join(REPO_ROOT,
                                        "BENCH_r19.json")).keys
        for key, spec in base["keys"].items():
            assert spec["value"] == r19[key], key
        # so the committed pair passes the gate by construction
        assert not R.regressions(R.compare(r19, base))

    def test_residency_key_gated_exact_at_zero(self):
        # r18's contract: a change that reintroduces a hidden
        # device->host sync (any nonzero undeclared_transfers) must
        # fail the gate, not a profiling session
        base = R.load_baseline(BASELINE)
        spec = base["keys"]["undeclared_transfers"]
        assert spec["direction"] == "exact"
        assert spec["value"] == 0
        dirty = dict(R.load_round(os.path.join(
            REPO_ROOT, "BENCH_r19.json")).keys)
        dirty["undeclared_transfers"] = 1
        bad = [d.key for d in R.regressions(R.compare(dirty, base))]
        assert bad == ["undeclared_transfers"], bad

    def test_leak_drift_key_gated_exact_at_zero(self):
        # r19's contract: the soak leak-drift monitor reading ANY
        # nonzero byte drift over the measured window must fail the
        # gate — a leak is never inside a noise band
        base = R.load_baseline(BASELINE)
        spec = base["keys"]["leak_drift_bytes"]
        assert spec["direction"] == "exact"
        assert spec["value"] == 0
        dirty = dict(R.load_round(os.path.join(
            REPO_ROOT, "BENCH_r19.json")).keys)
        dirty["leak_drift_bytes"] = 4096
        bad = [d.key for d in R.regressions(R.compare(dirty, base))]
        assert bad == ["leak_drift_bytes"], bad

    def test_true_r16_numbers_trip_only_the_r17_discontinuities(self):
        # the r17 obs-tax diet changed what two gated keys MEASURE:
        # device_util_pct's wall no longer contains the deferred
        # StatsProfile/doctor/history assembly (so util jumped from
        # ~52% to ~99%), and history_write_p99_us dropped ~10x when
        # the background writer stopped paying dumps+open per row.
        # The true r16 record must regress on exactly those two keys
        # against a baseline seeded from r17 — any third key tripping
        # means a band is too tight for real round-over-round noise.
        # (The committed baseline moved on to r18, so the r17 baseline
        # is reconstructed here with the same seeding path.)
        r16 = R.load_round(os.path.join(REPO_ROOT,
                                        "BENCH_r16.json")).keys
        r17 = R.load_round(os.path.join(REPO_ROOT,
                                        "BENCH_r17.json")).keys
        base17 = R.make_baseline(r17, round_n=17)
        bad = sorted(d.key
                     for d in R.regressions(R.compare(r16, base17)))
        assert bad == ["device_util_pct", "history_write_p99_us"], bad


# ---------------------------------------------------------------------------
# 5. the gate CLI + seeded fixtures
# ---------------------------------------------------------------------------

class TestGateCli:
    def test_seeded_regression_fixture_trips(self, capsys):
        rc = _gate().main(["--fixture", "regression"])
        out = capsys.readouterr().out
        assert rc == 1, out
        assert "PERF GATE: FAIL" in out
        # the doctor's verdict rides the failure: cause + roadmap item
        assert "doctor:" in out
        assert "primary bottleneck" in out
        assert "ROADMAP item" in out

    def test_seeded_improvement_fixture_passes_and_suggests_bump(
            self, capsys):
        rc = _gate().main(["--fixture", "improvement"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "PERF GATE: PASS" in out
        assert "baseline bump" in out

    def test_unknown_fixture_is_usage_error(self, capsys):
        assert _gate().main(["--fixture", "bogus"]) == 2

    def test_current_regressed_file_trips(self, tmp_path, capsys):
        base = R.load_baseline(BASELINE)
        rec = R.seeded_record(base, 0.7)
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"n": 99, "cmd": "x", "rc": 0,
                                 "tail": "", "parsed": rec}))
        rc = _gate().main(["--current", str(p)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "regression" in out

    def test_seed_baseline_roundtrip(self, tmp_path, monkeypatch):
        gate = _gate()
        out_path = tmp_path / "PERF_BASELINE.json"
        monkeypatch.setattr(gate, "BASELINE_PATH", str(out_path))
        rc = gate._seed_baseline(
            os.path.join(REPO_ROOT, "BENCH_r19.json"))
        assert rc == 0
        reseeded = R.load_baseline(str(out_path))
        committed = R.load_baseline(BASELINE)
        assert reseeded["keys"] == committed["keys"]


# ---------------------------------------------------------------------------
# 6. lint scope extension + seeded fixture
# ---------------------------------------------------------------------------

class TestLintScopes:
    def test_new_modules_in_sync_obs_hyg_scopes(self):
        for rel in ("spark_rapids_tpu/obs/doctor.py",
                    "spark_rapids_tpu/analysis/regression.py"):
            scopes = AL._scopes_for(rel)
            assert AL.SYNC001 in scopes, rel
            assert AL.OBS002 in scopes, rel
            assert AL.HYG002 in scopes, rel

    def test_scoped_lint_fires_on_device_pull_in_doctor(self):
        src = ("import jax\n"
               "def corroborate(dev):\n"
               "    return jax.device_get(dev)\n")
        fs = AL.lint_source(
            src, "spark_rapids_tpu/obs/doctor.py",
            scopes=AL._scopes_for("spark_rapids_tpu/obs/doctor.py"))
        assert any(f.rule == AL.SYNC001 for f in fs)

    def test_seeded_doctor_fixture_trips_all_three_rules(self):
        path = os.path.join(FIXTURES, "doctor_sync.py")
        with open(path) as f:
            fs = AL.lint_source(f.read(), path)
        rules = {f.rule for f in fs}
        assert {AL.SYNC001, AL.OBS002, AL.HYG002} <= rules

    def test_shipped_modules_lint_clean(self):
        for rel in ("spark_rapids_tpu/obs/doctor.py",
                    "spark_rapids_tpu/analysis/regression.py"):
            path = os.path.join(REPO_ROOT, rel)
            with open(path) as f:
                fs = AL.lint_source(f.read(), rel,
                                    scopes=AL._scopes_for(rel))
            assert fs == [], AL.format_findings(fs)
