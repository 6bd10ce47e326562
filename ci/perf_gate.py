#!/usr/bin/env python
"""Performance-regression gate — the CI face of
``analysis/regression.py``.

Usage:
  python ci/perf_gate.py                       # compare the newest
                                               # BENCH_r*.json round
                                               # against PERF_BASELINE.json
  python ci/perf_gate.py --current FILE        # compare one record
                                               # (wrapper or bare shape)
  python ci/perf_gate.py --run [ROWS]          # run a scaled-down
                                               # bench.py and gate its
                                               # fresh output (default
                                               # 200000 rows, scaled
                                               # thresholds off)
  python ci/perf_gate.py --fixture regression  # seeded -20% throughput
                                               # record; exit NONZERO iff
                                               # the gate trips (the
                                               # self-test CI inverts:
                                               # nonzero here is PASS)
  python ci/perf_gate.py --fixture improvement # seeded +50% record; must
                                               # pass AND suggest a
                                               # baseline bump
  python ci/perf_gate.py --fixture obs_tax     # seeded -5% record; must
                                               # trip ONLY the 2%-band
                                               # all_planes_on_vs_off
                                               # key (the obs-overhead
                                               # budget; the wide
                                               # throughput bands let
                                               # -5% through)
  python ci/perf_gate.py --fixture soak_drift  # seeded record with a
                                               # nonzero leak_drift_bytes
                                               # and a high
                                               # anomaly_fp_rate; the
                                               # exact-0 drift gate and
                                               # the fp-rate band MUST
                                               # trip (self-test of the
                                               # soak-plane gates; the
                                               # smoke harness inverts)
  python ci/perf_gate.py --seed-baseline FILE  # (re)write
                                               # PERF_BASELINE.json from a
                                               # bench record file

Exit codes: 0 clean (improvements allowed), 1 regression, 2 usage /
missing-file errors.  On a regression the gate prints the cross-plane
doctor's verdict for the record (``obs.doctor.diagnose_bench``) so
the failure names the bottleneck and the ROADMAP item that fixes it,
not just the number that moved.

``--run`` intentionally gates only the deterministic exact keys
(flush counts) plus any keys whose baseline carries
``scale_invariant: true``; absolute throughput at a scaled-down row
count is not comparable to the committed 8M-row baseline, so those
keys are skipped rather than mis-compared.
"""
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
# a host-only gate that spawns bench.py (--run): pin CPU for this
# process AND its children, whatever the caller exported — a child
# pointed at the chip its parent holds fails or hangs
os.environ["JAX_PLATFORMS"] = "cpu"

BASELINE_PATH = os.path.join(REPO_ROOT, "PERF_BASELINE.json")

#: keys safe to gate on a scaled-down --run (row-count independent)
_SCALE_INVARIANT = ("flushes", "superstage_off_flushes",
                    "predicted_flushes", "undeclared_transfers",
                    "leak_drift_bytes")


def _print_doctor_verdict(record):
    from spark_rapids_tpu.obs import doctor
    diag = doctor.diagnose_bench(record)
    if diag is None:
        print("doctor: no verdict (record predates the timeline keys)")
        return
    print(f"doctor: {diag.verdict_line()}")
    for cand in diag.headroom[:3]:
        item = (f"ROADMAP item {cand['roadmap_item']}"
                if cand["roadmap_item"] else "no mapped item")
        print(f"  - {cand['cause']}: {cand['share_pct']:.1f}% "
              f"(<= {cand['bound_x']:.2f}x) -> {item}: {cand['fix']}")


def _report(deltas, record, *, suggest_bump=True) -> int:
    from spark_rapids_tpu.analysis import regression as R
    for d in deltas:
        print(d)
    regs = R.regressions(deltas)
    imps = R.improvements(deltas)
    if regs:
        print(f"\nPERF GATE: FAIL — {len(regs)} regressed key(s): "
              + ", ".join(d.key for d in regs))
        _print_doctor_verdict(record)
        return 1
    if imps and suggest_bump:
        print(f"\nPERF GATE: PASS — {len(imps)} key(s) beyond the band "
              "in the GOOD direction: "
              + ", ".join(d.key for d in imps))
        print("consider a baseline bump: python ci/perf_gate.py "
              "--seed-baseline <new BENCH_r*.json>")
    elif not regs:
        print("\nPERF GATE: PASS — all gated keys within their "
              "noise bands")
    return 0


def _fixture(kind: str) -> int:
    """Gate a seeded synthetic record against the committed baseline.

    ``regression``: -20% on every throughput key — the gate MUST trip
    (exit 1), which the smoke harness inverts into its own pass.
    ``improvement``: +50% — the gate must pass and print the
    baseline-bump suggestion.
    ``obs_tax``: -5% on every throughput key — small enough to slip
    through the 15-18% throughput bands, but the 2%-band
    ``all_planes_on_vs_off`` ratio MUST trip: the seeded self-test of
    the observability ≤2%-overhead budget.
    ``soak_drift``: throughput untouched (scale 1.0) but
    ``leak_drift_bytes`` forced nonzero and ``anomaly_fp_rate``
    pushed past its band+floor — the exact-0 drift gate and the
    fp-rate band MUST trip: the seeded self-test of the soak-plane
    gates (a reintroduced inter-query leak or a sentinel that cries
    wolf on stationary traffic fails CI, not a soak postmortem).

    The seeded record starts from the newest recorded round's FULL
    key set (so it carries ``util_gap_breakdown`` and the doctor can
    diagnose the synthetic regression), with the scaled gate keys
    overlaid."""
    from spark_rapids_tpu.analysis import regression as R
    base = R.load_baseline(BASELINE_PATH)
    if kind == "regression":
        scaled = R.seeded_record(base, 0.8)
    elif kind == "improvement":
        scaled = R.seeded_record(base, 1.5)
    elif kind == "obs_tax":
        scaled = R.seeded_record(base, 0.95)
    elif kind == "soak_drift":
        scaled = R.seeded_record(base, 1.0)
        # a 4 KiB idle-floor regression — any nonzero drift IS a leak
        scaled["leak_drift_bytes"] = 4096
        # past both the 150% band and the 50-point abs floor
        scaled["anomaly_fp_rate"] = 90.0
    else:
        print(f"unknown fixture {kind!r}; expected regression, "
              "improvement, obs_tax or soak_drift", file=sys.stderr)
        return 2
    newest = _newest_round()
    rec = dict(newest.keys) if newest is not None else {}
    rec.update(scaled)
    print(f"perf-gate fixture: {kind} (seeded from baseline r"
          f"{base.get('round')})")
    return _report(R.compare(rec, base), rec)


def _seed_baseline(path: str) -> int:
    from spark_rapids_tpu.analysis import regression as R
    try:
        with open(path, "r", encoding="utf-8") as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        print(f"cannot read {path}: {e}", file=sys.stderr)
        return 2
    rec = R.parse_record(obj)
    if not rec:
        print(f"{path}: no bench key set found", file=sys.stderr)
        return 2
    round_n = obj.get("n") if isinstance(obj, dict) else None
    base = R.make_baseline(
        rec, round_n=round_n or 0, source=os.path.basename(path),
        cmd=(obj.get("cmd") if isinstance(obj, dict) else "") or "",
        rows=rec.get("rows"))
    with open(BASELINE_PATH, "w", encoding="utf-8") as f:
        json.dump(base, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"seeded {BASELINE_PATH} from {path} "
          f"({len(base['keys'])} gated keys)")
    return 0


def _newest_round():
    from spark_rapids_tpu.analysis import regression as R
    rounds = R.load_history(REPO_ROOT)
    return rounds[-1] if rounds else None


def _run_bench(rows: int):
    cmd = [sys.executable, os.path.join(REPO_ROOT, "bench.py"), str(rows)]
    print(f"perf-gate run: {' '.join(cmd)}")
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=REPO_ROOT)
    if proc.returncode != 0:
        print(proc.stdout[-2000:])
        print(proc.stderr[-2000:], file=sys.stderr)
        print(f"bench.py exited {proc.returncode}", file=sys.stderr)
        return None
    from spark_rapids_tpu.analysis import regression as R
    for line in reversed(proc.stdout.strip().splitlines()):
        rec = R.parse_record(line.strip())
        if rec:
            return rec
    print("bench.py produced no JSON record", file=sys.stderr)
    return None


def main(argv) -> int:
    from spark_rapids_tpu.analysis import regression as R
    if "--fixture" in argv:
        i = argv.index("--fixture")
        if i + 1 >= len(argv):
            print("--fixture requires regression|improvement|obs_tax"
                  "|soak_drift", file=sys.stderr)
            return 2
        return _fixture(argv[i + 1])
    if "--seed-baseline" in argv:
        i = argv.index("--seed-baseline")
        if i + 1 >= len(argv):
            print("--seed-baseline requires a bench record file",
                  file=sys.stderr)
            return 2
        return _seed_baseline(argv[i + 1])
    try:
        base = R.load_baseline(BASELINE_PATH)
    except (OSError, ValueError) as e:
        print(f"cannot load {BASELINE_PATH}: {e}", file=sys.stderr)
        return 2
    if "--run" in argv:
        i = argv.index("--run")
        rows = 200000
        if i + 1 < len(argv) and argv[i + 1].isdigit():
            rows = int(argv[i + 1])
        rec = _run_bench(rows)
        if rec is None:
            return 2
        # scaled-down run: only row-count-independent keys compare
        # meaningfully against the full-size committed baseline
        scoped = dict(base)
        scoped["keys"] = {k: v for k, v in base["keys"].items()
                          if k in _SCALE_INVARIANT
                          or v.get("scale_invariant")}
        print(f"(scaled run: gating {len(scoped['keys'])} "
              "row-count-independent key(s))")
        return _report(R.compare(rec, scoped), rec)
    if "--current" in argv:
        i = argv.index("--current")
        if i + 1 >= len(argv):
            print("--current requires a record file", file=sys.stderr)
            return 2
        path = argv[i + 1]
        try:
            with open(path, "r", encoding="utf-8") as f:
                rec = R.parse_record(json.load(f))
        except (OSError, ValueError) as e:
            print(f"cannot read {path}: {e}", file=sys.stderr)
            return 2
        if not rec:
            print(f"{path}: no bench key set found", file=sys.stderr)
            return 2
        print(f"perf gate: {os.path.basename(path)} vs baseline r"
              f"{base.get('round')}")
        return _report(R.compare(rec, base), rec)
    newest = _newest_round()
    if newest is None:
        print("no BENCH_r*.json rounds found", file=sys.stderr)
        return 2
    print(f"perf gate: BENCH_r{newest.round:02d} vs baseline r"
          f"{base.get('round')}")
    return _report(R.compare(newest.keys, base), newest.keys)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
