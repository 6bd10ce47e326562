"""Soak-run CLI: drive the sustained mixed-traffic harness
(service/soak.py) from the command line and write the SoakReport.

    python -m spark_rapids_tpu.tools.soak --duration 60 --qps 20 \
        --out soak.json --chaos

    python -m spark_rapids_tpu.tools.soak --queries 200 --qps 50 \
        --fault 2.0:kill_pipeline_worker --fault 4.0:poison_query

The run's artifacts land where the confs point: ``--history-dir``
(fleet rows), ``--event-log`` (fault + terminal events, the input to
``tools/report.py --soak``) and ``--diag-dir`` (per-fault bundles).
Defaults put all three in a fresh temp directory, printed on exit.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

from ..service.faults import FAULT_KINDS, build_schedule
from ..service.soak import SoakConfig, run_soak


def _parse_fault(spec: str):
    try:
        at, kind = spec.split(":", 1)
        at = float(at)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"fault spec {spec!r} is not AT_SECONDS:KIND")
    if kind not in FAULT_KINDS:
        raise argparse.ArgumentTypeError(
            f"unknown fault kind {kind!r}; expected one of "
            f"{', '.join(FAULT_KINDS)}")
    return (at, kind)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="spark_rapids_tpu.tools.soak",
        description="sustained mixed-traffic soak through QueryService")
    p.add_argument("--duration", type=float, default=30.0,
                   help="run length in seconds (ignored with --queries)")
    p.add_argument("--queries", type=int, default=0,
                   help="exact submission count (deterministic runs)")
    p.add_argument("--qps", type=float, default=20.0,
                   help="open-loop target submissions/second")
    p.add_argument("--rows", type=int, default=4096)
    p.add_argument("--partitions", type=int, default=2)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--tenants", default="tenant-a,tenant-b,tenant-c")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--fault", action="append", type=_parse_fault,
                   default=[], metavar="AT:KIND",
                   help="inject KIND at AT seconds (repeatable); kinds: "
                        + ", ".join(FAULT_KINDS))
    p.add_argument("--chaos", action="store_true",
                   help="seeded default schedule: one fault of each "
                        "kind spread over the middle of the run")
    p.add_argument("--slo-target-ms", type=float, default=0.0,
                   help="obs.slo.targetMs for breach/burn accounting")
    p.add_argument("--out", default="",
                   help="write the SoakReport JSON here")
    p.add_argument("--history-dir", default="")
    p.add_argument("--event-log", default="")
    p.add_argument("--diag-dir", default="")
    p.add_argument("--quiet", action="store_true")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax
    from ..api import TpuSession
    from ..config import TpuConf
    td = tempfile.mkdtemp(prefix="soak_")
    history_dir = args.history_dir or os.path.join(td, "history")
    event_log = args.event_log or os.path.join(td, "events.jsonl")
    diag_dir = args.diag_dir or os.path.join(td, "diag")
    confs = {
        "spark.rapids.tpu.obs.history.dir": history_dir,
        "spark.rapids.tpu.eventLog.path": event_log,
        "spark.rapids.tpu.obs.diagnostics.dir": diag_dir,
    }
    if args.slo_target_ms > 0:
        confs["spark.rapids.tpu.obs.slo.targetMs"] = args.slo_target_ms
    session = TpuSession(TpuConf(confs))
    faults = list(args.fault)
    if args.chaos:
        span = (args.queries / args.qps
                if args.queries else args.duration)
        faults += build_schedule(args.seed, span)
    cfg = SoakConfig(
        duration_s=args.duration, total_queries=args.queries,
        qps=args.qps, rows=args.rows, partitions=args.partitions,
        tenants=[t for t in args.tenants.split(",") if t],
        seed=args.seed, faults=faults, num_workers=args.workers)

    last = {"n": -1}

    def _tick(t):
        if args.quiet or t["completed"] == last["n"]:
            return
        last["n"] = t["completed"]
        sys.stderr.write(
            f"\rt+{t['elapsed_s']:7.1f}s  submitted={t['submitted']} "
            f"completed={t['completed']} shed={t['shed']} "
            f"inflight={t['inflight']} "
            f"faults={t['faults_fired']}"
            + (f" ACTIVE:{','.join(t['active_faults'])}"
               if t["active_faults"] else "") + "   ")
        sys.stderr.flush()
    report = run_soak(session, cfg, on_tick=_tick)
    if not args.quiet:
        sys.stderr.write("\n")
    d = report.to_dict()
    tot, lat = d["totals"], d["latency"]
    print(f"soak: {tot['completed']}/{tot['submitted']} completed, "
          f"{tot['shed']} shed, {tot['failed']} failed, "
          f"{tot['sha_mismatch']} sha mismatches over "
          f"{tot['duration_s']}s ({tot['qps_actual']} qps)")
    print(f"latency: p50={lat['p50_ms']}ms p95={lat['p95_ms']}ms "
          f"p99={lat['p99_ms']}ms; shed_rate={d['shed_rate_pct']}%")
    st = d["steady"]
    print(f"steady-state: {'YES' if st['steady'] else 'no'} "
          f"(converged {st['converge_count']}x, losses {st['losses']}, "
          f"slope {st['slope_pct']}%); "
          f"leak_drift={d['leak_drift_bytes']}B")
    for w in d["faults"]:
        print(f"fault {w['id']}: t+{w['at_s']}s "
              f"p99 {w['p99_before_ms']} -> {w['p99_during_ms']} -> "
              f"{w['p99_after_ms']}ms, "
              f"recovered={'yes' if w['recovered'] else 'NO'}"
              + (f" in {w['recovery_s']}s" if w["recovery_s"] else "")
              + (f", bundle={w['diag_bundle']}"
                 if w["diag_bundle"] else ""))
    print(f"artifacts: history={history_dir} events={event_log} "
          f"diag={diag_dir}")
    if args.out:
        report.write(args.out)
        print(f"report: {args.out}")
    bad = (tot["failed"] or tot["sha_mismatch"]
           or any(not w["recovered"] for w in d["faults"]))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
