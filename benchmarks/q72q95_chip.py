"""What TPC-DS q72 and q95 at the ``tpcds_sf1_q72q95`` configuration's
scale do, query by query: seconds, the residual join's candidate
pairs, launches, survivors and bytes (``join.residual.*``), the
conjuncts moved through outer joins (``plan.pushdown.outer``), the
adaptive joins' decisions and the device's peak bytes.  The readings
behind ``PERF.md`` sections 4 and 5 for that configuration.

    python benchmarks/q72q95_chip.py --seed <n> [--passes 2] [--out file]

One process, the normal path (``chipbench/run.py``'s own
``ensure_data`` / ``start_engine`` / ``run_query``): a cold pass, then
``--passes`` warm passes, each query's counters from
``obs.trace.coarse_counts()``.  One JSON line per query run, the whole
report in ``--out`` when it is given.  Refuses
to run anywhere but on a TPU unless ``--rehearse-cpu --scale <s>``
(which prints no reading under a device's name)."""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpcds_sf1_q72q95.power"
PREFIXES = ("join.residual.", "plan.pushdown.", "join.adaptive.",
            "join.batches.", "join.out_capacity_rows")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--passes", type=int, default=2)
    p.add_argument("--out", default=None)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--scale", type=float, default=None)
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "chipbench")]
    import jax
    import run as harness
    from spark_rapids_tpu.obs import trace
    platform = jax.devices()[0].platform
    if platform != "tpu" and not (args.rehearse_cpu and args.scale):
        print("q72q95_chip: no TPU (--rehearse-cpu --scale <s> debugs "
              "the script on the CPU backend)", file=sys.stderr)
        return 2
    if platform != "tpu":
        jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(CELL)
    config = cell["config"]
    scale = config["scale"] if args.scale is None else args.scale
    data_dir = harness.ensure_data(cell["config_name"], config, scale,
                                   args.seed)
    session = harness.start_engine(config, data_dir)
    report = {"platform": platform, "scale": scale, "seed": args.seed,
              "runs": []}
    for pass_no in range(1 + args.passes):
        for q in config["queries"]:
            trace.reset()
            rec = harness.run_query(session, q, cell["texts"][q])
            counts = {}
            for tbl in trace.coarse_counts().values():
                for name, n in tbl.items():
                    if name.startswith(PREFIXES):
                        counts[name] = counts.get(name, 0) + n
            stats = jax.devices()[0].memory_stats() or {}
            line = {"pass": pass_no, "query": q,
                    "seconds": rec["seconds"], "error": rec["error"],
                    "rows": None if rec["rows"] is None
                    else len(rec["rows"]),
                    "counts": counts,
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
            report["runs"].append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
