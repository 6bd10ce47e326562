#!/usr/bin/env python3
"""Readings that a cell's ``limits`` are set from, many seeds in one
process (set-up is most of a run's cost):

    python chipbench/limits.py --workload <cell> --seeds 11,12,13 --control-seeds 3 [--control-only] [--out file]

For each seed: the cell's tables are generated, a fresh session runs
one cold pass and one scan-cache pass of the cell's queries through
``run.py``'s own ``run_query``, and every answer is compared with the
plain reference (the lower reading: the program's widest gap).  On the
first ``--control-seeds`` seeds the reference computed in the
configuration's ``control_precision`` is put in the program's place
(the upper reading; ``--control-only`` reads nothing else, and needs no
engine).  Prints one JSON line per seed and a summary.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as harness
import reference


def read_seed(cell: dict, seed: int, scale: float, control: bool,
              program: bool = True) -> dict:
    config = cell["config"]
    data_dir = harness.ensure_data(cell["config_name"], config, scale, seed)
    records = []
    if program:
        session = harness.start_engine(config, data_dir)
        for pass_no in range(2):
            for q in config["queries"]:
                rec = harness.run_query(session, q, cell["texts"][q])
                rec["pass"] = pass_no
                records.append(rec)
        del session
        from spark_rapids_tpu.io.scan_cache import DeviceScanCache
        DeviceScanCache.get().clear()
    t0 = time.perf_counter()
    want, _ = reference.answers(cell["config_name"], config["queries"],
                                data_dir, config["precision"])
    ref_s = time.perf_counter() - t0
    out = {"seed": seed, "reference_s": ref_s,
           "errors": [r["error"] for r in records if r["error"]],
           "seconds": {r["name"]: r["seconds"] for r in records
                       if r["pass"] == 1},
           "program": {}}
    for r in records:
        if r["rows"] is None:
            continue
        c = reference.compare(r["rows"], want[r["name"]])
        prev = out["program"].get(r["name"],
                                  {"wrong_cells": 0, "max_rel_gap": 0.0})
        out["program"][r["name"]] = {
            "wrong_cells": prev["wrong_cells"] + c["wrong_cells"],
            "max_rel_gap": max(prev["max_rel_gap"], c["max_rel_gap"])}
    if control:
        low, _ = reference.answers(cell["config_name"], config["queries"],
                                   data_dir, config["control_precision"])
        out["control"] = {q: reference.compare(low[q], want[q])
                          for q in want}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--control-only", action="store_true")
    p.add_argument("--scale", type=float, default=None)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    platform = "host"
    if not args.control_only:
        import jax
        platform = jax.devices()[0].platform
        if platform != "tpu" and args.scale is None:
            print("limits: no TPU; give --scale to rehearse on the CPU "
                  "backend")
            return 2
        if platform != "tpu":
            jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    scale = cell["config"]["scale"] if args.scale is None else args.scale
    readings = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        r = read_seed(cell, seed, scale, i < args.control_seeds,
                      program=not args.control_only)
        readings.append(r)
        print(json.dumps(r), flush=True)
    prog = [max((v["max_rel_gap"] for v in r["program"].values()),
                default=0.0) for r in readings]
    ctrl = [max(v["max_rel_gap"] for v in r["control"].values())
            for r in readings if "control" in r]
    summary = {"workload": args.workload, "platform": platform,
               "scale": scale, "seeds": [r["seed"] for r in readings],
               "program_max_rel_gap_by_seed": prog,
               "program_wrong_cells": sum(
                   v["wrong_cells"] for r in readings
                   for v in r["program"].values()),
               "errors": sum(len(r["errors"]) for r in readings),
               "lower_reading": max(prog) if prog else None,
               "control_max_rel_gap_by_seed": ctrl,
               "control_wrong_cells_by_seed": [
                   sum(v["wrong_cells"] for v in r["control"].values())
                   for r in readings if "control" in r],
               "upper_reading": min(ctrl) if ctrl else None}
    print("SUMMARY " + json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"summary": summary, "readings": readings}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
