"""How far the data decides the order of ``tpcds_sf1_olap``'s answers
(host only, the plain references; ISSUE 34, step 3): for each seed, the
smallest relative gap between neighbouring ``sumsales`` among each q67
partition's first 101 ranks (``rank()`` and the answer's rows hang on
them), and between neighbouring ``sum_sales - avg_monthly_sales`` among
q89's first 101 rows (its first ORDER BY key).  A gap of 0 is a tie the
text does not break.

    python benchmarks/tpcds_olap_gaps.py --seeds 71,4242 [--scale 1.0]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "chipbench")]

CONFIG = "tpcds_sf1_olap"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", required=True)
    p.add_argument("--scale", type=float, default=None)
    args = p.parse_args(argv)
    import reference
    import run as harness
    cell = harness.load_cell(f"{CONFIG}.power")
    config = cell["config"]
    scale = config["scale"] if args.scale is None else args.scale
    num = reference.Num("float64")
    qdir = os.path.join(ROOT, "chipbench", "queries", CONFIG)
    q67 = reference.load_py(os.path.join(qdir, "q67.py"))
    q89 = reference.load_py(os.path.join(qdir, "q89.py"))
    for seed in (int(s) for s in args.seeds.split(",")):
        data_dir = harness.ensure_data(CONFIG, config, scale, seed)
        needs = {}
        for q in ("q67", "q89"):
            for t, cols in reference.query_needs(CONFIG, q).items():
                needs.setdefault(t, set()).update(cols)
        tables, _ = reference.load_tables(data_dir, needs, num)
        keys, _, sums = q67.rollup(tables, num)
        out = {"seed": seed, "scale": scale, "q67": {}}
        for cat in np.unique(keys[0]).tolist():
            top = np.sort(sums[keys[0] == cat])[::-1][:101]
            gaps = (top[:-1] - top[1:]) / np.abs(top[:-1])
            out["q67"][str(cat)] = float(gaps.min()) if len(gaps) else None
        first = [r[6] - r[7] for r in q89.deviating(tables, num)[:101]]
        out["q89"] = min(abs(b - a) / max(abs(a), 1e-300)
                         for a, b in zip(first, first[1:]))
        out["q67_smallest"] = min(v for v in out["q67"].values()
                                  if v is not None)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
