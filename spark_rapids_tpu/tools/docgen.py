"""Self-generated documentation.

Reference: RapidsConf.help/main -> docs/configs.md (RapidsConf.scala:1229)
and SupportedOpsDocs -> docs/supported_ops.md (TypeChecks.scala:1611).

Usage: python -m spark_rapids_tpu.tools.docgen [output_dir]
"""
from __future__ import annotations

import jax as _jax

# host-side CLI: never touch the accelerator backend
_jax.config.update("jax_platforms", "cpu")

import os
import sys

from ..config import generate_docs
from ..plan import overrides as ov
from ..plan import typesig as TS

TS_CAST_FAMILIES = ["bool", "integral", "fp", "decimal", "string",
                    "date", "timestamp", "null"]


def supported_ops_doc() -> str:
    lines = [
        "# Supported expressions on TPU",
        "",
        "Generated from the planner's expression registry "
        "(plan/overrides.py), the analogue of the reference's "
        "supported_ops.md generated from TypeChecks.scala.",
        "",
        "| Expression | Signature (per-parameter where declared) | "
        "Notes |",
        "|---|---|---|",
    ]
    for cls, sig in sorted(ov._EXPR_RULES.items(),
                           key=lambda kv: kv[0].__name__):
        note = getattr(sig, "note", "") or ""
        lines.append(f"| `{cls.__name__}` | {sig.describe()} | {note} |")
    lines += [
        "",
        "# Cast support matrix",
        "",
        "CAST pairs the TPU engine implements (absent pairs fall back "
        "to the CPU engine; TypeChecks.scala:367 CastChecks role):",
        "",
        "| from \\\\ to | " + " | ".join(TS_CAST_FAMILIES) + " |",
        "|---|" + "---|" * len(TS_CAST_FAMILIES),
    ]
    for src in TS_CAST_FAMILIES:
        row = [f"| {src} "]
        for dst in TS_CAST_FAMILIES:
            ok = (src, dst) in TS.CAST_MATRIX or src == dst
            row.append("| S " if ok else "|   ")
        lines.append("".join(row) + "|")
    lines += [
        "",
        "# Supported operators on TPU",
        "",
        "| Logical operator | TPU physical operator | Notes |",
        "|---|---|---|",
        "| LocalRelation | TpuLocalScan | |",
        "| Range | TpuRange | |",
        "| Scan (parquet/orc/csv/json) | TpuFileScan | PERFILE / "
        "MULTITHREADED / COALESCING reader strategies |",
        "| Project | TpuProject | |",
        "| Filter | TpuFilter | |",
        "| Aggregate | TpuHashAggregate | partial/final around exchanges; "
        "sort+segmented-reduce design |",
        "| Distinct | TpuHashAggregate | keys-only aggregate |",
        "| Join | TpuShuffledHashJoin / TpuBroadcastHashJoin / "
        "TpuNestedLoopJoin | inner/left/right/full/semi/anti/cross |",
        "| Sort | TpuSort (+ RangePartitioner exchange for global) | |",
        "| Limit | TpuLocalLimit + TpuGlobalLimit; TopN fusion over "
        "Sort+Limit | |",
        "| Union | TpuUnion | |",
        "| Repartition | TpuShuffleExchange (hash / round-robin) | |",
        "| Window | TpuWindow | row frames; rank/dense_rank/row_number/"
        "lead/lag/sum/count/min/max/avg |",
        "| Expand | TpuExpand | grouping sets |",
        "| WriteFile | TpuFileWrite | parquet/orc/csv |",
        "",
        "The planner (`plan/logical_opt.py`) rewrites a WHERE over joins "
        "before a physical operator is chosen: each conjunct that reads "
        "one side of an inner or cross join filters that side, a "
        "cross-side equality becomes a hash-join key, and another "
        "two-sided conjunct becomes the equi join's residual condition "
        "(decided over the candidate pairs, at most "
        "`spark.rapids.tpu.sql.join.gather.chunkRows` pairs a launch; a "
        "join with no key keeps it as a Filter above).  A conjunct that "
        "reads only a LEFT (RIGHT) OUTER join's preserved side filters "
        "that side first, through a chain of outer joins (Spark's "
        "PushPredicateThroughJoin); one that reads the null-supplying "
        "side stays above, and a FULL join takes none.  An outer join's "
        "ON conjunct on its null-supplying side alone filters that side.  "
        "`date + int`, `int + date` and `date - int` are `date_add` / "
        "`date_sub`, and a STRING compared with a DATE is cast to one "
        "(`yyyy`, `yyyy-[m]m`, `yyyy-[m]m-[d]d`), as in Spark.",
        "",
        "Unsupported constructs fall back to the CPU (pyarrow) engine "
        "per-operator with automatic RowToColumnar/ColumnarToRow "
        "transitions; `spark.rapids.tpu.sql.explain=NOT_ON_TPU` prints "
        "the reasons.",
    ]
    return "\n".join(lines) + "\n"


def main(argv=None):
    argv = argv or sys.argv[1:]
    out_dir = argv[0] if argv else "docs"
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "configs.md"), "w") as f:
        f.write(generate_docs())
    with open(os.path.join(out_dir, "supported_ops.md"), "w") as f:
        f.write(supported_ops_doc())
    print(f"wrote {out_dir}/configs.md and {out_dir}/supported_ops.md")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
