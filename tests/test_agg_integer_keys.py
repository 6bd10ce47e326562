"""Integer-family group-by keys land on a one-program path, whichever
takes them.  A key of each type the bucket table admits
(``TpuHashAggregate._table_key_ok``), with and without a filter folded
into the aggregate, in batches of 4,096 slots through a PARTIAL
aggregate: the bucket-table core where the batch reaches
``sql.agg.tableSize``, the whole-stage / grouped core (what a table
misfit is redone on) where it does not.  Each case equals the pyarrow
engine row for row and counts no ``agg.batches.eager``."""
import datetime
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from harness import _compare_rows, _row_key
from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import trace

ROWS = 6000                 # two partitions of 3,000 rows: 4,096 slots
TABLE_SIZE = "spark.rapids.tpu.sql.agg.tableSize"


def _keys(kind, rng):
    """``ROWS`` keys of 37 distinct values and some NULLs."""
    ids = rng.integers(0, 37, ROWS)
    null = rng.integers(0, 50, ROWS) == 0

    def arr(values, typ):
        return pa.array([None if n else v for v, n in zip(values, null)],
                        typ)
    if kind == "bool":
        return arr([bool(i % 2) for i in ids], pa.bool_())
    if kind == "date":
        day0 = datetime.date(1995, 6, 17)
        return arr([day0 + datetime.timedelta(days=int(i) * 31)
                    for i in ids], pa.date32())
    if kind == "timestamp":
        t0 = datetime.datetime(2001, 2, 3, 4, 5, 6)
        return arr([t0 + datetime.timedelta(hours=int(i) * 7)
                    for i in ids], pa.timestamp("us"))
    if kind == "decimal64":
        return arr([Decimal(int(i) * 125 - 2000) / 100 for i in ids],
                   pa.decimal128(12, 2))
    return arr([int(i) - 18 for i in ids], getattr(pa, kind)())


def _table(kind):
    rng = np.random.default_rng(len(kind))
    return pa.table({
        "k": _keys(kind, rng),
        "x": pa.array(rng.uniform(-1e4, 1e4, ROWS), pa.float64()),
        "n": pa.array(rng.integers(-500, 500, ROWS), pa.int64()),
        "ship": pa.array(rng.integers(0, 100, ROWS), pa.int64()),
    })


def _collect(enabled, data, filtered, conf):
    s = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": enabled,
                            **conf}))
    df = s.create_dataframe(data, num_partitions=2)
    if filtered:
        df = df.filter(F.col("ship") <= 90)
    rows = df.group_by("k").agg(
        F.sum(F.col("x")).alias("sx"), F.avg(F.col("x")).alias("ax"),
        F.min(F.col("n")).alias("mn"), F.count("*").alias("c")).collect()
    return s, rows


@pytest.mark.parametrize("path", ["table", "sort"])
@pytest.mark.parametrize("filtered", [False, True],
                         ids=["plain", "folded_filter"])
@pytest.mark.parametrize("kind", ["int8", "int16", "int32", "int64", "bool",
                                  "date", "timestamp", "decimal64"])
def test_integer_family_key_is_one_program(kind, filtered, path):
    data = _table(kind)
    # a table larger than any batch here: the table core declines
    conf = {TABLE_SIZE: 1 << 20} if path == "sort" else {}
    _, want = _collect(False, data, filtered, conf)
    s, got = _collect(True, data, filtered, conf)
    _compare_rows(sorted(want, key=_row_key), sorted(got, key=_row_key))
    plan = s.last_physical_plan.tree_string()
    assert "Cpu" not in plan, plan
    assert "partial" in plan, plan
    counts = max((q, t) for q, t in trace.coarse_counts().items()
                 if q is not None)[1]
    assert counts.get("agg.batches.eager", 0) == 0, counts
    # the FINAL side's merge is the grouped core on either path
    assert counts.get("agg.batches.fused", 0) > 0, counts
    if path == "table":
        assert counts.get("agg.batches.table", 0) > 0, counts
    else:
        assert counts.get("agg.batches.table", 0) == 0, counts
