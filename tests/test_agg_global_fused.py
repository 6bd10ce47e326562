"""Global aggregates (no group keys) run each batch as one program that
reduces over a row mask (``exec/tpu_aggregate.py`` ``_fused_global_core``
/ ``_global_agg`` over ``kernels/aggregate.single_group_plan``): the
filter / project chain folds into the mask, nothing is sorted, gathered
into order or scattered into one slot.  Each case equals the pyarrow
engine (or numpy, for ``stddev``, which the pyarrow engine cannot
compute without keys), counts ``agg.global.folded`` once a batch and no
``agg.batches.eager``.  The structural cases hold the core's jaxpr and
that the grouped whole-stage core's is the one it was."""
import math
import statistics

import jax
import numpy as np
import pyarrow as pa
import pytest

from harness import _compare_rows
from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.columnar.schema import Field, Schema
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec import tpu_aggregate as TA
from spark_rapids_tpu.expr import aggregates as ea
from spark_rapids_tpu.expr import core as ec
from spark_rapids_tpu.expr.arithmetic import Multiply
from spark_rapids_tpu.expr.predicates import LessThan
from spark_rapids_tpu.obs import trace
from spark_rapids_tpu.plan.logical import AggExpr

BATCH_ROWS = "spark.rapids.tpu.sql.batchSizeRows"


def _table(n=700, seed=37):
    """Q6-like rows, and ``v``: a DOUBLE with NULLs, NaN, -0.0 and 0.0
    among ordinary values."""
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n)
    v = rng.uniform(-1e3, 1e3, n).tolist()
    for i in range(0, n, 11):
        v[i] = None
    for i, x in zip(range(3, n, 97), [math.nan, -0.0, 0.0, math.inf]):
        v[i] = x
    return pa.table({
        "q": pa.array(qty.astype(float), pa.float64()),
        "p": pa.array(qty * rng.uniform(900.0, 2100.0, n), pa.float64()),
        "d": pa.array(rng.integers(0, 11, n) / 100.0, pa.float64()),
        "s": pa.array(rng.integers(0, 100, n), pa.int64()),
        "v": pa.array(v, pa.float64())})


def _filtered(df, keep=60):
    """Q6's shape: a filter and a projection ahead of the aggregate."""
    return df.filter(F.col("s") < keep).select(
        (F.col("p") * F.col("d")).alias("x"), F.col("v"), F.col("s"))


# name -> the aggregate, beside count(*)
FUNCS = {
    "sum": lambda: F.sum(F.col("x")),
    "sum_int": lambda: F.sum(F.col("s")),
    "count_x": lambda: F.count("v"),
    "min": lambda: F.min(F.col("v")),
    "max": lambda: F.max(F.col("v")),
    "min_int": lambda: F.min(F.col("s")),
    "max_int": lambda: F.max(F.col("s")),
    "avg": lambda: F.avg(F.col("x")),
    "first": lambda: F.first("v"),
    "first_with_nulls": lambda: F.first("v", ignore_nulls=False),
    "last": lambda: F.last("v"),
    "last_with_nulls": lambda: F.last("v", ignore_nulls=False),
}

# name -> (rows, rows the filter keeps below, partitions, batch rows)
INPUTS = {
    "one_batch": (700, 60, 1, None),
    "many_batches_and_a_final_merge": (700, 60, 2, 128),
    "filter_keeps_nothing": (700, -1, 2, 128),
    "empty_input": (0, 60, 1, None),
}


def _session(enabled, batch_rows=None):
    settings = {"spark.rapids.tpu.sql.enabled": enabled}
    if batch_rows:
        settings[BATCH_ROWS] = batch_rows
    return TpuSession(TpuConf(settings))


def _newest_counts():
    # the newest query's table (counts made outside any query sit
    # under None)
    return max((q, t) for q, t in trace.coarse_counts().items()
               if q is not None)[1]


def _run(enabled, func, inputs):
    n, keep, partitions, batch_rows = INPUTS[inputs]
    s = _session(enabled, batch_rows)
    df = _filtered(s.create_dataframe(_table(n), num_partitions=partitions),
                   keep)
    rows = df.agg(FUNCS[func]().alias("r"),
                  F.count("*").alias("n")).collect()
    return s, rows


def _kept_in_order(inputs):
    """``v`` over the rows the filter keeps, in input order."""
    n, keep = INPUTS[inputs][:2]
    t = _table(n)
    return [v for v, s in zip(t["v"].to_pylist(), t["s"].to_pylist())
            if s < keep]


@pytest.mark.parametrize("inputs", sorted(INPUTS))
@pytest.mark.parametrize("func", sorted(FUNCS))
def test_global_aggregate_over_a_mask(func, inputs):
    if func.endswith("_with_nulls"):
        # the pyarrow engine skips NULLs whatever the flag says
        kept = _kept_in_order(inputs)
        want = [(kept[0 if func.startswith("first") else -1]
                 if kept else None, len(kept))]
    else:
        _, want = _run(False, func, inputs)
    s, got = _run(True, func, inputs)
    _compare_rows(want, got)
    assert len(got) == 1
    plan = s.last_physical_plan.tree_string()
    assert "Cpu" not in plan, plan
    counts = _newest_counts()
    assert counts.get("agg.global.folded", 0) > 0, counts
    assert counts.get("agg.batches.eager", 0) == 0, counts
    if INPUTS[inputs][1] < 0 or INPUTS[inputs][0] == 0:
        # one row: count 0, every other result NULL
        assert got == [(0 if func == "count_x" else None, 0)], got


@pytest.mark.parametrize("func", ["stddev", "stddev_pop"])
def test_stddev_over_a_mask(func):
    """The pyarrow engine cannot compute a key-less ``stddev``: numpy's
    over the rows the filter keeps, through several batches and a
    merge."""
    data = _table(900)
    v = [1e8 + (x if x is not None and math.isfinite(x) else 0.5)
         for x in data["v"].to_pylist()]
    data = data.set_column(4, "v", pa.array(v, pa.float64()))
    s = _session(True, 128)
    df = _filtered(s.create_dataframe(data, num_partitions=2))
    (got, n), = df.agg(getattr(F, func)("v").alias("r"),
                       F.count("*").alias("n")).collect()
    kept = [x for x, k in zip(v, data["s"].to_pylist()) if k < 60]
    want = (statistics.stdev if func == "stddev" else statistics.pstdev)(
        kept)
    assert n == len(kept)
    assert got == pytest.approx(want, rel=1e-9)
    assert _newest_counts().get("agg.global.folded", 0) > 0


@pytest.mark.parametrize("partitions,batch_rows", [(1, None), (1, 128),
                                                   (2, 128), (3, 100)])
def test_folded_once_a_batch(partitions, batch_rows):
    """``agg.global.folded`` is +1 for every input batch the global core
    ran under its mask, beside ``agg.batches.fused``; nothing eager."""
    n = 700
    s = _session(True, batch_rows)
    df = _filtered(s.create_dataframe(_table(n), num_partitions=partitions))
    df.agg(F.sum(F.col("x")).alias("r")).collect()
    counts = _newest_counts()
    per_part = [len(p) for p in np.array_split(np.arange(n), partitions)]
    batches = sum(math.ceil(k / (batch_rows or k)) for k in per_part)
    assert counts.get("agg.global.folded") == batches, counts
    assert counts.get("agg.batches.fused", 0) >= batches, counts
    assert counts.get("agg.batches.eager", 0) == 0, counts


# -- the structure of the cores -----------------------------------------------

CAP = 4096                  # the batch's capacity: distinct from the
                            # output's (1024) and from any count here


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _every_function(value, group_exprs):
    """An update over (v, s, k): Q6's filter (s < 60) and every function
    the cores fuse on ``value``, keyed by ``group_exprs``."""
    ref = {n: ec.BoundReference(i, dt, True, n) for i, (n, dt) in
           enumerate([("v", T.FLOAT64), ("s", T.INT64), ("k", T.INT64)])}
    schema = Schema([Field(n, r.dtype(), True) for n, r in ref.items()])
    x = Multiply(ref[value], ref[value]) if value == "v" else ref[value]
    agg = object.__new__(TA.TpuHashAggregate)
    agg.group_exprs = [ref[k] for k in group_exprs]
    agg.aggs = [AggExpr(f, f"a{i}") for i, f in enumerate([
        ea.Sum(x), ea.Count(), ea.Count(ref["v"]), ea.Min(x), ea.Max(x),
        ea.Average(x), ea.First(x), ea.Last(x, False), ea.StddevSamp(x)])]
    agg.mode = TA.PARTIAL
    agg.pre_ops = [("filter", LessThan(ref["s"], ec.lit(60)), schema)]
    agg._ws_memo = {}
    rng = np.random.default_rng(3)
    cols = [Column.from_numpy(rng.uniform(-9, 9, 40), T.FLOAT64),
            Column.from_numpy(rng.integers(0, 99, 40), T.INT64),
            Column.from_numpy(rng.integers(0, 4, 40), T.INT64)]
    return agg, ColumnarBatch(schema, cols, 40)


def _jaxpr_of_new_core(agg, batch):
    """The jaxpr of the core ``agg``'s update of ``batch`` built."""
    before = set(TA.TpuHashAggregate._CORE_CACHE)
    agg._aggregate_batch(batch, TA.PARTIAL)
    core, = [c for k, c in TA.TpuHashAggregate._CORE_CACHE.items()
             if k not in before]
    assert core is not False, "the core failed and fell back"
    sds = [jax.ShapeDtypeStruct((CAP,), dt)
           for dt in (np.float64, np.int64, np.int64)]
    valids = (jax.ShapeDtypeStruct((CAP,), np.bool_),) * 3
    return jax.make_jaxpr(core)(tuple(sds), valids,
                                jax.ShapeDtypeStruct((), np.int32))


@pytest.mark.parametrize("value", ["v", "s"])
def test_global_core_sorts_gathers_and_scatters_nothing(value):
    agg, batch = _every_function(value, [])
    eqns = list(_equations(_jaxpr_of_new_core(agg, batch).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert "sort" not in names
    assert not [n for n in names if n.startswith("scatter")], names
    wide = [e for e in eqns if e.primitive.name == "gather"
            and e.outvars[0].aval.shape[:1] == (CAP,)]
    assert wide == []
    # the DOUBLE sums are a tree of full float64 adds, not a scan
    assert "cumsum" not in names and "reduce_precision" not in names


@pytest.mark.parametrize("value", ["v", "s"])
def test_grouped_whole_stage_core_keeps_its_program(value):
    """The same functions by a key: the grouped whole-stage core traces
    what it traced before the one-group plan existed (the counts of
    its sorts, scatters and capacity-sized gathers; its jaxpr was the
    same text)."""
    agg, batch = _every_function(value, ["k"])
    eqns = list(_equations(_jaxpr_of_new_core(agg, batch).jaxpr))
    sorts = [e for e in eqns if e.primitive.name == "sort"]
    scatters = [e for e in eqns if e.primitive.name.startswith("scatter")]
    gathers = [e for e in eqns if e.primitive.name == "gather"
               and e.outvars[0].aval.shape[:1] == (CAP,)]
    assert (len(sorts), len(scatters), len(gathers)) == \
        GROUPED_COUNTS[value]


#: value -> (sorts, scatters, capacity-sized gathers) of the grouped
#: core above, as the tree before the one-group plan traced it
GROUPED_COUNTS = {"v": (3, 8, 31), "s": (3, 10, 35)}
