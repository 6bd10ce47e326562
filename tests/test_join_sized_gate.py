"""The superstage join's sizing gate (exec/tpu_join.py
``_SIZED_MIN_CAPACITY``): a partition whose largest stream batch is at
or above the gate takes probe / flush / expand and sizes its outputs; a
smaller one keeps the sync-free speculative probe at the stream's
capacity.  Both give the rows of the pyarrow engine and of the uncarved
plan, the ``join.*`` counters say which path ran, and the two benchmark
metrics that read them return nothing where there is nothing to read."""
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from spark_rapids_tpu.analysis import predict_flushes
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import pending
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.columnar.schema import Field, Schema
from spark_rapids_tpu.exec.tpu_join import TpuHashJoinBase
from spark_rapids_tpu.expr import core as ec
from spark_rapids_tpu.obs import trace

from harness import with_cpu_session, with_tpu_session

GATE = TpuHashJoinBase._SIZED_MIN_CAPACITY
SIDES = {"below": GATE // 2, "at": GATE}
DIM_ROWS = 1000
UNCARVED = {"spark.rapids.tpu.sql.superstage": False}


def _star(s, n):
    """``n`` fact rows against 1,000 unique keys of which a filter
    keeps 5%, under a group-by (the consumer the carve pass needs)."""
    rng = np.random.default_rng(n)
    fact = s.create_dataframe({
        "k": rng.integers(0, DIM_ROWS, n).astype(np.int64),
        "v": np.arange(n, dtype=np.int64)}, num_partitions=1)
    dim = s.create_dataframe({
        "dk": np.arange(DIM_ROWS, dtype=np.int64),
        "w": (np.arange(DIM_ROWS) % 20).astype(np.int64)},
        num_partitions=1)
    return (fact.join(dim.filter(F.col("w") == 3),
                      fact["k"] == dim["dk"], "inner")
            .group_by("w")
            .agg(F.sum("v").alias("sv"), F.count("v").alias("c")))


@pytest.fixture
def join_outputs(monkeypatch):
    """Every batch a hash join yields, in order."""
    outs = []
    orig = TpuHashJoinBase._run_partition

    def spy(self, left_iter, right_iter):
        for out in orig(self, left_iter, right_iter):
            outs.append(out)
            yield out
    monkeypatch.setattr(TpuHashJoinBase, "_run_partition", spy)
    return outs


def _join_counts():
    """The ``join.*`` counters summed over the recorded queries."""
    total = {}
    for tbl in trace.coarse_counts().values():
        for name, n in tbl.items():
            if name.startswith("join."):
                total[name] = total.get(name, 0) + n
    return total


def _carved_run(n, before_warm=lambda: None):
    """One warm collect of the carved plan -> rows, the predicted and
    the observed flushes."""
    def fn(s):
        df = _star(s, n)
        pred = predict_flushes(s._plan(df._plan), conf=s.conf)
        df.collect()                       # cold (compile caches)
        before_warm()
        trace.reset()
        f0 = pending.FLUSH_COUNT
        rows = df.collect()
        return rows, pred.expected(len(rows)), pending.FLUSH_COUNT - f0
    return with_tpu_session(fn)


@pytest.mark.parametrize("side", sorted(SIDES))
def test_rows_equal_the_oracle_and_the_uncarved_plan(side):
    n = SIDES[side]
    want = with_cpu_session(lambda s: _star(s, n).collect())
    on = with_tpu_session(lambda s: _star(s, n).collect())
    off = with_tpu_session(lambda s: _star(s, n).collect(), UNCARVED)
    assert sorted(on) == sorted(want) and len(on) == 1
    assert on == off


@pytest.mark.parametrize("side", sorted(SIDES))
def test_the_path_follows_the_stream_capacity(side, join_outputs):
    n = SIDES[side]
    _carved_run(n, before_warm=join_outputs.clear)
    counts, outs = _join_counts(), join_outputs
    assert len(outs) == 1
    matches = int(outs[0].num_rows)
    assert 0 < matches < n // 10
    if side == "at":
        assert counts.get("join.batches.sized", 0) > 0
        assert counts.get("join.batches.spec", 0) == 0
        assert outs[0].capacity == bucket_capacity(matches)
        assert getattr(outs[0], "_speculative", None) is None
    else:
        assert counts.get("join.batches.spec", 0) > 0
        assert counts.get("join.batches.sized", 0) == 0
        assert outs[0].capacity == n        # the stream's, whatever matched
        assert outs[0]._speculative is not None
    assert counts["join.out_capacity_rows"] == sum(o.capacity for o in outs)


@pytest.mark.parametrize("side", sorted(SIDES))
def test_flush_prediction_holds_below_the_gate(side):
    """The plan cannot know a stream's run-time capacity: the static
    prediction is the speculative path's, and a gated join pays its
    phase-A barrier on top of it."""
    _rows, predicted, observed = _carved_run(SIDES[side])
    assert observed == predicted + (1 if side == "at" else 0)


# ---------------------------------------------------------------------------
# one partition, two stream batches: the operator decides once
# ---------------------------------------------------------------------------

def _armed_join():
    sschema = Schema([Field("k", T.INT64, True), Field("v", T.INT64, True)])
    bschema = Schema([Field("dk", T.INT64, True), Field("w", T.INT64, True)])
    logical = SimpleNamespace(
        join_type="inner", condition=None,
        left_keys=[ec.AttributeReference("k", T.INT64)],
        right_keys=[ec.AttributeReference("dk", T.INT64)],
        schema=Schema(list(sschema.fields) + list(bschema.fields)))
    j = TpuHashJoinBase(logical, SimpleNamespace(output_schema=sschema),
                        SimpleNamespace(output_schema=bschema))
    j._superstage = True
    return j


def _partition(capacities):
    rng = np.random.default_rng(3)
    stream = [ColumnarBatch.from_numpy(
        {"k": rng.integers(0, DIM_ROWS, cap).astype(np.int64),
         "v": np.arange(cap, dtype=np.int64)}) for cap in capacities]
    dk = np.arange(0, DIM_ROWS, 20, dtype=np.int64)         # 5% of the keys
    build = ColumnarBatch.from_numpy({"dk": dk, "w": dk * 7})
    return stream, build


@pytest.mark.parametrize("capacities,path", [
    ((GATE, GATE // 2), "sized"),
    ((GATE // 2, GATE), "sized"),
    ((GATE // 2, GATE // 4), "spec"),
])
def test_one_decision_per_partition(capacities, path):
    def fn(_session):
        stream, build = _partition(capacities)
        trace.reset()
        trace.begin_query()
        outs = list(_armed_join()._run_partition(iter(stream),
                                                 iter([build])))
        return stream, outs
    stream, outs = with_tpu_session(fn)
    counts = _join_counts()
    other = "spec" if path == "sized" else "sized"
    assert counts[f"join.batches.{path}"] == len(capacities)
    assert f"join.batches.{other}" not in counts
    assert counts["join.out_capacity_rows"] == sum(o.capacity for o in outs)
    assert len(outs) == len(stream)
    for sb, out in zip(stream, outs):
        k = np.asarray(sb.columns[0].data)[:sb.num_rows]
        keep = k % 20 == 0
        n = int(out.num_rows)
        assert n == int(keep.sum())
        assert out.capacity == (bucket_capacity(n) if path == "sized"
                                else sb.capacity)
        got = out.to_pydict()
        assert got["k"] == k[keep].tolist() and got["dk"] == got["k"]
        assert got["v"] == np.flatnonzero(keep).tolist()
        assert got["w"] == (k[keep] * 7).tolist()


# ---------------------------------------------------------------------------
# the two benchmark metrics that read the counters (chipbench/metrics/)
# ---------------------------------------------------------------------------

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
RUN = {"queries": [{"done": 0.2, "seconds": 0.1},
                   {"done": 0.4, "seconds": 0.1}],
       "peaks": {"hbm_gbps": 1}}


@pytest.fixture
def bench(monkeypatch):
    """``chipbench/``'s harness and span reduction, importable."""
    monkeypatch.syspath_prepend(CHIPBENCH)
    import run as harness
    import span_reduce
    yield SimpleNamespace(harness=harness, span_reduce=span_reduce)
    for name in ("run", "span_reduce", "reference"):
        sys.modules.pop(name, None)


def _window(counts):
    return {"spans": [], "self_ns": {}, "n_queries": 2, "counts": counts}


@pytest.mark.parametrize("metric,counts,want", [
    ("spec_join_batches_per_query",
     {1: {"join.batches.spec": 3, "join.batches.sized": 6,
          "eager.column_gather": 40},
      2: {"join.batches.spec": 2}}, 2.5),
    # every batch sized: 0 is a reading
    ("spec_join_batches_per_query", {1: {"join.batches.sized": 9}}, 0.0),
    # an engine without the counters (the parent), or no join at all
    ("spec_join_batches_per_query", {1: {"eager.column_gather": 40}}, None),
    ("join_out_capacity_rows_per_query",
     {1: {"join.out_capacity_rows": 1 << 20, "join.batches.sized": 1},
      2: {"join.out_capacity_rows": 2048}}, ((1 << 20) + 2048) / 2),
    ("join_out_capacity_rows_per_query", {1: {"jit_build.x": 2}}, None),
])
def test_join_counter_metrics(bench, monkeypatch, metric, counts, want):
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, _window(counts)])
    assert bench.harness.metric_reader(metric)(RUN) == want


@pytest.mark.parametrize("metric", ["spec_join_batches_per_query",
                                    "join_out_capacity_rows_per_query"])
def test_join_counter_metrics_without_a_window(bench, monkeypatch, metric):
    """A rehearsal (no chip: ``peaks`` is None) has no window."""
    monkeypatch.setattr(bench.span_reduce, "_LAST", [None, None])
    run = dict(RUN, peaks=None)
    assert bench.span_reduce.window(run) is None
    assert bench.harness.metric_reader(metric)(run) is None
