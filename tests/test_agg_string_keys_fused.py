"""String-keyed group-bys run their per-batch update and their merge as
one program (``exec/tpu_aggregate.py`` ``_fused_whole_stage_core`` /
``_fused_agg_core``, string keys as packed words): each case equals the
pyarrow engine row for row, counts ``agg.batches.fused`` and no
``agg.batches.eager``, and launches no eager gather or segment sum from
inside the aggregate.  One structural case lowers the core for TPC-H
Q1's shape and holds the number of capacity-sized gathers and
scatter-adds in it; two more hold the benchmark metric that reads the
counters (``chipbench/metrics/agg_eager_batches_per_query.py``)."""
import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pyarrow as pa
import pytest

from harness import _compare_rows, _row_key
from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.columnar.schema import Field, Schema
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec import tpu_aggregate as TA
from spark_rapids_tpu.expr import aggregates as ea
from spark_rapids_tpu.expr import core as ec
from spark_rapids_tpu.expr.predicates import LessThanOrEqual
from spark_rapids_tpu.obs import trace
from spark_rapids_tpu.plan.logical import AggExpr

AGG_SITES = ("eager.column_gather", "eager.seg_sum_scatter")
BATCH_ROWS = "spark.rapids.tpu.sql.batchSizeRows"
COMPACT_ROWS = "spark.rapids.tpu.sql.agg.speculativeCompactRows"
FLAGS = ["A", "N", "R", None]
LONG = ["a key of more than one word", "a key of more than one wore",
        "a key of more than sixteen bytes, and then some", "short"]


def _table(n=600, seed=29, keys=FLAGS, null_inputs=False):
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 50, n).astype(float)
    price = rng.uniform(900.0, 1e5, n)
    return pa.table({
        "flag": pa.array([keys[i] for i in rng.integers(0, len(keys), n)],
                         pa.string()),
        "status": pa.array([("F", "O")[i] for i in rng.integers(0, 2, n)],
                           pa.string()),
        "year": pa.array(rng.integers(1998, 2002, n), pa.int64()),
        "brand_id": pa.array(rng.integers(0, 7, n), pa.int64()),
        "qty": pa.array([None if null_inputs and i % 7 == 0 else q
                         for i, q in enumerate(qty)], pa.float64()),
        "price": pa.array([None if null_inputs and i % 11 == 0 else p
                           for i, p in enumerate(price)], pa.float64()),
        "disc": pa.array(rng.integers(0, 11, n) / 100.0, pa.float64()),
        "ship": pa.array(rng.integers(0, 100, n), pa.int64()),
    })


def _q1(df, keys=("flag", "status"), keep=90):
    return df.filter(F.col("ship") <= keep).group_by(*keys).agg(
        F.sum(F.col("qty")).alias("sum_qty"),
        F.sum(F.col("price") * (1 - F.col("disc"))).alias("sum_disc"),
        F.avg(F.col("qty")).alias("avg_qty"),
        F.avg(F.col("disc")).alias("avg_disc"),
        F.min(F.col("price")).alias("min_price"),
        F.count("*").alias("n"))


def _many_groups(n=400):
    return {"name": [f"group-{i % 300:04d}" for i in range(n)],
            "v": [float(i) for i in range(n)]}


# name -> (table, query, conf, partitions)
CASES = {
    "one_string_key": (_table(), lambda df: _q1(df, ("flag",)), {}, 1),
    "two_string_keys": (_table(), _q1, {}, 1),
    "string_beside_integer_keys": (
        _table(), lambda df: _q1(df, ("year", "brand_id", "flag")), {}, 1),
    "null_keys": (_table(keys=[None, "A", None, "N"]), _q1, {}, 1),
    "null_inputs": (_table(null_inputs=True), _q1, {}, 1),
    "key_longer_than_one_word": (_table(keys=LONG), _q1, {}, 1),
    "empty_batch": (_table(n=0), _q1, {}, 1),
    "filter_keeps_nothing": (_table(), lambda df: _q1(df, keep=-1), {}, 1),
    # 3.3 'Z' rows in 600: most batches of 64 hold none
    "group_missing_from_a_batch": (
        _table(keys=["A"] * 90 + ["N"] * 90 + ["Z"]), _q1,
        {BATCH_ROWS: 64}, 1),
    # 300 groups a batch against room for 16: the fit flag fails and the
    # update is redone uncompacted
    "more_groups_than_compact_rows": (
        _many_groups(), lambda df: df.group_by("name").agg(
            F.sum(F.col("v")).alias("s"), F.count("*").alias("n")),
        {COMPACT_ROWS: 16}, 1),
    "partial_final_around_a_shuffle": (_table(), _q1, {BATCH_ROWS: 128}, 3),
}


def _collect(enabled, data, query, conf, partitions):
    settings = {"spark.rapids.tpu.sql.enabled": enabled}
    settings.update(conf)
    s = TpuSession(TpuConf(settings))
    df = query(s.create_dataframe(data, num_partitions=partitions))
    return s, df.collect()


@pytest.mark.parametrize("name", sorted(CASES))
def test_string_keyed_group_by_is_one_program(name, monkeypatch):
    data, query, conf, partitions = CASES[name]
    _, want = _collect(False, data, query, conf, partitions)

    inside = {site: 0 for site in AGG_SITES}
    depth = [0]
    real = TA.TpuHashAggregate._aggregate_batch

    def counted(self, *args, **kwargs):
        def now():
            tbl = trace.coarse_counts().get(trace.current_query(), {})
            return {site: tbl.get(site, 0) for site in AGG_SITES}
        before = now() if depth[0] == 0 else None
        depth[0] += 1
        try:
            return real(self, *args, **kwargs)
        finally:
            depth[0] -= 1
            if before is not None:
                for site, n in now().items():
                    inside[site] += n - before[site]
    monkeypatch.setattr(TA.TpuHashAggregate, "_aggregate_batch", counted)

    s, got = _collect(True, data, query, conf, partitions)
    _compare_rows(sorted(want, key=_row_key), sorted(got, key=_row_key))
    plan = s.last_physical_plan.tree_string()
    assert "Cpu" not in plan, plan
    # the newest query's table (a count made outside any query, by an
    # earlier test on this worker, sits under None)
    counts = max((q, t) for q, t in trace.coarse_counts().items()
                 if q is not None)[1]
    assert counts.get("agg.batches.fused", 0) > 0, counts
    assert counts.get("agg.batches.eager", 0) == 0, counts
    assert inside == {site: 0 for site in AGG_SITES}, inside
    if name == "partial_final_around_a_shuffle":
        assert "partial" in plan and "final" in plan, plan
    if name == "more_groups_than_compact_rows":
        assert len(got) == 300


# -- the structure of the core for TPC-H Q1's shape --------------------------

CAP = 4096                  # the batch's capacity: distinct from any
SLOTS = 1024                # output capacity and from the key words' count


def _q1_core():
    """The whole-stage core for Q1 (filter on a date, two string keys,
    four sums, three averages and a count over five distinct DOUBLE
    inputs), as ``_fused_whole_stage_core`` caches it, and its argument
    shapes at ``CAP`` rows."""
    names = ["qty", "price", "disc", "tax", "flag", "status", "ship"]
    dts = [T.FLOAT64] * 4 + [T.STRING] * 2 + [T.INT32]
    schema = Schema([Field(n, dt, True) for n, dt in zip(names, dts)])
    ref = {n: ec.BoundReference(i, dt, True, n)
           for i, (n, dt) in enumerate(zip(names, dts))}
    from spark_rapids_tpu.expr.arithmetic import Add, Multiply, Subtract
    disc_price = Multiply(ref["price"], Subtract(ec.lit(1.0), ref["disc"]))
    charge = Multiply(disc_price, Add(ec.lit(1.0), ref["tax"]))
    agg = object.__new__(TA.TpuHashAggregate)
    agg.group_exprs = [ref["flag"], ref["status"]]
    agg.aggs = [AggExpr(f, f"a{i}") for i, f in enumerate([
        ea.Sum(ref["qty"]), ea.Sum(ref["price"]), ea.Sum(disc_price),
        ea.Sum(charge), ea.Average(ref["qty"]), ea.Average(ref["price"]),
        ea.Average(ref["disc"]), ea.Count()])]
    agg.mode = TA.PARTIAL
    agg.pre_ops = [("filter", LessThanOrEqual(ref["ship"], ec.lit(90)),
                    schema)]
    agg._ws_memo = {}
    n = 40
    rng = np.random.default_rng(7)
    cols = [Column.from_numpy(rng.uniform(1, 9, n), T.FLOAT64)
            for _ in range(4)]
    cols += [StringColumn.from_pylist([FLAGS[i % 3] for i in range(n)]),
             StringColumn.from_pylist([("F", "O")[i % 2] for i in range(n)]),
             Column.from_numpy(rng.integers(0, 100, n).astype(np.int32),
                               T.INT32)]
    out = agg._fused_whole_stage_core(ColumnarBatch(schema, cols, n),
                                      emit_buffers=True, out_cap=SLOTS)
    assert out is not None, "Q1's shape fell off the whole-stage core"
    core, = [c for k, c in TA.TpuHashAggregate._CORE_CACHE.items()
             if k[0] == "ws" and k[-3:-1] == (True, SLOTS)]

    def sds(dt):
        return jax.ShapeDtypeStruct((CAP,), dt)
    words = (sds(np.uint64), sds(np.uint64))     # one byte word, length
    datas = (sds(np.float64),) * 4 + (words, words, sds(np.int32))
    valids = (sds(np.bool_),) * 7
    return core, (datas, valids, jax.ShapeDtypeStruct((), np.int32))


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_q1_core_moves_each_row_once():
    core, args = _q1_core()
    eqns = list(_equations(jax.make_jaxpr(core)(*args).jaxpr))
    gathers = [e for e in eqns if e.primitive.name == "gather"
               and e.outvars[0].aval.shape[:1] == (CAP,)]
    scatters = [e for e in eqns
                if e.primitive.name in ("scatter-add", "scatter_add")
                and CAP in (e.invars[0].aval.shape[:1],
                            e.invars[2].aval.shape[:1])]
    # ONE capacity-sized gather: the five distinct DOUBLE inputs (two
    # 32-bit lanes each) and their five validities (one lane of bits),
    # by rows.  The sums are one segmented scan: no scatter-add.
    # (Before PR 29: 99 takes and 7 scatter-adds a batch, one program
    # each.)
    assert [e.invars[0].aval.shape for e in gathers] == [(CAP, 11)]
    assert scatters == []
    sorts = [e for e in eqns if e.primitive.name == "sort"
             and e.outvars[0].aval.shape == (CAP,)]
    # the two string keys' six words merge into one 22-bit word: one
    # pair sort of (uint32 key, row id), and the sort that lists the
    # groups' first rows (one uint32 word a row, sorted alone: PR 32)
    assert [[v.aval.dtype.name for v in e.invars] for e in sorts] == [
        ["uint32", "int32"], ["uint32"]]


# -- the kernels the cores are made of ----------------------------------------

def _lexsort(words):
    return np.lexsort([np.asarray(w) for w in reversed(words)])


@pytest.mark.parametrize("keys", [
    ["flag"], ["flag", "status"], ["long", "flag"], ["year", "flag", "ok"],
    ["ok", "status", "year"]])
def test_merged_key_words_keep_groups_and_order(keys):
    """``canon.group_key_words`` sorts and groups rows exactly as the
    unmerged ``batch_key_words`` do, in fewer words; dead rows last."""
    from spark_rapids_tpu.kernels import canon
    rng = np.random.default_rng(len(keys))
    n, cap = 200, 256
    pool = {
        "flag": StringColumn.from_pylist(
            [FLAGS[i] for i in rng.integers(0, 4, n)], cap),
        "status": StringColumn.from_pylist(
            [("F", "O", "", "OF")[i] for i in rng.integers(0, 4, n)], cap),
        "long": StringColumn.from_pylist(
            [LONG[i] for i in rng.integers(0, 4, n)], cap),
        "year": Column.from_numpy(np.pad(rng.integers(-3, 3, n),
                                         (0, cap - n)), T.INT64),
        "ok": Column(T.BOOL, jax.numpy.asarray(rng.integers(0, 2, cap) > 0),
                     jax.numpy.asarray(rng.integers(0, 5, cap) > 0)),
    }
    cols = [pool[k] for k in keys]
    live = np.arange(cap) < n
    live[rng.integers(0, n, 30)] = False
    plain = canon.batch_key_words(cols, n)
    plain[0] = jax.numpy.where(live, plain[0], jax.numpy.uint64(2))
    packed = []
    for c in cols:
        if c.dtype == T.STRING:
            (words, validity), bound = TA._pack_string_key(c, n)
            c = canon.PackedStringKey(words, validity, bound)
        packed.append(c)
    merged = canon.group_key_words(packed, n, jax.numpy.asarray(live))
    assert len(merged) < len(plain)
    a, b = _lexsort(plain), _lexsort(merged)
    assert a.tolist() == b.tolist()
    same_a = np.all([np.asarray(w)[a][1:] == np.asarray(w)[a][:-1]
                     for w in plain], axis=0)
    same_b = np.all([np.asarray(w)[b][1:] == np.asarray(w)[b][:-1]
                     for w in merged], axis=0)
    alive = live[a][1:]
    assert (same_a == same_b)[alive].all()
    assert not live[a][int(live.sum()):].any()
    if keys == ["flag", "status"]:
        assert [w.dtype.name for w in merged] == ["uint32"]


@pytest.mark.parametrize("dtype,chip_floats", [
    ("int8", False), ("int16", False), ("int32", False), ("int64", False),
    ("uint64", False), ("float32", False), ("float64", False),
    ("float64", True)])
def test_row_gather_moves_every_width_exactly(dtype, chip_floats,
                                              monkeypatch):
    """``gather_rows_once``: any fixed-width array and any number of
    validities, through 32-bit lanes and back, bit for bit.  The chip's
    branch for float64 (a pair of float32s) is exact for what the chip
    can hold: sums of two float32s."""
    from spark_rapids_tpu.kernels import canon, gather as gather_k
    jnp = jax.numpy
    rng = np.random.default_rng(5)
    n = 300
    if dtype.startswith("float"):
        vals = rng.standard_normal(n).astype("float32").astype(dtype)
        if dtype == "float64":
            vals = vals + rng.standard_normal(n).astype("float32") * 1e-5
        vals[:6] = [0.0, -0.0, np.inf, -np.inf, np.nan, np.float32(1e-30)]
    else:
        info = np.iinfo(dtype)
        vals = rng.integers(info.min, info.max, n, dtype=dtype,
                            endpoint=True)
    if chip_floats:
        monkeypatch.setattr(canon, "_f64_bitcast_supported", lambda: False)
    flags = [jnp.asarray(rng.integers(0, 2, n) > 0) for _ in range(35)]
    data = jnp.asarray(vals)
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    moved = gather_k.gather_rows_once(perm, [data] + flags + [data])
    assert len(moved) == 36
    got = np.asarray(moved[id(data)][1])
    want = vals[np.asarray(perm)]
    assert got.dtype == want.dtype
    assert got.tobytes() == want.tobytes()
    for f in flags:
        assert np.asarray(moved[id(f)][1]).tolist() == \
            np.asarray(f)[np.asarray(perm)].tolist()


def _uniform(groups, dead):
    """Three columns of uniform values over ``groups`` sorted segments
    (some ids unused), ``dead`` zero rows after them."""
    rng = np.random.default_rng(groups)
    seg = np.sort(rng.integers(0, max(groups, 1), 1000)) \
        if groups else np.zeros(0, np.int64)
    return rng.uniform(-1e5, 1e5, (3, len(seg))), seg, dead


def _keyed(vals, keys):
    """One column, rows brought into their keys' order."""
    order = np.argsort(np.asarray(keys), kind="stable")
    return np.asarray(vals, np.float64)[None, order], \
        np.asarray(keys)[order], 3


def _wide_exponents():
    rng = np.random.default_rng(8)
    return _keyed(np.ldexp(rng.standard_normal(600),
                           rng.integers(-60, 60, 600)),
                  rng.integers(0, 5, 600))


# name -> (values [k, live rows], sorted segment ids, dead rows)
TOTALS = {
    "1_group": lambda: _uniform(1, 0),
    "4_groups_17_dead": lambda: _uniform(4, 17),
    "300_groups": lambda: _uniform(300, 5),
    "1000_groups": lambda: _uniform(1000, 0),
    "no_live_row": lambda: _uniform(0, 64),
    # the inputs of the pair-sum accumulator's tests (gone with it in
    # PR 30): what it promised, the tree-ordered scan has to keep
    "wide_exponents": _wide_exponents,
    "specials_and_signs": lambda: _keyed(
        [1e30, 1.0, -1e30, np.inf, 3.0, np.nan, 2.0, -0.5, -0.25, -0.25,
         0.0, -0.0, np.inf, -np.inf],
        [0, 0, 0, 1, 1, 2, 3, 3, 4, 4, 5, 5, 6, 6]),
    # +x / -x pairs leave a small residue a group
    "cancellation": lambda: _keyed(
        np.array([1e12, -1e12] * 500) + 1e-3, np.zeros(1000, np.int64)),
    # a 1e38 group beside a 1e-9 group: neither reaches into the other
    "group_isolation": lambda: _keyed([1e38, 1e-9, 1e-9, 3e37, 2e-9],
                                      [0, 1, 1, 0, 1]),
}


@pytest.mark.parametrize("case", sorted(TOTALS))
def test_segmented_totals_equal_a_sum_per_group(case):
    """The stacked segmented scan against ``math.fsum`` a group:
    float64 adds in tree order (an error of at most log2(rows) roundings
    of the group's absolute sum), infinities and NaNs as IEEE adds them,
    dead rows (zeros past the live ones) and empty slots included."""
    import math
    from spark_rapids_tpu.kernels import aggregate as agg_k
    jnp = jax.numpy
    live_vals, seg, dead = TOTALS[case]()
    if len(seg):
        seg = np.unique(seg, return_inverse=True)[1]     # dense ids
    k, live_n = live_vals.shape
    n = live_n + dead
    vals = np.zeros((k, n))
    vals[:, :live_n] = live_vals
    boundary = np.zeros(n, bool)
    if live_n:
        boundary[:live_n] = np.r_[True, seg[1:] != seg[:-1]]
    g = int(boundary.sum())
    slots = 1024
    heads = np.flatnonzero(boundary)
    last = np.zeros(slots, np.int32)
    last[:g] = np.r_[heads[1:] - 1, live_n - 1][:g] if g else []
    plan = SimpleNamespace(boundary=jnp.asarray(boundary),
                           last_pos=jnp.asarray(last), num_slots=slots,
                           num_groups=jnp.int32(g))
    got = np.asarray(agg_k._segmented_totals(plan, jnp.asarray(vals)))
    assert got.shape == (k, slots)
    assert not got[:, g:].any()
    for i in range(k):
        for grp in range(g):
            sel = live_vals[i, seg == grp]
            if np.all(np.isfinite(sel)):
                bound = np.sum(np.abs(sel)) * 2.0 ** -48
                assert abs(got[i, grp] - math.fsum(sel)) <= bound, \
                    (grp, got[i, grp], math.fsum(sel), bound)
            else:
                with np.errstate(invalid="ignore"):
                    want = np.sum(sel)
                assert (np.isnan(got[i, grp]) and np.isnan(want)) or \
                    got[i, grp] == want, (grp, got[i, grp], want)


# -- the benchmark metric that reads the counters ----------------------------

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
RUN = {"peaks": {"hbm_gb_s": 1.0}, "queries": [{"done": 1.0, "seconds": 1.0}]}


@pytest.fixture
def bench(monkeypatch):
    """``chipbench/``'s harness and span reduction, importable."""
    monkeypatch.syspath_prepend(CHIPBENCH)
    import run as harness
    import span_reduce
    yield SimpleNamespace(harness=harness, span_reduce=span_reduce)
    for name in ("run", "span_reduce", "reference"):
        sys.modules.pop(name, None)


@pytest.mark.parametrize("counts,want", [
    ({}, None),                                 # an engine without them
    ({"eager.column_gather": 9}, None),
    ({"agg.batches.fused": 30}, 0.0),           # 0 is a reading
    ({"agg.batches.table": 2, "agg.batches.fused": 1}, 0.0),
    ({"agg.batches.fused": 4, "agg.batches.eager": 6}, 3.0),
])
def test_agg_eager_batches_metric(bench, monkeypatch, counts, want):
    window = {"spans": [], "self_ns": {}, "n_queries": 2,
              "counts": {1: counts}}
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, window])
    read = bench.harness.metric_reader("agg_eager_batches_per_query")
    assert read(RUN) == want


def test_agg_eager_batches_metric_without_a_window(bench, monkeypatch):
    run = {"peaks": None, "queries": []}        # a rehearsal
    monkeypatch.setattr(bench.span_reduce, "_LAST", [None, None])
    assert bench.span_reduce.window(run) is None
    assert bench.harness.metric_reader(
        "agg_eager_batches_per_query")(run) is None
