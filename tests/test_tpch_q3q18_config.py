"""The TPC-H customer / orders / lineitem Q3/Q18 deployment (chipbench
configuration ``tpch_q3q18``, cell ``tpch_q3q18.power``) at test size on
the CPU backend: the generator keeps dbgen's key structure and the
stated row counts; a whole rehearsal of the cell comes out ``correct``,
the float32 control and a run with a ``lineitem`` row group missing
under the engine do not; with the planner's broadcast thresholds lowered
to what SF5 crosses both plans stay on the device, Q18's three-way join
shuffles, and the counters this deployment added (``exchange.*``,
``join.adaptive.*``, ``join.build_rows``, ``agg.table.misfit``,
``agg.groups_capacity``) read what the plan implies, from whichever
thread counted; the four metric readers return nothing where there is
nothing to read."""
import importlib
import os
import sys
from types import SimpleNamespace

import numpy as np
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec.adaptive import TpuAdaptiveShuffledJoin
from spark_rapids_tpu.io.scan_cache import DeviceScanCache
from spark_rapids_tpu.obs import trace
from spark_rapids_tpu.plan import overrides

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
CELL = "tpch_q3q18.power"
SCALE = 0.01                    # 1,500 customers, 15,000 orders, 60,000 lines
SEED = 2147483659
TABLES = ["customer", "lineitem", "orders"]
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
ADAPTIVE_BYTES = "spark.rapids.tpu.sql.adaptive.autoBroadcastJoinBytes"
NEW_METRICS = ["exchange_rows_per_query", "exchange_device_ms_per_query",
               "agg_table_misfits_per_query", "join_build_rows_per_query"]


@pytest.fixture(scope="module")
def bench():
    """``chipbench/``'s harness, reference, span reduction and the
    generator, importable."""
    sys.path.insert(0, CHIPBENCH)
    import reference
    import run as harness
    import span_reduce
    tpch3 = importlib.import_module("datagen.tpch3")
    yield SimpleNamespace(harness=harness, reference=reference,
                          span_reduce=span_reduce, tpch3=tpch3)
    sys.path.remove(CHIPBENCH)
    for name in ("run", "span_reduce", "reference", "datagen",
                 "datagen.tpch3"):
        sys.modules.pop(name, None)


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):
    """The cell, its tables at test size and the reference's answers."""
    cell = bench.harness.load_cell(CELL)
    config = cell["config"]
    data_dir = str(tmp_path_factory.mktemp("tpch_q3q18"))
    rows = bench.tpch3.generate(data_dir, SCALE, SEED, TABLES)
    want, _ = bench.reference.answers(
        cell["config_name"], config["queries"], data_dir,
        config["precision"])
    tables = {t: papq.read_table(os.path.join(data_dir, f"{t}.parquet"))
              for t in TABLES}
    return SimpleNamespace(cell=cell, config=config, data_dir=data_dir,
                           want=want, rows=rows, tables=tables)


@pytest.fixture(autouse=True)
def _fresh_scan_cache():
    DeviceScanCache.get().clear()
    yield
    DeviceScanCache.get().clear()


# ---------------------------------------------------------------------------
# (a) the generator: dbgen's key structure, the stated row counts
# ---------------------------------------------------------------------------

def test_one_to_seven_lines_an_order_clustered_by_key(deployment):
    key = deployment.tables["lineitem"].column("l_orderkey").to_numpy()
    assert (np.diff(key) >= 0).all()            # an order's lines adjoin
    orders, lines = np.unique(key, return_counts=True)
    assert lines.min() == 1 and lines.max() == 7
    assert set(lines) == set(range(1, 8))
    okey = deployment.tables["orders"].column("o_orderkey").to_numpy()
    assert (orders == okey).all()               # every order has its lines
    assert len(key) == 4 * len(okey)


def test_order_keys_are_sparse(deployment):
    okey = deployment.tables["orders"].column("o_orderkey").to_numpy()
    assert (np.diff(okey) > 0).all()
    assert (okey % 32 < 8).all()                # 8 keys kept of every 32
    assert okey[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 32, 33]
    assert okey[-1] >= 4 * len(okey) - 32


def test_no_order_of_a_customer_whose_key_divides_by_three(bench, deployment):
    cust = deployment.tables["orders"].column("o_custkey").to_numpy()
    n = deployment.rows["customer"]
    assert (cust % 3 != 0).all()
    assert cust.min() >= 1 and cust.max() <= n
    assert len(np.unique(cust)) > 0.6 * n       # uniform over the other two
    ckey = deployment.tables["customer"].column("c_custkey").to_numpy()
    assert (ckey == np.arange(1, n + 1)).all()
    names = deployment.tables["customer"].column("c_name").to_pylist()
    assert names[0] == "Customer#000000001" and len(set(names)) == n
    assert set(deployment.tables["customer"].column(
        "c_mktsegment").to_pylist()) == set(bench.tpch3.SEGMENTS)
    assert len(bench.tpch3.SEGMENTS) == 5 and "BUILDING" in bench.tpch3.SEGMENTS


def test_totalprice_is_the_sum_over_the_orders_lines(bench, deployment):
    """From the generator's own chunks, which hold the ``l_tax`` no
    file gets; the files hold those chunks' other columns."""
    n = bench.tpch3.row_counts(SCALE)
    (orders, lines), = bench.tpch3.chunks(n, SEED)
    cents = np.round(lines["l_extendedprice"] * 100).astype(np.int64)
    full = cents * np.round(100 + 100 * lines["l_tax"]).astype(np.int64) \
        * np.round(100 - 100 * lines["l_discount"]).astype(np.int64)
    first = np.flatnonzero(np.r_[True, np.diff(lines["l_orderkey"]) != 0])
    total = np.add.reduceat(full, first)
    assert (orders["o_totalprice"] == ((total + 5000) // 10000) / 100).all()
    assert (orders["o_shippriority"] == 0).all()
    ship = lines["l_shipdate"] - np.repeat(
        orders["o_orderdate"], np.diff(np.r_[first, len(full)]))
    assert ship.min() >= 1 and ship.max() <= 121
    assert orders["o_orderdate"].min() >= 8035
    assert orders["o_orderdate"].max() <= 10440
    assert 1 <= lines["l_quantity"].min() and lines["l_quantity"].max() <= 50
    assert lines["l_discount"].max() <= 0.10 and lines["l_tax"].max() <= 0.08
    written = deployment.tables["lineitem"]
    assert "l_tax" not in written.column_names
    for name in written.column_names:
        assert (written.column(name).to_numpy() == lines[name]).all()
    for name in deployment.tables["orders"].column_names:
        assert (deployment.tables["orders"].column(name).to_numpy()
                == orders[name]).all()


@pytest.mark.parametrize("scale,want", [
    (5, {"customer": 750_000, "orders": 7_500_000, "lineitem": 30_000_000}),
    (2, {"customer": 300_000, "orders": 3_000_000, "lineitem": 12_000_000}),
    (1, {"customer": 150_000, "orders": 1_500_000, "lineitem": 6_000_000}),
])
def test_row_counts_by_arithmetic(bench, deployment, scale, want):
    assert bench.tpch3.row_counts(scale) == want
    # the shuffle of a balanced multiset: 4 lines an order, any seed
    small = bench.tpch3.lines_per_order(7 * 13 + scale, seed=scale)
    assert small.sum() == 4 * len(small)
    if scale == deployment.config["scale"]:
        assert want == {t: v["rows"]
                        for t, v in deployment.config["tables"].items()}


def test_two_seeds_differ_one_seed_repeats_and_a_table_stands_alone(
        bench, deployment, tmp_path):
    again, other = str(tmp_path / "again"), str(tmp_path / "other")
    os.makedirs(again)
    os.makedirs(other)
    # lineitem alone gets the rows the whole schema would
    assert bench.tpch3.generate(again, SCALE, SEED, ["lineitem"]) == \
        {"lineitem": deployment.rows["lineitem"]}
    assert sorted(os.listdir(again)) == ["lineitem.parquet"]
    same = papq.read_table(os.path.join(again, "lineitem.parquet"))
    assert same.equals(deployment.tables["lineitem"])
    assert bench.tpch3.generate(other, SCALE, SEED + 1, TABLES) == \
        deployment.rows
    for t in TABLES:
        differs = papq.read_table(os.path.join(other, f"{t}.parquet"))
        assert differs.num_rows == deployment.tables[t].num_rows
        assert not differs.equals(deployment.tables[t])
    with pytest.raises(KeyError):
        bench.tpch3.generate(other, SCALE, SEED, ["part"])


# ---------------------------------------------------------------------------
# (b) the cell by name, a whole rehearsal, the control, a lost row group
# ---------------------------------------------------------------------------

def test_the_cell_resolves_by_name(bench, deployment):
    cell, config = deployment.cell, deployment.config
    assert cell["config_name"] == config["name"] == "tpch_q3q18"
    assert cell["chips"] == 1 and config["schema"] == "tpch3"
    assert cell["mix"]["generator"] == "closed_loop"
    assert config["queries"] == ["q3", "q18"]
    assert len(config["source"]) <= 200
    assert sorted(config["reduced"]) == ["columns", "scale_factor",
                                         "tables"]
    assert config["engine_conf"] == {
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.test.enabled": True}
    assert config["limits"]["wrong_cells"] == 0
    assert "ordering" in config["guarantees"]
    assert set(NEW_METRICS) <= {m["name"] for m in cell["per_layer"]}
    for other in ("tpcds_sf1_store.power", "tpch_sf5_q1q6.power"):
        theirs = bench.harness.load_cell(other)["per_layer"]
        assert not set(NEW_METRICS) & {m["name"] for m in theirs}
    for name in NEW_METRICS:
        assert callable(bench.harness.metric_reader(name))
    assert len(deployment.want["q3"]) == 10
    assert 1 <= len(deployment.want["q18"]) <= 100


@pytest.mark.parametrize("traced", [False, True])
def test_a_rehearsal_of_the_cell_is_correct(
        bench, deployment, tmp_path, monkeypatch, traced):
    monkeypatch.setattr(bench.harness, "DATA_DIR", str(tmp_path))
    result = bench.harness.run_cell(deployment.cell, SEED, 0.3, traced,
                                    scale=SCALE, device=dict(DEVICE))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert list(result)[-1] == "compared"
    if traced:
        # no chip: nothing of the device trace, the spans or the counters
        assert not set(NEW_METRICS) & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"queries_per_hour",
                                          "query_p95_s", "setup_s"}


def test_the_float32_control_is_not_correct(bench, deployment):
    config = deployment.config
    low, _ = bench.reference.answers(
        deployment.cell["config_name"], config["queries"],
        deployment.data_dir, config["control_precision"])
    run = {"queries": [{"name": q, "rows": low[q]}
                       for q in deployment.want]}
    compared = bench.harness.verdict(run, deployment.want,
                                     config["limits"])
    assert compared["max_rel_gap"]["value"] > \
        3 * compared["max_rel_gap"]["limit"]
    # Q18's one or two rows at this size can hold prices a float32 holds
    # exactly; Q3's sums never do
    assert not all(r["verified"] for r in run["queries"])
    assert not run["queries"][0]["verified"]


def test_a_lineitem_row_group_lost_under_the_engine_is_not_correct(
        bench, deployment, tmp_path, monkeypatch):
    """The engine scans a ``lineitem`` file one row group short; the
    reference reads the whole."""
    monkeypatch.setattr(bench.harness, "DATA_DIR", str(tmp_path))
    real = bench.harness.start_engine

    def short(config, data_dir):
        short_dir = os.path.join(data_dir, "short")
        os.makedirs(short_dir, exist_ok=True)
        for t in config["tables"]:
            table = papq.read_table(os.path.join(data_dir, f"{t}.parquet"))
            if t == "lineitem":
                groups = [table.slice(i, 8192)
                          for i in range(0, table.num_rows, 8192)]
                del groups[3]
                with papq.ParquetWriter(
                        os.path.join(short_dir, f"{t}.parquet"),
                        table.schema) as w:
                    for g in groups:
                        w.write_table(g)
            else:
                papq.write_table(table,
                                 os.path.join(short_dir, f"{t}.parquet"))
        return real(config, short_dir)
    monkeypatch.setattr(bench.harness, "start_engine", short)
    result = bench.harness.run_cell(deployment.cell, SEED, 0.3, False,
                                    scale=SCALE, device=dict(DEVICE))
    assert result["failed"] == 0 and result["correct"] is False
    assert result["compared"]["wrong_cells"]["value"] > 0


# ---------------------------------------------------------------------------
# (c) the plans SF5 gets, and what the new counters read under them
# ---------------------------------------------------------------------------

@pytest.fixture
def sf5_thresholds(monkeypatch):
    """``customer`` broadcasts and ``orders`` does not, as at SF5
    (750,000 rows under 2^20, 7,500,000 over); Q3's filtered build side
    falls under the runtime threshold and Q18's, which carries
    ``c_name``, over it."""
    monkeypatch.setattr(overrides, "BROADCAST_ROW_THRESHOLD", 2000)
    return {ADAPTIVE_BYTES: 200_000}


def _session(dep, tmp_path, **conf):
    settings = dict(dep.config["engine_conf"])
    settings["spark.rapids.tpu.memory.spill.dir"] = str(tmp_path / "spill")
    settings.update(conf)
    s = TpuSession(TpuConf(settings))
    for t in TABLES:
        s.read.parquet(os.path.join(dep.data_dir, f"{t}.parquet")) \
            .create_or_replace_temp_view(t)
    return s


def _run(bench, dep, session, q):
    """One query through the harness's ``run_query``, compared with the
    reference -> (its one counter table, its adaptive joins)."""
    trace.reset()
    rec = bench.harness.run_query(session, q, dep.cell["texts"][q])
    assert rec["error"] is None             # no CPU operator, no fallback
    c = bench.reference.compare(rec["rows"], dep.want[q])
    assert c["wrong_cells"] == 0
    assert c["max_rel_gap"] <= dep.config["limits"]["max_rel_gap"]
    tables = trace.coarse_counts()
    # every counter in ONE table, the query's: none stranded on a
    # pipeline worker's own number
    assert len(tables) == 1
    (qno, counts), = tables.items()
    spans = [s for s in trace.coarse_spans() if s["name"] == "srt.query"]
    assert [s["query"] for s in spans] == [qno]
    joins = [n for n in session.last_physical_plan.collect_nodes()
             if isinstance(n, TpuAdaptiveShuffledJoin)]
    return counts, joins


def test_q3_at_sf5_thresholds(bench, deployment, tmp_path, sf5_thresholds):
    s = _session(deployment, tmp_path, **sf5_thresholds)
    counts, joins = _run(bench, deployment, s, "q3")
    assert [j.strategy for j in joins] == ["broadcast"]
    assert counts["join.adaptive.broadcast"] == 1
    assert "join.adaptive.shuffled" not in counts
    # only the build side (customer x orders under both filters) is
    # materialized through an exchange; lineitem never shuffles
    o = deployment.tables["orders"]
    seg = dict(zip(*[deployment.tables["customer"].column(c).to_pylist()
                     for c in ("c_custkey", "c_mktsegment")]))
    build = sum(1 for k, d in zip(o.column("o_custkey").to_pylist(),
                                  o.column("o_orderdate").to_pylist())
                if d < 9204 and seg[k] == "BUILDING")
    assert counts["exchange.rows"] == build
    assert counts["exchange.batches"] >= 1
    # customer's side of the first join, then the converted join's
    assert counts["join.build_rows"] >= build
    assert counts["agg.groups_capacity"] >= len(deployment.want["q3"])
    names = {sp["name"] for sp in trace.coarse_spans()}
    assert "srt.exchange.map" in names


def test_q18_at_sf5_thresholds(bench, deployment, tmp_path, sf5_thresholds):
    s = _session(deployment, tmp_path, **sf5_thresholds)
    counts, joins = _run(bench, deployment, s, "q18")
    by_type = {j.logical.join_type: j.strategy for j in joins}
    assert by_type == {"inner": "shuffled", "semi": "broadcast"}
    assert counts["join.adaptive.shuffled"] == 1
    assert counts["join.adaptive.broadcast"] == 1
    # both sides of the three-way join cross the exchange, and the
    # subquery's survivors as the semi join's build side
    li = deployment.tables["lineitem"]
    qsum = {}
    for k, q in zip(li.column("l_orderkey").to_pylist(),
                    li.column("l_quantity").to_pylist()):
        qsum[k] = qsum.get(k, 0.0) + q
    survivors = sum(1 for v in qsum.values() if v > 300)
    assert survivors == len(deployment.want["q18"])
    assert counts["exchange.rows"] == deployment.rows["orders"] + \
        deployment.rows["lineitem"] + survivors
    # 15,000 groups in one batch do not fit the 4,096-bucket table: the
    # batch is computed again on the sort path, once
    assert counts["agg.batches.table"] == 1
    assert counts["agg.table.misfit"] == 1
    assert counts["join.build_rows"] >= deployment.rows["orders"]
    assert counts["agg.groups_capacity"] >= len(qsum)
    # the map side's span carries the query's number
    spans = [sp for sp in trace.coarse_spans()
             if sp["name"] == "srt.exchange.map"]
    assert len(spans) == 3
    assert {sp["query"] for sp in spans} == \
        {sp["query"] for sp in trace.coarse_spans()
         if sp["name"] == "srt.query"}


def test_counts_made_on_pipeline_workers_land_in_the_querys_table(
        tmp_path):
    """Four map partitions drained by pool workers (``drain_parallel``):
    the exchange's, the join's and the aggregate's counts all land in
    the draining query's table."""
    s = TpuSession(TpuConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.test.enabled": True,
        "spark.rapids.tpu.memory.spill.dir": str(tmp_path / "spill"),
        "spark.rapids.tpu.sql.pipeline.parallelism": 4,
        "spark.rapids.tpu.sql.adaptive.enabled": True,
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
        ADAPTIVE_BYTES: 1}))
    n = 4000
    left = s.create_dataframe(
        {"k": list(range(n)), "v": [float(i % 7) for i in range(n)]},
        num_partitions=4)
    right = s.create_dataframe(
        {"k2": list(range(0, n, 2)), "w": [1.0] * (n // 2)},
        num_partitions=4)
    from spark_rapids_tpu.api import functions as F
    trace.reset()
    rows = left.join(right, left["k"] == right["k2"]) \
        .group_by("v").agg(F.sum("w")).collect()
    assert sum(r[1] for r in rows) == n // 2
    tables = trace.coarse_counts()
    assert len(tables) == 1
    (counts,) = tables.values()
    if "join.adaptive.shuffled" in counts:
        assert counts["exchange.rows"] >= n + n // 2
        assert counts["exchange.batches"] >= 8
    assert counts["join.build_rows"] > 0
    assert counts["agg.groups_capacity"] > 0


# ---------------------------------------------------------------------------
# (c2) what the first SF5 run forced: a concat compiles by capacity, not
# by row count (ROADMAP S9: 431 programs missed the cache in one cold Q3)
# ---------------------------------------------------------------------------

def _pieces(rows_a, rows_b, strings):
    """Two batches of capacities 64 and 32 holding ``rows_a`` and
    ``rows_b`` live rows, nulls among them."""
    from spark_rapids_tpu.columnar import ColumnarBatch
    out = []
    for base, n, cap in ((0, rows_a, 64), (1000, rows_b, 32)):
        data = {"k": [None if i % 5 == 3 else base + i for i in range(n)],
                "v": [float(base + i) / 4 for i in range(n)]}
        if strings:
            data["s"] = [None if i % 7 == 2 else f"name-{base + i:05d}"
                         for i in range(n)]
        schema = out[0].schema if out else None
        out.append(ColumnarBatch.from_pydict(data, schema=schema,
                                             capacity=cap))
    return out


@pytest.mark.parametrize("strings", [False, True],
                         ids=["fixed_width", "with_a_string"])
def test_a_concat_compiles_by_capacity_not_by_row_count(strings):
    from spark_rapids_tpu.columnar import batch as cbatch
    from spark_rapids_tpu.columnar import concat_batches
    first = _pieces(40, 20, strings)
    out = concat_batches(first)
    assert out.num_rows == 60 and out.capacity == 64
    want = {name: first[0].to_pydict()[name] + first[1].to_pydict()[name]
            for name in first[0].to_pydict()}
    assert out.to_pydict() == want
    programs = set(cbatch._CONCAT_JIT)
    builds = sum(n for tbl in trace.coarse_counts().values()
                 for k, n in tbl.items() if k.startswith("jit_build.batch_"))
    # other row counts in the same capacities (and the same output
    # bucket): no new program, the same answer
    for a, b in ((33, 31), (64, 0), (1, 32), (17, 19)):
        again = _pieces(a, b, strings)
        got = concat_batches(again)
        assert got.num_rows == a + b
        assert got.to_pydict() == {
            name: again[0].to_pydict()[name] + again[1].to_pydict()[name]
            for name in want}
        if got.capacity == out.capacity:
            assert set(cbatch._CONCAT_JIT) == programs
        # past the live rows the lanes are cleared, as a pad clears them
        col = got.columns[0]
        assert not np.asarray(col.validity)[a + b:].any()
        assert not np.asarray(col.data)[a + b:].any()
    assert builds == sum(
        n for tbl in trace.coarse_counts().values()
        for k, n in tbl.items() if k.startswith("jit_build.batch_"))


def test_the_generator_refuses_an_engine_that_concats_by_row_counts(
        bench, monkeypatch, tmp_path):
    """The parent of PR 32 under this PR's benchmark files ends before
    any data is written, with an exit code other than 0; this engine
    generates."""
    from spark_rapids_tpu.columnar import batch as cbatch
    bench.tpch3.refuse_engine_before_pr32()
    monkeypatch.setattr(cbatch, "_concat_plain_jit", lambda *a: None,
                        raising=False)
    with pytest.raises(SystemExit) as e:
        bench.tpch3.generate(str(tmp_path), SCALE, SEED, TABLES)
    assert e.value.code not in (0, None) and os.listdir(tmp_path) == []


# the cold start's repairs: no cumsum and no stable flag sort in a program

@pytest.mark.parametrize("n", [1, 2, 7, 64, 1000, 4096])
@pytest.mark.parametrize("dtype", ["int32", "int64"])
def test_prefix_sum_is_cumsum(n, dtype):
    import jax.numpy as jnp
    from spark_rapids_tpu.kernels.basic import prefix_sum
    x = np.random.default_rng(n).integers(-9, 1 << 20, n).astype(dtype)
    got = prefix_sum(jnp.asarray(x))
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.cumsum(x))


@pytest.mark.parametrize("n", [1, 5, 64, 1000, 4096])
def test_filter_compact_indices_is_the_stable_argsort(n):
    import jax.numpy as jnp
    from spark_rapids_tpu.kernels import basic
    rng = np.random.default_rng(n)
    keep = rng.integers(0, 2, n).astype(bool)
    rows = int(rng.integers(0, n + 1))
    order, count = basic.filter_compact_indices(jnp.asarray(keep),
                                                jnp.int32(rows))
    live = keep & (np.arange(n) < rows)
    assert order.dtype == jnp.int64 and int(count) == live.sum()
    np.testing.assert_array_equal(
        np.asarray(order), np.argsort(np.where(live, 0, 1), kind="stable"))


def test_the_join_expansion_and_the_group_plan_hold_no_cumsum():
    """``cumsum`` compiled for 28-62 s at 2^20 elements on the chip's
    compiler; the programs of the cell's cold start carry none."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.kernels import aggregate, join

    def names(fn, *args, **kw):
        found = set()

        def walk(jaxpr):
            for e in jaxpr.eqns:
                found.add(e.primitive.name)
                for v in e.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            walk(inner)
        walk(jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args).jaxpr)
        return found

    i32 = jnp.zeros(256, jnp.int32)
    expand = names(join.join_expand_matches.__wrapped__, i32, i32, i32,
                   out_cap=512)
    plan = names(lambda w, ok: aggregate.seg_count(
        aggregate.groupby_plan([w]), ok),
        jnp.zeros(256, jnp.uint64), jnp.ones(256, bool))
    for found in (expand, plan):
        assert not {"cumsum", "reduce_window_sum", "reduce_window"} & found


def test_a_concat_of_many_pieces_keeps_their_order():
    """Thirty pieces of mixed capacities, as a reduce partition reads
    them: later pieces overwrite the slack of earlier ones."""
    from spark_rapids_tpu.columnar import ColumnarBatch, concat_batches
    rng = np.random.default_rng(5)
    pieces, want = [], []
    for i in range(30):
        n = int(rng.integers(0, 33))
        cap = 32 if i % 3 else 128
        vals = [int(v) for v in rng.integers(0, 1 << 40, n)]
        want += vals
        pieces.append(ColumnarBatch.from_pydict(
            {"k": vals}, schema=pieces[0].schema if pieces else None,
            capacity=cap))
    out = concat_batches(pieces)
    assert out.to_pydict() == {"k": want}


# ---------------------------------------------------------------------------
# (d) the four metric readers on hand-made runs
# ---------------------------------------------------------------------------

#: two passes of q3, q18; the traced pass is the first
RUN = {"queries": [{"done": 0.2, "seconds": 0.1},
                   {"done": 0.4, "seconds": 0.1},
                   {"done": 0.6, "seconds": 0.1},
                   {"done": 0.8, "seconds": 0.1}],
       "peaks": {"hbm_gbps": 1},
       "trace": {"queries": ["q3", "q18"], "busy_s": 3.0, "window_s": 4.0,
                 "device_ops": [["jit_join_probe_core", 1.0],
                                ["jit_partition_split", 0.5],
                                ["jit_partition_hash_ids", 0.25]]}}


def _window(counts):
    return {"spans": [], "self_ns": {}, "n_queries": 4, "counts": counts}


OLD = {"eager.column_gather": 40, "join.batches.sized": 29,
       "agg.batches.fused": 30}


@pytest.mark.parametrize("metric,counts,want", [
    ("exchange_rows_per_query",
     {7: dict(OLD, **{"exchange.rows": 700, "exchange.batches": 4}),
      8: dict(OLD, **{"exchange.rows": 37_500, "exchange.batches": 40}),
      9: dict(OLD, **{"exchange.rows": 700, "exchange.batches": 4}),
      10: dict(OLD, **{"exchange.rows": 37_500, "exchange.batches": 40})},
     19_100.0),
    # every map batch was empty: 0 rows crossed is a reading
    ("exchange_rows_per_query",
     {7: dict(OLD, **{"exchange.rows": 0, "exchange.batches": 4})}, 0.0),
    # an engine without the counters (the parent), or no shuffle
    ("exchange_rows_per_query", {7: dict(OLD), 8: dict(OLD)}, None),
    ("agg_table_misfits_per_query",
     {7: dict(OLD, **{"agg.table.misfit": 1}),
      8: dict(OLD, **{"agg.table.misfit": 1}),
      9: dict(OLD, **{"agg.table.misfit": 0}),
      10: dict(OLD)}, 0.5),
    # every table batch fit: the engine added 0, which is a reading
    ("agg_table_misfits_per_query",
     {7: dict(OLD, **{"agg.table.misfit": 0})}, 0.0),
    ("agg_table_misfits_per_query", {7: dict(OLD), 8: dict(OLD)}, None),
    ("join_build_rows_per_query",
     {7: dict(OLD, **{"join.build_rows": 1 << 20}),
      8: dict(OLD, **{"join.build_rows": 9 << 20})}, 10 * (1 << 20) / 4),
    ("join_build_rows_per_query", {7: dict(OLD), 8: dict(OLD)}, None),
])
def test_counter_metrics(bench, monkeypatch, metric, counts, want):
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, _window(counts)])
    got = bench.harness.metric_reader(metric)(RUN)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_metrics_without_a_window_or_a_trace(bench, monkeypatch, metric):
    """A rehearsal (no chip: ``peaks`` is None) has no window, and an
    untraced run no device trace."""
    monkeypatch.setattr(bench.span_reduce, "_LAST", [None, None])
    run = dict(RUN, peaks=None, trace=None)
    assert bench.harness.metric_reader(metric)(run) is None


def test_exchange_device_ms_reads_the_partition_programs(bench):
    read = bench.harness.metric_reader("exchange_device_ms_per_query")
    assert read(RUN) == pytest.approx(750.0 / 2)
    # off the top-ten list: a lower bound of 0, still a reading
    only_join = dict(RUN, trace=dict(
        RUN["trace"], device_ops=[["jit_join_probe_core", 1.0]]))
    assert read(only_join) == 0.0
    # an engine that does not name its programs
    unnamed = dict(RUN, trace=dict(RUN["trace"],
                                   device_ops=[["jit__take", 1.0]]))
    assert read(unnamed) is None
