"""The TPC-DS ROLLUP / window-function deployment (chipbench
configuration ``tpcds_sf1_olap``, cell ``tpcds_sf1_olap.power``: q98,
q89, q67) at test size on the CPU backend: the texts are
``benchmarks/tpcds_queries.py``'s byte for byte; a whole rehearsal of
the cell comes out ``correct``, the float32 control and a run with a
``store_sales`` row group missing under the engine do not; every plan
stays on the device and holds its ``TpuWindow`` (q67 its ``TpuExpand``);
the counters this deployment added (``window.*``, ``expand.*``,
``agg.key_words``) read what the plan implies; the five metric readers
return nothing where there is nothing to read; and the plain references
answer a hand-made dozen rows with a tie in ``sumsales`` and NULL
roll-up keys as the texts say."""
import importlib
import os
import sys
from types import SimpleNamespace

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu.io.scan_cache import DeviceScanCache
from spark_rapids_tpu.obs import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")
CELL = "tpcds_sf1_olap.power"
SCALE = 0.01                    # 28,800 sales, 180 items, 12 stores
SEED = 2147483777
TABLES = ["date_dim", "item", "store", "store_sales"]
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
NEW_METRICS = ["window_device_ms_per_query", "window_hbm_roofline_pct",
               "window_rows_per_query", "expand_rows_per_query",
               "agg_key_words_per_batch"]


@pytest.fixture(scope="module")
def bench():
    """``chipbench/``'s harness, reference, span reduction and the
    generator, importable."""
    sys.path.insert(0, CHIPBENCH)
    import reference
    import run as harness
    import span_reduce
    tpcds = importlib.import_module("datagen.tpcds_olap")
    yield SimpleNamespace(harness=harness, reference=reference,
                          span_reduce=span_reduce, tpcds=tpcds)
    sys.path.remove(CHIPBENCH)
    for name in ("run", "span_reduce", "reference", "datagen",
                 "datagen.tpcds", "datagen.tpcds_olap"):
        sys.modules.pop(name, None)


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):
    """The cell, its tables at test size and the reference's answers."""
    cell = bench.harness.load_cell(CELL)
    config = cell["config"]
    data_dir = str(tmp_path_factory.mktemp("tpcds_sf1_olap"))
    rows = bench.tpcds.generate(data_dir, SCALE, SEED, TABLES)
    want, _ = bench.reference.answers(
        cell["config_name"], config["queries"], data_dir,
        config["precision"])
    return SimpleNamespace(cell=cell, config=config, data_dir=data_dir,
                           want=want, rows=rows)


@pytest.fixture(autouse=True)
def _fresh_scan_cache():
    DeviceScanCache.get().clear()
    yield
    DeviceScanCache.get().clear()


# ---------------------------------------------------------------------------
# (a) the cell by name, a whole rehearsal, the control, a lost row group
# ---------------------------------------------------------------------------

def test_the_cell_resolves_by_name(bench, deployment):
    cell, config = deployment.cell, deployment.config
    assert cell["config_name"] == config["name"] == "tpcds_sf1_olap"
    assert cell["chips"] == 1 and config["schema"] == "tpcds_olap"
    assert config["scale"] == 1.0
    assert cell["mix"]["generator"] == "closed_loop"
    assert config["queries"] == ["q98", "q89", "q67"]     # the heavy one last
    assert len(config["source"]) <= 200
    assert sorted(config["reduced"]) == ["columns", "scale_factor",
                                         "tables"]
    assert config["engine_conf"] == {
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.test.enabled": True}
    assert config["limits"]["wrong_cells"] == 0
    sibling = bench.harness.load_cell("tpcds_sf1_store.power")["config"]
    assert set(sibling["guarantees"]) | {"ordering"} == \
        set(config["guarantees"])
    assert {t: v["rows"] for t, v in config["tables"].items()} == \
        {t: bench.tpcds.row_counts(1.0)[t] for t in TABLES}
    assert set(NEW_METRICS) <= {m["name"] for m in cell["per_layer"]}
    for other in ("tpcds_sf1_store.power", "tpch_sf5_q1q6.power",
                  "tpch_q3q18.power"):
        theirs = bench.harness.load_cell(other)["per_layer"]
        assert not set(NEW_METRICS) & {m["name"] for m in theirs}
    for name in NEW_METRICS:
        assert callable(bench.harness.metric_reader(name))
    assert [len(deployment.want[q]) for q in config["queries"]] == \
        [81, 100, 100]


@pytest.mark.parametrize("query", ["q67", "q89", "q98"])
def test_the_texts_are_the_repos_byte_for_byte(deployment, query):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        import tpcds_queries
    finally:
        sys.path.pop(0)
    assert deployment.cell["texts"][query] == tpcds_queries.QUERIES[query]
    needs = deployment.cell["config"]["tables"]
    import json
    with open(os.path.join(CHIPBENCH, "queries", "tpcds_sf1_olap",
                           f"{query}.json")) as f:
        spec = json.load(f)
    assert spec["template"].startswith(f"TPC-DS query {query[1:]}")
    for table, columns in spec["tables"].items():
        assert set(columns) <= set(needs[table]["columns"])
        assert all(c in deployment.cell["texts"][query] for c in columns)


def test_the_generator_is_the_store_schemas_behind_one_check(
        bench, monkeypatch, tmp_path):
    """The parent of PR 34 under this PR's benchmark files (an eager
    window, q89's second triple lost) ends before any data is written,
    with an exit code other than 0; this engine generates what
    ``datagen/tpcds.py`` does."""
    from spark_rapids_tpu.plan import logical_opt
    store = importlib.import_module("datagen.tpcds")
    assert bench.tpcds.ROWS_PER_SF is store.ROWS_PER_SF
    assert bench.tpcds.row_counts is store.row_counts
    bench.tpcds.refuse_engine_before_pr34()
    ours, theirs = tmp_path / "olap", tmp_path / "store"
    ours.mkdir()
    theirs.mkdir()
    assert bench.tpcds.generate(str(ours), SCALE, SEED, ["store"]) == \
        store.generate(str(theirs), SCALE, SEED, ["store"])
    assert papq.read_table(str(ours / "store.parquet")).equals(
        papq.read_table(str(theirs / "store.parquet")))
    monkeypatch.delattr(logical_opt, "_same_as")
    refused = tmp_path / "refused"
    refused.mkdir()
    with pytest.raises(SystemExit) as e:
        bench.tpcds.generate(str(refused), SCALE, SEED, TABLES)
    assert e.value.code not in (0, None) and os.listdir(refused) == []


@pytest.mark.parametrize("traced", [False, True])
def test_a_rehearsal_of_the_cell_is_correct(
        bench, deployment, tmp_path, monkeypatch, traced):
    monkeypatch.setattr(bench.harness, "DATA_DIR", str(tmp_path))
    result = bench.harness.run_cell(deployment.cell, SEED, 0.3, traced,
                                    scale=SCALE, device=dict(DEVICE))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert list(result)[-1] == "compared"
    assert list(result["per_query_s"]) == ["q98", "q89", "q67"]
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
    # every query sums prices no float32 holds
    assert not any(r["verified"] for r in run["queries"])


def test_a_store_sales_row_group_lost_under_the_engine_is_not_correct(
        bench, deployment, tmp_path, monkeypatch):
    """The engine scans a ``store_sales`` file one row group short; the
    reference reads the whole."""
    monkeypatch.setattr(bench.harness, "DATA_DIR", str(tmp_path))
    real = bench.harness.start_engine

    def short(config, data_dir):
        short_dir = os.path.join(data_dir, "short")
        os.makedirs(short_dir, exist_ok=True)
        for t in config["tables"]:
            table = papq.read_table(os.path.join(data_dir, f"{t}.parquet"))
            if t == "store_sales":
                groups = [table.slice(i, 4096)
                          for i in range(0, table.num_rows, 4096)]
                del groups[2]
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
    assert result["compared"]["max_rel_gap"]["value"] > \
        result["compared"]["max_rel_gap"]["limit"]


# ---------------------------------------------------------------------------
# (b) the plans, and what the new counters read under them
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def session(bench, deployment, tmp_path_factory):
    real = bench.harness.DATA_DIR
    bench.harness.DATA_DIR = str(tmp_path_factory.mktemp("olap_session"))
    try:
        yield bench.harness.start_engine(deployment.config,
                                         deployment.data_dir)
    finally:
        bench.harness.DATA_DIR = real


def _run(bench, dep, session, q):
    """One query through the harness's ``run_query``, compared with the
    reference -> (its one counter table, its plan's nodes by name)."""
    trace.reset()
    rec = bench.harness.run_query(session, q, dep.cell["texts"][q])
    assert rec["error"] is None             # no CPU operator, no fallback
    c = bench.reference.compare(rec["rows"], dep.want[q])
    assert c["wrong_cells"] == 0
    assert c["max_rel_gap"] <= dep.config["limits"]["max_rel_gap"]
    tables = trace.coarse_counts()
    assert len(tables) == 1                 # every counter in the query's
    (counts,) = tables.values()
    nodes = {}
    for n in session.last_physical_plan.collect_nodes():
        nodes.setdefault(n.name, []).append(n)
    return counts, nodes


#: query -> (window functions, group-by keys, 64-bit words of the merged
#: key on the CPU backend, where a DOUBLE is one word; every grouping
#: set of q67 sorts the finest set's 21, a rolled-up key's being zero)
SHAPES = {"q98": (1, 5, 13), "q89": (1, 6, 13), "q67": (1, 9, 21)}


@pytest.mark.parametrize("query", ["q98", "q89", "q67"])
def test_the_plan_and_its_counters(bench, deployment, session, query,
                                   monkeypatch):
    from spark_rapids_tpu.exec.tpu_aggregate import TpuHashAggregate
    monkeypatch.setattr(TpuHashAggregate, "_CORE_CACHE", {})
    counts, nodes = _run(bench, deployment, session, query)
    funcs, keys, words = SHAPES[query]
    assert len(nodes["TpuWindow"]) == 1
    assert len(nodes["TpuWindow"][0].logical.window_funcs) == funcs
    # one partition, no shuffle: the window sees its input as one batch
    assert counts["window.batches"] == 1
    assert counts["window.specs"] == 1 and counts["window.funcs"] == funcs
    cap = counts["window.rows"]
    assert cap & (cap - 1) == 0
    groups = nodes["TpuWindow"][0].metrics.snapshot()["numOutputRows"]
    assert groups <= cap < 4 * max(groups, 256)
    # at least a key word and a validity a slot, and the result column
    assert counts["window.bytes"] >= 9 * cap + 9 * cap
    agg = nodes["TpuHashAggregate"][-1]
    assert len(agg.group_exprs) == keys
    assert counts["agg.key_words"] == words * counts["agg.batches.fused"]
    names = {sp["name"] for sp in trace.coarse_spans()}
    assert "srt.exec.TpuWindow" in names
    if query != "q67":
        assert "TpuExpand" not in nodes and "expand.batches" not in counts
        return
    (expand,) = nodes["TpuExpand"]
    assert len(expand.logical.projections) == 9        # eight keys and ()
    assert "srt.exec.TpuExpand" in names
    # one scan batch at this size: nine projections of the joined batch,
    # each at the joined batch's capacity
    assert counts["expand.batches"] == 9
    per = counts["expand.rows"] // 9
    assert counts["expand.rows"] == 9 * per and per & (per - 1) == 0
    d = papq.read_table(os.path.join(deployment.data_dir,
                                     "date_dim.parquet"))
    ss = papq.read_table(os.path.join(deployment.data_dir,
                                      "store_sales.parquet"))
    in_2000 = set(d.column("d_date_sk").to_numpy()[
        (d.column("d_month_seq").to_numpy() >= 1200)
        & (d.column("d_month_seq").to_numpy() <= 1211)].tolist())
    joined = int(np.isin(ss.column("ss_sold_date_sk").to_numpy(),
                         list(in_2000)).sum())
    assert joined <= per
    assert expand.metrics.snapshot()["numOutputRows"] == 9 * joined
    # nine updates and the merge of their partials: ONE update program
    # for the nine grouping sets (a rolled-up string key is sized like
    # the finest set's) and one merge program
    assert counts["agg.batches.fused"] == counts["expand.batches"] + 1
    assert sorted(k[0] for k in TpuHashAggregate._CORE_CACHE) == \
        [False, True]


def test_q89_keeps_both_category_class_triples(bench, deployment):
    """The two OR arms share ``i_category in (...)`` and ``i_class in
    (...)`` by shape, not by value list: factoring them out as common
    left ``true or true`` and lost the second triple."""
    cats = {r[0] for r in deployment.want["q89"]}
    assert cats & {"Books", "Music", "Sports"}
    assert cats & {"Men", "Women", "Home"}


@pytest.mark.parametrize("predicate,keep", [
    # two arms alike in shape and unlike in their lists: nothing common
    ("(a in (1, 2) and b in ('x')) or (a in (3) and b in ('y'))",
     lambda a, b: (a in (1, 2) and b == "x") or (a == 3 and b == "y")),
    ("(a in (1) and b like 'x%') or (a in (1) and b like 'y%')",
     lambda a, b: a == 1 and b[0] in "xy"),
    # the same list in both arms is common and is still factored out
    ("(a in (1, 3) and b = 'x') or (a in (1, 3) and b = 'y')",
     lambda a, b: a in (1, 3) and b in ("x", "y")),
    ("(a = 2 and b = 'x') or (a = 3 and b = 'x')",
     lambda a, b: a in (2, 3) and b == "x"),
])
def test_or_arms_over_a_join_keep_their_own_lists(predicate, keep):
    """``plan/logical_opt._factor_or`` compares conjuncts by everything
    they hold, not by ``repr`` (which leaves an ``In``'s values and a
    ``Like``'s pattern out), on the device and in the CPU engine."""
    from harness import with_cpu_session, with_tpu_session
    a = [1, 2, 3, 1, 2, 3, 1, 2, 3, 4]
    b = ["x", "y", "x", "y", "x", "y", "xx", "yy", "x", "x"]

    def run(s):
        s.create_dataframe({"k": list(range(10)), "a": a, "b": b}) \
            .create_or_replace_temp_view("l")
        s.create_dataframe({"k2": list(range(10)), "v": [1] * 10}) \
            .create_or_replace_temp_view("r")
        return s.sql("select k from l, r where k = k2 and "
                     f"({predicate}) order by k").collect()
    want = [(k,) for k in range(10) if keep(a[k], b[k])]
    assert 0 < len(want) < 10
    assert with_tpu_session(run) == want
    assert with_cpu_session(run) == want


# ---------------------------------------------------------------------------
# (c) the five metric readers on hand-made runs
# ---------------------------------------------------------------------------

#: two passes of q98, q89, q67; the traced pass is the first
RUN = {"queries": [{"done": 0.1 * (i + 1), "seconds": 0.05}
                   for i in range(6)],
       "peaks": {"hbm_gbps": 1},
       "trace": {"queries": ["q98", "q89", "q67"], "busy_s": 3.0,
                 "window_s": 4.0,
                 "device_ops": [["jit_agg_grouped_core", 1.0],
                                ["jit_window_plan", 0.5],
                                ["jit_window_rank", 0.25]]}}


def _window(counts):
    return {"spans": [], "self_ns": {}, "n_queries": 6, "counts": counts}


OLD = {"eager.column_gather": 40, "join.batches.sized": 3,
       "agg.batches.fused": 10}
WIN = {"window.batches": 1, "window.rows": 1 << 20, "window.specs": 1,
       "window.funcs": 1, "window.bytes": 3_000_000}


@pytest.mark.parametrize("metric,counts,want", [
    ("window_rows_per_query",
     {n: dict(OLD, **WIN) for n in range(6)}, float(1 << 20)),
    ("window_rows_per_query", {7: dict(OLD), 8: dict(OLD)}, None),
    ("expand_rows_per_query",
     {7: dict(OLD, **{"expand.batches": 27, "expand.rows": 27 << 18}),
      8: dict(OLD)}, (27 << 18) / 6),
    ("expand_rows_per_query", {7: dict(OLD), 8: dict(OLD)}, None),
    ("agg_key_words_per_batch",
     {7: dict(OLD, **{"agg.key_words": 130}),
      8: dict(OLD, **{"agg.key_words": 160})}, 14.5),
    # an engine before the counter, or only table and global batches
    ("agg_key_words_per_batch", {7: dict(OLD), 8: dict(OLD)}, None),
    ("agg_key_words_per_batch",
     {7: {"agg.key_words": 0, "agg.batches.table": 3}}, None),
    # 1.5 MB a query at 1 GB/s is 1.5 ms, over 250 ms of the programs
    ("window_hbm_roofline_pct",
     {n: dict(OLD, **WIN) for n in range(3)}, 100 * 1.5 / 250.0),
    ("window_hbm_roofline_pct", {7: dict(OLD), 8: dict(OLD)}, None),
    ("window_device_ms_per_query",
     {n: dict(OLD, **WIN) for n in range(6)}, 250.0),
    # the parent: an eager window, no program and no counter
    ("window_device_ms_per_query", {7: dict(OLD), 8: dict(OLD)}, None),
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


def test_window_programs_off_the_top_ten(bench, monkeypatch):
    """A lower bound of 0 ms is a reading; a share of it is not."""
    counts = {n: dict(OLD, **WIN) for n in range(6)}
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, _window(counts)])
    off = dict(RUN, trace=dict(
        RUN["trace"], device_ops=[["jit_agg_grouped_core", 1.0]]))
    monkeypatch.setattr(bench.span_reduce, "_LAST", [off, _window(counts)])
    assert bench.harness.metric_reader(
        "window_device_ms_per_query")(off) == 0.0
    assert bench.harness.metric_reader(
        "window_hbm_roofline_pct")(off) is None


# ---------------------------------------------------------------------------
# (d) the plain references on a dozen hand-made rows
# ---------------------------------------------------------------------------

def _strcol(bench, values):
    return bench.reference.StrCol(
        pa.chunked_array([pa.array(values).dictionary_encode()]))


@pytest.fixture
def dozen(bench):
    """Five items of category ``B`` in three classes and one of ``A``;
    twelve sales, one of them outside the year 2000.  By class ``B``
    sells c1 = 30 + 30, c2 = 20 + 10 + 30, c3 = 20 + 20: a tie at 60."""
    item = {"i_item_sk": np.arange(6, dtype=np.int64),
            "i_item_id": _strcol(bench, [f"ID{i}" for i in range(6)]),
            "i_item_desc": _strcol(bench, [f"desc {i}" for i in range(6)]),
            "i_category": _strcol(bench, ["B", "B", "B", "B", "B", "A"]),
            "i_class": _strcol(bench, ["c1", "c1", "c2", "c2", "c3", "c1"]),
            "i_brand": _strcol(bench, ["b1", "b2", "b1", "b2", "b1", "b1"]),
            "i_product_name": _strcol(bench, [f"p{i}" for i in range(6)]),
            "i_current_price": np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])}
    date = {"d_date_sk": np.array([10, 11, 12, 13], dtype=np.int64),
            "d_year": np.array([2000, 2000, 2000, 1999], dtype=np.int32),
            "d_qoy": np.array([1, 1, 2, 4], dtype=np.int32),
            "d_moy": np.array([2, 3, 4, 12], dtype=np.int32),
            "d_month_seq": np.array([1201, 1202, 1203, 1199],
                                    dtype=np.int32)}
    store = {"s_store_sk": np.array([0, 1], dtype=np.int64),
             "s_store_id": _strcol(bench, ["S0", "S1"]),
             "s_store_name": _strcol(bench, ["ese", "able"]),
             "s_company_id": np.array([1, 1], dtype=np.int32)}
    #          item date store price qty
    sales = [(0, 10, 0, 10.0, 3), (1, 10, 0, 15.0, 2), (2, 10, 0, 20.0, 1),
             (2, 11, 1, 5.0, 2), (3, 11, 0, 30.0, 1), (4, 10, 0, 20.0, 1),
             (4, 12, 1, 10.0, 2), (5, 10, 0, 7.0, 1), (5, 11, 0, 1.0, 1),
             (5, 12, 1, 2.0, 1), (0, 13, 0, 99.0, 9), (5, 13, 1, 50.0, 1)]
    cols = list(zip(*sales))
    ss = {"ss_item_sk": np.array(cols[0], dtype=np.int64),
          "ss_sold_date_sk": np.array(cols[1], dtype=np.int64),
          "ss_store_sk": np.array(cols[2], dtype=np.int64),
          "ss_sales_price": np.array(cols[3]),
          "ss_quantity": np.array(cols[4], dtype=np.int32),
          "ss_ext_sales_price": np.array(cols[3]) * np.array(cols[4])}
    return {"item": item, "date_dim": date, "store": store,
            "store_sales": ss}


def _answer(bench, query, tables):
    fn = bench.reference.load_py(os.path.join(
        CHIPBENCH, "queries", "tpcds_sf1_olap", f"{query}.py")).answer
    return fn(tables, bench.reference.Num("float64"))


def test_q67_reference_ranks_ties_and_orders_nulls_first(bench, dozen):
    rows = _answer(bench, "q67", dozen)
    # the grand total is the NULL category's one row; NULL sorts first
    assert rows[0] == (None,) * 8 + (170.0, 1)
    assert rows[1][:2] == ("A", None) and rows[1][-2:] == (10.0, 1)
    b = [r for r in rows if r[0] == "B"]
    assert b[0] == ("B",) + (None,) * 7 + (160.0, 1)
    by_class = {r[1]: r[-2:] for r in b if r[1] is not None
                and r[2] is None}
    # the tie at 60 shares rank 2 and the next sum takes rank 4
    assert by_class == {"c1": (60.0, 2), "c2": (60.0, 2), "c3": (40.0, 4)}
    assert sorted(r[-1] for r in b)[:4] == [1, 2, 2, 4]
    # i5's one brand, one product and one year repeat its class's 40
    assert sum(1 for r in b if r[-2:] == (40.0, 4)) == 4
    # a rolled-up key is NULL and sorts before every value under its
    # parent; the 1999 sale of 891 is in no sum
    assert [r[1] for r in b[:2]] == [None, "c1"]
    assert [r[2] for r in b[1:3]] == [None, "b1"]
    assert max(r[-2] for r in rows) == 170.0
    a = [r for r in rows if r[0] == "A"]
    assert [r[-1] for r in a[:2]] == [1, 1]         # one class: a tie
    years = {r[4] for r in rows if r[4] is not None}
    assert years == {2000}


def test_q98_reference_shares_sum_to_a_hundred_a_class(bench, dozen):
    # every category and month of the dozen: the text's filter by hand
    dozen["item"]["i_category"] = _strcol(
        bench, ["Books", "Books", "Books", "Books", "Books", "Home"])
    dozen["date_dim"]["d_year"] = np.array([1999] * 4, dtype=np.int32)
    dozen["date_dim"]["d_moy"] = np.array([2, 3, 3, 12], dtype=np.int32)
    rows = _answer(bench, "q98", dozen)
    assert [r[0] for r in rows] == ["ID0", "ID1", "ID2", "ID3", "ID4",
                                    "ID5"]
    revenue = {r[0]: r[5] for r in rows}
    assert revenue == {"ID0": 30.0, "ID1": 30.0, "ID2": 30.0, "ID3": 30.0,
                       "ID4": 40.0, "ID5": 10.0}
    # c1 spans two categories: the partition is the class alone
    share = {r[0]: r[6] for r in rows}
    assert share["ID0"] == pytest.approx(100 * 30 / 70)
    assert share["ID5"] == pytest.approx(100 * 10 / 70)
    assert share["ID4"] == 100.0
    for k in ("c1", "c2", "c3"):
        assert sum(r[6] for r in rows if r[3] == k) == pytest.approx(100.0)


def test_q89_reference_averages_over_the_partition(bench, dozen):
    dozen["item"]["i_category"] = _strcol(
        bench, ["Books", "Books", "Men", "Men", "Men", "Books"])
    dozen["item"]["i_class"] = _strcol(
        bench, ["fishing", "fishing", "pants", "pants", "rock", "football"])
    dozen["date_dim"]["d_year"] = np.array([1999] * 4, dtype=np.int32)
    rows = _answer(bench, "q89", dozen)
    # (Men, rock) is in neither triple.  The partition has no class and
    # no month: (Books, b1, ese) averages fishing's 10 and 99 with
    # football's 7 and 1; (Books, b1, able) football's 2 and 50.  A
    # partition of one group is its own average and is filtered out.
    assert [(r[0], r[1], r[2], r[3], r[4], r[5]) for r in rows] == [
        ("Books", "football", "b1", "ese", 1, 3),
        ("Books", "football", "b1", "able", 1, 4),
        ("Books", "football", "b1", "ese", 1, 2),
        ("Books", "fishing", "b1", "ese", 1, 2),
        ("Books", "football", "b1", "able", 1, 12),
        ("Books", "fishing", "b1", "ese", 1, 12)]
    assert [r[6:] for r in rows] == [(1.0, 29.25), (2.0, 26.0),
                                     (7.0, 29.25), (10.0, 29.25),
                                     (50.0, 26.0), (99.0, 29.25)]
