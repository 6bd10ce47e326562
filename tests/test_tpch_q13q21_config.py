"""The TPC-H Q21 / Q13 deployment (chipbench configuration
``tpch_q13q21``, cell ``tpch_q13q21.power``) at test size on the CPU
backend: the generator keeps tpch3's key structure and dbgen's rules for
the columns it adds; a whole rehearsal of the cell comes out
``correct``, and a run with the timed path broken underneath does not
(a ``lineitem`` row group dropped, the residual ignored so the semi
join is a plain equi semi join, the LIKE matching across a row
boundary); the counters this deployment added (``str.like.*``,
``join.residual.*``, ``plan.join.on_pushdown``) read what the plan
implies; the two metric readers return nothing where there is
nothing to read."""
import importlib
import os
import re
import sys
from types import SimpleNamespace

import numpy as np
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu.io.scan_cache import DeviceScanCache
from spark_rapids_tpu.obs import trace

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
CELL = "tpch_q13q21.power"
SCALE = 0.01      # 1,500 customers, 15,000 orders, 60,000 lines, 100 suppliers
SEED = 2147483659
TABLES = ["customer", "lineitem", "nation", "orders", "supplier"]
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
NEW_METRICS = ["str_like_device_ms_per_query", "join_residual_pairs_per_query"]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, CHIPBENCH)
    import reference
    import run as harness
    import span_reduce
    tpch5 = importlib.import_module("datagen.tpch5")
    yield SimpleNamespace(harness=harness, reference=reference,
                          span_reduce=span_reduce, tpch5=tpch5)
    sys.path.remove(CHIPBENCH)
    for name in ("run", "span_reduce", "reference", "datagen",
                 "datagen.tpch3", "datagen.tpch5"):
        sys.modules.pop(name, None)


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):
    cell = bench.harness.load_cell(CELL)
    config = cell["config"]
    data_dir = str(tmp_path_factory.mktemp("tpch_q13q21"))
    rows = bench.tpch5.generate(data_dir, SCALE, SEED, TABLES)
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


def _np(dep, table, column):
    return dep.tables[table].column(column).to_numpy()


# ---------------------------------------------------------------------------
# (a) the generator
# ---------------------------------------------------------------------------

def test_lines_orders_and_customers_keep_tpch3s_structure(bench, deployment):
    okey = _np(deployment, "lineitem", "l_orderkey")
    assert (np.diff(okey) >= 0).all()
    orders, lines = np.unique(okey, return_counts=True)
    assert set(lines) == set(range(1, 8)) and len(okey) == 4 * len(orders)
    assert (orders == _np(deployment, "orders", "o_orderkey")).all()
    cust = _np(deployment, "orders", "o_custkey")
    assert (cust % 3 != 0).all()
    assert (_np(deployment, "customer", "c_custkey") ==
            np.arange(1, deployment.rows["customer"] + 1)).all()
    # the key columns are tpch3's, chunk by chunk
    n = bench.tpch5.row_counts(SCALE)
    (o3, l3), = bench.tpch5.tpch3.chunks(n, SEED)
    assert (o3["o_custkey"] == cust).all()
    assert (l3["l_orderkey"] == okey).all()


def test_suppliers_dates_and_status_follow_dbgen(bench, deployment):
    n_supp = deployment.rows["supplier"]
    supp = _np(deployment, "lineitem", "l_suppkey")
    assert supp.min() >= 1 and supp.max() <= n_supp
    # l_suppkey is one of its part's four suppliers: from the generator's
    # own draws, (p + i x (S/4 + (p-1)/S)) mod S + 1
    n = bench.tpch5.row_counts(SCALE)
    rng = bench.tpch5.table_rng(SEED, "lineitem.q13q21")
    part = rng.integers(1, 2000 + 1, len(supp))
    i = rng.integers(0, 4, len(supp))
    assert n["lineitem"] == len(supp)
    assert (supp == (part + i * (n_supp // 4 + (part - 1) // n_supp))
            % n_supp + 1).all()
    (o3, l3), = bench.tpch5.tpch3.chunks(n, SEED)
    of_line = np.searchsorted(o3["o_orderkey"], l3["l_orderkey"])
    commit = _np(deployment, "lineitem", "l_commitdate") - \
        o3["o_orderdate"][of_line]
    receipt = _np(deployment, "lineitem", "l_receiptdate") - \
        l3["l_shipdate"]
    assert commit.min() == 30 and commit.max() == 90
    assert receipt.min() == 1 and receipt.max() == 30
    late = (l3["l_shipdate"] > bench.tpch5.CURRENT_DAY).astype(np.int8)
    first = np.flatnonzero(np.r_[True, np.diff(of_line) != 0])
    all_late = np.minimum.reduceat(late, first).astype(bool)
    any_late = np.maximum.reduceat(late, first).astype(bool)
    want = np.where(all_late, "O", np.where(any_late, "P", "F"))
    status = deployment.tables["orders"].column("o_orderstatus").to_pylist()
    assert status == want.tolist()
    assert {"F", "O", "P"} == set(status)


def test_comments_suppliers_and_nations(bench, deployment):
    comments = deployment.tables["orders"].column("o_comment").to_pylist()
    lens = np.array([len(c) for c in comments])
    assert lens.min() == 19 and lens.max() == 78
    pool = bench.tpch5.text_pool().decode()
    assert all(c in pool for c in comments[:200])
    for word in ("special", "requests", "furiously", "packages"):
        assert word in pool
    share = np.mean([re.search("special.*requests", c) is not None
                     for c in comments])
    assert 0.002 < share < 0.03
    names = deployment.tables["supplier"].column("s_name").to_pylist()
    assert names[0] == "Supplier#000000001" and len(set(names)) == len(names)
    nk = _np(deployment, "supplier", "s_nationkey")
    assert nk.min() >= 0 and nk.max() <= 24
    nation = deployment.tables["nation"].to_pydict()
    assert nation["n_nationkey"] == list(range(25))
    assert nation["n_name"][20] == "SAUDI ARABIA"


@pytest.mark.parametrize("scale,want", [
    (2, {"customer": 300_000, "orders": 3_000_000, "lineitem": 12_000_000,
         "supplier": 20_000, "nation": 25}),
    (1, {"customer": 150_000, "orders": 1_500_000, "lineitem": 6_000_000,
         "supplier": 10_000, "nation": 25}),
    (5, {"customer": 750_000, "orders": 7_500_000, "lineitem": 30_000_000,
         "supplier": 50_000, "nation": 25}),
])
def test_row_counts_by_arithmetic(bench, deployment, scale, want):
    assert bench.tpch5.row_counts(scale) == want
    if scale == deployment.config["scale"]:
        assert want == {t: v["rows"]
                        for t, v in deployment.config["tables"].items()}


def test_one_seed_repeats_another_differs_a_table_stands_alone(
        bench, deployment, tmp_path):
    again, other = str(tmp_path / "again"), str(tmp_path / "other")
    os.makedirs(again)
    os.makedirs(other)
    assert bench.tpch5.generate(again, SCALE, SEED, ["orders"]) == \
        {"orders": deployment.rows["orders"]}
    assert sorted(os.listdir(again)) == ["orders.parquet"]
    assert papq.read_table(os.path.join(again, "orders.parquet")).equals(
        deployment.tables["orders"])
    bench.tpch5.generate(other, SCALE, SEED + 1, ["lineitem", "orders"])
    for t in ("lineitem", "orders"):
        assert not papq.read_table(os.path.join(other, f"{t}.parquet")) \
            .equals(deployment.tables[t])
    with pytest.raises(KeyError):
        bench.tpch5.generate(other, SCALE, SEED, ["part"])


def test_the_generator_refuses_an_engine_without_the_like_program(
        bench, monkeypatch, tmp_path):
    from spark_rapids_tpu.kernels import strings
    monkeypatch.delattr(strings, "str_like_match")
    with pytest.raises(SystemExit) as e:
        bench.tpch5.generate(str(tmp_path), SCALE, SEED, TABLES)
    assert e.value.code not in (0, None) and os.listdir(tmp_path) == []


# ---------------------------------------------------------------------------
# (b) the cell by name, a whole rehearsal, three breakages underneath
# ---------------------------------------------------------------------------

def test_the_cell_resolves_by_name(bench, deployment):
    cell, config = deployment.cell, deployment.config
    assert cell["config_name"] == config["name"] == "tpch_q13q21"
    assert cell["chips"] == 1 and config["schema"] == "tpch5"
    assert config["queries"] == ["q21", "q13"]
    assert sorted(config["reduced"]) == ["columns", "scale_factor", "tables"]
    assert config["engine_conf"] == {
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.test.enabled": True}
    assert config["limits"] == {"wrong_cells": 0, "max_rel_gap": 0.0}
    assert set(NEW_METRICS) <= {m["name"] for m in cell["per_layer"]}
    for other in ("tpch_q3q18.power", "tpcds_sf1_store.power"):
        theirs = bench.harness.load_cell(other)["per_layer"]
        assert not set(NEW_METRICS) & {m["name"] for m in theirs}
    assert 1 <= len(deployment.want["q21"]) <= 100
    assert deployment.want["q13"][0][0] == 0   # customers with no order
    for rows in deployment.want.values():
        assert all(type(v) in (int, str) for r in rows for v in r)


def _rehearse(bench, deployment, tmp_path, monkeypatch, traced=False):
    monkeypatch.setattr(bench.harness, "DATA_DIR", str(tmp_path))
    return bench.harness.run_cell(deployment.cell, SEED, 0.3, traced,
                                  scale=SCALE, device=dict(DEVICE))


@pytest.mark.parametrize("traced", [False, True])
def test_a_rehearsal_of_the_cell_is_correct(
        bench, deployment, tmp_path, monkeypatch, traced):
    result = _rehearse(bench, deployment, tmp_path, monkeypatch, traced)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and list(result)[-1] == "compared"
    if traced:
        assert not set(NEW_METRICS) & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"queries_per_hour",
                                          "query_p95_s", "setup_s"}


def _short_lineitem(bench, monkeypatch):
    real = bench.harness.start_engine

    def short(config, data_dir):
        short_dir = os.path.join(data_dir, "short")
        os.makedirs(short_dir, exist_ok=True)
        for t in config["tables"]:
            table = papq.read_table(os.path.join(data_dir, f"{t}.parquet"))
            if t == "lineitem":
                groups = [table.slice(i, 8192)
                          for i in range(0, table.num_rows, 8192)]
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


def _residual_ignored(bench, monkeypatch):
    """Every candidate pair survives: the semi join is the plain equi
    semi join and the anti join keeps no line with another line."""
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.tpu_join import TpuHashJoinBase
    real = TpuHashJoinBase._residual_keep

    def ignored(self, sb, build, bt, lo, counts, out_cap, pairs):
        got = real(self, sb, build, bt, lo, counts, out_cap, pairs)
        return (counts > 0, jnp.sum(counts)) + tuple(got[2:])
    monkeypatch.setattr(TpuHashJoinBase, "_residual_keep", ignored)


def _like_across_rows(bench, monkeypatch):
    """A row also matches where the pattern's pieces run on into the
    next row's bytes."""
    import jax.numpy as jnp
    from spark_rapids_tpu.kernels import strings

    def crossing(col, segs):
        rx = re.compile(".*".join(re.escape(s.decode()) for s in segs),
                        re.DOTALL)
        from spark_rapids_tpu.analysis import residency
        with residency.declared_transfer(site="strings_prep"):
            offsets = np.asarray(col.offsets)
            data = bytes(np.asarray(col.data))
        out = np.zeros(col.capacity, bool)
        for r in range(col.capacity):
            text = data[offsets[r]:offsets[min(r + 2, col.capacity)]]
            out[r] = rx.fullmatch(text.decode(errors="replace")) is not None
        return jnp.asarray(out)
    monkeypatch.setattr(strings, "like", crossing)


@pytest.mark.parametrize("breakage", [
    _short_lineitem, _residual_ignored, _like_across_rows,
], ids=["lineitem_row_group_dropped", "residual_ignored",
        "like_across_rows"])
def test_the_timed_path_broken_underneath_is_not_correct(
        bench, deployment, tmp_path, monkeypatch, breakage):
    breakage(bench, monkeypatch)
    result = _rehearse(bench, deployment, tmp_path, monkeypatch)
    assert result["failed"] == 0 and result["correct"] is False
    assert result["compared"]["wrong_cells"]["value"] > 0


# ---------------------------------------------------------------------------
# (c) the counters, against what the plan implies
# ---------------------------------------------------------------------------

def _session(dep, tmp_path):
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.config import TpuConf
    settings = dict(dep.config["engine_conf"])
    settings["spark.rapids.tpu.memory.spill.dir"] = str(tmp_path / "spill")
    s = TpuSession(TpuConf(settings))
    for t in TABLES:
        s.read.parquet(os.path.join(dep.data_dir, f"{t}.parquet")) \
            .create_or_replace_temp_view(t)
    return s


def _run(bench, dep, session, q):
    trace.reset()
    rec = bench.harness.run_query(session, q, dep.cell["texts"][q])
    assert rec["error"] is None
    assert bench.reference.compare(rec["rows"], dep.want[q])["wrong_cells"] \
        == 0
    (counts,) = [c for k, c in trace.coarse_counts().items()
                 if k is not None]
    return counts


def test_q21_counts_its_candidate_and_surviving_pairs(
        bench, deployment, tmp_path):
    """The semi join decides every line of a candidate ``l1``'s order,
    the anti join every late line of a survivor's order; a pair
    survives where the suppliers differ."""
    counts = _run(bench, deployment, _session(deployment, tmp_path), "q21")
    t = {c: _np(deployment, "lineitem", c) for c in
         ("l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate")}
    late = t["l_receiptdate"] > t["l_commitdate"]
    nation = _np(deployment, "supplier", "s_nationkey")
    status = np.array(deployment.tables["orders"].column(
        "o_orderstatus").to_pylist())
    okeys = _np(deployment, "orders", "o_orderkey")
    of_line = np.searchsorted(okeys, t["l_orderkey"])
    cand = late & (nation[t["l_suppkey"] - 1] == 20) & \
        (status[of_line] == "F")
    pairs = kept = 0
    for row in np.flatnonzero(cand):
        mine = of_line == of_line[row]
        other = mine & (t["l_suppkey"] != t["l_suppkey"][row])
        pairs += mine.sum()
        kept += other.sum()
        if other.any():                       # a survivor of the semi join
            pairs += (mine & late).sum()
            kept += (other & late).sum()
    assert counts["join.residual.pairs"] == pairs
    assert counts["join.residual.kept"] == kept
    # the program gathered the condition's two columns, out_cap each
    lanes = {k: v for k, v in counts.items()
             if k.startswith("lanes.join_residual_core@")}
    launches = sum(v for k, v in counts.items()
                   if k.startswith("launch.join_residual_core@"))
    assert launches >= 2 and sum(lanes.values()) >= 2 * pairs
    assert "plan.join.on_pushdown" not in counts


def test_q13_counts_its_like_bytes_and_its_pushdown(
        bench, deployment, tmp_path):
    counts = _run(bench, deployment, _session(deployment, tmp_path), "q13")
    assert counts["plan.join.on_pushdown"] == 1
    n = deployment.rows["orders"]
    assert counts["str.like.rows"] >= n
    live = sum(len(c) for c in deployment.tables["orders"].column(
        "o_comment").to_pylist())
    assert live <= counts["str.like.bytes"] < 4 * live
    launches = sum(v for k, v in counts.items()
                   if k.startswith("launch.str_like_match@"))
    assert launches >= 1 and \
        counts["lanes.str_like_match@TpuFilter"] == counts["str.like.bytes"]
    assert not any(k.startswith("join.residual.") for k in counts)


# ---------------------------------------------------------------------------
# (d) the three metric readers on hand-made runs
# ---------------------------------------------------------------------------

#: two passes of q21, q13; the traced pass is the first
RUN = {"queries": [{"done": 0.2, "seconds": 0.1},
                   {"done": 0.4, "seconds": 0.1},
                   {"done": 0.6, "seconds": 0.1},
                   {"done": 0.8, "seconds": 0.1}],
       "peaks": {"hbm_gbps": 819},
       "trace": {"queries": ["q21", "q13"], "busy_s": 3.0, "window_s": 4.0,
                 "device_ops": [["jit_join_probe_core", 1.0],
                                ["jit_str_like_match", 0.2],
                                ["jit_join_residual_core", 0.1]]}}

LIKE = {"str.like.bytes": 3 << 26, "str.like.rows": 3 << 20}
PAIRS = {"join.residual.pairs": 1_200_000, "join.residual.kept": 900_000}


def _window(counts):
    return {"spans": [], "self_ns": {}, "n_queries": 4, "counts": counts}


@pytest.mark.parametrize("metric,counts,want", [
    ("str_like_device_ms_per_query", {8: dict(LIKE), 10: dict(LIKE)},
     100.0),
    ("join_residual_pairs_per_query", {7: dict(PAIRS), 9: dict(PAIRS)},
     600_000.0),
    # an engine without the counters (the parent), or nothing counted
    ("str_like_device_ms_per_query", {7: {}, 8: {}}, None),
    ("join_residual_pairs_per_query", {7: {}, 8: {}}, None),
])
def test_metric_readers(bench, monkeypatch, metric, counts, want):
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, _window(counts)])
    got = bench.harness.metric_reader(metric)(RUN)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_metrics_without_a_window_or_a_trace(bench, monkeypatch, metric):
    monkeypatch.setattr(bench.span_reduce, "_LAST", [None, None])
    run = dict(RUN, peaks=None, trace=None)
    assert bench.harness.metric_reader(metric)(run) is None


def test_the_like_program_off_the_top_ten_reads_zero(bench, monkeypatch):
    """Counted, but off the traced pass's ten longest programs (the
    cell's case on the chip: three launches of 0.15 s in a 24 s-busy
    pass): a lower bound of 0, still a reading."""
    run = dict(RUN, trace=dict(RUN["trace"], device_ops=[
        ["jit_join_probe_core", 1.0]]))
    monkeypatch.setattr(bench.span_reduce, "_LAST",
                        [run, _window({8: dict(LIKE)})])
    assert bench.harness.metric_reader("str_like_device_ms_per_query")(
        run) == 0.0
