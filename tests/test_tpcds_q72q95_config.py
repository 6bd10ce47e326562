"""The TPC-DS q72 / q95 deployment (chipbench configuration
``tpcds_sf1_q72q95``, cell ``tpcds_sf1_q72q95.power``) at test size on
the CPU backend: the generator keeps the spec's SF1 counts and the
rules it states (weekly inventory of half the items, orders of distinct
items, returns drawn from the sales lines, fixed keys and seeded
measures); it refuses an engine without the planner rules and the
chunked residual join; a rehearsal of the cell by name runs both texts
through ``TpuSession`` on the device path with no CPU operator and
answers as the configuration's numpy references do (not the CPU
engine: it shares the planner); the residual join's counters read the
pairs, survivors and bytes the data imply, and q72's two residual joins
are decided by bounds, a launch a stream batch; the three metric
readers return what a recorded run holds, and nothing where there is
nothing to read."""
import importlib
import os
import sys
from types import SimpleNamespace

import numpy as np
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu.io.scan_cache import DeviceScanCache
from spark_rapids_tpu.obs import trace

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
CELL = "tpcds_sf1_q72q95.power"
SCALE = 0.01      # 14,415 catalog lines, 117,450 inventory rows, 180 items
SEED = 2147483659
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
NEW_METRICS = ["join_residual_chunks_per_query",
               "join_residual_device_ms_per_query",
               "join_residual_hbm_roofline_pct"]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, CHIPBENCH)
    import reference
    import run as harness
    import span_reduce
    gen = importlib.import_module("datagen.tpcds_cw")
    yield SimpleNamespace(harness=harness, reference=reference,
                          span_reduce=span_reduce, gen=gen)
    sys.path.remove(CHIPBENCH)
    for name in ("run", "span_reduce", "reference", "datagen",
                 "datagen.tpcds", "datagen.tpcds_cw"):
        sys.modules.pop(name, None)


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):
    cell = bench.harness.load_cell(CELL)
    config = cell["config"]
    data_dir = str(tmp_path_factory.mktemp("tpcds_q72q95"))
    rows = bench.gen.generate(data_dir, SCALE, SEED, list(config["tables"]))
    want, _ = bench.reference.answers(
        cell["config_name"], config["queries"], data_dir,
        config["precision"])
    return SimpleNamespace(cell=cell, config=config, data_dir=data_dir,
                           want=want, rows=rows)


def _col(dep, table, column):
    return papq.read_table(os.path.join(dep.data_dir, f"{table}.parquet"),
                           columns=[column]).column(0).to_numpy()


# ---------------------------------------------------------------------------
# (a) the generator
# ---------------------------------------------------------------------------

def test_sf1_counts_are_the_specs(bench, deployment):
    n = bench.gen.row_counts(1.0)
    assert {t: v["rows"] for t, v in deployment.config["tables"].items()} \
        == n
    assert n["catalog_sales"] == 1_441_548 and n["inventory"] == 11_745_000
    assert n["web_sales"] == 719_384 and n["catalog_returns"] == 144_067
    assert n["web_returns"] == 71_763 and n["customer_address"] == 50_000
    assert (n["warehouse"], n["web_site"], n["item"]) == (5, 30, 18_000)
    assert deployment.rows == bench.gen.row_counts(SCALE)


def test_inventory_is_weekly_snapshots_of_half_the_items(bench, deployment):
    date = _col(deployment, "inventory", "inv_date_sk")
    item = _col(deployment, "inventory", "inv_item_sk")
    wh = _col(deployment, "inventory", "inv_warehouse_sk")
    qty = _col(deployment, "inventory", "inv_quantity_on_hand")
    assert len(np.unique(date)) == 261 and set(np.diff(np.unique(date))) \
        == {7}
    assert set(item % 2) == {0} and len(np.unique(item)) == \
        deployment.rows["item"] // 2
    assert set(wh) == set(range(5))
    assert len(np.unique(np.stack([date, item, wh]), axis=1).T) == len(date)
    assert qty.min() == 0 and qty.max() == 1000


def test_orders_returns_and_measures(bench, deployment):
    for fact, ret, pre in (("catalog_sales", "catalog_returns", "cs"),
                           ("web_sales", "web_returns", "ws")):
        order = _col(deployment, fact, f"{pre}_order_number")
        item = _col(deployment, fact, f"{pre}_item_sk")
        assert (np.diff(order) >= 0).all()
        sizes = np.bincount(order)[1:]
        assert sizes.max() <= 16 and sizes[:-1].min() >= 1
        line = order * (1 << 20) + item
        assert len(np.unique(line)) == len(line)      # distinct items
        r = ret.split("_")[0][:1] + "r"
        rline = _col(deployment, ret, f"{r}_order_number") * (1 << 20) + \
            _col(deployment, ret, f"{r}_item_sk")
        assert np.isin(rline, line).all() and \
            len(np.unique(rline)) == len(rline)
    wh = _col(deployment, "web_sales", "ws_warehouse_sk")
    assert set(wh) == set(range(5))
    qty = _col(deployment, "catalog_sales", "cs_quantity")
    assert qty.min() == 1 and qty.max() == 100
    promo = papq.read_table(os.path.join(
        deployment.data_dir, "catalog_sales.parquet"))["cs_promo_sk"]
    assert 0.01 < promo.null_count / len(promo) < 0.03
    names = papq.read_table(os.path.join(
        deployment.data_dir, "web_site.parquet"))["web_company_name"]
    assert names.to_pylist().count("pri") == 5


def test_keys_are_fixed_measures_follow_the_seed(bench, deployment,
                                                 tmp_path):
    bench.gen.generate(str(tmp_path), SCALE, SEED + 1,
                       ["catalog_sales", "inventory", "web_sales"])
    for table, keys, measure in (
            ("catalog_sales", "cs_item_sk", "cs_quantity"),
            ("inventory", "inv_date_sk", "inv_quantity_on_hand"),
            ("web_sales", "ws_ship_date_sk", "ws_net_profit")):
        other = papq.read_table(str(tmp_path / f"{table}.parquet"))
        assert (other[keys].to_numpy() ==
                _col(deployment, table, keys)).all()
        assert not (other[measure].to_numpy() ==
                    _col(deployment, table, measure)).all()
    with pytest.raises(KeyError):
        bench.gen.generate(str(tmp_path), SCALE, SEED, ["store_sales"])


@pytest.mark.parametrize("missing", [
    ("spark_rapids_tpu.plan.logical_opt", "_rewrite_filter_outer"),
    ("spark_rapids_tpu.plan.logical_opt", "_residual_condition"),
    ("spark_rapids_tpu.exec.tpu_join:TpuHashJoinBase", "_residual_batches"),
], ids=["outer_pushdown", "two_sided_condition", "chunked_residual"])
def test_the_generator_refuses_an_engine_without_the_change(
        bench, monkeypatch, tmp_path, missing):
    where, attr = missing
    module, _, cls = where.partition(":")
    target = importlib.import_module(module)
    if cls:
        target = getattr(target, cls)
    monkeypatch.delattr(target, attr)
    with pytest.raises(SystemExit) as e:
        bench.gen.generate(str(tmp_path), SCALE, SEED, ["warehouse"])
    assert e.value.code not in (0, None) and os.listdir(tmp_path) == []


# ---------------------------------------------------------------------------
# (b) the cell by name: both texts on the device path, against the
# references, and the counters the data imply
# ---------------------------------------------------------------------------

def test_the_cell_resolves_by_name(bench, deployment):
    cell, config = deployment.cell, deployment.config
    assert cell["config_name"] == config["name"] == "tpcds_sf1_q72q95"
    assert cell["chips"] == 1 and config["schema"] == "tpcds_cw"
    assert config["queries"] == ["q72", "q95"]
    assert sorted(config["reduced"]) == ["columns", "tables"]
    assert config["engine_conf"] == {
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.test.enabled": True}
    assert config["limits"] == {"wrong_cells": 0, "max_rel_gap": 1e-09}
    assert {m["name"] for m in cell["per_layer"]} >= set(NEW_METRICS)
    for other in ("tpch_q13q21.power", "tpcds_sf1_store.power"):
        theirs = bench.harness.load_cell(other)["per_layer"]
        assert not set(NEW_METRICS) & {m["name"] for m in theirs}
    assert 1 <= len(deployment.want["q72"]) <= 100
    (count, ship, profit), = deployment.want["q95"]
    assert count > 0 and isinstance(ship, float)


def _residual_counts(dep):
    """-> {join: (candidate pairs, survivors)} of q72's two residual
    joins, from the data alone: sales lines x inventory rows of their
    item under ``inv_quantity_on_hand < cs_quantity``, then the rows
    that reach ``date_dim d3`` (one ship date each) under ``d3.d_date >
    d1.d_date + 5``."""
    cs = {c: _col(dep, "catalog_sales", c) for c in (
        "cs_item_sk", "cs_quantity", "cs_bill_hdemo_sk",
        "cs_bill_cdemo_sk", "cs_sold_date_sk", "cs_ship_date_sk")}
    inv = {c: _col(dep, "inventory", c) for c in (
        "inv_item_sk", "inv_quantity_on_hand", "inv_date_sk")}
    n = dep.rows["item"]
    pairs = int((np.bincount(cs["cs_item_sk"], minlength=n).astype(
        np.int64) * np.bincount(inv["inv_item_sk"], minlength=n)).sum())
    kept = 0
    for i in np.unique(inv["inv_item_sk"]):
        q = np.sort(inv["inv_quantity_on_hand"][inv["inv_item_sk"] == i])
        kept += int(np.searchsorted(
            q, cs["cs_quantity"][cs["cs_item_sk"] == i], "left").sum())
    # the lines the dimension filters keep, then their week's snapshots
    hd = papq.read_table(os.path.join(dep.data_dir,
                                      "household_demographics.parquet"))
    cd = papq.read_table(os.path.join(dep.data_dir,
                                      "customer_demographics.parquet"))
    dd = papq.read_table(os.path.join(dep.data_dir, "date_dim.parquet"))
    sk0 = dd["d_date_sk"][0].as_py()
    week = dd["d_week_seq"].to_numpy()
    year = dd["d_year"].to_numpy()
    ok = np.isin(cs["cs_bill_hdemo_sk"], hd["hd_demo_sk"].to_numpy()[
        np.array(hd["hd_buy_potential"].to_pylist()) == ">10000"])
    ok &= np.isin(cs["cs_bill_cdemo_sk"], cd["cd_demo_sk"].to_numpy()[
        np.array(cd["cd_marital_status"].to_pylist()) == "D"])
    ok &= year[cs["cs_sold_date_sk"] - sk0] == 1999
    reach = 0
    inv_week = week[inv["inv_date_sk"] - sk0]
    for line in np.flatnonzero(ok):
        reach += int(((inv["inv_item_sk"] == cs["cs_item_sk"][line]) &
                      (inv_week == week[cs["cs_sold_date_sk"][line] - sk0])
                      & (inv["inv_quantity_on_hand"] <
                         cs["cs_quantity"][line])).sum())
    return pairs, kept, reach


def test_a_rehearsal_of_the_cell_is_correct(bench, deployment, tmp_path,
                                            monkeypatch):
    monkeypatch.setattr(bench.harness, "DATA_DIR", str(tmp_path))
    DeviceScanCache.get().clear()
    trace.reset()
    result = bench.harness.run_cell(deployment.cell, SEED, 0.3, True,
                                    scale=SCALE, device=dict(DEVICE))
    DeviceScanCache.get().clear()
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["wrong_cells"]["value"] == 0
    assert result["attempted"] >= 2 and list(result)[-1] == "compared"
    # a CPU rehearsal reads no device metric
    assert not set(NEW_METRICS) & set(result["metrics"])
    pairs, kept, reach = _residual_counts(deployment)
    *_, q72 = [c for c in trace.coarse_counts().values()
               if "plan.pushdown.outer" in c]
    # the inventory join's pairs, then the d3 join's: a pair a row
    assert 0 < reach < kept < pairs
    assert q72["join.residual.pairs"] == pairs + reach
    assert kept < q72["join.residual.kept"] <= kept + reach
    # both conditions read two 4-byte columns (data + validity: 5 bytes
    # each); two int32 maps and a flag a pair
    assert q72["join.residual.bytes"] == (pairs + reach) * (2 * 5 + 8 + 1)
    # both joins are decided by bounds: one launch a stream batch, where
    # deciding pair by pair took one a 2^22 pairs and one a batch more
    assert q72["join.residual.range"] == q72["join.residual.chunks"]
    assert 2 <= q72["join.residual.chunks"] < -(-pairs // (1 << 22))
    # the six WHERE conjuncts, each through both LEFT OUTER joins
    assert q72["plan.pushdown.outer"] == 12


# ---------------------------------------------------------------------------
# (c) the three metric readers on recorded runs
# ---------------------------------------------------------------------------

#: two passes of q72, q95; the traced pass is the first
RUN = {"queries": [{"done": 0.2, "seconds": 0.1},
                   {"done": 0.4, "seconds": 0.1},
                   {"done": 0.6, "seconds": 0.1},
                   {"done": 0.8, "seconds": 0.1}],
       "peaks": {"hbm_gbps": 819},
       "trace": {"queries": ["q72", "q95"], "busy_s": 3.0, "window_s": 4.0,
                 "device_ops": [["jit_join_probe_core", 1.0],
                                ["jit_join_residual_core", 0.5],
                                ["jit_join_expand_core", 0.2]]}}

#: a q72 and a q95 of a window: 240 + 4 launches, 19 bytes a pair
Q72 = {"join.residual.chunks": 240, "join.residual.pairs": 942_014_250,
       "join.residual.bytes": 19 * 942_014_250}
Q95 = {"join.residual.chunks": 4, "join.residual.pairs": 15_823_532,
       "join.residual.bytes": 27 * 15_823_532}


def _window(counts):
    return {"spans": [], "self_ns": {}, "n_queries": 4, "counts": counts}


def _least_ms(counts):
    total = sum(c["join.residual.bytes"] for c in counts.values())
    return total / 4 / (819 * 1e9) * 1e3


WINDOW = {1: dict(Q72), 2: dict(Q95), 3: dict(Q72), 4: dict(Q95)}


@pytest.mark.parametrize("metric,counts,want", [
    ("join_residual_chunks_per_query", WINDOW, 122.0),
    ("join_residual_device_ms_per_query", WINDOW, 250.0),
    ("join_residual_hbm_roofline_pct", WINDOW,
     100.0 * _least_ms(WINDOW) / 250.0),
    # an engine without the counters (the parent), or nothing counted
    ("join_residual_chunks_per_query", {1: {}, 2: {}}, None),
    ("join_residual_device_ms_per_query", {1: {}, 2: {}}, None),
    ("join_residual_hbm_roofline_pct", {1: {}, 2: {}}, None),
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


def test_the_residual_program_off_the_top_ten(bench, monkeypatch):
    """Counted, but off the traced pass's ten longest programs: the
    device time reads 0 (a lower bound), and the roofline nothing."""
    run = dict(RUN, trace=dict(RUN["trace"], device_ops=[
        ["jit_join_probe_core", 1.0]]))
    monkeypatch.setattr(bench.span_reduce, "_LAST", [run, _window(WINDOW)])
    assert bench.harness.metric_reader(
        "join_residual_device_ms_per_query")(run) == 0.0
    assert bench.harness.metric_reader(
        "join_residual_hbm_roofline_pct")(run) is None
