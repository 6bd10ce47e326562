"""TPC-DS catalog, web and inventory tables for q72 and q95 (schema
``tpcds_cw``): ``datagen/tpcds.py``'s dimension generators and streams
(``date_dim``, ``item``, ``customer_demographics``,
``household_demographics``, ``promotion``) with the tables those two
queries add, at the spec's SF1 row counts, by dsdgen's rules where this
module can follow them.

``catalog_sales`` (1,441,548 lines): orders of 1..16 lines, numbered
from 1 in line order; an order's lines carry its sold date (uniform over
1998-01-01 to 2002-12-30, as ``tpcds.py``'s sales), its bill
demographics (uniform keys) and distinct items (``(start + j x step) mod
items``, ``step`` < items / 16, so ``(cs_item_sk, cs_order_number)`` is a
key as in the spec); a line ships 2..90 days after the sale and carries a
promotion (uniform, NULL on one line in fifty) and ``cs_quantity``
uniform in 1..100.  ``catalog_returns`` (144,067): a tenth of the sales
lines, drawn without repeats, with the sale's item and order number.

``inventory`` (11,745,000): a snapshot each week from 1998-01-01 (261
weeks), for each of the 5 warehouses and each of half the item keys
(the even ones: 9,000 of 18,000), ``inv_quantity_on_hand`` uniform in
0..1000.  ``warehouse``: 5 rows with names of 10..20 characters.

``web_sales`` (719,384 lines): orders of 1..16 lines placed at one of the
30 web sites and shipped to one of the 50,000 addresses; each line from
one of the 5 warehouses (uniform), shipped 1..120 days after the order's
sold date, ``ws_ext_ship_cost`` and ``ws_net_profit`` from its quantity
(1..100) and unit amounts.  ``web_returns`` (71,763): a tenth of the web
lines, drawn without repeats, with the sale's item and order number.
``web_site``: 30 rows, ``web_company_name`` the spec's six names in turn
(five sites are 'pri').  ``customer_address``: 50,000 rows, ``ca_state``
uniform over ten states ('IL' one of them), so q95's answer is not
empty.

Every key column, date and count comes from ``tpcds.SHAPE_SEED``'s
fixed streams; ``--seed`` draws the measures (the quantities and money
columns).  DECIMAL is written as DOUBLE, surrogate keys count from 0
as in ``tpcds.py``; no NULL in any column read but ``cs_promo_sk``."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq

from . import table_rng, tpcds

ROWS_PER_SF = {"catalog_sales": 1_441_548, "catalog_returns": 144_067,
               "web_sales": 719_384, "web_returns": 71_763,
               "customer_address": 50_000}
N_WAREHOUSES = 5
N_WEB_SITES = 30
INVENTORY_WEEKS = 261
MAX_LINES = 16                       # lines an order: uniform 1..16
WAREHOUSE_NAMES = ["Conventional childr", "Important issues liv",
                   "Doors canno", "Bad cards must make.", "Operations"]
COMPANY_NAMES = ["pri", "able", "ought", "ese", "anti", "cally"]
STATES = ["IL", "TX", "GA", "VA", "KY", "MO", "OH", "IN", "NC", "TN"]
DIMENSIONS = ("date_dim", "item", "customer_demographics",
              "household_demographics", "promotion")
TABLES = DIMENSIONS + ("catalog_sales", "catalog_returns", "inventory",
                       "warehouse", "web_sales", "web_returns", "web_site",
                       "customer_address")


def row_counts(scale: float) -> dict:
    """Rows of each table at ``scale``, the same for every seed."""
    dims = tpcds.row_counts(scale)
    n = {t: dims[t] for t in DIMENSIONS}
    for t, v in ROWS_PER_SF.items():
        n[t] = max(int(v * scale), 64)
    n["inventory"] = INVENTORY_WEEKS * N_WAREHOUSES * (n["item"] // 2)
    n["warehouse"] = N_WAREHOUSES
    n["web_site"] = N_WEB_SITES
    return n


def _orders(n_lines: int, rng):
    """-> (order of each line, position of the line in its order): orders
    of 1..MAX_LINES lines until ``n_lines``, the last one cut."""
    sizes = rng.integers(1, MAX_LINES + 1, n_lines // 4 + MAX_LINES)
    ends = np.cumsum(sizes)
    k = int(np.searchsorted(ends, n_lines)) + 1
    sizes = sizes[:k].copy()
    sizes[-1] -= int(ends[k - 1]) - n_lines
    order = np.repeat(np.arange(k), sizes)
    first = np.repeat(np.cumsum(sizes) - sizes, sizes)
    return order, np.arange(n_lines) - first, k


def _distinct_items(order, pos, k, n_items, rng):
    """An item a line, distinct within its order."""
    start = rng.integers(0, n_items, k)
    step = rng.integers(1, max(n_items // MAX_LINES, 2), k)
    return ((start[order] + pos * step[order]) % n_items).astype(np.int64)


def _sample_lines(n_lines: int, n_returns: int, rng) -> np.ndarray:
    return np.sort(rng.choice(n_lines, n_returns, replace=False))


def _catalog(n, seed):
    keys = table_rng(tpcds.SHAPE_SEED, "catalog_sales.keys")
    nl = n["catalog_sales"]
    order, pos, k = _orders(nl, keys)
    sold = tpcds.SALES_SK0 + keys.integers(0, tpcds.N_SALES_DATES, k)
    promo = keys.integers(0, n["promotion"], nl).astype(np.int64)
    promo_null = keys.random(nl) < 0.02
    sales = {
        "cs_sold_date_sk": sold[order].astype(np.int64),
        "cs_ship_date_sk": (sold[order] + keys.integers(2, 91, nl))
        .astype(np.int64),
        "cs_bill_cdemo_sk": keys.integers(
            0, n["customer_demographics"], k).astype(np.int64)[order],
        "cs_bill_hdemo_sk": keys.integers(
            0, n["household_demographics"], k).astype(np.int64)[order],
        "cs_item_sk": _distinct_items(order, pos, k, n["item"], keys),
        "cs_promo_sk": pa.array(promo, mask=promo_null),
        "cs_order_number": (order + 1).astype(np.int64),
        "cs_quantity": table_rng(seed, "catalog_sales").integers(
            1, 101, nl).astype(np.int32),
    }
    ret = _sample_lines(nl, n["catalog_returns"],
                        table_rng(tpcds.SHAPE_SEED, "catalog_returns"))
    returns = {"cr_item_sk": sales["cs_item_sk"][ret],
               "cr_order_number": sales["cs_order_number"][ret]}
    return sales, returns


def _inventory(n, seed):
    items = np.arange(0, 2 * (n["item"] // 2), 2, dtype=np.int64)
    w, i, h = np.meshgrid(np.arange(INVENTORY_WEEKS), items,
                          np.arange(N_WAREHOUSES), indexing="ij")
    return {
        "inv_date_sk": (tpcds.SALES_SK0 + 7 * w.ravel()).astype(np.int64),
        "inv_item_sk": i.ravel(),
        "inv_warehouse_sk": h.ravel().astype(np.int64),
        "inv_quantity_on_hand": table_rng(seed, "inventory").integers(
            0, 1001, n["inventory"]).astype(np.int32),
    }


def _web(n, seed):
    keys = table_rng(tpcds.SHAPE_SEED, "web_sales.keys")
    nl = n["web_sales"]
    order, pos, k = _orders(nl, keys)
    sold = tpcds.SALES_SK0 + keys.integers(0, tpcds.N_SALES_DATES, k)
    m = table_rng(seed, "web_sales")
    qty = m.integers(1, 101, nl)
    ship_unit = m.integers(0, 5001, nl) / 100.0
    profit_unit = m.integers(-5000, 10001, nl) / 100.0
    sales = {
        "ws_order_number": (order + 1).astype(np.int64),
        "ws_item_sk": _distinct_items(order, pos, k, n["item"], keys),
        "ws_warehouse_sk": keys.integers(0, N_WAREHOUSES, nl)
        .astype(np.int64),
        "ws_ship_date_sk": (sold[order] + keys.integers(1, 121, nl))
        .astype(np.int64),
        "ws_ship_addr_sk": keys.integers(
            0, n["customer_address"], k).astype(np.int64)[order],
        "ws_web_site_sk": keys.integers(0, N_WEB_SITES, k)
        .astype(np.int64)[order],
        "ws_ext_ship_cost": np.round(qty * ship_unit, 2),
        "ws_net_profit": np.round(qty * profit_unit, 2),
    }
    ret = _sample_lines(nl, n["web_returns"],
                        table_rng(tpcds.SHAPE_SEED, "web_returns"))
    returns = {"wr_item_sk": sales["ws_item_sk"][ret],
               "wr_order_number": sales["ws_order_number"][ret]}
    return sales, returns


def _small(n):
    keys = table_rng(tpcds.SHAPE_SEED, "customer_address")
    return {
        "warehouse": {
            "w_warehouse_sk": np.arange(N_WAREHOUSES, dtype=np.int64),
            "w_warehouse_name": WAREHOUSE_NAMES},
        "web_site": {
            "web_site_sk": np.arange(N_WEB_SITES, dtype=np.int64),
            "web_company_name": [COMPANY_NAMES[i % len(COMPANY_NAMES)]
                                 for i in range(N_WEB_SITES)]},
        "customer_address": {
            "ca_address_sk": np.arange(n["customer_address"],
                                       dtype=np.int64),
            "ca_state": pa.DictionaryArray.from_arrays(
                pa.array(keys.integers(0, len(STATES),
                                       n["customer_address"])
                         .astype(np.int8)),
                pa.array(STATES)).cast(pa.string())},
    }


def refuse_engine_without_chunked_residual() -> None:
    """End the run, cleanly and before any data is written, on an engine
    that cannot give this configuration a result: one whose planner
    leaves q72's WHERE above its two outer joins and makes a two-sided
    inequality a Filter above the join, and whose residual join decides
    all of a stream batch's candidate pairs in one program.  q72 then
    expands about 9.4 x 10^8 rows through every inner join before a row
    drops, a pass that runs toward a run's time limit rather than
    failing.  A parent commit that cannot run a new configuration is to
    fail with an exit code other than 0, soon.  A generator used
    without the engine generates."""
    import importlib.util
    if importlib.util.find_spec("spark_rapids_tpu") is None:
        return
    from spark_rapids_tpu.exec.tpu_join import TpuHashJoinBase
    from spark_rapids_tpu.plan import logical_opt
    if not (hasattr(logical_opt, "_rewrite_filter_outer")
            and hasattr(logical_opt, "_residual_condition")
            and hasattr(TpuHashJoinBase, "_residual_batches")):
        raise SystemExit(
            "chipbench/datagen/tpcds_cw.py: this engine keeps a WHERE "
            "above outer joins, filters a two-sided inequality above its "
            "join and decides a batch's candidate pairs in one program; "
            "q72 of schema tpcds_cw cannot end inside a run's time limit "
            "on it")


def generate(data_dir: str, scale: float, seed: int, tables) -> dict:
    refuse_engine_without_chunked_residual()
    n = row_counts(scale)
    tables = list(tables)
    for name in tables:
        if name not in TABLES:
            raise KeyError(f"tpcds_cw datagen has no table {name!r}")
    cols = {}
    if {"catalog_sales", "catalog_returns"} & set(tables):
        cols["catalog_sales"], cols["catalog_returns"] = _catalog(n, seed)
    if {"web_sales", "web_returns"} & set(tables):
        cols["web_sales"], cols["web_returns"] = _web(n, seed)
    cols.update(_small(n))
    rows = {}
    for name in tables:
        if name in DIMENSIONS:
            table = pa.table(tpcds.DIMENSIONS[name](
                n, table_rng(tpcds.SHAPE_SEED, name)))
        elif name == "inventory":
            table = pa.table(_inventory(n, seed))
        else:
            table = pa.table(cols[name])
        papq.write_table(table, os.path.join(data_dir, f"{name}.parquet"))
        rows[name] = table.num_rows
    return rows
