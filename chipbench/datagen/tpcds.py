"""TPC-DS store-channel star schema: the benchmark's own copy of
``benchmarks/tpcds.py``, a numpy stand-in for dsdgen, cut to the tables
the store queries read and re-seeded per table.  What it keeps of the
spec: the tables' names, their SF1 row counts (``store_sales`` rounded
to 2,880,000 for 2,880,404) and the spec's names for the columns it
writes.  What it does not: ``store_sales`` and the two demographics
tables carry every column; ``date_dim``, ``item``, ``promotion``,
``time_dim`` and ``store`` only the columns listed in the configuration
(a columnar scan prunes the rest); foreign keys are uniform, surrogate
keys count from 0, DECIMAL(7,2) is DOUBLE.  ``date_dim`` is the spec's
calendar (1900-01-02 to 2100-01-01, Julian day numbers) and
``customer_demographics`` the spec's full cross product of its eight
attributes; sales fall in 1998-2002.

``--seed`` draws ``store_sales``' measure columns (quantities, prices,
costs).  The dimension tables and ``store_sales``' key columns come from
one fixed stream, the same for every seed: the store queries filter and
join on keys and dimension attributes alone, so every seed gives every
query the same row counts at every operator (and so the same compiled
shapes) and different sums.  With keys drawn from the seed, each new
seed made the engine compile 58-90 more programs for 58-111 s of set-up
(my chip run, PR 25, PERF.md section 6)."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq

from . import table_rng

ROWS_PER_SF = {"store_sales": 2_880_000, "item": 18_000,
               "customer": 100_000, "customer_address": 50_000,
               "customer_demographics": 1_920_800, "store": 12,
               "household_demographics": 7_200, "promotion": 300,
               "date_dim": 73_049, "time_dim": 86_400}
FIXED = ("date_dim", "time_dim")          # the same at every scale
#: the same at every scale factor from 1 up; cut below it (rehearsals)
FIXED_FROM_SF1 = ("customer_demographics", "household_demographics")
SHAPE_SEED = 0               # the fixed stream of keys and dimensions
CALENDAR_DAY0 = np.datetime64("1900-01-02")     # d_date_sk 2415022
CALENDAR_SK0 = 2415022
SALES_SK0 = 2450815         # 1998-01-01
N_SALES_DATES = 365 * 5     # sales fall in 1998-2002
#: customer_demographics is the cross product of these, cd_demo_sk its
#: mixed-radix index (the spec's 1,920,800 rows at every scale factor)
CD_ATTRIBUTES = (
    ("cd_gender", ["M", "F"]),
    ("cd_marital_status", ["M", "S", "D", "W", "U"]),
    ("cd_education_status", ["Primary", "Secondary", "College",
                             "2 yr Degree", "4 yr Degree",
                             "Advanced Degree", "Unknown"]),
    ("cd_purchase_estimate", np.arange(500, 10001, 500, dtype=np.int32)),
    ("cd_credit_rating", ["Good", "High Risk", "Low Risk", "Unknown"]),
    ("cd_dep_count", np.arange(7, dtype=np.int32)),
    ("cd_dep_employed_count", np.arange(7, dtype=np.int32)),
    ("cd_dep_college_count", np.arange(7, dtype=np.int32)),
)


def row_counts(scale: float) -> dict:
    n = {k: (v if k in FIXED else max(int(v * (
        min(scale, 1) if k in FIXED_FROM_SF1 else scale)), 64))
         for k, v in ROWS_PER_SF.items()}
    n["store"] = max(int(ROWS_PER_SF["store"] * max(scale, 1)), 4)
    return n


def _date_dim(n, rng):
    nd = n["date_dim"]
    ymd = CALENDAR_DAY0 + np.arange(nd).astype("timedelta64[D]")
    years = ymd.astype("datetime64[Y]").astype(int) + 1970
    months = ymd.astype("datetime64[M]").astype(int) % 12 + 1
    dom = (ymd - ymd.astype("datetime64[M]")).astype(int) + 1
    dow = (ymd.astype(int) + 4) % 7  # 0=Sunday
    qoy = (months - 1) // 3 + 1
    day_names = np.array(["Sunday", "Monday", "Tuesday", "Wednesday",
                          "Thursday", "Friday", "Saturday"])
    return {
        "d_date_sk": (CALENDAR_SK0 + np.arange(nd)).astype(np.int64),
        "d_date": ymd,
        "d_year": years.astype(np.int32),
        "d_moy": months.astype(np.int32),
        "d_dom": dom.astype(np.int32),
        "d_qoy": qoy.astype(np.int32),
        "d_dow": dow.astype(np.int32),
        "d_day_name": day_names[dow],
        "d_month_seq": ((years - 1900) * 12 + months - 1).astype(np.int32),
        "d_week_seq": (np.arange(nd) // 7 + 1).astype(np.int32),
        "d_quarter_name": np.array(
            [f"{y}Q{q}" for y, q in zip(years, qoy)]),
    }


def _time_dim(n, rng):
    sec = np.arange(86400)
    return {"t_time_sk": sec.astype(np.int64),
            "t_time": sec.astype(np.int64),
            "t_hour": (sec // 3600).astype(np.int32),
            "t_minute": ((sec % 3600) // 60).astype(np.int32)}


def _item(n, rng):
    ni = n["item"]
    return {
        "i_item_sk": np.arange(ni, dtype=np.int64),
        "i_item_id": np.array([f"AAAAAAAA{i:08d}" for i in range(ni)]),
        "i_item_desc": np.array([f"desc of item {i}" for i in range(ni)]),
        "i_brand_id": rng.integers(1000000, 1000100, ni).astype(np.int64),
        "i_brand": np.array([f"brand#{i % 100}" for i in range(ni)]),
        "i_class": rng.choice(
            ["dresses", "shirts", "pants", "football", "fishing",
             "classical", "rock"], ni),
        "i_class_id": rng.integers(1, 17, ni).astype(np.int64),
        "i_category": rng.choice(
            ["Women", "Men", "Sports", "Music", "Books", "Home"], ni),
        "i_category_id": rng.integers(1, 11, ni).astype(np.int64),
        "i_manufact_id": rng.integers(1, 1000, ni).astype(np.int64),
        "i_manufact": np.array([f"manufact#{i % 1000}" for i in range(ni)]),
        "i_manager_id": rng.integers(1, 100, ni).astype(np.int64),
        "i_current_price": (rng.random(ni) * 100).round(2),
        "i_wholesale_cost": (rng.random(ni) * 80).round(2),
        "i_color": rng.choice(
            ["red", "blue", "green", "yellow", "purple", "orange",
             "white", "black"], ni),
        "i_size": rng.choice(
            ["small", "medium", "large", "extra large", "petite",
             "economy"], ni),
        "i_units": rng.choice(["Each", "Dozen", "Case", "Pallet"], ni),
        "i_product_name": np.array([f"product{i}" for i in range(ni)]),
    }


def _store(n, rng):
    ns = n["store"]
    return {
        "s_store_sk": np.arange(ns, dtype=np.int64),
        "s_store_id": np.array([f"AAAAAAAA{i:04d}" for i in range(ns)]),
        "s_store_name": rng.choice(["ese", "ought", "able", "pri"], ns),
        "s_state": rng.choice(["TN", "SD", "AL", "GA"], ns),
        "s_county": rng.choice(
            ["Williamson County", "Ziebach County", "Walker County"], ns),
        "s_city": rng.choice(["Midway", "Fairview", "Oakland"], ns),
        "s_zip": np.array([str(z) for z in rng.integers(10000, 99999, ns)]),
        "s_number_employees": rng.integers(200, 300, ns).astype(np.int32),
        "s_company_id": np.ones(ns, dtype=np.int32),
        "s_gmt_offset": np.full(ns, -5.0),
        "s_market_id": rng.integers(1, 11, ns).astype(np.int32),
    }


def _customer_demographics(n, rng):
    nd = n["customer_demographics"]
    sk = np.arange(nd, dtype=np.int64)
    cols, rest = {"cd_demo_sk": sk}, sk
    for name, values in CD_ATTRIBUTES:
        rest, digit = np.divmod(rest, len(values))
        if isinstance(values, list):
            cols[name] = pa.DictionaryArray.from_arrays(
                pa.array(digit.astype(np.int8)),
                pa.array(values)).cast(pa.string())
        else:
            cols[name] = values[digit]
    return cols


def _household_demographics(n, rng):
    nh = n["household_demographics"]
    return {
        "hd_demo_sk": np.arange(nh, dtype=np.int64),
        "hd_dep_count": rng.integers(0, 10, nh).astype(np.int32),
        "hd_vehicle_count": rng.integers(-1, 5, nh).astype(np.int32),
        "hd_income_band_sk": rng.integers(1, 21, nh).astype(np.int64),
        "hd_buy_potential": rng.choice(
            ["0-500", "501-1000", "1001-5000", "5001-10000", ">10000",
             "Unknown"], nh),
    }


def _promotion(n, rng):
    npx = n["promotion"]
    return {"p_promo_sk": np.arange(npx, dtype=np.int64),
            "p_channel_email": rng.choice(["Y", "N"], npx),
            "p_channel_event": rng.choice(["Y", "N"], npx),
            "p_channel_dmail": rng.choice(["Y", "N"], npx),
            "p_channel_tv": rng.choice(["Y", "N"], npx)}


def _store_sales(n, keys, rng):
    """``keys``: the fixed stream; ``rng``: the seed's."""
    nss = n["store_sales"]
    price = (rng.random(nss) * 200).round(2)
    qty = rng.integers(1, 100, nss)
    wcost = (rng.random(nss) * 100).round(2)
    ext_sales = (price * qty).round(2)
    ext_wcost = (wcost * qty).round(2)

    def fk(table):
        return keys.integers(0, n[table], nss).astype(np.int64)
    return {
        "ss_sold_date_sk": (SALES_SK0 + keys.integers(
            0, N_SALES_DATES, nss)).astype(np.int64),
        "ss_sold_time_sk": keys.integers(0, 86400, nss).astype(np.int64),
        "ss_item_sk": fk("item"),
        "ss_customer_sk": fk("customer"),
        "ss_cdemo_sk": fk("customer_demographics"),
        "ss_hdemo_sk": fk("household_demographics"),
        "ss_addr_sk": fk("customer_address"),
        "ss_store_sk": fk("store"),
        "ss_promo_sk": fk("promotion"),
        "ss_ticket_number": (keys.integers(0, nss, nss) // 4)
        .astype(np.int64),
        "ss_quantity": qty.astype(np.int32),
        "ss_wholesale_cost": wcost,
        "ss_list_price": (price * 1.2).round(2),
        "ss_sales_price": price,
        "ss_ext_discount_amt": (rng.random(nss) * 100).round(2),
        "ss_ext_sales_price": ext_sales,
        "ss_ext_wholesale_cost": ext_wcost,
        "ss_ext_list_price": (price * 1.2 * qty).round(2),
        "ss_ext_tax": (ext_sales * 0.08).round(2),
        "ss_coupon_amt": (rng.random(nss) * 50).round(2),
        "ss_net_paid": (ext_sales * 0.95).round(2),
        "ss_net_paid_inc_tax": (ext_sales * 1.03).round(2),
        "ss_net_profit": (ext_sales - ext_wcost).round(2),
    }


DIMENSIONS = {"date_dim": _date_dim, "time_dim": _time_dim, "item": _item,
              "store": _store,
              "customer_demographics": _customer_demographics,
              "household_demographics": _household_demographics,
              "promotion": _promotion}
TABLES = tuple(DIMENSIONS) + ("store_sales",)


def generate(data_dir: str, scale: float, seed: int, tables) -> dict:
    n = row_counts(scale)
    rows = {}
    for name in tables:
        if name == "store_sales":
            cols = _store_sales(n, table_rng(SHAPE_SEED, "store_sales.keys"),
                                table_rng(seed, name))
        else:
            cols = DIMENSIONS[name](n, table_rng(SHAPE_SEED, name))
        table = pa.table(cols)
        papq.write_table(table, os.path.join(data_dir, f"{name}.parquet"))
        rows[name] = table.num_rows
    return rows
