"""TPC-H lineitem: a numpy stand-in for dbgen that writes the eight
columns ``benchmarks/tpch.py`` does (dates as day numbers, DOUBLE for
DECIMAL(15,2)), in chunks so that millions of rows never sit in memory
twice.  The distributions Q1 and Q6 see are dbgen's (TPC-H v3 clause
4.2.3): ``l_quantity`` 1-50, ``l_discount`` 0.00-0.10, ``l_tax``
0.00-0.08, ``l_extendedprice`` = quantity x the part's retail price
(900.00-2098.99 from ``l_partkey``), ``l_shipdate`` = order date + 1-121
days with order dates uniform over 1992-01-01 to 1998-08-02,
``l_returnflag`` R or A when the receipt date (ship + 1-30 days) is on
or before 1995-06-17 and N after, ``l_linestatus`` O when shipped after
that day and F before: four groups, N/F the small one.  Not dbgen's:
``l_orderkey`` is uniform over the orders (dbgen gives each order 1-7
lines), and the row count is 6,000,000 x scale."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq

from . import table_rng

ROWS_PER_SF = {"lineitem": 6_000_000, "orders": 1_500_000}
CHUNK_ROWS = 4_000_000
ORDER_DAY0, ORDER_DAY1 = 8035, 10440    # 1992-01-01 .. 1998-08-02
CURRENT_DAY = 9298                      # dbgen's CURRENTDATE, 1995-06-17


def row_counts(scale: float) -> dict:
    return {"lineitem": max(int(ROWS_PER_SF["lineitem"] * scale), 1000),
            "orders": max(int(ROWS_PER_SF["orders"] * scale), 250)}


def _strings(codes, values):
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int8)), pa.array(values)).cast(pa.string())


def _lineitem(path, n, seed):
    rng = table_rng(seed, "lineitem")
    # an order's date belongs to the orders table: its own stream, so an
    # orders generator added later draws the same dates
    o_date = table_rng(seed, "orders.o_orderdate").integers(
        ORDER_DAY0, ORDER_DAY1 + 1, n["orders"])
    n_parts = max(int(200_000 * n["lineitem"] / ROWS_PER_SF["lineitem"]), 1)
    writer = None
    try:
        for pos in range(0, n["lineitem"], CHUNK_ROWS):
            k = min(CHUNK_ROWS, n["lineitem"] - pos)
            li_order = rng.integers(0, n["orders"], k)
            part = rng.integers(1, n_parts + 1, k)
            retail_cents = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
            qty = rng.integers(1, 51, k)
            ship = o_date[li_order] + rng.integers(1, 122, k)
            receipt = ship + rng.integers(1, 31, k)
            # 0 = A, 1 = N, 2 = R
            flag = np.where(receipt <= CURRENT_DAY,
                            rng.integers(0, 2, k) * 2, 1)
            chunk = pa.table({
                "l_orderkey": li_order.astype(np.int64),
                "l_quantity": qty.astype(np.float64),
                "l_extendedprice": qty * retail_cents / 100.0,
                "l_discount": rng.integers(0, 11, k) / 100.0,
                "l_tax": rng.integers(0, 9, k) / 100.0,
                "l_returnflag": _strings(flag, ["A", "N", "R"]),
                "l_linestatus": _strings(ship > CURRENT_DAY, ["F", "O"]),
                "l_shipdate": ship.astype(np.int32),
            })
            if writer is None:
                writer = papq.ParquetWriter(path, chunk.schema)
            writer.write_table(chunk)
    finally:
        if writer is not None:
            writer.close()
    return n["lineitem"]


def generate(data_dir: str, scale: float, seed: int, tables) -> dict:
    n = row_counts(scale)
    rows = {}
    for name in tables:
        if name != "lineitem":
            raise KeyError(f"tpch datagen has no table {name!r}")
        rows[name] = _lineitem(
            os.path.join(data_dir, "lineitem.parquet"), n, seed)
    return rows
