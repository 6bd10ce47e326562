"""TPC-H customer / orders / lineitem: a numpy stand-in for dbgen that
keeps dbgen's key structure for everything Q3 and Q18 read (TPC-H v3
clause 4.2.3), written in chunks of orders so that 30M lines never sit
in memory twice.  Dates are INT day numbers, DECIMAL(15,2) is DOUBLE.

``customer``: 150,000 x scale rows, ``c_custkey`` 1..N, ``c_name``
``Customer#%09d``, ``c_mktsegment`` uniform over the five segments.

``orders``: 1,500,000 x scale rows in key order, ``o_orderkey`` sparse
(the first 8 of every 32 keys: dbgen's ``mk_sparse``), ``o_custkey``
uniform over the customers whose key is not a multiple of 3,
``o_orderdate`` uniform over 1992-01-01..1998-08-02, ``o_shippriority``
0, ``o_totalprice`` the sum over the order's lines of ``l_extendedprice *
(1 + l_tax) * (1 - l_discount)``, summed exactly and rounded to cents
once (dbgen truncates each line).

``lineitem``: 1-7 lines an order, written clustered by order key as
dbgen writes them, ``l_quantity`` 1-50, ``l_extendedprice`` = quantity x
the part's retail price (900.00-2098.99 from ``l_partkey``),
``l_discount`` 0.00-0.10, ``l_tax`` 0.00-0.08 (drawn for
``o_totalprice``, not written), ``l_shipdate`` = order date + 1-121 days.

Not dbgen's: the lines an order are a seeded shuffle of a balanced
multiset (each of 1..7 equally often, the remainder at 4), so every
seed writes exactly 4 lines an order on average: 6,000,000 x scale rows
(30,000,000 at SF5 for dbgen's 29,999,795).  A table's rows are the
same whichever other tables are asked for: order-level draws come from
``table_rng(seed, "orders")``, line-level draws from ``table_rng(seed,
"lineitem")``, and both run for every chunk."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as papq

from . import table_rng

ROWS_PER_SF = {"customer": 150_000, "orders": 1_500_000,
               "lineitem": 6_000_000}
TABLES = tuple(ROWS_PER_SF)
CHUNK_ORDERS = 1_000_000
ORDER_DAY0, ORDER_DAY1 = 8035, 10440    # 1992-01-01 .. 1998-08-02
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]


def row_counts(scale: float) -> dict:
    """Rows of each table at ``scale``, the same for every seed."""
    orders = max(int(ROWS_PER_SF["orders"] * scale), 250)
    return {"customer": max(int(ROWS_PER_SF["customer"] * scale), 25),
            "orders": orders, "lineitem": 4 * orders}


def order_keys(index: np.ndarray) -> np.ndarray:
    """dbgen's sparse order keys for the 1-based order numbers
    ``index``: 8 keys kept of every 32."""
    index = np.asarray(index, np.int64)
    return ((index >> 3) << 5) + (index & 7)


def lines_per_order(n_orders: int, seed: int) -> np.ndarray:
    """1..7 equally often and the remainder at 4, shuffled: 4 x
    ``n_orders`` lines whatever the seed."""
    each, rest = divmod(n_orders, 7)
    counts = np.concatenate([np.repeat(np.arange(1, 8, dtype=np.int8), each),
                             np.full(rest, 4, np.int8)])
    table_rng(seed, "orders.lines").shuffle(counts)
    return counts


def _customer(path, n, seed):
    rng = table_rng(seed, "customer")
    key = np.arange(1, n["customer"] + 1, dtype=np.int64)
    name = pc.binary_join_element_wise(
        "Customer#", pc.utf8_lpad(pa.array(key).cast(pa.string()), 9, "0"),
        "")
    seg = pa.DictionaryArray.from_arrays(
        pa.array(rng.integers(0, len(SEGMENTS), len(key)).astype(np.int8)),
        pa.array(SEGMENTS)).cast(pa.string())
    papq.write_table(pa.table({"c_custkey": key, "c_name": name,
                               "c_mktsegment": seg}), path)


def chunks(n: dict, seed: int):
    """-> (orders columns, lineitem columns) as numpy arrays, one pair
    for every ``CHUNK_ORDERS`` orders in key order; the lines hold
    ``l_tax`` too, which no file gets."""
    o_rng, l_rng = table_rng(seed, "orders"), table_rng(seed, "lineitem")
    counts = lines_per_order(n["orders"], seed)
    open_cust = n["customer"] - n["customer"] // 3
    n_parts = max(int(200_000 * n["lineitem"] / ROWS_PER_SF["lineitem"]), 1)
    for pos in range(0, n["orders"], CHUNK_ORDERS):
        k = min(CHUNK_ORDERS, n["orders"] - pos)
        okey = order_keys(np.arange(pos + 1, pos + k + 1))
        j = o_rng.integers(0, open_cust, k)
        o_date = o_rng.integers(ORDER_DAY0, ORDER_DAY1 + 1, k)
        cnt = counts[pos:pos + k].astype(np.int64)
        first = np.concatenate([[0], np.cumsum(cnt)[:-1]])
        of_line = np.repeat(np.arange(k), cnt)
        m = len(of_line)
        part = l_rng.integers(1, n_parts + 1, m)
        retail_cents = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
        qty = l_rng.integers(1, 51, m)
        disc = l_rng.integers(0, 11, m)
        tax = l_rng.integers(0, 9, m)
        ship = o_date[of_line] + l_rng.integers(1, 122, m)
        ext_cents = qty * retail_cents
        # cents x 10^4, exact in int64: at most 7 x 1.05e7 x 108 x 100
        total = np.add.reduceat(ext_cents * (100 + tax) * (100 - disc), first)
        yield ({"o_orderkey": okey,
                "o_custkey": j + j // 2 + 1,
                "o_orderdate": o_date.astype(np.int32),
                "o_shippriority": np.zeros(k, np.int32),
                "o_totalprice": ((total + 5000) // 10000) / 100.0},
               {"l_orderkey": okey[of_line],
                "l_quantity": qty.astype(np.float64),
                "l_extendedprice": ext_cents / 100.0,
                "l_discount": disc / 100.0,
                "l_tax": tax / 100.0,
                "l_shipdate": ship.astype(np.int32)})


def _orders_and_lines(paths, n, seed):
    """Both tables chunk by chunk; only those in ``paths`` are written."""
    writers = {}
    try:
        for orders, lines in chunks(n, seed):
            del lines["l_tax"]
            for name, columns in (("orders", orders), ("lineitem", lines)):
                if name not in paths:
                    continue
                chunk = pa.table(columns)
                if name not in writers:
                    writers[name] = papq.ParquetWriter(paths[name],
                                                       chunk.schema)
                writers[name].write_table(chunk)
    finally:
        for w in writers.values():
            w.close()


def refuse_engine_before_pr32() -> None:
    """End the run, cleanly and before any data is written, on an engine
    that cannot finish it: until PR 32 ``columnar/batch.py`` compiled a
    concat for every exact tuple of row counts (``_concat_plain_jit``),
    and this schema's filtered row counts, shuffle partitions and group
    counts all move with the seed, so every run compiled a few hundred
    programs more and the parent of PR 32 was still running at the
    driver's limit of 1,200 s (its refusal of 2026-10-03; PERF.md section
    6).  The driver's contract asks a parent that cannot run a new
    configuration to fail with an exit code other than 0, soon.  A
    generator used without the engine generates."""
    import importlib.util
    if importlib.util.find_spec("spark_rapids_tpu") is None:
        return
    from spark_rapids_tpu.columnar import batch
    if hasattr(batch, "_concat_plain_jit"):
        raise SystemExit(
            "chipbench/datagen/tpch3.py: this engine compiles "
            "concat_batches by exact row counts (_concat_plain_jit); a run "
            "of schema tpch3 on it does not end inside a run's time limit")


def generate(data_dir: str, scale: float, seed: int, tables) -> dict:
    refuse_engine_before_pr32()
    n = row_counts(scale)
    tables = list(tables)
    for name in tables:
        if name not in TABLES:
            raise KeyError(f"tpch3 datagen has no table {name!r}")
    if "customer" in tables:
        _customer(os.path.join(data_dir, "customer.parquet"), n, seed)
    paths = {t: os.path.join(data_dir, f"{t}.parquet")
             for t in ("orders", "lineitem") if t in tables}
    if paths:
        _orders_and_lines(paths, n, seed)
    return {t: n[t] for t in tables}
