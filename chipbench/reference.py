"""The plain reference and the comparison that decides ``correct``.

Independent of the engine: it reads the generated Parquet files with
pyarrow, answers each query with the numpy function kept beside the
query's text (``queries/<config>/<query>.py``) and imports nothing of
``spark_rapids_tpu``.  The same functions computed in float32 are the
control: the nearest precision below the DOUBLE the configurations
state (the step ``variableFloatAgg.enabled`` takes inside the engine).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq

HERE = os.path.dirname(os.path.abspath(__file__))
#: exact python sums up to this many addends, extended precision beyond
FSUM_MAX = 1 << 16


class StrCol:
    """A string column as codes into its distinct values (18M python
    strings would take minutes; the codes take a second)."""

    def __init__(self, column: pa.ChunkedArray):
        """``column`` as Parquet's reader hands it over with
        ``read_dictionary``: dictionary-typed chunks."""
        column = column.unify_dictionaries()
        self.cats = column.chunk(0).dictionary.to_pylist() \
            if column.num_chunks else []
        self.codes = np.concatenate(
            [c.indices.to_numpy(zero_copy_only=False)
             for c in column.chunks]) if column.num_chunks \
            else np.zeros(0, np.int32)

    def arrow_nbytes(self) -> int:
        """Buffer bytes of the column as a plain Arrow string array:
        int32 offsets plus the characters."""
        lens = np.array([len(c.encode()) for c in self.cats], np.int64)
        return 4 * (len(self.codes) + 1) + int(
            (np.bincount(self.codes, minlength=len(lens)) * lens).sum())

    def eq(self, value: str) -> np.ndarray:
        if value not in self.cats:
            return np.zeros(len(self.codes), bool)
        return self.codes == self.cats.index(value)

    def decode(self, index) -> list:
        return [self.cats[c] for c in self.codes[index].tolist()]


class Num:
    """Arithmetic of one precision: ``float64`` is the reference (sums
    exact or in extended precision, rounded once), ``float32`` the
    control (every product and sum in float32)."""

    def __init__(self, precision: str):
        self.f = {"float64": np.float64, "float32": np.float32}[precision]
        self.exact = precision == "float64"

    def sum(self, values):
        v = np.asarray(values)
        if v.size == 0:
            return None                     # SQL: sum of no rows is NULL
        if v.dtype.kind != "f":
            return int(v.sum())
        if not self.exact:
            return float(v.astype(self.f).sum(dtype=self.f))
        if v.size <= FSUM_MAX:
            return math.fsum(v.tolist())
        return float(v.sum(dtype=np.longdouble))

    def avg(self, values):
        v = np.asarray(values)
        if v.size == 0:
            return None
        if not self.exact:
            s = v.astype(self.f).sum(dtype=self.f)
            return float(s / self.f(v.size))
        return float(np.longdouble(self.sum(v)) / v.size)

    @staticmethod
    def lookup(keys: np.ndarray, probe: np.ndarray) -> np.ndarray:
        """Row of ``keys`` (unique) equal to each probe value, -1 if none."""
        order = np.argsort(keys, kind="stable")
        pos = np.searchsorted(keys, probe, sorter=order)
        pos = np.minimum(pos, len(keys) - 1)
        row = order[pos]
        return np.where(keys[row] == probe, row, -1)

    @staticmethod
    def group(keys, values) -> dict:
        out: dict = {}
        for k, v in zip(keys, np.asarray(values).tolist()):
            out.setdefault(k, []).append(v)
        return out


def query_needs(config: str, query: str) -> dict:
    with open(os.path.join(HERE, "queries", config, f"{query}.json")) as f:
        return json.load(f)["tables"]


def load_tables(data_dir: str, needs: dict, num: Num):
    """-> ({table: {column: array | StrCol}}, {table: {column: bytes}}):
    the columns as the reference computes on them, and each column's
    Arrow buffer size (what the roofline's byte count reads)."""
    tables, nbytes = {}, {}
    for table, columns in needs.items():
        path = os.path.join(data_dir, f"{table}.parquet")
        schema = papq.read_schema(path)
        strings = [c for c in columns
                   if pa.types.is_string(schema.field(c).type)]
        t = papq.read_table(path, columns=sorted(columns),
                            read_dictionary=strings)
        tables[table], nbytes[table] = {}, {}
        for name in t.column_names:
            col = t.column(name)
            if name in strings:
                tables[table][name] = StrCol(col)
                nbytes[table][name] = tables[table][name].arrow_nbytes()
            else:
                arr = col.to_numpy()
                nbytes[table][name] = col.nbytes
                if arr.dtype.kind == "f":
                    arr = arr.astype(num.f, copy=False)
                tables[table][name] = arr
    return tables, nbytes


def load_py(path: str):
    """The module in ``path``: how a query's reference and a metric's
    reader are found by name."""
    name = "chipbench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _answer_fn(config: str, query: str):
    return load_py(os.path.join(HERE, "queries", config,
                                f"{query}.py")).answer


def answers(config: str, queries, data_dir: str,
            precision: str = "float64"):
    """-> ({query: rows}, {query: referenced bytes}).  Every column
    some query references is loaded once (3.4 GB of host memory for
    TPC-H SF10's lineitem, 1.7 GB for SF5) and each query sees the whole."""
    num = Num(precision)
    queries = list(dict.fromkeys(queries))
    needs = {q: query_needs(config, q) for q in queries}
    union: dict = {}
    for need in needs.values():
        for table, columns in need.items():
            union.setdefault(table, set()).update(columns)
    tables, nb = load_tables(data_dir, union, num)
    rows = {q: _answer_fn(config, q)(tables, num) for q in queries}
    nbytes = {q: sum(nb[t][c] for t, cols in needs[q].items() for c in cols)
              for q in queries}
    return rows, nbytes


def compare(got, want) -> dict:
    """One answer against the reference's, row by row in the order both
    return.  ``wrong_cells`` counts what must be equal and is not (rows
    missing or extra, keys, counts, strings, NULLs); ``max_rel_gap`` is
    the widest gap of a float cell as a share of the reference's value."""
    wrong = abs(len(got) - len(want))
    gap = 0.0
    for rg, rw in zip(got, want):
        if len(rg) != len(rw):
            wrong += 1
            continue
        for g, w in zip(rg, rw):
            if isinstance(w, float) and isinstance(g, float):
                if math.isnan(g) or math.isnan(w):
                    wrong += math.isnan(g) != math.isnan(w)
                elif g != w:
                    gap = max(gap, abs(g - w) / max(abs(w), 1e-300))
            elif g != w or type(g) is not type(w):
                wrong += 1
    return {"wrong_cells": wrong, "max_rel_gap": gap}
