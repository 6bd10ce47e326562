"""File scans: Parquet / ORC / CSV with the reference's reader strategies.

Reference: GpuParquetScan.scala:84-1757 — three strategies:
  PERFILE       one file per read (ParquetPartitionReader)
  MULTITHREADED thread-pool prefetch of host buffers per file, overlapping
                I/O with device transfer (MultiFileCloudParquetPartitionReader)
  COALESCING    many small files combined into one host buffer and decoded
                in a single pass (MultiFileParquetPartitionReader)

TPU adaptation: pyarrow does the host-side decode (the cuDF-parser role is
host-side here since TPUs cannot parse Parquet), producing arrow tables
that are transferred to the device as columnar batches.  The strategy
machinery (prefetch threads, coalescing small files, batch-size caps) is
preserved.
"""
from __future__ import annotations

import concurrent.futures
import glob as globmod
import os
from typing import Iterator, List, Optional

import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.parquet as papq

try:
    import pyarrow.orc as paorc
    HAVE_ORC = True
except Exception:  # pragma: no cover
    HAVE_ORC = False

from ..columnar.arrow import from_arrow, schema_from_arrow
from ..obs import trace as _trace
from ..columnar.schema import Schema


def rewrite_paths(paths: List[str], conf=None) -> List[str]:
    """Alluxio-role path rewrite (RapidsConf.scala:1072): apply
    'from->to' prefix rules from spark.rapids.tpu.alluxio.pathsToReplace
    so scans read the configured mirror.  ``conf`` is the scan's own
    TpuConf when available (the active conf is last-session-wins and
    would apply the WRONG session's rules)."""
    from ..config import get_active, ALLUXIO_PATHS_TO_REPLACE
    spec = str((conf or get_active()).get(ALLUXIO_PATHS_TO_REPLACE)
               or "")
    if not spec.strip():
        return paths
    rules = []
    for part in spec.split(";"):
        part = part.strip()
        if part and "->" in part:
            src, dst = part.split("->", 1)
            if not src.strip():
                raise ValueError(
                    "spark.rapids.tpu.alluxio.pathsToReplace rule has "
                    f"an empty 'from' side: {part!r}")
            rules.append((src.strip(), dst.strip()))
    out = []
    for p in paths:
        for src, dst in rules:
            if p.startswith(src):
                p = dst + p[len(src):]
                break
        out.append(p)
    return out


def expand_paths_with_partitions(paths: List[str], conf=None):
    """Expand dirs/globs to files with Hive-style ``key=value`` directory
    components decoded as partition values (reference:
    ColumnarPartitionReaderWithPartitionValues — partition values are
    appended as columns after the file read)."""
    out = []
    for p in rewrite_paths(paths, conf):
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                # hidden/system dirs (in-flight _temporary-* attempt
                # dirs from the write commit protocol) are not data
                dirs[:] = sorted(d for d in dirs
                                 if not d.startswith(("_", ".")))
                pvals = {}
                rel = os.path.relpath(root, p)
                if rel != ".":
                    from urllib.parse import unquote
                    for comp in rel.split(os.sep):
                        if "=" in comp:
                            k, v = comp.split("=", 1)
                            pvals[k] = None \
                                if v == "__HIVE_DEFAULT_PARTITION__" \
                                else unquote(v)
                for f in sorted(files):
                    if not f.startswith(("_", ".")):
                        out.append((os.path.join(root, f), pvals))
        elif any(ch in p for ch in "*?["):
            out.extend((f, {}) for f in sorted(globmod.glob(p)))
        else:
            out.append((p, {}))
    return out


def expand_paths(paths: List[str], conf=None) -> List[str]:
    return [f for f, _ in expand_paths_with_partitions(paths, conf)]


def _read_file(fmt: str, path: str, columns: Optional[List[str]] = None,
               options=None) -> pa.Table:
    if fmt == "parquet":
        return papq.read_table(path, columns=columns, use_threads=False)
    if fmt == "orc":
        if not HAVE_ORC:
            raise RuntimeError("pyarrow.orc unavailable")
        t = paorc.ORCFile(path).read(columns=columns)
        return t
    if fmt == "csv":
        opts = options or {}
        read_opts = pacsv.ReadOptions(
            column_names=opts.get("column_names"),
            skip_rows=1 if opts.get("header", True) and
            not opts.get("column_names") else 0)
        if opts.get("header", True) and not opts.get("column_names"):
            read_opts = pacsv.ReadOptions()
        parse_opts = pacsv.ParseOptions(
            delimiter=opts.get("sep", ","))
        conv = pacsv.ConvertOptions(column_types=opts.get("column_types"))
        t = pacsv.read_csv(path, read_options=read_opts,
                           parse_options=parse_opts, convert_options=conv)
        if columns:
            t = t.select(columns)
        return t
    if fmt == "json":
        import pyarrow.json as pajson
        t = pajson.read_json(path)
        if columns:
            t = t.select(columns)
        return t
    raise ValueError(f"unknown format {fmt}")


def _partition_fields(pairs) -> List:
    """Infer partition-column fields from Hive path values (int64 when
    every value parses as an integer, else string)."""
    from ..columnar.schema import Field
    from ..columnar import dtypes as T
    keys: List[str] = []
    values: dict = {}
    for _, pvals in pairs:
        for k, v in pvals.items():
            if k not in values:
                keys.append(k)
                values[k] = []
            values[k].append(v)
    fields = []
    for k in keys:
        dt = T.INT64
        for v in values[k]:
            if v is None:
                continue
            try:
                int(v)
            except ValueError:
                dt = T.STRING
                break
        fields.append(Field(k, dt, True))
    return fields


def infer_schema(fmt: str, paths: List[str], options=None,
                 conf=None) -> Schema:
    pairs = expand_paths_with_partitions(paths, conf)
    if not pairs:
        raise FileNotFoundError(f"no files match {paths}")
    first = pairs[0][0]
    if fmt == "parquet":
        base = schema_from_arrow(papq.read_schema(first))
    else:
        base = schema_from_arrow(
            _read_file(fmt, first, options=options).schema)
    pf = _partition_fields(pairs)
    if not pf:
        return base
    names = set(base.names)
    return Schema(list(base.fields) +
                  [f for f in pf if f.name not in names])


class FilePartitionReader:
    """Iterator of host arrow tables for a set of files under a strategy."""

    def __init__(self, fmt: str, files: List,
                 columns: Optional[List[str]] = None,
                 strategy: str = "PERFILE", num_threads: int = 4,
                 coalesce_target_rows: int = 1 << 20, options=None,
                 pushed_filters=None, partition_dtypes=None):
        self.fmt = fmt
        # files: plain paths or (path, {partition_col: raw_value}) pairs
        self.files = [(f, {}) if isinstance(f, str) else f for f in files]
        self.columns = columns
        self.strategy = strategy
        self.num_threads = num_threads
        self.coalesce_target_rows = coalesce_target_rows
        self.options = options
        self.pushed_filters = pushed_filters
        self.partition_dtypes = partition_dtypes or {}

    def _read(self, pair) -> pa.Table:
        # coarse span: file read + decode to a host table (set-up time
        # once the scan cache holds the upload)
        with _trace.span("srt.scan.read", "scan", True):
            return self._read_file(pair)

    def _read_file(self, pair) -> pa.Table:
        path, pvals = pair
        # partition-value columns live in the directory layout, not the
        # file: never ask the file reader for them
        cols = self.columns
        if cols is not None and pvals:
            cols = [c for c in cols if c not in pvals]
        if self.fmt == "parquet" and self.pushed_filters:
            import pyarrow.parquet as papq
            try:
                t = papq.read_table(path, columns=cols,
                                    use_threads=False,
                                    filters=self.pushed_filters)
            except Exception:
                # e.g. a pushed predicate on a partition column that is
                # not in the file: fall back to the plain read
                t = _read_file(self.fmt, path, cols, self.options)
        else:
            t = _read_file(self.fmt, path, cols, self.options)
        for k, v in pvals.items():
            if k in t.column_names:
                continue
            dt = self.partition_dtypes.get(k)
            from ..columnar.arrow import to_arrow_type
            at = to_arrow_type(dt) if dt is not None else pa.string()
            if v is None:
                val = None
            elif pa.types.is_integer(at):
                val = int(v)
            else:
                val = v
            t = t.append_column(
                k, pa.array([val] * t.num_rows, type=at))
        if self.columns is not None:
            # restore the requested order (partition values append last)
            sel = [c for c in self.columns if c in t.column_names]
            if sel != t.column_names:
                t = t.select(sel)
        return t

    def __iter__(self) -> Iterator[pa.Table]:
        if self.strategy == "MULTITHREADED" and len(self.files) > 1:
            yield from self._multithreaded()
        elif self.strategy == "COALESCING" and len(self.files) > 1:
            yield from self._coalescing()
        else:
            for f in self.files:
                yield self._read(f)

    def _multithreaded(self):
        """Prefetch host buffers with a thread pool; preserve file order.

        (MultiFileCloudParquetPartitionReader role.)"""
        with concurrent.futures.ThreadPoolExecutor(self.num_threads) as pool:
            futures = [pool.submit(self._read, f) for f in self.files]
            for fut in futures:
                yield fut.result()

    def _coalescing(self):
        """Combine small files into bigger host tables before device

        transfer (MultiFileParquetPartitionReader role)."""
        pending: List[pa.Table] = []
        rows = 0
        for f in self.files:
            t = self._read(f)
            pending.append(t)
            rows += t.num_rows
            if rows >= self.coalesce_target_rows:
                yield pa.concat_tables(pending, promote_options="permissive")
                pending, rows = [], 0
        if pending:
            yield pa.concat_tables(pending, promote_options="permissive")


def split_files_into_partitions(files: List,
                                num_partitions: int) -> List[List]:
    """Greedy size-balanced assignment of files to partitions (accepts
    plain paths or (path, partition_values) pairs)."""
    def path_of(f):
        return f[0] if isinstance(f, tuple) else f
    sizes = [(f, os.path.getsize(path_of(f))
              if os.path.exists(path_of(f)) else 0)
             for f in files]
    sizes.sort(key=lambda x: -x[1])
    num_partitions = max(1, min(num_partitions, len(files) or 1))
    buckets: List[List[str]] = [[] for _ in range(num_partitions)]
    loads = [0] * num_partitions
    for f, s in sizes:
        i = loads.index(min(loads))
        buckets[i].append(f)
        loads[i] += s
    return buckets
