"""Scan/write physical operators for both engines.

Reference: GpuFileSourceScanExec / GpuParquetFileFormat (write) and their
CPU counterparts; the planner (plan/overrides.py) picks TPU vs CPU per
tagging.
"""
from __future__ import annotations

import os
from typing import List, Optional

import pyarrow as pa
import pyarrow.parquet as papq
import pyarrow.csv as pacsv

from ..columnar.arrow import from_arrow, to_arrow, schema_to_arrow
from ..columnar.schema import Schema
from ..config import (TpuConf, PARQUET_READER_TYPE, MULTITHREAD_READ_THREADS,
                      SHUFFLE_PARTITIONS, MAX_READER_BATCH_ROWS)
from ..exec.base import PhysicalPlan, NUM_OUTPUT_ROWS
from ..exec.cpu import CpuExec
from ..exec.tpu_basic import TpuExec
from ..obs import trace as _trace
from ..plan import logical as L
from .readers import (FilePartitionReader,
                      expand_paths_with_partitions,
                      split_files_into_partitions)


def _strategy(fmt: str, conf: TpuConf) -> str:
    if fmt != "parquet":
        return "PERFILE"
    s = conf.get(PARQUET_READER_TYPE).upper()
    if s == "AUTO":
        return "MULTITHREADED"
    return s


def _count_batch(how: str, rows: int) -> None:
    """One batch a scan yields, into the calling query's ``scan.*``
    counters: ``read`` (decoded and uploaded) or ``cached`` (replayed
    from the device scan cache)."""
    _trace.count(f"scan.batches.{how}")
    _trace.count("scan.rows", rows)


class TpuFileScan(TpuExec):
    """Reference: GpuFileSourceScanExec + reader strategies (§2.6)."""

    def __init__(self, logical: L.Scan, conf: TpuConf):
        super().__init__()
        self.logical = logical
        self.conf = conf
        self.files = expand_paths_with_partitions(logical.paths,
                                               conf)
        self.strategy = _strategy(logical.fmt, conf)
        self._partitions = split_files_into_partitions(
            self.files, conf.get(SHUFFLE_PARTITIONS))
        self.pushed_filters = None
        self._part_dtypes = {f.name: f.dtype
                             for f in logical.schema.fields}

    def set_pushed_filters(self, filters):
        """Planner-pushed predicate (GpuParquetScan pushdown role)."""
        self.pushed_filters = filters

    @property
    def output_schema(self):
        return self.logical.schema

    def num_partitions_hint(self):
        return len(self._partitions)

    def _node_string(self):
        pf = f", pushed={self.pushed_filters}" if self.pushed_filters else ""
        return (f"TpuFileScan[{self.logical.fmt}, {self.strategy}, "
                f"{len(self.files)} files{pf}]")

    def _reader(self, files):
        return FilePartitionReader(
            self.logical.fmt, files,
            columns=[f.name for f in self.logical.schema.fields],
            strategy=self.strategy,
            num_threads=self.conf.get(MULTITHREAD_READ_THREADS),
            options=self.logical.options,
            pushed_filters=self.pushed_filters,
            partition_dtypes=self._part_dtypes)

    def _chunks(self, table, max_rows):
        pos = 0
        n = table.num_rows
        while pos < n or (n == 0 and pos == 0):
            k = min(max_rows, n - pos)
            yield table.slice(pos, k)
            pos += max(k, 1)
            if n == 0:
                break

    def _cache_key(self, max_rows):
        """Identity of this scan's device batches: files+mtimes+sizes,
        column set/order, pushdown, batching geometry, and every session
        conf that changes the cached batch REPRESENTATION (exactDouble
        decides Binary64Column-vs-f64 at from_arrow time; the cache is
        process-global, so two sessions with different settings must not
        share batches)."""
        files = []
        for part in self._partitions:
            for f in part:
                path = f[0] if isinstance(f, tuple) else f
                pv = tuple(sorted(f[1].items())) if isinstance(f, tuple) \
                    else ()
                try:
                    st = os.stat(path)
                    files.append((path, st.st_mtime_ns, st.st_size, pv))
                except OSError:
                    return None
            files.append(("|",))        # partition boundary
        def freeze(x):
            if isinstance(x, dict):
                return tuple(sorted((k, freeze(v)) for k, v in x.items()))
            if isinstance(x, (set, frozenset)):
                return tuple(sorted(map(repr, x)))
            if isinstance(x, (list, tuple)):
                return tuple(freeze(v) for v in x)
            return x
        from ..columnar.binary64 import exact_double_enabled
        try:
            pushed = freeze(self.pushed_filters) \
                if self.pushed_filters else None
            key = (self.logical.fmt, tuple(files),
                   tuple((f.name, f.dtype.name)
                         for f in self.logical.schema.fields),
                   freeze(self.logical.options or {}),
                   pushed, max_rows, self.strategy,
                   exact_double_enabled())
            hash(key)                 # reject exotic unhashable leaves
        except Exception:
            return None               # unhashable option: never cache
        return key

    def execute(self):
        from ..config import SCAN_PREFETCH, SCAN_CACHE
        from .scan_cache import DeviceScanCache
        max_rows = self.conf.get(MAX_READER_BATCH_ROWS)
        key = self._cache_key(max_rows) if self.conf.get(SCAN_CACHE) \
            else None
        if key is not None:
            cached = DeviceScanCache.get().lookup(key)
            if cached is not None:
                def replay(batches):
                    for b in batches:
                        self.metrics[NUM_OUTPUT_ROWS] += b.num_rows
                        _count_batch("cached", b.num_rows)
                        yield b
                return self._stats_wrap([replay(part) for part in cached])
        if not self.conf.get(SCAN_PREFETCH) or \
                sum(len(f) for f in self._partitions) <= 1:
            def run(files):
                for table in self._reader(files):
                    for chunk in self._chunks(table, max_rows):
                        self.metrics[NUM_OUTPUT_ROWS] += chunk.num_rows
                        with _trace.span("srt.scan.upload", "scan", True):
                            batch = from_arrow(chunk)
                        _count_batch("read", chunk.num_rows)
                        yield batch
            parts = [run(files) for files in self._partitions]
        else:
            parts = self._execute_prefetch(max_rows)
        if key is None:
            return self._stats_wrap(parts)
        return self._stats_wrap(self._caching_iters(key, parts))

    def _stats_wrap(self, parts):
        """Per-partition output-row stats for the stats plane; the
        counting wrapper sits OUTSIDE the caching layer so the device
        cache stores unwrapped batches."""
        from ..obs import stats as obs_stats
        if not obs_stats.enabled(self.conf):
            return parts
        return obs_stats.count_scan_partitions(self, parts)

    def _caching_iters(self, key, parts):
        """Collect each partition's batches as they stream; install the
        scan into the device cache only when EVERY partition was fully
        consumed (a LIMIT short-circuit must not cache a prefix).
        Collection must never pin more than the cache budget: past it
        the scan cannot be cached anyway, so collection is abandoned
        and batches stream through unpinned (out-of-HBM scans keep
        their streaming memory profile)."""
        import threading
        from ..config import SCAN_CACHE_BYTES
        from .scan_cache import DeviceScanCache
        cap = int(self.conf.get(SCAN_CACHE_BYTES))
        # partition iterators may be consumed from concurrent tasks:
        # byte accounting / completion state shares one lock so the
        # budget cannot be overrun and insert happens exactly once
        lock = threading.Lock()
        state = {"bytes": 0, "abandoned": False, "inserted": False}
        collected = [[] for _ in parts]
        done = [False] * len(parts)

        def wrap(i, it):
            for b in it:
                with lock:
                    if not state["abandoned"]:
                        state["bytes"] += b.nbytes()
                        if state["bytes"] > cap:
                            state["abandoned"] = True
                            _trace.count("scan.cache.abandoned")
                            for part in collected:
                                part.clear()
                        else:
                            collected[i].append(b)
                yield b
            with lock:
                done[i] = True
                do_insert = (all(done) and not state["abandoned"]
                             and not state["inserted"])
                if do_insert:
                    state["inserted"] = True
            if do_insert:
                DeviceScanCache.get().insert(key, collected, cap)
        return [wrap(i, it) for i, it in enumerate(parts)]

    def _execute_prefetch(self, max_rows):
        """Producer threads decode host arrow tables AHEAD of
        consumption (bounded queue per partition), so scan I/O for
        partition N+1 overlaps device compute for partition N; the
        host->device upload of each chunk runs under the
        DeviceSemaphore (the GpuSemaphore.scala:27,101 admission gate —
        at most concurrentTpuTasks partitions touch the device at
        once)."""
        import queue as _q
        import threading
        from ..memory.arena import DeviceManager

        sem = DeviceManager.get().semaphore
        sentinels = {"end": object(), "err": object()}

        def start_producer(files):
            qd: "_q.Queue" = _q.Queue(maxsize=2)
            cancel = threading.Event()

            def put_or_cancel(item) -> bool:
                while not cancel.is_set():
                    try:
                        qd.put(item, timeout=0.5)
                        return True
                    except _q.Full:
                        continue
                return False

            def produce():
                try:
                    for table in self._reader(files):
                        if not put_or_cancel(table):
                            return
                    put_or_cancel(sentinels["end"])
                    # linger until the consumer drains the queue (or
                    # abandons the partition): a producer mid-decode
                    # already pins its thread on the bounded put, so a
                    # finished one holding its decoded tables until
                    # they're taken keeps the lifetime discipline
                    # uniform regardless of table count
                    while not cancel.is_set() and not qd.empty():
                        cancel.wait(0.05)
                except Exception as e:  # noqa: BLE001 - re-raised below
                    put_or_cancel((sentinels["err"], e))
            t = threading.Thread(target=produce, daemon=True,
                                 name="tpu-scan-prefetch")
            t.start()
            return qd, cancel

        pairs = [start_producer(files) for files in self._partitions]

        def run(qd, cancel):
            try:
                while True:
                    item = qd.get()
                    if item is sentinels["end"]:
                        return
                    if isinstance(item, tuple) and item and \
                            item[0] is sentinels["err"]:
                        raise item[1]
                    for chunk in self._chunks(item, max_rows):
                        self.metrics[NUM_OUTPUT_ROWS] += chunk.num_rows
                        sem.acquire_if_necessary()
                        try:
                            with _trace.span("srt.scan.upload", "scan",
                                             True):
                                batch = from_arrow(chunk)
                        finally:
                            sem.release()
                        _count_batch("read", chunk.num_rows)
                        yield batch
            finally:
                # abandonment (LIMIT short-circuit, error, GC of the
                # generator) must release the producer: without this
                # the thread blocks forever on the bounded queue,
                # pinning decoded tables for the process lifetime
                cancel.set()
        return [run(qd, cancel) for qd, cancel in pairs]


class CpuFileScan(CpuExec):
    def __init__(self, logical: L.Scan, conf: TpuConf):
        super().__init__()
        self.logical = logical
        self.conf = conf
        self.files = expand_paths_with_partitions(logical.paths,
                                               conf)
        self._partitions = split_files_into_partitions(
            self.files, conf.get(SHUFFLE_PARTITIONS))
        self._part_dtypes = {f.name: f.dtype
                             for f in logical.schema.fields}

    @property
    def output_schema(self):
        return self.logical.schema

    def num_partitions_hint(self):
        return len(self._partitions)

    def execute(self):
        def run(files):
            reader = FilePartitionReader(
                self.logical.fmt, files,
                columns=[f.name for f in self.logical.schema.fields],
                options=self.logical.options,
                partition_dtypes=self._part_dtypes)
            for t in reader:
                yield t
        return [run(files) for files in self._partitions]


def tpu_scan_exec(logical: L.Scan, conf: TpuConf) -> PhysicalPlan:
    return TpuFileScan(logical, conf)


def cpu_scan_exec(logical: L.Scan, conf: TpuConf) -> PhysicalPlan:
    return CpuFileScan(logical, conf)


# ---------------------------------------------------------------------------
# writers (reference: GpuParquetFileFormat.scala:348, GpuFileFormatWriter)
# ---------------------------------------------------------------------------

class TpuFileWrite(TpuExec):
    """Write device batches to part files (one per partition)."""

    def __init__(self, logical: L.WriteFile, child: PhysicalPlan,
                 conf: TpuConf):
        super().__init__(child)
        self.logical = logical
        self.conf = conf

    @property
    def output_schema(self):
        return Schema([])

    def execute(self):
        return _run_committed_write(
            self.logical, self.children[0],
            lambda part: [to_arrow(b) for b in part if b.num_rows > 0],
            self.metrics)


class CpuFileWrite(CpuExec):
    def __init__(self, logical: L.WriteFile, child: PhysicalPlan,
                 conf: TpuConf):
        super().__init__(child)
        self.logical = logical

    @property
    def output_schema(self):
        return Schema([])

    def execute(self):
        return _run_committed_write(self.logical, self.children[0],
                                    list, self.metrics)


class WriteCommitProtocol:
    """Temp-dir + atomic-rename task commit for file writes.

    Reference: GpuFileFormatWriter.scala + the Hadoop commit protocol,
    with write statistics per BasicColumnarWriteStatsTracker.scala:1.
    Tasks write under ``<path>/_temporary-<job>/task-<i>/`` (partition
    subdirs included); a successful task promotes its files into the
    final directory with atomic ``os.replace``; a failed task aborts by
    deleting its attempt dir, leaving the output untouched.  Job commit
    drops the temp tree and writes the ``_SUCCESS`` marker."""

    def __init__(self, path: str, overwrite: bool = False):
        import uuid
        self.path = path
        self.overwrite = overwrite
        self.tmp = os.path.join(path, f"_temporary-{uuid.uuid4().hex[:8]}")
        #: job-level stats (BasicColumnarWriteJobStatsTracker metric
        #: names: numFiles / numOutputBytes / numOutputRows / numParts)
        self.stats = {"numFiles": 0, "numOutputBytes": 0,
                      "numOutputRows": 0, "numParts": 0}
        self._part_dirs = set()   # distinct partition paths, job-wide

    def setup_job(self):
        os.makedirs(self.tmp, exist_ok=True)

    def task_dir(self, task_id: int) -> str:
        d = os.path.join(self.tmp, f"task-{task_id:05d}")
        os.makedirs(d, exist_ok=True)
        return d

    def commit_task(self, task_id: int, num_rows: int):
        """Stage the task's files into the job-commit area (v1
        protocol: nothing reaches the final directory until JOB commit,
        so any failure leaves the target untouched); accumulate
        stats."""
        d = os.path.join(self.tmp, f"task-{task_id:05d}")
        staged = os.path.join(self.tmp, "__committed__")
        for root, _dirs, files in os.walk(d):
            rel = os.path.relpath(root, d)
            dest_dir = staged if rel == "." else \
                os.path.join(staged, rel)
            os.makedirs(dest_dir, exist_ok=True)
            if rel != "." and files:
                # DISTINCT partition paths job-wide, leaf dirs only
                # (BasicColumnarWriteJobStatsTracker semantics)
                self._part_dirs.add(rel)
            for f in files:
                fsrc = os.path.join(root, f)
                self.stats["numFiles"] += 1
                self.stats["numOutputBytes"] += os.path.getsize(fsrc)
                os.replace(fsrc, os.path.join(dest_dir, f))
        self.stats["numParts"] = len(self._part_dirs)
        self.stats["numOutputRows"] += int(num_rows)
        import shutil
        shutil.rmtree(d, ignore_errors=True)

    def abort_task(self, task_id: int):
        import shutil
        shutil.rmtree(os.path.join(self.tmp, f"task-{task_id:05d}"),
                      ignore_errors=True)

    def commit_job(self):
        """Promote every committed task's staged files atomically
        (per-file os.replace) into the final directory, then drop the
        temp tree and write the _SUCCESS marker.  Overwrite mode
        deletes the PREVIOUS dataset here — after every task has
        committed — so a failed overwrite leaves the old data intact.
        """
        import shutil
        if self.overwrite:
            for f in os.listdir(self.path):
                full = os.path.join(self.path, f)
                if f.startswith("part-") or f == "_SUCCESS":
                    os.unlink(full)
                elif "=" in f and os.path.isdir(full):
                    shutil.rmtree(full)
        staged = os.path.join(self.tmp, "__committed__")
        if os.path.isdir(staged):
            for root, _dirs, files in os.walk(staged):
                rel = os.path.relpath(root, staged)
                dest_dir = self.path if rel == "." else \
                    os.path.join(self.path, rel)
                os.makedirs(dest_dir, exist_ok=True)
                for f in files:
                    os.replace(os.path.join(root, f),
                               os.path.join(dest_dir, f))
        shutil.rmtree(self.tmp, ignore_errors=True)
        with open(os.path.join(self.path, "_SUCCESS"), "w"):
            pass

    def abort_job(self):
        import shutil
        shutil.rmtree(self.tmp, ignore_errors=True)


def _write_partitioned(fmt: str, table: pa.Table, root: str,
                       part_cols, task_id: int):
    """Hive-layout dynamic partitioned write: one file per key combo."""
    import pyarrow.compute as pc
    data_cols = [c for c in table.column_names if c not in part_cols]
    keys = table.select(part_cols)
    combos = keys.group_by(part_cols).aggregate([])
    for row in range(combos.num_rows):
        mask = None
        comps = []
        for c in part_cols:
            v = combos.column(c)[row]
            eq = pc.is_null(table.column(c)) if not v.is_valid else \
                pc.equal(table.column(c), v)
            eq = pc.fill_null(eq, False)
            mask = eq if mask is None else pc.and_(mask, eq)
            if not v.is_valid:
                sval = "__HIVE_DEFAULT_PARTITION__"
            else:
                from urllib.parse import quote
                # escape path separators/metacharacters (Spark's
                # escapePathName role)
                sval = quote(str(v.as_py()), safe="")
            comps.append(f"{c}={sval}")
        sub = table.filter(mask).select(data_cols)
        d = os.path.join(root, *comps)
        os.makedirs(d, exist_ok=True)
        _write_table(fmt, sub, os.path.join(d, f"part-{task_id:05d}"))


def _write_table(fmt: str, table: pa.Table, base: str):
    if fmt == "parquet":
        papq.write_table(table, base + ".parquet")
    elif fmt == "csv":
        pacsv.write_csv(table, base + ".csv")
    elif fmt == "orc":
        from pyarrow import orc as paorc
        paorc.write_table(table, base + ".orc")
    else:
        raise ValueError(f"unknown write format {fmt}")


def _run_committed_write(lg, child, tables_of, metrics):
    """Shared commit-protocol write driver for both engines:
    ``tables_of(part)`` yields the partition's arrow tables."""
    os.makedirs(lg.path, exist_ok=True)
    if lg.partition_by and any(c.startswith(("_", "."))
                               for c in lg.partition_by):
        # readers treat _/. prefixed directories as hidden (commit
        # temp dirs live there); such partition columns would write
        # data that every scan silently skips
        raise ValueError(
            "partition column names must not start with '_' or '.'")
    import shutil
    for f in os.listdir(lg.path):
        full = os.path.join(lg.path, f)
        if f.startswith("_temporary") and os.path.isdir(full):
            # leftover attempt dirs from a crashed writer
            shutil.rmtree(full)
    parts = child.execute()
    arrow_schema = schema_to_arrow(child.output_schema)
    # overwrite deletes the previous dataset at JOB COMMIT, not here:
    # a failed overwrite must leave the old data intact
    proto = WriteCommitProtocol(lg.path, overwrite=lg.mode == "overwrite")
    proto.setup_job()

    def run(i, part):
        tdir = proto.task_dir(i)
        try:
            tables = tables_of(part)
            table = pa.concat_tables(tables) if tables else \
                arrow_schema.empty_table()
            if lg.partition_by:
                _write_partitioned(lg.fmt, table, tdir,
                                   lg.partition_by, i)
            else:
                _write_table(lg.fmt, table,
                             os.path.join(tdir, f"part-{i:05d}"))
        except BaseException:
            proto.abort_task(i)
            proto.abort_job()
            raise
        proto.commit_task(i, table.num_rows)
        return iter(())
    try:
        out = [run(i, p) for i, p in enumerate(parts)]
    except BaseException:
        proto.abort_job()
        raise
    proto.commit_job()
    for k, v in proto.stats.items():
        metrics[k] += v
    return out


def tpu_write_exec(logical, child, conf):
    return TpuFileWrite(logical, child, conf)


def cpu_write_exec(logical, child, conf):
    return CpuFileWrite(logical, child, conf)
