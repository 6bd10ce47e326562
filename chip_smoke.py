#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the engine still starts on
the chip.

One process, default JAX platform selection, public entry points only
(``TpuSession``, ``DataFrame``, ``session.sql``, ``QueryService``), with
``spark.rapids.tpu.sql.test.enabled=true`` so an operator that falls
back to the pyarrow engine raises instead of quietly running on the
host.  Legs (docs: README "Running"):

  resident  bench.build_df's headline pipeline, 64M rows x 4 columns in
            4 partitions, default conf: one cold + two warm runs, rows
            equal to the CPU engine under tests/harness.py's tolerance
  sql       TPC-DS SF1 generated beside the output, q3/q7/q42/q55/q96
            through session.sql(), each twice (cold scan, scan-cache
            hit), rows equal to the CPU engine
  service   QueryService, 2 workers, 8 submissions from 2 tenants (the
            resident shape at 4M rows + two of the SQL queries)
  kernel    the hash-partition ids program (1 and 2 key words) against
            a numpy reference at the resident leg's row count
  mesh      only with >= 4 devices: the resident query (and a global
            sort of it) under shuffle.mode=mesh, with per-device
            evidence that the data spread.  64M rows when it runs alone
            (--legs mesh, 520 s on four v5e chips); cut to 8M rows, and
            printed as a cut, when the other legs share the time limit

Any leg that raises, mismatches or finds a CPU operator ends the run
non-zero; nothing is caught and reported as a warning.  Without a TPU
the script exits 2 before doing any work.  ``--rehearse-cpu`` is the one
way to run it on the CPU backend (tiny sizes, prints platform=cpu,
prints no result line): it debugs the script, it proves nothing.

The last stdout line of a passing chip run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Timings printed on the way are SMOKE TIMINGS: one reading each, no
warm-up discipline, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import types

ROOT = os.path.dirname(os.path.abspath(__file__))
LEGS = ("resident", "sql", "service", "kernel", "mesh")
SQL_QUERIES = ("q3", "q7", "q42", "q55", "q96")
SERVICE_SQL = ("q3", "q55")
T0 = time.perf_counter()


def say(*parts) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s]", *parts, flush=True)


class CompileMeter:
    """Sums JAX's own compile telemetry: backend compile seconds (a
    persistent-cache hit costs its retrieval, not a compile) and the
    persistent cache's hit / miss events."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HITS = "/jax/compilation_cache/cache_hits"
    MISSES = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.seconds = 0.0
        self.events = {self.HITS: 0, self.MISSES: 0}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == self.BACKEND:
            self.compiles += 1
            self.seconds += secs

    def _on_event(self, event, **_kw):
        if event in self.events:
            self.events[event] += 1

    def snapshot(self) -> dict:
        return {"backend_compiles": self.compiles,
                "backend_compile_s": round(self.seconds, 2),
                "persistent_cache_hits": self.events[self.HITS],
                "persistent_cache_misses": self.events[self.MISSES]}


def assert_on_device(session, what: str) -> None:
    """No CPU operator in the plan that just ran (test.enabled already
    raised at plan time; this re-checks the executed tree)."""
    from spark_rapids_tpu.exec.tpu_basic import ColumnarToRow
    phys = session.last_physical_plan
    bad = [n.name for n in phys.collect_nodes()
           if not n.columnar and not isinstance(n, ColumnarToRow)]
    fallbacks = list(session._last_planner.fallbacks)
    if bad or fallbacks:
        raise AssertionError(
            f"{what}: CPU operators {bad}, fallback reasons {fallbacks}\n"
            f"{phys.tree_string()}")


def table_rows(table) -> list:
    cols = [table.column(i).to_pylist() for i in range(table.num_columns)]
    return list(zip(*cols)) if cols else []


def assert_rows_equal(cpu_rows, tpu_rows, what: str) -> None:
    """tests/harness.py's order-insensitive compare (ulp-level float
    tolerance: the default conf aggregates floats exactly)."""
    from tests.harness import _compare_rows, _row_key
    try:
        _compare_rows(sorted(cpu_rows, key=_row_key),
                      sorted(tpu_rows, key=_row_key))
    except AssertionError as e:
        raise AssertionError(f"{what}: {e}") from None


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_resident(ctx) -> dict:
    import bench
    from spark_rapids_tpu.columnar import pending
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.obs import stats as obs_stats
    n, parts = ctx.rows, 4
    tpu, cpu, dev = ctx.tpu, ctx.cpu, ctx.device
    t0 = time.perf_counter()
    df = bench.build_df(tpu, n, parts)
    say(f"resident: built {n} rows x 4 columns in {parts} partitions "
        f"(bench.build_df, numpy seed 7) in "
        f"{time.perf_counter() - t0:.1f}s")
    cat = BufferCatalog.get()
    spill0 = cat.spilled_device_to_host + cat.spilled_host_to_disk
    runs, secs = [], []
    for i in range(3):
        t0 = time.perf_counter()
        runs.append(df.collect())
        secs.append(time.perf_counter() - t0)
        assert_on_device(tpu, f"resident run {i}")
    say("resident plan:\n" + tpu.last_physical_plan.tree_string())
    flushes = tpu.last_query_flushes
    cost = tpu.last_query_costplane
    spill = (cat.spilled_device_to_host + cat.spilled_host_to_disk) - spill0
    t0 = time.perf_counter()
    want = bench.build_df(cpu, n, parts).collect()
    cpu_s = time.perf_counter() - t0
    for i, got in enumerate(runs):
        assert_rows_equal(want, got, f"resident run {i} vs CPU engine")
    if cost is None:
        raise AssertionError("resident: no cost block (cost plane failed)")
    peaks = ctx.peaks
    if not ctx.rehearsal and \
            cost["peak_source"] != f"device_table:{dev.device_kind}":
        raise AssertionError(f"cost block peaks from {cost['peak_source']}")
    if pending.encoding_verdict() is not True:
        raise AssertionError(
            f"pending-pool encoding probe verdict "
            f"{pending.encoding_verdict()!r}: flushes pull per item")
    if not obs_stats.sketch_ok():
        raise AssertionError("exchange stats sketch program failed")
    stats = dev.memory_stats() or {}
    out = {
        "rows": n, "partitions": parts, "groups": len(want),
        "smoke_cold_s": round(secs[0], 3),
        "smoke_warm_s": [round(s, 3) for s in secs[1:]],
        "cpu_engine_s": round(cpu_s, 3),
        "warm_flushes": flushes, "spill_bytes": int(spill),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "encoding_probe": pending.encoding_verdict(),
        "cost_peak_source": cost["peak_source"],
        "cost_peaks": [cost["peak_tflops"], cost["peak_gbps"]],
        "cost_verdict": cost["verdict"],
    }
    say(f"resident: SMOKE TIMINGS cold {secs[0]:.2f}s, warm "
        f"{secs[1]:.2f}s / {secs[2]:.2f}s (CPU engine {cpu_s:.2f}s); "
        f"warm flushes {flushes}; spill bytes {spill}; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}; "
        f"encoding probe {pending.encoding_verdict()}; roofline peaks "
        f"{cost['peak_tflops']} TFLOP/s / {cost['peak_gbps']} GB/s from "
        f"{cost['peak_source']} ({peaks.source}); {len(want)} rows equal "
        f"to the CPU engine in all 3 runs")
    return out


def leg_sql(ctx) -> dict:
    import tpcds
    from tpcds_queries import QUERIES
    from tpcds_sf1 import _rows_equal
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    data_dir = os.path.join(ctx.out, f"tpcds_sf{ctx.scale}_seed{ctx.seed}")
    t0 = time.perf_counter()
    tpcds.generate(data_dir, ctx.scale, ctx.seed)
    size = sum(os.path.getsize(os.path.join(data_dir, f))
               for f in os.listdir(data_dir))
    say(f"sql: generated TPC-DS scale {ctx.scale} (seed {ctx.seed}, "
        f"{size / 1e6:.0f} MB) under {data_dir} in "
        f"{time.perf_counter() - t0:.1f}s")
    tpcds.register(ctx.tpu, data_dir)
    tpcds.register(ctx.cpu, data_dir)
    cache = DeviceScanCache.get()
    out = {"scale": ctx.scale, "queries": {}}
    ctx.sql_oracle = {}
    for q in SQL_QUERIES:
        t0 = time.perf_counter()
        cold = ctx.tpu.sql(QUERIES[q]).collect()
        cold_s = time.perf_counter() - t0
        assert_on_device(ctx.tpu, f"sql {q} cold")
        hits0 = cache.hits
        t0 = time.perf_counter()
        warm = ctx.tpu.sql(QUERIES[q]).collect()
        warm_s = time.perf_counter() - t0
        assert_on_device(ctx.tpu, f"sql {q} warm")
        scan_hits = cache.hits - hits0
        if scan_hits <= 0:
            raise AssertionError(f"sql {q}: second run missed the "
                                 f"device scan cache")
        want = ctx.cpu.sql(QUERIES[q]).collect()
        ctx.sql_oracle[q] = want
        for name, got in (("cold", cold), ("warm", warm)):
            if not _rows_equal(want, got):
                raise AssertionError(
                    f"sql {q} {name}: rows differ from the CPU engine "
                    f"({len(want)} vs {len(got)} rows)")
        out["queries"][q] = {
            "rows": len(want), "smoke_cold_s": round(cold_s, 2),
            "smoke_warm_s": round(warm_s, 2),
            "warm_flushes": ctx.tpu.last_query_flushes,
            "scan_cache_hits": scan_hits}
        say(f"sql: {q} SMOKE TIMINGS cold {cold_s:.1f}s (scan + "
            f"compile), warm {warm_s:.2f}s ({scan_hits} scan-cache "
            f"hits, {ctx.tpu.last_query_flushes} flushes); {len(want)} "
            f"rows equal to the CPU engine in both runs")
    return out


def leg_service(ctx) -> dict:
    import bench
    from tpcds_queries import QUERIES
    from tpcds_sf1 import _rows_equal
    from spark_rapids_tpu.service.server import QueryService
    n = ctx.service_rows
    df = bench.build_df(ctx.tpu, n, 4)
    want_df = bench.build_df(ctx.cpu, n, 4).collect()
    submissions = []       # (label, query, oracle rows, sql?)
    for i in range(4):
        submissions.append((f"resident@{n}", df, want_df, False))
    for q in SERVICE_SQL:
        for _ in range(2):
            submissions.append((q, QUERIES[q], ctx.sql_oracle[q], True))
    t0 = time.perf_counter()
    with QueryService(session=ctx.tpu, num_workers=2) as svc:
        handles = [(label, svc.submit(query, tenant=("alpha", "beta")[i % 2]),
                    want, is_sql)
                   for i, (label, query, want, is_sql)
                   in enumerate(submissions)]
        for label, h, want, is_sql in handles:
            got = table_rows(h.result(timeout=ctx.query_timeout))
            if is_sql:
                if not _rows_equal(want, got):
                    raise AssertionError(f"service {label} ({h.tenant}): "
                                         f"rows differ from the CPU engine")
            else:
                assert_rows_equal(want, got,
                                  f"service {label} ({h.tenant})")
        snap = svc.stats().snapshot()
    wall = time.perf_counter() - t0
    counts = {k: snap.get(k) for k in
              ("submitted", "admitted", "completed", "failed", "shed",
               "cancelled", "deadline_exceeded", "retries")}
    if (counts["completed"], counts["failed"], counts["shed"]) != \
            (len(submissions), 0, 0):
        raise AssertionError(f"service stats {counts}")
    say(f"service: {len(submissions)} submissions from 2 tenants over 2 "
        f"workers in {wall:.1f}s (SMOKE TIMING), every result equal to "
        f"the CPU engine; stats {counts}")
    return {"submissions": len(submissions), "resident_rows": n,
            "smoke_wall_s": round(wall, 2), "stats": counts}


def leg_kernel(ctx) -> dict:
    import numpy as np
    import jax.numpy as jnp
    from spark_rapids_tpu.kernels import basic as bk
    from spark_rapids_tpu.shuffle.partitioners import partition_hash_ids
    n = ctx.kernel_rows
    rng = np.random.default_rng(ctx.seed)
    out = {"rows": n}
    # hash partition ids: the XLA program against a numpy murmur-mix
    # reference
    m1c, m2c = np.uint64(bk.M1), np.uint64(bk.M2)
    for nwords in (1, 2):
        words = [rng.integers(0, 2**63, n).astype(np.uint64)
                 for _ in range(nwords)]
        got = np.asarray(partition_hash_ids(
            tuple(jnp.asarray(w) for w in words), 4))
        h = np.full(n, 42, np.uint64)
        with np.errstate(over="ignore"):
            for w in words:
                x = h ^ w
                x ^= x >> np.uint64(33)
                x *= m1c
                x ^= x >> np.uint64(33)
                x *= m2c
                x ^= x >> np.uint64(33)
                h = x
        np.testing.assert_array_equal(got, (h % np.uint64(4)).astype(
            np.int32))
    say(f"kernel: hash partition ids (XLA, 1 and 2 key words, n={n}) "
        f"equal to the numpy reference")
    return out


def leg_mesh(ctx) -> dict:
    import jax
    import bench
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.exec.base import (MESH_INPUT_DEVICES,
                                            MESH_OVERFLOW_FALLBACKS)
    devs = jax.devices()
    if len(devs) < 4:
        say(f"mesh: NOT RUN — needs >= 4 devices, this machine has "
            f"{len(devs)} (run chip_smoke.py on a four-chip host)")
        return {"ran": False, "reason": f"{len(devs)} device(s)"}
    n = ctx.mesh_rows
    if n != 64_000_000:
        say(f"CUT: mesh rows {n} (full size 64000000) — the run's time "
            f"limit or the command line; same columns, keys and query")
    peak0 = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    # a new session: shuffle.mode is planned from the session conf.  It
    # re-initializes the process-wide device manager, so this leg runs
    # last
    mesh = TpuSession(TpuConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.test.enabled": True,
        "spark.rapids.tpu.shuffle.mode": "mesh"}))
    want = bench.build_df(ctx.cpu, n, 4).collect()
    out = {"ran": True, "rows": n, "devices": len(devs), "queries": {}}
    queries = (
        ("resident", lambda s: bench.build_df(s, n, 4),
         ("TpuMeshAggregate", "TpuMeshShuffledJoin"), False),
        ("resident+sort", lambda s: bench.build_df(s, n, 4).sort(F.col("k")),
         ("TpuMeshAggregate", "TpuMeshShuffledJoin", "TpuMeshSort"), True))
    for label, build, need, ordered in queries:
        df = build(mesh)
        t0 = time.perf_counter()
        got = df.collect()
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got2 = df.collect()
        warm_s = time.perf_counter() - t0
        assert_on_device(mesh, f"mesh {label}")
        phys = mesh.last_physical_plan
        nodes = {}
        for node in phys.collect_nodes():
            if node.name.startswith("TpuMesh"):
                m = node.metrics.snapshot()
                nodes[node.name] = {
                    "input_devices": m.get(MESH_INPUT_DEVICES),
                    "overflow_fallbacks": m.get(MESH_OVERFLOW_FALLBACKS,
                                                0)}
        missing = [x for x in need if x not in nodes]
        if missing:
            raise AssertionError(f"mesh {label}: plan lacks {missing}\n"
                                 f"{phys.tree_string()}")
        for name, ev in nodes.items():
            if ev["input_devices"] != len(devs) or ev["overflow_fallbacks"]:
                raise AssertionError(
                    f"mesh {label}: {name} input on "
                    f"{ev['input_devices']} device(s), "
                    f"{ev['overflow_fallbacks']} overflow fallback(s)")
        for run_rows in (got, got2):
            if ordered and [r[0] for r in run_rows] != sorted(
                    r[0] for r in want):
                raise AssertionError(f"mesh {label}: not ordered by k")
            assert_rows_equal(want, run_rows, f"mesh {label} vs CPU engine")
        out["queries"][label] = {"smoke_cold_s": round(cold_s, 2),
                                 "smoke_warm_s": round(warm_s, 2),
                                 "nodes": nodes}
        say(f"mesh: {label} at {n} rows SMOKE TIMINGS cold {cold_s:.1f}s "
            f"warm {warm_s:.2f}s; {nodes}; {len(want)} rows equal to the "
            f"CPU engine\n" + phys.tree_string())
    peak1 = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    out["peak_bytes_in_use"] = {"before": peak0, "after": peak1}
    if not ctx.rehearsal:
        flat = [i for i, (a, b) in enumerate(zip(peak0, peak1)) if b <= a]
        if flat:
            raise AssertionError(
                f"mesh: peak_bytes_in_use did not grow on device(s) "
                f"{flat}: before {peak0}, after {peak1}")
    say(f"mesh: per-device peak_bytes_in_use before {peak0} after {peak1}")
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="debug the script on the CPU backend at tiny "
                        "sizes; prints platform=cpu and no result line")
    p.add_argument("--legs", default=",".join(LEGS),
                   help="comma-separated subset of " + ",".join(LEGS))
    p.add_argument("--rows", type=int, default=64_000_000,
                   help="resident-leg rows (a cut is printed as one)")
    p.add_argument("--mesh-rows", type=int, default=None,
                   help="mesh-leg rows (default: --rows when the mesh leg "
                        "runs alone, else cut to 8M to keep all five legs "
                        "inside the time limit)")
    p.add_argument("--scale", type=float, default=1.0,
                   help="TPC-DS scale factor of the sql leg")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                   help="report.json and the generated data go here")
    p.add_argument("--deadline", type=float, default=1150.0,
                   help="hard wall-clock limit in seconds (exit 4)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    legs = [x for x in args.legs.split(",") if x]
    unknown = [x for x in legs if x not in LEGS]
    if unknown:
        print(f"unknown legs {unknown}; choose from {LEGS}")
        return 2

    import jax
    meter = CompileMeter()
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(devs)}", flush=True)
    rehearsal = False
    if dev.platform != "tpu":
        if not (args.rehearse_cpu and dev.platform == "cpu"):
            print(f"chip_smoke: JAX found no TPU (platform="
                  f"{dev.platform}); refusing to run.  --rehearse-cpu "
                  f"debugs the script on the CPU backend.")
            return 2
        rehearsal = True
        print("REHEARSAL platform=cpu: tiny sizes; "
              "this debugs the script and proves nothing about the chip",
              flush=True)
        # XLA:CPU AOT results reloaded on another machine can SIGILL
        # (the tests/conftest.py exemption): cache nothing on this path
        jax.config.update("jax_enable_compilation_cache", False)
    elif args.rehearse_cpu:
        print("--rehearse-cpu given but the platform is tpu; drop it")
        return 2

    def on_deadline():
        print(f"chip_smoke: exceeded --deadline {args.deadline:.0f}s; "
              f"aborting", flush=True)
        os._exit(4)
    watchdog = threading.Timer(args.deadline, on_deadline)
    watchdog.daemon = True
    watchdog.start()

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from spark_rapids_tpu import device_peaks
    from spark_rapids_tpu import native
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.compile import xla_cache
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.memory.arena import DeviceManager
    from spark_rapids_tpu.memory.catalog import BufferCatalog

    ctx = types.SimpleNamespace()
    ctx.rehearsal = rehearsal
    ctx.device = dev
    ctx.seed = args.seed
    ctx.out = os.path.abspath(args.out)
    os.makedirs(ctx.out, exist_ok=True)
    ctx.rows = args.rows
    ctx.service_rows = 4_000_000
    ctx.kernel_rows = 1 << 22
    ctx.scale = args.scale
    ctx.query_timeout = 900
    if rehearsal:
        ctx.rows = min(args.rows, 200_000)
        ctx.service_rows = 50_000
        ctx.kernel_rows = 1 << 13
        ctx.scale = min(args.scale, 0.01)
    # all five legs at full size do not fit the time limit (64M rows on
    # the mesh path alone took 520 s on four chips): with the other legs
    # in the run the mesh leg's ROWS are cut unless --mesh-rows says so
    ctx.mesh_rows = args.mesh_rows or (
        ctx.rows if legs == ["mesh"] else min(ctx.rows, 8_000_000))
    if ctx.rows != 64_000_000 or ctx.scale != 1.0:
        say(f"CUT: resident rows {ctx.rows} (full size 64000000), TPC-DS "
            f"scale {ctx.scale} (full size 1.0) — "
            f"{'CPU rehearsal' if rehearsal else 'asked for on the command line'}"
            f"; columns, key count and query shapes are unchanged")
    ctx.peaks = device_peaks.lookup(dev)

    # the CPU engine's session first: every TpuSession re-initializes the
    # process-wide device manager, and the device session must be the
    # one whose catalog the legs read
    ctx.cpu = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": False}))
    ctx.tpu = TpuSession(TpuConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.test.enabled": True}))
    dm = DeviceManager.get()
    arena = BufferCatalog.get().arena
    if arena is None:
        raise AssertionError("native host arena did not load")
    cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir != xla_cache.cache_dir():
        raise AssertionError(f"compile cache at {cache_dir}, owner says "
                             f"{xla_cache.cache_dir()}")
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"device manager: {dm.device.device_kind} bytes_limit "
        f"{dm.hbm_total} device budget {dm.device_limit}; table row: "
        f"{ctx.peaks.bf16_tflops} bf16 TFLOP/s, {ctx.peaks.hbm_gbps} "
        f"GB/s, {ctx.peaks.hbm_bytes >> 30} GiB ({ctx.peaks.source})")
    say(f"native host arena loaded: {arena.capacity} bytes from "
        f"{os.path.relpath(native._SO, ROOT)}")
    say(f"compile cache: {cache_dir} ({xla_cache.ENV_VAR} "
        f"{'set' if os.environ.get(xla_cache.ENV_VAR) else 'unset'}), "
        f"{cached} entries before this run")

    run = {"resident": leg_resident, "sql": leg_sql,
           "service": leg_service, "kernel": leg_kernel, "mesh": leg_mesh}
    if "service" in legs and "sql" not in legs:
        print("the service leg replays two of the sql leg's queries: "
              "run them together")
        return 2
    report = {"device": device, "rehearsal": rehearsal, "legs": {},
              "compile_cache_dir": cache_dir,
              "compile_cache_entries_before": cached}
    for name in LEGS:                      # fixed order, mesh last
        if name not in legs:
            say(f"{name}: not asked for (--legs {args.legs})")
            continue
        t0 = time.perf_counter()
        c0 = meter.snapshot()
        report["legs"][name] = run[name](ctx)
        c1 = meter.snapshot()
        leg_compile = {k: round(c1[k] - c0[k], 2) for k in c1}
        report["legs"][name]["leg_wall_s"] = round(
            time.perf_counter() - t0, 1)
        report["legs"][name]["compile"] = leg_compile
        say(f"{name}: leg done in {time.perf_counter() - t0:.1f}s; "
            f"compile {leg_compile}")
    report["compile"] = meter.snapshot()
    report["wall_s"] = round(time.perf_counter() - T0, 1)
    say(f"compile seconds this run: {report['compile']} (cache had "
        f"{cached} entries before)")
    with open(os.path.join(ctx.out, "report.json"), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True, default=str)
    watchdog.cancel()
    if rehearsal:
        print("REHEARSAL PASSED on platform=cpu — not a chip result, no "
              "result line printed", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
