#!/usr/bin/env python3
"""chipbench/run.py — one cell of BENCHMARK.json, once, on the chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: generate the configuration's tables from the seed, start
the engine, warm up, measure a window of the cell's traffic mix, then
check every answer of the window against the plain reference
(``reference.py``).  The last stdout line is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``compared`` last).  Without a TPU
the script exits 2 before any work; ``--rehearse-cpu`` debugs the
harness on the CPU backend, prints ``platform=cpu`` and no result line.

Everything that belongs to one configuration, query, traffic mix or
per-layer metric is a file found by the name BENCHMARK.json gives it
(README.md); nothing here names a cell.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # process start, as near as python gets

import argparse                 # noqa: E402
import importlib                # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import math                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import traceback                # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

DATA_DIR = os.path.join(HERE, ".data")


def say(*parts) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s]", *parts, file=sys.stderr,
          flush=True)


def load_json(*rel) -> dict:
    with open(os.path.join(*rel)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the manifest: cell -> configuration, mix, queries, metrics, all by name
# ---------------------------------------------------------------------------

def load_cell(workload: str, manifest: str | None = None) -> dict:
    bench = load_json(manifest or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(ROOT, files[cell["config"]])
    mix = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    qdir = os.path.join(HERE, "queries", cell["config"])
    texts = {}
    for q in config["queries"]:
        with open(os.path.join(qdir, f"{q}.sql")) as f:
            texts[q] = f.read()

    def in_cell(m):
        return "workloads" not in m or workload in m["workloads"]
    return {"name": workload, "chips": cell["chips"],
            "config_name": cell["config"], "config": config, "mix": mix,
            "texts": texts,
            "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
            "per_layer": [m for m in bench["per_layer"] if in_cell(m)]}


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(run)``."""
    import reference
    return reference.load_py(
        os.path.join(HERE, "metrics", f"{name}.py")).read


def load_generator(mix: dict):
    """``generators/<name>.py``, named by the mix's ``generator``."""
    import reference
    return reference.load_py(
        os.path.join(HERE, "generators", f"{mix['generator']}.py"))


# ---------------------------------------------------------------------------
# data: one seed's files per configuration, manifest written last
# ---------------------------------------------------------------------------

def ensure_data(config_name: str, config: dict, scale: float,
                seed: int) -> str:
    d = os.path.join(DATA_DIR, config_name)
    want = {"schema": config["schema"], "scale": scale, "seed": seed,
            "tables": sorted(config["tables"])}
    mpath = os.path.join(d, "manifest.json")
    if os.path.exists(mpath):
        have = load_json(mpath)
        if {k: have.get(k) for k in want} == want and all(
                os.path.exists(os.path.join(d, f"{t}.parquet"))
                for t in want["tables"]):
            say(f"data: reusing {d} (seed {seed})")
            return d
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    gen = importlib.import_module(f"datagen.{config['schema']}")
    t0 = time.perf_counter()
    rows = gen.generate(d, scale, seed, want["tables"])
    if scale == config["scale"]:
        stated = {t: config["tables"][t]["rows"] for t in rows}
        if rows != stated:
            raise AssertionError(f"generated {rows}, the configuration "
                                 f"states {stated}")
    with open(mpath, "w") as f:
        json.dump(dict(want, rows=rows), f)
    say(f"data: generated {rows} under {d} in "
        f"{time.perf_counter() - t0:.1f}s")
    return d


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class CompileMeter:
    """JAX's own compile telemetry (copy of chip_smoke.py's): backend
    compile seconds and count, persistent-cache hits and misses."""

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
                "backend_compile_s": self.seconds,
                "persistent_cache_hits": self.events[self.HITS],
                "persistent_cache_misses": self.events[self.MISSES]}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def off_device(session) -> list:
    """CPU operators and planner fallbacks of the plan that just ran
    (chip_smoke.py's assert_on_device, as a list)."""
    from spark_rapids_tpu.exec.tpu_basic import ColumnarToRow
    phys = session.last_physical_plan
    bad = [n.name for n in phys.collect_nodes()
           if not n.columnar and not isinstance(n, ColumnarToRow)]
    return bad + [str(f) for f in session._last_planner.fallbacks]


def start_engine(config: dict, data_dir: str):
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.compile import xla_cache
    from spark_rapids_tpu.config import TpuConf
    # every program into the persistent cache, the sub-0.5 s eager ones
    # too: a second process then loads where it would compile
    xla_cache.enable(persist_everything=True)
    conf = dict(config["engine_conf"])
    # the engine's default spill directory is a fixed /tmp path
    conf["spark.rapids.tpu.memory.spill.dir"] = os.path.join(
        DATA_DIR, "spill")
    session = TpuSession(TpuConf(conf))
    for table in config["tables"]:
        session.read.parquet(os.path.join(data_dir, f"{table}.parquet")) \
            .create_or_replace_temp_view(table)
    return session


def run_query(session, name: str, text: str) -> dict:
    """One query through the entry point users call, timed from
    submission to its rows being on the host.  A query that raises, or
    whose executed plan left the device, is a failed query."""
    import jax.profiler as prof
    rec = {"name": name, "rows": None, "error": None}
    t0 = time.perf_counter()
    try:
        with prof.TraceAnnotation(f"chipbench.sql.{name}"):
            df = session.sql(text)
        with prof.TraceAnnotation(f"chipbench.collect.{name}"):
            rows = df.collect()
        rec["done"] = time.perf_counter()
        away = off_device(session)
        if away:
            rec["error"] = f"plan left the device: {away}"
        else:
            rec["rows"] = rows
        rec["flushes"] = session.last_query_flushes
        pc = session.last_query_plan_cache
        rec["plan_cache"], rec["planner_ms"] = pc if pc else (None, None)
    except Exception as e:  # noqa: BLE001 - counted in `failed`, never hidden
        rec["done"] = time.perf_counter()
        rec["error"] = f"{type(e).__name__}: {e}"
        traceback.print_exc(file=sys.stderr)
    rec["seconds"] = rec["done"] - t0
    return rec


# ---------------------------------------------------------------------------
# metrics and the verdict
# ---------------------------------------------------------------------------

def nearest_rank(values: list, q: float) -> float:
    s = sorted(values)
    return s[max(math.ceil(q * len(s)), 1) - 1]


def end_to_end(run: dict) -> dict:
    recs = run["queries"]
    good = sum(1 for r in recs if r["verified"])
    return {"queries_per_hour": good * 3600.0 / run["window_s"],
            "query_p95_s": nearest_rank([r["seconds"] for r in recs], 0.95),
            "setup_s": run["setup_s"]}


def verdict(run: dict, want: dict, limits: dict) -> dict:
    """Every answer of the window against the reference's."""
    import reference
    wrong, gap, unanswered = 0, 0.0, 0
    for r in run["queries"]:
        r["verified"] = False
        if r["rows"] is None:
            unanswered += 1
            continue
        c = reference.compare(r["rows"], want[r["name"]])
        wrong += c["wrong_cells"]
        gap = max(gap, c["max_rel_gap"])
        r["verified"] = (c["wrong_cells"] <= limits["wrong_cells"] and
                         c["max_rel_gap"] <= limits["max_rel_gap"])
    return {"unanswered": {"value": unanswered, "limit": 0},
            "wrong_cells": {"value": wrong, "limit": limits["wrong_cells"]},
            "max_rel_gap": {"value": gap, "limit": limits["max_rel_gap"]}}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             scale: float | None = None, device: dict | None = None,
             peaks: dict | None = None) -> dict:
    """Everything after the look for a chip.  -> the result object."""
    import reference
    import jax
    config = cell["config"]
    scale = config["scale"] if scale is None else scale
    meter = CompileMeter()
    data_dir = ensure_data(cell["config_name"], config, scale, seed)
    session = start_engine(config, data_dir)
    generator = load_generator(cell["mix"])
    for line in generator.warm_up(session, cell, run_query):
        say(f"{line} (compile so far {meter.snapshot()})")
    c_setup = meter.snapshot()
    setup_s = time.perf_counter() - T0

    trace_dir = None
    if trace:
        trace_dir = os.path.join(DATA_DIR, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = generator.measure(session, cell, seconds, trace_dir, run_query)
    c_window = CompileMeter.delta(c_setup, meter.snapshot())
    stats = [d.memory_stats() or {} for d in jax.devices()]
    peak = max(st.get("peak_bytes_in_use") or 0 for st in stats) or None
    say(f"window: {win['passes']} passes, {len(win['records'])} queries "
        f"in {win['window_s']:.2f}s; compile in window {c_window}; "
        f"peak_bytes_in_use {peak} of {stats[0].get('bytes_limit')}")

    # the reference runs once the window has closed and the peak is read
    del session
    t0 = time.perf_counter()
    want, nbytes = reference.answers(
        cell["config_name"], config["queries"], data_dir,
        config["precision"])
    say(f"reference: {len(want)} queries in "
        f"{time.perf_counter() - t0:.1f}s")

    run = {"queries": win["records"], "window_s": win["window_s"],
           "setup_s": setup_s,
           "compile": {"setup": c_setup, "window": c_window},
           "memory_peak_bytes": peak, "bytes_by_query": nbytes,
           "peaks": peaks, "trace": None}
    compared = verdict(run, want, config["limits"])
    device = dict(device or {}, memory_peak_bytes=peak)
    breakdown = {}
    if trace and win["traced"] and device.get("platform") == "tpu":
        import trace_reduce
        t0 = time.perf_counter()
        red = trace_reduce.reduce_dir(trace_dir, device["count"])
        say(f"trace: reduced in {time.perf_counter() - t0:.1f}s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        run["trace"] = dict(red, **win["traced"])
        device.update(busy_s=red["busy_s"],
                      window_s=win["traced"]["window_s"])
        breakdown = {"breakdown": {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}}
    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(run)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {
        "correct": all(v["value"] <= v["limit"] for v in compared.values()),
        "attempted": len(run["queries"]),
        "failed": sum(1 for r in run["queries"] if r["error"]),
        "metrics": metrics, "device": device, **breakdown,
        "per_query_s": {
            q: [r["seconds"] for r in run["queries"] if r["name"] == q]
            for q in config["queries"]},
        "compared": compared}                   # last, as the contract asks
    for name, v in compared.items():
        print(f"compared {name} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    return result


# ---------------------------------------------------------------------------

def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="debug the harness on the CPU backend; prints "
                        "platform=cpu and no result line")
    p.add_argument("--scale", type=float, default=None,
                   help="rehearsal only: scale factor in place of the "
                        "configuration's")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload)
    if importlib.util.find_spec("spark_rapids_tpu") is None:
        say("chipbench: the system under test (spark_rapids_tpu) is not "
            f"in {ROOT}")
        return 2
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"device_count={device['count']}")
    rehearsal = False
    if device["platform"] != "tpu":
        if not (args.rehearse_cpu and device["platform"] == "cpu"):
            say("chipbench: JAX found no TPU; refusing to run "
                "(--rehearse-cpu debugs the harness on the CPU backend)")
            return 2
        rehearsal = True
        # XLA:CPU AOT results reloaded on another machine can SIGILL
        jax.config.update("jax_enable_compilation_cache", False)
    elif args.rehearse_cpu or args.scale is not None:
        say("--rehearse-cpu/--scale given but the platform is tpu")
        return 2
    elif len(devs) < cell["chips"]:
        say(f"chipbench: the cell asks for {cell['chips']} chips, JAX "
            f"found {len(devs)}")
        return 2
    peaks = None
    if not rehearsal:
        table = load_json(HERE, "peaks.json")
        if device["kind"] not in table:
            say(f"chipbench: device_kind {device['kind']!r} is not in "
                f"peaks.json ({sorted(table)})")
            return 2
        peaks = table[device["kind"]]
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      scale=args.scale, device=device, peaks=peaks)
    if rehearsal:
        say("REHEARSAL on platform=cpu, not a chip result: "
            + json.dumps(result))
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
