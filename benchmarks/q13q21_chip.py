"""What TPC-H Q21 and Q13 at the ``tpch_q13q21`` configuration's scale
cost one chip, query by query and program by program, and what its LIKE
costs a batch of comments: the readings behind ``PERF.md`` sections 5
and 6 for that configuration.

One process, the normal path (``chipbench/run.py``'s own ``ensure_data``
/ ``start_engine`` / ``run_query``), with the engine imported from
``--tree`` (another checkout first on ``sys.path``; the data are made by
this checkout's generator, so run it here first):

- ``--like 1``: the first ``--like-rows`` comments of ``orders`` as one
  batch, ``o_comment LIKE '%special%requests%'`` and its ``NOT`` through
  the engine's own ``Like`` expression, whatever the imported tree runs
  for it: the first call (compiles; the persistent cache is off), the
  median of ``--like-reps`` warm calls ending in ``block_until_ready``,
  the device time of up to three calls under the profiler (busy union,
  by program), the
  device's peak bytes over the calls, and the matches against Python's
  ``re``;
- ``--queries``: a cold pass (``--empty-cache``: every program compiles,
  seconds by program name from JAX's "Finished XLA compilation" log),
  ``--warm`` warm passes and one traced pass, each query's plan, its
  adaptive joins' decisions and its counters.  ``--test-mode 0`` lets a
  tree that plans an operator on the CPU run it there (the parent's
  Q13).

One JSON line per step, the whole report in
``chiprun_out/q13q21_chip/<--label>.json``.  Refuses to run anywhere
but on a TPU unless ``--rehearse-cpu --scale <s>`` (which prints no
reading under a device's name)."""
from __future__ import annotations

import argparse
import collections
import json
import logging
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FINISHED = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.e+-]+) sec")
PATTERN = "%special%requests%"


class CompileLog(logging.Handler):
    """Seconds and count of XLA compiles by program name."""

    def __init__(self):
        super().__init__()
        self.by_name = collections.defaultdict(lambda: [0, 0.0])

    def emit(self, record):
        m = FINISHED.search(record.getMessage())
        if m:
            row = self.by_name[m.group(1)]
            row[0] += 1
            row[1] += float(m.group(2))

    def table(self, top: int = 40) -> list:
        rows = sorted(self.by_name.items(), key=lambda kv: -kv[1][1])
        return [{"program": k, "compiles": n, "seconds": round(s, 2)}
                for k, (n, s) in rows[:top]]

    def total(self) -> dict:
        return {"programs": sum(v[0] for v in self.by_name.values()),
                "seconds": round(sum(v[1] for v in self.by_name.values()), 1)}


def traced(jax, trace_reduce, trace_dir, fn, platform):
    """``fn()`` under the profiler -> the trace's busy time and programs
    (nothing off the chip)."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    fn()
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    out = {"window_s": round(window_s, 3)}
    if platform == "tpu":
        red = trace_reduce.reduce_dir(trace_dir, 1)
        out.update(busy_s=red["busy_s"], device_ops=red["device_ops"],
                   idle_gaps=red["idle_gaps"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def like_bench(jax, trace_reduce, data_dir, rows, platform, trace_dir,
               reps, exprs):
    import numpy as np
    import pyarrow.parquet as papq
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.columnar.arrow import from_arrow
    from spark_rapids_tpu.expr.core import AttributeReference, Literal
    from spark_rapids_tpu.expr.predicates import Not
    from spark_rapids_tpu.expr.string_ops import Like
    table = papq.read_table(os.path.join(data_dir, "orders.parquet"),
                            columns=["o_comment"]).slice(0, rows)
    batch = from_arrow(table)
    col = AttributeReference("o_comment", T.STRING, True)
    rx = re.compile(".*special.*requests.*", re.DOTALL)
    want = np.array([rx.fullmatch(v) is not None
                     for v in table.column(0).to_pylist()])
    dev = jax.devices()[0]
    out = {"rows": rows, "capacity": batch.capacity,
           "bytes": int(batch.columns[0].data.shape[0]),
           "matches": int(want.sum())}
    jax.config.update("jax_enable_compilation_cache", False)
    like = Like(col, Literal(PATTERN, T.STRING))
    for name, expr in (("like", like), ("not_like", Not(like))):
        if name not in exprs:
            continue
        bound = expr.bind(batch.schema)
        before = (dev.memory_stats() or {}).get("bytes_in_use")

        def call():
            c = bound.columnar_eval(batch)
            return jax.block_until_ready((c.data, c.validity))
        t0 = time.perf_counter()
        got = call()
        first = time.perf_counter() - t0
        ms = []
        for _ in range(reps):
            t0 = time.perf_counter()
            call()
            ms.append((time.perf_counter() - t0) * 1e3)
        data = np.asarray(got[0]).astype(bool)[:rows] & \
            np.asarray(got[1]).astype(bool)[:rows]
        row = {"expr": name, "first_call_s": round(first, 2),
               "median_ms": statistics.median(ms), "min_ms": min(ms),
               "max_ms": max(ms),
               "wrong": int((data != (want if name == "like"
                                      else ~want)).sum())}
        row["trace"] = traced(jax, trace_reduce, trace_dir,
                              lambda: [call() for _ in range(min(reps, 3))],
                              platform)
        row["trace"]["calls"] = min(reps, 3)
        stats = dev.memory_stats() or {}
        row["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        row["bytes_in_use_before"] = before
        out[name] = row
    jax.config.update("jax_enable_compilation_cache", True)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tree", default=None)
    p.add_argument("--label", default="change")
    p.add_argument("--workload", default="tpch_q13q21.power")
    p.add_argument("--seed", type=int, default=3800000011)
    p.add_argument("--like", type=int, choices=(0, 1), default=1)
    p.add_argument("--like-rows", type=int, default=1 << 20)
    p.add_argument("--like-reps", type=int, default=7)
    p.add_argument("--like-exprs", default="like,not_like")
    p.add_argument("--queries", default="q21,q13")
    p.add_argument("--test-mode", type=int, choices=(0, 1), default=1)
    p.add_argument("--warm", type=int, default=2)
    p.add_argument("--empty-cache", action="store_true")
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--scale", type=float, default=None)
    args = p.parse_args(argv)
    sys.path[:0] = [os.path.abspath(args.tree or ROOT),
                    os.path.join(ROOT, "chipbench")]
    if args.empty_cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
            prefix="q13q21_cache_")
    import jax
    # the engine from --tree, before run.py puts this checkout first
    import spark_rapids_tpu
    import run as harness
    import trace_reduce
    trace_reduce.TOP = 30
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        if not (args.rehearse_cpu and args.scale is not None):
            print("q13q21_chip: no TPU (--rehearse-cpu --scale <s> debugs "
                  "the script on the CPU backend)")
            return 2
        jax.config.update("jax_enable_compilation_cache", False)
    log = CompileLog()
    jax.config.update("jax_log_compiles", True)
    for name in ("jax._src.dispatch", "jax._src.interpreters.pxla"):
        lg = logging.getLogger(name)
        lg.addHandler(log)
        lg.propagate = False
    cell = harness.load_cell(args.workload)
    config = dict(cell["config"])
    config["engine_conf"] = dict(config["engine_conf"])
    config["engine_conf"]["spark.rapids.tpu.sql.test.enabled"] = \
        bool(args.test_mode)
    scale = config["scale"] if args.scale is None else args.scale
    t0 = time.perf_counter()
    data_dir = harness.ensure_data(cell["config_name"], config, scale,
                                   args.seed)
    report = {"label": args.label,
              "engine": os.path.dirname(spark_rapids_tpu.__file__),
              "platform": dev.platform, "kind": dev.device_kind,
              "scale": scale, "seed": args.seed,
              "datagen_s": round(time.perf_counter() - t0, 1),
              "passes": []}
    session = harness.start_engine(config, data_dir)
    trace_dir = os.path.join(harness.DATA_DIR, "trace", "q13q21_chip")
    if args.like:
        report["like"] = like_bench(jax, trace_reduce, data_dir,
                                    args.like_rows, dev.platform, trace_dir,
                                    args.like_reps,
                                    args.like_exprs.split(","))
        print(json.dumps({"like": report["like"]}), flush=True)
        report["compile_after_like"] = log.total()
    from spark_rapids_tpu.exec.adaptive import TpuAdaptiveShuffledJoin
    from spark_rapids_tpu.obs import trace as obs_trace
    queries = [q for q in args.queries.split(",") if q]

    def one_pass(label):
        out = {"pass": label, "queries": {}}
        for q in queries:
            before = log.total()
            rec = harness.run_query(session, q, cell["texts"][q])
            after = log.total()
            by_query = {k: v for k, v in obs_trace.coarse_counts().items()
                        if k is not None}
            plan = session.last_physical_plan
            out["queries"][q] = {
                "seconds": round(rec["seconds"], 3),
                "error": (rec["error"] or "")[:300] or None,
                "compiles": after["programs"] - before["programs"],
                "compile_s": round(after["seconds"] - before["seconds"], 1),
                "adaptive": [[n.logical.join_type,
                              getattr(n, "strategy", None)]
                             for n in plan.collect_nodes()
                             if isinstance(n, TpuAdaptiveShuffledJoin)],
                "counts": dict(sorted(by_query[max(by_query)].items()))
                if by_query else {}}
            if label == "cold":
                out["queries"][q]["plan"] = str(plan)
        report["passes"].append(out)
        print(json.dumps(out), flush=True)

    if queries:
        one_pass("cold")
        report["compile_by_program"] = log.table()
        report["compile_total"] = log.total()
        print(json.dumps({"compile_total": report["compile_total"],
                          "compile_by_program":
                              report["compile_by_program"]}), flush=True)
        for i in range(args.warm):
            one_pass(f"warm{i}")
        report["trace"] = traced(jax, trace_reduce, trace_dir,
                                 lambda: one_pass("traced"), dev.platform)
        print(json.dumps(report["trace"]), flush=True)
    stats = dev.memory_stats() or {}
    report["memory"] = {k: stats.get(k) for k in ("peak_bytes_in_use",
                                                  "bytes_limit")}
    report["compile_after"] = log.total()
    print(json.dumps({"memory": report["memory"],
                      "compile_after": report["compile_after"]}), flush=True)
    if dev.platform == "tpu":
        out_dir = os.path.join(ROOT, "chiprun_out", "q13q21_chip")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{args.label}.json"), "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
