"""What the ROLLUP / window reports cost one chip, query by query and
program by program: the readings behind ``PERF.md`` section 5's
``tpcds_sf1_olap`` table (ISSUE 34, step 0).

One process, the normal path (``chipbench/run.py``'s own
``ensure_data`` / ``start_engine`` / ``run_query``): the configuration's
tables from ``--seed``, one ``TpuSession`` with its ``engine_conf``, a
cold pass of its queries and ``--warm`` warm passes, then (``--trace``)
one more pass under the profiler, reduced by ``chipbench/trace_reduce``.
With ``--empty-cache`` the persistent compile cache starts in a new
empty directory, so every program compiles, and the log of JAX's own
"Finished XLA compilation of jit(<name>) in <s> sec" lines gives the
seconds by program name.  One JSON line per step, the whole report in
``chiprun_out/window_chip.json``; refuses to run anywhere but on a TPU
unless ``--rehearse-cpu --scale <s>`` (which prints no reading under a
device's name)."""
from __future__ import annotations

import argparse
import collections
import json
import logging
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "chipbench")]

FINISHED = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.e+-]+) sec")


class CompileLog(logging.Handler):
    """Seconds and count of XLA compiles by program name (a persistent
    cache hit is logged too: it reads as a few milliseconds)."""

    def __init__(self):
        super().__init__()
        self.by_name = collections.defaultdict(lambda: [0, 0.0, 0.0])

    def emit(self, record):
        m = FINISHED.search(record.getMessage())
        if m:
            row = self.by_name[m.group(1)]
            secs = float(m.group(2))
            row[0] += 1
            row[1] += secs
            row[2] = max(row[2], secs)

    def table(self, top: int = 40) -> list:
        rows = sorted(self.by_name.items(), key=lambda kv: -kv[1][1])
        return [{"program": k, "compiles": n, "seconds": round(s, 2),
                 "longest_s": round(mx, 2)} for k, (n, s, mx) in rows[:top]]

    def total(self) -> dict:
        return {"programs": sum(v[0] for v in self.by_name.values()),
                "seconds": round(sum(v[1] for v in self.by_name.values()), 1)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="tpcds_sf1_olap.power")
    p.add_argument("--seed", type=int, default=3400000007)
    p.add_argument("--warm", type=int, default=2)
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    p.add_argument("--empty-cache", action="store_true")
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--scale", type=float, default=None)
    args = p.parse_args(argv)
    if args.empty_cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
            prefix="window_chip_cache_")
    import jax
    import run as harness
    import trace_reduce
    trace_reduce.TOP = 40            # the harness keeps ten; step 0 wants the tail
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        if not (args.rehearse_cpu and args.scale is not None):
            print("window_chip: no TPU (--rehearse-cpu --scale <s> debugs "
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
    config = cell["config"]
    scale = config["scale"] if args.scale is None else args.scale
    data_dir = harness.ensure_data(cell["config_name"], config, scale,
                                   args.seed)
    session = harness.start_engine(config, data_dir)
    from spark_rapids_tpu.obs import trace as obs_trace
    report = {"platform": dev.platform, "kind": dev.device_kind,
              "scale": scale, "seed": args.seed, "passes": []}

    def last_counts() -> dict:
        by_query = obs_trace.coarse_counts()
        return dict(sorted(by_query[max(by_query)].items())) \
            if by_query else {}

    def one_pass(label):
        out = {"pass": label, "queries": {}}
        for q in config["queries"]:
            before = log.total()
            rec = harness.run_query(session, q, cell["texts"][q])
            after = log.total()
            out["queries"][q] = {
                "seconds": round(rec["seconds"], 3), "error": rec["error"],
                "rows": None if rec["rows"] is None else len(rec["rows"]),
                "compiles": after["programs"] - before["programs"],
                "compile_s": round(after["seconds"] - before["seconds"], 1),
                "counts": last_counts()}
        report["passes"].append(out)
        print(json.dumps(out), flush=True)

    one_pass("cold")
    report["compile_by_program"] = log.table()
    report["compile_total"] = log.total()
    print(json.dumps({"compile_total": report["compile_total"],
                      "compile_by_program": report["compile_by_program"]}),
          flush=True)
    for i in range(args.warm):
        one_pass(f"warm{i}")
    if args.trace:
        trace_dir = os.path.join(harness.DATA_DIR, "trace", "window_chip")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        one_pass("traced")
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        if dev.platform == "tpu":
            red = trace_reduce.reduce_dir(trace_dir, len(jax.devices()))
            report["trace"] = {"window_s": round(window_s, 2),
                               "busy_s": red["busy_s"],
                               "device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
            print(json.dumps(report["trace"]), flush=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    report["memory"] = {k: stats.get(k) for k in ("peak_bytes_in_use",
                                                  "bytes_limit")}
    report["compile_after_warm"] = log.total()
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    report["scan_cache_bytes"] = int(DeviceScanCache.get().nbytes)
    print(json.dumps({"memory": report["memory"],
                      "scan_cache_bytes": report["scan_cache_bytes"],
                      "compile_after_warm": report["compile_after_warm"]}),
          flush=True)
    if dev.platform == "tpu":
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "window_chip.json"),
                  "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
