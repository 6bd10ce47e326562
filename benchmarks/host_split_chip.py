"""What the host does between the device's programs, on the chip: the
readings behind ``PERF.md``'s host-split table (ISSUE 36).

    python benchmarks/host_split_chip.py --calibrate
    python benchmarks/host_split_chip.py --workload <cell> --seed <n> [--seconds 51]

``--calibrate``: the CPU / wall of two coarse spans (``np.asarray`` of a
device program of at least 100 ms; 1,000 eager takes of 2^20 slots) and
what the tracing costs a launch, a coarse span and a counter add, by
micro loops.  ``--workload``: one traced run of the cell through the
harness (``chipbench/run.py`` ``run_cell``, as the driver runs it),
then, from the window's counter tables and spans: ``launch_ms`` by
operator, the five ``<name>@<Op>`` with the most ``launch_ns`` and the
most lanes, ``srt.pull`` ms by site, CPU / wall of ``host_dispatch_ms``,
coarse spans per query and the ring's fill.  One JSON file per step
under ``--out``; the result line is printed as the harness prints it.
Refuses to run anywhere but on a TPU unless ``--rehearse-cpu --scale``
(which reads nothing under a device's name)."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "chipbench")]


def say(*parts):
    print("[host_split]", *parts, file=sys.stderr, flush=True)


def per_loop_ns(fn, n: int) -> float:
    t0 = time.perf_counter_ns()
    for _ in range(n):
        fn()
    return (time.perf_counter_ns() - t0) / n


def calibrate() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from spark_rapids_tpu.obs import trace

    out = {}

    @jax.jit
    def spin(x, n):
        v = lax.fori_loop(0, n, lambda i, v: v * 1.0000001 + 1e-7, x)
        return jnp.sum(v)
    x = jnp.ones(1 << 24, jnp.float32)
    np.asarray(spin(x, 8))
    t0 = time.perf_counter()
    np.asarray(spin(x, 200))
    per = (time.perf_counter() - t0) / 200
    n = max(200, int(0.25 / max(per, 1e-7)))    # about 250 ms
    trace.reset()
    with trace.span("srt.calib.pull", "calib", True):
        np.asarray(spin(x, n))
    src = jnp.arange(1 << 20, dtype=jnp.int32)
    idx = jax.random.permutation(jax.random.key(7), 1 << 20) \
        .astype(jnp.int32)
    jnp.take(src, idx).block_until_ready()
    with trace.span("srt.calib.takes", "calib", True):
        for _ in range(1000):
            got = jnp.take(src, idx)
    t0 = time.perf_counter()
    got.block_until_ready()
    drain = time.perf_counter() - t0
    with trace.span("srt.calib.takes_launched", "calib", True):
        for _ in range(1000):
            with trace.launch("calib_take", 1, 1 << 20):
                got = jnp.take(src, idx)
    got.block_until_ready()
    for sp in trace.coarse_spans():
        if not sp["name"].startswith("srt.calib."):
            continue                    # a compile inside one of them
        out[sp["name"]] = {"wall_ms": sp["dur_ns"] / 1e6,
                           "cpu_ms": sp["cpu_ns"] / 1e6,
                           "cpu_over_wall": sp["cpu_ns"] / sp["dur_ns"]}
    out["srt.calib.pull"]["loop_iterations"] = n
    out["srt.calib.takes"]["drain_after_ms"] = drain * 1e3

    # micro loops: the helper's own cost, no device in them
    loops = 200_000
    raw = lambda: None                                      # noqa: E731
    wrapped = trace.Launcher(raw, "calib_noop")
    out["ns"] = {
        "python_call": per_loop_ns(raw, loops),
        "launcher_call": per_loop_ns(wrapped, loops),
        "launch_region": per_loop_ns(lambda: _region(trace), loops),
        "count_add": per_loop_ns(lambda: trace.count("calib.add"), loops),
        "coarse_span": per_loop_ns(lambda: _span(trace), loops),
        "thread_time_ns_read": per_loop_ns(time.thread_time_ns, loops),
        "perf_counter_ns_read": per_loop_ns(time.perf_counter_ns, loops),
    }
    out["ns"]["launcher_overhead"] = \
        out["ns"]["launcher_call"] - out["ns"]["python_call"]
    out["thread_clock"] = thread_clock()
    trace.reset()
    return out


def thread_clock() -> dict:
    """The thread CPU clock's grain: its stated resolution, the steps a
    busy loop of 100 ms sees it take, and CPU / wall of that loop."""
    steps = set()
    t0, c0 = time.perf_counter_ns(), time.thread_time_ns()
    last = c0
    while time.perf_counter_ns() - t0 < 100_000_000:
        now = time.thread_time_ns()
        if now != last:
            steps.add(now - last)
            last = now
    wall = time.perf_counter_ns() - t0
    return {"getres_ns": time.clock_getres(time.CLOCK_THREAD_CPUTIME_ID)
            * 1e9, "distinct_steps": len(steps),
            "smallest_step_ns": min(steps, default=None),
            "largest_step_ns": max(steps, default=None),
            "busy_cpu_over_wall": (last - c0) / wall}


def _region(trace):
    with trace.launch("calib_noop", 1, 8):
        pass


def _span(trace):
    with trace.span("srt.calib.span", "calib", True):
        pass


def cell_tables(run: dict) -> dict:
    """The host split of the window, from the ring and the tables."""
    import span_reduce
    from spark_rapids_tpu.obs import trace
    w = span_reduce.window(run)
    if w is None:
        return {"window": None}
    nq = w["n_queries"]
    total = {}
    for tbl in w["counts"].values():
        for k, v in tbl.items():
            total[k] = total.get(k, 0) + v

    def by(prefixes, scale=1.0):
        rows = [(k.split(".", 1)[1], v / nq / scale) for k, v in
                total.items() if k.startswith(prefixes)]
        return sorted(rows, key=lambda r: -r[1])
    launch_ms = by(("launch_ns.",), 1e6)
    by_op = {}
    for name, ms in launch_ms:
        op = name.rsplit("@", 1)[1]
        by_op[op] = by_op.get(op, 0.0) + ms
    pulls = {}
    for s in w["spans"]:
        if s["name"] == "srt.pull":
            row = pulls.setdefault(s["args"].get("site", "?"),
                                   [0, 0.0, 0.0])
            row[0] += 1 / nq
            row[1] += s["dur_ns"] / 1e6 / nq
            row[2] += w["self_ns"][s["id"]] / 1e6 / nq
    names = {}
    for s in w["spans"]:
        names[s["name"]] = names.get(s["name"], 0) + 1
    return {
        "n_queries": nq,
        "launch_ms_by_operator": sorted(by_op.items(), key=lambda r: -r[1]),
        "top_launch_ms": launch_ms[:8],
        "top_lanes": by(("lanes.", "eager_lanes."))[:8],
        "top_launches": by(("launch.", "eager."))[:8],
        "compiles": by(("compile.",)),
        "agg_counters": by(("agg.",)),
        "pull_by_site_count_ms_selfms": sorted(
            pulls.items(), key=lambda r: -r[1][1]),
        "pull_spans_per_query": span_reduce.spans_per_query(run,
                                                            "srt.pull"),
        "launches_per_query": sum(v for k, v in total.items()
                                  if k.startswith("launch.")) / nq,
        "eager_per_query": sum(v for k, v in total.items()
                               if k.startswith("eager.")) / nq,
        "launch_ms_outside_operators": sum(ms for n, ms in launch_ms
                                           if n.endswith("@-")),
        "coarse_spans_per_query": len(w["spans"]) / nq,
        "coarse_spans_by_name_per_query": sorted(
            ((k, v / nq) for k, v in names.items()), key=lambda r: -r[1]),
        "ring_written": trace.get_tracer().ring_written(),
        "ring_slots": trace.get_tracer().ring_slots,
        "count_tables": len(trace.coarse_counts()),
        "window_tables": len(w["counts"]),
    }


def run_workload(args) -> dict:
    import jax
    import run as harness
    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = None
    if device["platform"] == "tpu":
        peaks = harness.load_json(harness.HERE, "peaks.json")[device["kind"]]
    elif not args.rehearse_cpu:
        say("no TPU; refusing (--rehearse-cpu --scale debugs this here)")
        sys.exit(2)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    seen = {}
    real = harness.metric_reader

    def capturing(name):
        fn = real(name)

        def read(run):
            seen["run"] = run
            return fn(run)
        return read
    harness.metric_reader = capturing
    result = harness.run_cell(cell, args.seed, args.seconds, True,
                              scale=args.scale, device=device, peaks=peaks)
    run = seen["run"]
    if peaks is None:               # a rehearsal: the reduction all the same
        run = dict(run, peaks={"hbm_gbps": 1.0})
    return {"result": result, "tables": cell_tables(run),
            "rehearsal": peaks is None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calibrate", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--out", default="chiprun_out/host_split")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    steps = []
    if args.calibrate:
        import jax
        if jax.devices()[0].platform != "tpu" and not args.rehearse_cpu:
            say("no TPU; refusing")
            return 2
        steps.append(("calibrate", calibrate()))
    if args.workload:
        rep = run_workload(args)
        print(json.dumps(rep["result"]), flush=True)
        steps.append((args.workload, rep))
    for name, rep in steps:
        if args.rehearse_cpu:
            name += ".rehearsal"
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(rep, f, indent=1, default=str)
        say(json.dumps(rep.get("tables", rep), default=str)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
