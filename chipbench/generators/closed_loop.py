"""One closed-loop client straight on ``TpuSession`` (a TPC Power-test
stream): the configuration's queries in the order it lists them, each
waiting for the one before, whole passes only.

A load generator is a module here, found by the ``generator`` name in a
``traffic/<mix>.json``.  It exposes ``warm_up(session, cell, run_query)``,
which yields a log line per step, and ``measure(session, cell, seconds,
trace_dir, run_query)``; ``run_query(session, name, text)`` is the
harness's one way through the entry point users call and returns one
record per query.  ``measure``
returns ``records``, ``window_s`` (window start to last completion),
``passes`` and ``traced`` (``None``, or the traced span's ``window_s``
and ``queries``).
"""
import time


def one_pass(session, cell, pass_no, run_query) -> list:
    out = []
    for q in cell["config"]["queries"]:
        rec = run_query(session, q, cell["texts"][q])
        rec["pass"] = pass_no
        out.append(rec)
    return out


def warm_up(session, cell, run_query):
    """The mix's ``warmup_passes``; a query that fails here ends the
    run.  Yields one line per pass as it ends, for the log."""
    for i in range(cell["mix"]["warmup_passes"]):
        warm = one_pass(session, cell, -1 - i, run_query)
        errors = [(r["name"], r["error"]) for r in warm if r["error"]]
        if errors:
            raise RuntimeError(f"warm-up pass {i}: {errors}")
        yield f"warm-up pass {i}: " + " ".join(
            f"{r['name']}={r['seconds']:.2f}s" for r in warm)


def measure(session, cell, seconds, trace_dir, run_query) -> dict:
    """A new pass starts only while fewer than ``seconds`` have elapsed,
    and the pass in flight is finished: every window holds the same mix
    of queries, so a long query cannot fall in or out of it.  With
    ``trace_dir`` the profiler is on for the first pass."""
    import jax
    records, traced = [], None
    start = time.perf_counter()
    pass_no = 0
    while True:
        tracing = trace_dir is not None and pass_no == 0
        if tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t_on = time.perf_counter()
        records += one_pass(session, cell, pass_no, run_query)
        if tracing:
            t_off = time.perf_counter()
            jax.profiler.stop_trace()
            traced = {"window_s": t_off - t_on,
                      "queries": [r["name"] for r in records]}
        pass_no += 1
        if time.perf_counter() - start >= seconds:
            break
    # all the work over all the time: start -> the last completion
    return {"records": records, "traced": traced,
            "window_s": records[-1]["done"] - start, "passes": pass_no}
