"""``span_reduce``: the self-time arithmetic on hand-written spans
(nesting, two threads, a wrapped ring), the eight readers it feeds, and
one whole CPU run through the harness with the reduction switched on."""
import pytest

import run as harness
import span_reduce

MS = 1_000_000


def span(sid, parent, name, t0_ms, dur_ms, thread=1, query=1, **args):
    return {"id": sid, "parent": parent, "name": name, "t0_ns": t0_ms * MS,
            "dur_ns": dur_ms * MS, "thread": thread, "query": query,
            "args": args}


#: one query, [100 ms, 200 ms] of the clock:
#:   main thread  srt.sql.parse 2, srt.plan 5, srt.query 80 with children
#:                exec.Agg 50 (flush 20 inside it, jit_build 10 inside
#:                it), pull 10 (flush 4 inside it), obs.assemble 6
#:   worker       exec.Join 30 with a sem_wait 5 inside it
SPANS = [
    span(1, 0, "srt.sql.parse", 100, 2),
    span(2, 0, "srt.plan", 103, 5, cache="hit"),
    span(5, 4, "srt.flush", 130, 20, items=3),
    span(6, 4, "srt.jit_build", 112, 10, cache="hash_aggregate",
         site="agg_grouped_core"),
    span(4, 3, "srt.exec.TpuHashAggregate", 111, 50),
    span(8, 7, "srt.flush", 165, 4, items=1),
    span(7, 3, "srt.pull", 163, 10),
    span(9, 3, "srt.obs.assemble", 175, 6),
    span(3, 0, "srt.query", 110, 80, root="TpuHashAggregate"),
    span(11, 10, "srt.sem_wait", 120, 5, thread=2),
    span(10, 0, "srt.exec.TpuHashJoin", 115, 30, thread=2),
    # another query's span, outside the interval
    span(12, 0, "srt.query", 300, 50, query=2),
]
RUN = {"queries": [{"done": 0.2, "seconds": 0.1}], "peaks": {"hbm_gbps": 1}}


def reduced(spans=SPANS, run=RUN, counts=None):
    return span_reduce.reduce_spans(spans, span_reduce.query_intervals(run),
                                    counts or {})


def test_self_time_is_duration_less_children():
    selfs = span_reduce.self_times(SPANS)
    assert selfs[4] == (50 - 20 - 10) * MS          # agg: flush + jit_build
    assert selfs[7] == (10 - 4) * MS                # pull: the flush in it
    assert selfs[3] == (80 - 50 - 10 - 6) * MS      # query: three children
    assert selfs[10] == (30 - 5) * MS               # worker's own child
    assert selfs[5] == 20 * MS and selfs[1] == 2 * MS


def test_overlapping_and_overhanging_children_count_once():
    spans = [span(1, 0, "p", 0, 10),
             span(2, 1, "a", 2, 4),          # [2, 6]
             span(3, 1, "b", 4, 4),          # [4, 8] overlaps a
             span(4, 1, "c", 9, 5)]          # [9, 14] overhangs p
    assert span_reduce.self_times(spans)[1] == (10 - 6 - 1) * MS


def test_two_threads_do_not_subtract_from_each_other():
    w = reduced()
    # the worker's exec span ran inside srt.query's interval on another
    # thread: it is no child of srt.query and takes nothing from it
    assert w["self_ns"][3] == 14 * MS
    assert {s["id"] for s in w["spans"]} == set(range(1, 12))
    assert w["n_queries"] == 1


@pytest.mark.parametrize("prefixes,self_time,want_ms", [
    (("srt.sql.", "srt.plan"), True, 7.0),
    (("srt.obs.assemble",), False, 6.0),
    (("srt.exec.",), True, 20.0 + 25.0),
    (("srt.flush", "srt.pull"), True, 20.0 + 4.0 + 6.0),
])
def test_layer_ms(monkeypatch, prefixes, self_time, want_ms):
    monkeypatch.setattr(span_reduce, "_LAST", [RUN, reduced()])
    assert span_reduce.layer_ms(RUN, prefixes, self_time) == want_ms


def test_counts_and_shares(monkeypatch):
    counts = {1: {"eager.column_gather": 40, "eager.seg_sum_scatter": 2,
                  "other": 9},
              2: {"eager.column_gather": 1000}}
    monkeypatch.setattr(span_reduce, "_LAST",
                        [RUN, reduced(counts=counts)])
    assert span_reduce.spans_per_query(RUN, "srt.jit_build") == 1.0
    assert span_reduce.counts_per_query(RUN, "eager.") == 42.0
    assert span_reduce.query_self_share(RUN) == 14 / 80


def test_wrapped_ring_gives_nothing(monkeypatch):
    """The engine returns None when its ring wrapped past the window's
    start: every reader then returns None, never a partial sum."""
    from spark_rapids_tpu.obs import trace
    tr = trace.SpanTracer(ring_slots=4)
    monkeypatch.setattr(trace, "_TRACER", tr)
    monkeypatch.setattr(span_reduce, "_LAST", [None, None])
    import time
    t0 = time.perf_counter()
    for i in range(9):
        with trace.span("srt.exec.X", "exec", True):
            pass
    run = {"queries": [{"done": time.perf_counter(),
                        "seconds": time.perf_counter() - t0}],
           "peaks": {"hbm_gbps": 1}}
    assert span_reduce.window(run) is None
    for name in ("front_end_ms", "obs_assemble_ms", "host_dispatch_ms",
                 "flush_wait_ms", "jit_builds_per_query",
                 "eager_launches_per_query"):
        assert harness.metric_reader(name)(run) is None


def test_engine_without_a_ring_gives_nothing(monkeypatch):
    """The parent of the PR that added the ring has no
    ``coarse_spans``: the readers return None and do not raise."""
    from spark_rapids_tpu.obs import trace
    monkeypatch.delattr(trace, "coarse_spans")
    monkeypatch.setattr(span_reduce, "_LAST", [None, None])
    assert harness.metric_reader("front_end_ms")(dict(RUN)) is None


@pytest.mark.parametrize("metric,prefix", [
    ("agg_device_ms_per_query", "jit_agg_"),
    ("join_device_ms_per_query", "jit_join_"),
])
def test_device_ms_reads_the_top_ten(metric, prefix):
    read = harness.metric_reader(metric)
    ops = [["jit_agg_grouped_core", 6.0], ["jit__take", 3.0],
           ["jit_agg_global_core", 2.0], ["jit_join_probe_core", 0.5]]
    run = {"trace": {"queries": ["q3", "q7"], "device_ops": ops}}
    want = {"jit_agg_": 4000.0, "jit_join_": 250.0}[prefix]
    assert read(run) == want
    # operator names present but none of this operator's: a lower
    # bound of 0, still a reading
    other = [r for r in ops if not r[0].startswith(prefix)]
    assert read({"trace": {"queries": ["q3"], "device_ops": other}}) == 0.0
    # an engine that does not name its programs, or no trace at all
    old = [["jit__core", 10.9], ["jit__take", 3.7]]
    assert read({"trace": {"queries": ["q3"], "device_ops": old}}) is None
    assert read({"trace": None}) is None


def test_a_cpu_run_through_the_harness(monkeypatch):
    """One rehearsal with the chip's gate lifted: the six host readers
    find the engine's spans of the window's queries and nothing else."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    seen = {}
    real = harness.metric_reader

    def capturing(name):
        fn = real(name)

        def read(run):
            seen["run"] = run
            return fn(run)
        return read
    monkeypatch.setattr(harness, "metric_reader", capturing)
    cell = harness.load_cell("tpcds_sf1_store.power")
    result = harness.run_cell(cell, 2147483659, 0.5, True, scale=0.01,
                              device={"platform": "cpu", "kind": "cpu",
                                      "count": 1})
    assert result["correct"] is True
    # a rehearsal prints none of the span metrics ...
    assert not {"front_end_ms", "host_dispatch_ms"} & set(result["metrics"])
    # ... the reduction itself works on the CPU backend
    run = dict(seen["run"], peaks={"hbm_gbps": 1.0})
    monkeypatch.setattr(span_reduce, "_LAST", [None, None])
    w = span_reduce.window(run)
    n = len(run["queries"])
    assert w["n_queries"] == n
    names = [s["name"] for s in w["spans"]]
    assert names.count("srt.query") == n and names.count("srt.plan") == n
    assert names.count("srt.flush") == sum(r["flushes"]
                                           for r in run["queries"])
    values = {m: real(m)(run) for m in (
        "front_end_ms", "obs_assemble_ms", "host_dispatch_ms",
        "flush_wait_ms", "jit_builds_per_query",
        "eager_launches_per_query")}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["front_end_ms"] > real("planner_ms")(run) > 0
    assert 0 < span_reduce.query_self_share(run) < 1
    # every span's self time lies between 0 and its duration
    for s in w["spans"]:
        assert 0 <= w["self_ns"][s["id"]] <= s["dur_ns"]
