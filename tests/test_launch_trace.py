"""What the host does between the device's programs (``obs/trace.py``,
``obs/compile_watch.py``): every eager launch timed and counted by
program or site and by operator, with the indices it gathers; CPU time
beside wall time on every coarse span; every declared sync counted by
site; every compile inside a query named; counter tables and a ring
that hold a whole window; and the five ``chipbench/metrics/`` readers
(``launch_ms``, ``host_dispatch_cpu_ms``, ``pulls_per_query``,
``eager_take_lanes_per_query``, ``compile_wait_ms``)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar.column import Column, StringColumn, \
    StructColumn
from spark_rapids_tpu.exec.base import Metric, timed
from spark_rapids_tpu.obs import compile_watch, trace
# TPC-H Q3 at test scale, the cell's own deployment and harness
from test_tpch_q3q18_config import (  # noqa: F401
    _fresh_scan_cache, _session, bench, deployment)

CAP = 16


@pytest.fixture(autouse=True)
def _clean():
    trace.reset()
    yield
    trace.reset()


class Node:
    """An operator as ``timed`` sees it: a name."""

    def __init__(self, name):
        self.name = name


def under(op):
    return timed(Metric("opTime"), Node(op))


def table():
    """The one counter table written since the reset."""
    (tbl,) = trace.coarse_counts().values()
    return tbl


def site_counts(site):
    """``site``'s counters, its host ns left out."""
    return {k: v for k, v in table().items()
            if f".{site}@" in k and not k.startswith("launch_ns.")}


def _strings():
    return StringColumn.from_pylist(["ab", None, "cde", ""] * 4, CAP)


def _struct():
    dtype = T.StructType((T.StructField("a", T.INT64),))
    return StructColumn(dtype, [Column(T.INT64, jnp.arange(CAP),
                                       jnp.ones(CAP, bool))],
                        jnp.ones(CAP, bool))


def _column():
    return Column(T.INT64, jnp.arange(CAP), jnp.ones(CAP, bool))


#: gather -> (column, what counts it: ``launch`` (the engine program
#: ``batch_gather``, one index a row) or ``eager`` (a one-op site), its
#: name, launches a call)
SITES = {
    "column_gather": (_column, "launch", "batch_gather", 1),
    "string_gather": (_strings, "launch", "batch_gather", 1),
    "struct_gather": (_struct, "eager", "struct_gather", 1),
}


@pytest.mark.parametrize("site", sorted(SITES))
def test_a_gather_under_an_operator_counts_launches_and_lanes(site):
    make, kind, name, n = SITES[site]
    lanes = "lanes." if kind == "launch" else "eager_lanes."
    col = make()
    idx = jnp.arange(8)[::-1]
    with under("TpuHashJoin"):
        col.gather(idx)
    got = site_counts(name)
    assert got[f"{kind}.{name}@TpuHashJoin"] == n
    assert got[f"{lanes}{name}@TpuHashJoin"] == 8
    assert table()[f"launch_ns.{name}@TpuHashJoin"] > 0
    # outside any operator: ``-``
    col.gather(idx)
    assert site_counts(name)[f"{kind}.{name}@-"] == n


@pytest.mark.parametrize("site", sorted(SITES))
def test_under_a_jit_trace_nothing_is_counted_or_timed(site):
    col = SITES[site][0]()

    @jax.jit
    def inside(i):
        return col.gather(i).validity
    with under("TpuHashJoin"):
        got = inside(jnp.arange(8))
    assert np.asarray(got).shape == (8,)
    assert not any(SITES[site][2] in k
                   for tbl in trace.coarse_counts().values() for k in tbl)


def test_a_composed_string_gather_is_its_own_site():
    """A view made over a view (a core's string key output) composes its
    map by one take; an eager gather of a view moves the map as a lane
    of ``batch_gather`` and composes nothing."""
    from spark_rapids_tpu.columnar.column import GatheredStringColumn
    view = _strings().gather(jnp.arange(CAP))
    trace.reset()
    with under("TpuSuperstage"):
        GatheredStringColumn(view, jnp.arange(4), jnp.ones(4, bool))
        view.gather(jnp.arange(4))
    got = site_counts("string_gather_compose")
    assert got == {"eager.string_gather_compose@TpuSuperstage": 1,
                   "eager_lanes.string_gather_compose@TpuSuperstage": 4}


def test_the_innermost_operator_takes_the_launch_and_hands_it_back():
    col = _column()
    with under("TpuHashAggregate"):
        with under("TpuFilter"):
            col.gather(jnp.arange(4))
        col.gather(jnp.arange(4))
    got = site_counts("batch_gather")
    assert got["launch.batch_gather@TpuFilter"] == 1
    assert got["launch.batch_gather@TpuHashAggregate"] == 1
    assert trace.operator("-") == "-"


def test_an_engine_program_is_a_launcher():
    def _core(x):
        return x * 2
    fn = compile_watch.jit(_core, "agg_launch_test_core")
    assert isinstance(fn, trace.Launcher)
    assert "@jit_agg_launch_test_core" in \
        fn.lower(jnp.arange(4)).as_text()
    x = jnp.arange(4)
    fn(x)                                       # compiled outside
    trace.reset()
    with under("TpuHashAggregate"):
        fn(x)
        fn(x)
        jax.jit(lambda v: fn(v) + 1)(x)         # traced: not a launch
    got = table()
    assert got["launch.agg_launch_test_core@TpuHashAggregate"] == 2
    assert got["launch_ns.agg_launch_test_core@TpuHashAggregate"] > 0
    assert "lanes.agg_launch_test_core@TpuHashAggregate" not in got


# ---------------------------------------------------------------------------
# TPC-H Q3 at test scale, warm: the counters against the spans
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def q3(bench, deployment, tmp_path_factory):
    """The second of two Q3 runs: its counter table and its spans."""
    s = _session(deployment, tmp_path_factory.mktemp("q3"))
    text = deployment.cell["texts"]["q3"]
    bench.harness.run_query(s, "q3", text)
    trace.reset()
    rec = bench.harness.run_query(s, "q3", text)
    assert rec["error"] is None
    (qno, counts), = trace.coarse_counts().items()
    spans = trace.coarse_spans()
    assert {sp["query"] for sp in spans} == {qno}
    return counts, spans


def test_eager_launches_read_the_parents_count(q3):
    """The 65 one-op launches this query made when every column gathered
    by two takes (a CPU run at this seed and scale: 32 fixed-width
    columns and one string validity) are 33 columns moved by
    ``batch_gather`` launches, and no one-op gather is left."""
    counts, _ = q3
    assert sum(v for k, v in counts.items() if k.startswith("eager.")) == 0
    assert counts["gather.batch.columns"] == 33
    launches = {k: v for k, v in counts.items()
                if k.startswith("launch.batch_gather@")}
    assert launches and not any(k.endswith("@-") for k in launches)


def test_pulls_are_the_pull_spans_by_site(q3):
    counts, spans = q3
    by_site = {}
    for sp in spans:
        if sp["name"] == "srt.pull":
            site = sp["args"]["site"]
            by_site[site] = by_site.get(site, 0) + 1
    assert "collect_sink" in by_site
    assert {k[len("pull."):]: v for k, v in counts.items()
            if k.startswith("pull.")} == by_site


def test_launch_ns_under_an_operator_fits_its_self_time(q3, bench):
    counts, spans = q3
    selfs = bench.span_reduce.self_times(spans)
    wall = {}
    for sp in spans:
        if sp["name"].startswith("srt.exec."):
            op = sp["name"][len("srt.exec."):]
            wall[op] = wall.get(op, 0) + selfs[sp["id"]]
    launched = {}
    for k, v in counts.items():
        if k.startswith("launch_ns.") and not k.endswith("@-"):
            op = k.rsplit("@", 1)[1]
            launched[op] = launched.get(op, 0) + v
    assert launched
    for op, ns in launched.items():
        assert 0 < ns <= wall[op], (op, ns, wall[op])


def test_cpu_never_exceeds_wall_on_a_ring_span(q3):
    _, spans = q3
    timed_spans = [sp for sp in spans if sp["cpu_ns"] is not None]
    assert len(timed_spans) > 20
    for sp in timed_spans:
        assert 0 <= sp["cpu_ns"] <= sp["dur_ns"], sp


def test_a_sleeping_span_reads_no_cpu():
    with trace.span("srt.exec.Sleep", "exec", True):
        time.sleep(0.05)
    (sp,) = trace.coarse_spans()
    assert sp["dur_ns"] >= 50_000_000
    assert sp["cpu_ns"] < 5_000_000


def test_a_compile_inside_a_query_is_named_under_the_open_span(bench):
    @jax.jit
    def launch_trace_probe(x):
        return x * 3 + 1
    trace.begin_query()
    with trace.span("srt.exec.TpuProject", "exec", True):
        launch_trace_probe(jnp.arange(37))          # a new shape
    spans = trace.coarse_spans()
    (comp,) = [sp for sp in spans if sp["name"] == "srt.compile"
               and sp["args"]["program"] == "jit_launch_trace_probe"]
    (outer,) = [sp for sp in spans if sp["name"] == "srt.exec.TpuProject"]
    assert comp["parent"] == outer["id"] and comp["cpu_ns"] is None
    assert comp["args"]["how"] == "compile"      # tests cache nothing
    assert outer["t0_ns"] <= comp["t0_ns"]
    assert comp["t0_ns"] + comp["dur_ns"] <= outer["t0_ns"] + \
        outer["dur_ns"]
    assert table()["compile.jit_launch_trace_probe"] == 1
    covered = sum(sp["dur_ns"] for sp in spans
                  if sp["parent"] == outer["id"])
    assert bench.span_reduce.self_times(spans)[outer["id"]] == \
        outer["dur_ns"] - covered


# ---------------------------------------------------------------------------
# a window's worth of queries
# ---------------------------------------------------------------------------

def test_a_window_of_200_queries_keeps_every_table_and_span():
    col = _column()
    col.gather(jnp.arange(4))
    trace.reset()
    numbers = []
    for _ in range(200):
        numbers.append(trace.begin_query())
        with trace.span("srt.query", "query", True):
            for _ in range(20):
                with under("TpuFilter"):
                    col.gather(jnp.arange(4))
    tables = trace.coarse_counts()
    assert sorted(tables) == numbers
    assert all(t["launch.batch_gather@TpuFilter"] == 20
               for t in tables.values())
    assert trace.get_tracer().ring_written() == 200 * 21
    assert len(trace.coarse_spans()) == 200 * 21
    assert trace.COUNT_QUERIES >= 1024 and trace.RING_SLOTS >= 4 * 200 * 21


# ---------------------------------------------------------------------------
# the five readers on a recorded window
# ---------------------------------------------------------------------------

MS = 1_000_000
RUN = {"queries": [{"done": 1.0, "seconds": 1.0},
                   {"done": 2.0, "seconds": 1.0}],
       "peaks": {"hbm_gbps": 1}}


def span(sid, parent, name, dur_ms, cpu_ms, thread=1):
    return {"id": sid, "parent": parent, "name": name, "t0_ns": 0,
            "dur_ns": dur_ms * MS, "thread": thread, "query": 1,
            "args": {},
            "cpu_ns": None if cpu_ms is None else cpu_ms * MS}


#: two queries: an exec span of 30 ms wall / 20 ms CPU holding a flush
#: (8 ms CPU) and a compile (no CPU time); another thread's exec span
SPANS = [span(1, 0, "srt.query", 100, 60),
         span(2, 1, "srt.exec.TpuHashJoin", 30, 20),
         span(3, 2, "srt.flush", 10, 8),
         span(4, 2, "srt.compile", 12, None),
         span(5, 0, "srt.exec.TpuFilter", 5, 3, thread=2)]
COUNTS = {1: {"launch.join_probe_core@TpuHashJoin": 3,
              "launch_ns.join_probe_core@TpuHashJoin": 3 * MS,
              "launch_ns.column_gather@TpuFilter": 1 * MS,
              "eager.column_gather@TpuFilter": 2,
              "eager_lanes.column_gather@TpuFilter": 2048,
              "pull.collect_sink": 1, "pull.size_probe": 2},
          2: {"launch_ns.column_gather@-": 2 * MS,
              "eager_lanes.seg_sum_scatter@TpuHashAggregate": 1000,
              "pull.collect_sink": 1}}
WINDOW = {"spans": SPANS, "self_ns": {}, "n_queries": 2, "counts": COUNTS}
#: the parent of PR 36: spans without ``cpu_ns``, none of the counters
OLD = {"spans": [{k: v for k, v in s.items() if k != "cpu_ns"}
                 for s in SPANS],
       "self_ns": {}, "n_queries": 2,
       "counts": {1: {"eager.column_gather": 2}}}


@pytest.mark.parametrize("metric,want", [
    ("launch_ms", (3 + 1 + 2) / 2),
    ("host_dispatch_cpu_ms", ((20 - 8) + 3) / 2),
    ("pulls_per_query", 4 / 2),
    ("eager_take_lanes_per_query", 3048 / 2),
    ("compile_wait_ms", 12 / 2),
])
def test_a_metric_reads_a_recorded_window(bench, monkeypatch, metric, want):
    read = bench.harness.metric_reader(metric)
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, WINDOW])
    assert read(RUN) == want
    # the parent's engine: nothing, not 0
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, OLD])
    assert read(RUN) is None
    # a rehearsal (no chip) has no window
    monkeypatch.setattr(bench.span_reduce, "_LAST", [None, None])
    assert read({"peaks": None, "queries": []}) is None


def test_no_compile_in_the_window_reads_zero(bench, monkeypatch):
    quiet = dict(WINDOW, spans=[s for s in SPANS
                                if s["name"] != "srt.compile"])
    monkeypatch.setattr(bench.span_reduce, "_LAST", [RUN, quiet])
    assert bench.harness.metric_reader("compile_wait_ms")(RUN) == 0.0
