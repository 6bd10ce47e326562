"""The tracing spine: program names (every ``jax.jit`` the engine makes
is named after its operator and role), coarse spans (always recorded,
in the tracer's ring and on the profiler's host plane), the per-query
counters, and ``compile_watch.jit_builds``."""
import ast
import glob
import logging
import os
import threading
import time

import jax
import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import compile_watch, flight, trace

ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "spark_rapids_tpu")
PREFIXES = ("agg_", "join_", "sort_", "filter_", "hash_", "scan_", "window_",
            "staged_", "fused_", "batch_", "partition_", "pending_",
            "str_", "list_", "mesh_", "stats_")
BANNED = {"_core", "_eval", "_prog", "_slice", "_concat"}


@pytest.fixture(autouse=True)
def _trace_clean():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()
    trace.get_tracer().path = None


# ---------------------------------------------------------------------------
# (1) source level: what every jax.jit under spark_rapids_tpu/ is called
# ---------------------------------------------------------------------------

def _is_jax_jit(node) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == "jit" and \
        isinstance(node.value, ast.Name) and node.value.id in ("jax", "_jax")


def _is_engine_jit(node) -> bool:
    """``compile_watch.jit`` under any of its import aliases."""
    return isinstance(node, ast.Attribute) and node.attr == "jit" and \
        isinstance(node.value, ast.Name) and \
        node.value.id in ("compile_watch", "_compile_watch", "_cw")


def jit_targets():
    """``(file, line, program name)`` of every jit the engine makes:
    the ``def`` a ``@jax.jit`` / ``@partial(jax.jit, ...)`` decorates,
    and the literal name each ``compile_watch.jit(fn, name)`` gives.
    A bare ``jax.jit(...)`` call outside ``compile_watch.py`` has no
    name of its own and is returned with ``None``."""
    out = []
    for path in sorted(glob.glob(os.path.join(ROOT, "**", "*.py"),
                                 recursive=True)):
        rel = os.path.relpath(path, ROOT)
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    inner = dec.args if isinstance(dec, ast.Call) else []
                    if _is_jax_jit(dec) or any(map(_is_jax_jit, inner)) or \
                            (isinstance(dec, ast.Call)
                             and _is_jax_jit(dec.func)):
                        out.append((rel, node.lineno, node.name))
            elif isinstance(node, ast.Call):
                if _is_engine_jit(node.func):
                    name = node.args[1] if len(node.args) > 1 else None
                    out.append((rel, node.lineno,
                                name.value if isinstance(name, ast.Constant)
                                else None))
                elif _is_jax_jit(node.func) and \
                        rel != os.path.join("obs", "compile_watch.py"):
                    out.append((rel, node.lineno, None))
    return out


class TestProgramNames:
    def test_every_jit_has_a_literal_name(self):
        targets = jit_targets()
        assert len(targets) >= 45, targets
        unnamed = [t for t in targets if t[2] is None]
        assert not unnamed, f"jax.jit without a program name: {unnamed}"

    def test_names_are_unique(self):
        seen = {}
        for rel, line, name in jit_targets():
            assert name not in seen, \
                f"{name}: {rel}:{line} and {seen[name]}"
            seen[name] = f"{rel}:{line}"

    def test_names_carry_an_operator_prefix(self):
        for rel, line, name in jit_targets():
            assert name not in BANNED, f"{rel}:{line} is called {name}"
            assert name.startswith(PREFIXES), \
                f"{rel}:{line}: {name} has none of {PREFIXES}"

    def test_jit_names_the_function_it_is_given(self):
        def _core(x):
            return x + 1
        before = compile_watch.jit_builds()
        fn = compile_watch.jit(_core, "agg_test_core")
        text = fn.lower(jax.numpy.arange(4)).as_text()
        assert "@jit_agg_test_core" in text
        assert compile_watch.jit_builds() == before + 1
        assert compile_watch.jit_build_sites()["agg_test_core"] >= 1

    def test_jit_names_a_bound_method(self):
        class Op:
            def _eval(self, cap, x):
                return x * cap
        fn = compile_watch.jit(Op()._eval, "fused_test_eval",
                               static_argnums=(0,))
        assert "@jit_fused_test_eval" in \
            fn.lower(3, jax.numpy.arange(4)).as_text()
        assert list(fn(3, jax.numpy.arange(2))) == [0, 3]


# ---------------------------------------------------------------------------
# a join + group-by query, the same for the cases below
# ---------------------------------------------------------------------------

def _session(extra=None):
    return TpuSession(TpuConf(dict(extra or {})))


def _join_group_by(s, rows=600):
    fact = s.create_dataframe(
        {"k": [i % 7 for i in range(rows)], "v": list(range(rows))},
        num_partitions=2)
    dim = s.create_dataframe({"k": list(range(7)),
                              "w": [10 * i for i in range(7)]})
    fact.create_or_replace_temp_view("spine_fact")
    dim.create_or_replace_temp_view("spine_dim")
    return ("select d.w, sum(f.v) as s, count(*) as c from spine_fact f "
            "join spine_dim d on f.k = d.k where f.v > 3 "
            "group by d.w order by d.w")


def _drop_engine_jit_caches():
    """Every engine jit cache empty: the next query builds (and so
    names, logs and counts) its programs again."""
    from spark_rapids_tpu.columnar import batch as cbatch, gather as cgather
    from spark_rapids_tpu.exec import fused, staged
    from spark_rapids_tpu.exec.tpu_aggregate import TpuHashAggregate
    from spark_rapids_tpu.exec.tpu_join import TpuHashJoinBase
    from spark_rapids_tpu.shuffle.partitioners import HashPartitioner
    from spark_rapids_tpu.cache import plan_cache
    for cache in (TpuHashAggregate._CORE_CACHE, TpuHashJoinBase._PROBE_JIT,
                  TpuHashJoinBase._SPEC_JIT, TpuHashJoinBase._EXPAND_JIT,
                  TpuHashJoinBase._DIRECT_JIT,
                  fused._JIT_CACHE, staged.TpuStagedCompute._JIT_CACHE,
                  cbatch._CONCAT_JIT, cgather._PROGRAMS,
                  HashPartitioner._SPLIT_JIT):
        cache.clear()
    plan_cache.reset()


class _Compiles(logging.Handler):
    def __init__(self):
        super().__init__()
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling jit("):
            self.names.append(msg[len("Compiling jit("):].split(")")[0])


def test_compiled_modules_are_named_after_operators():
    s = _session()
    text = _join_group_by(s)
    _drop_engine_jit_caches()
    handler = _Compiles()
    logging.getLogger("jax").addHandler(handler)
    jax.config.update("jax_log_compiles", True)
    try:
        rows = s.sql(text).collect()
    finally:
        jax.config.update("jax_log_compiles", False)
        logging.getLogger("jax").removeHandler(handler)
    assert [r[0] for r in rows] == [10 * i for i in range(7)]
    names = set(handler.names)
    assert any(n.startswith("agg_") for n in names), names
    assert any(n.startswith("join_") for n in names), names
    assert not names & BANNED, names


# ---------------------------------------------------------------------------
# (3) coarse spans with obs.trace.enabled unset
# ---------------------------------------------------------------------------

def _one_query_spans():
    s = _session()
    text = _join_group_by(s)
    s.sql(text).collect()                   # warm: compiles out of the way
    trace.reset()
    s.sql(text).collect()
    return s, trace.coarse_spans()


class TestCoarseSpans:
    def test_ring_holds_one_query_with_tracing_off(self):
        assert not trace.is_enabled()
        s, spans = _one_query_spans()
        assert trace.get_tracer().num_spans() == 0      # no fine record
        names = [x["name"] for x in spans]
        for want in ("srt.sql.parse", "srt.sql.analyze", "srt.plan",
                     "srt.query", "srt.obs.assemble", "srt.flush",
                     "srt.pull"):
            assert want in names, (want, sorted(set(names)))
        # every declared device-to-host transfer is an srt.pull that
        # names its site; the pool's own sits inside its srt.flush
        pulls = [x for x in spans if x["name"] == "srt.pull"]
        sites = [x["args"]["site"] for x in pulls]
        assert "collect_sink" in sites
        assert len(sites) == sum(s.last_query_declared_transfers.values())
        flushes = {x["id"] for x in spans if x["name"] == "srt.flush"}
        assert {x["parent"] for x in pulls
                if x["args"]["site"] == "pending_flush"} == flushes
        assert any(n.startswith("srt.exec.Tpu") for n in names)
        assert names.count("srt.flush") == s.last_query_flushes
        assert names.count("srt.query") == 1

    def test_ids_parents_and_one_query_number(self):
        _s, spans = _one_query_spans()
        ids = [x["id"] for x in spans]
        assert len(set(ids)) == len(ids) and all(i > 0 for i in ids)
        by_id = {x["id"]: x for x in spans}
        query = next(x for x in spans if x["name"] == "srt.query")
        assert query["parent"] == 0 and query["args"]["root"]
        numbers = {x["query"] for x in spans}
        assert len(numbers) == 1 and None not in numbers, numbers
        for x in spans:
            assert x["dur_ns"] >= 0
            parent = by_id.get(x["parent"])
            if parent is None:
                continue
            # a child lies inside its parent, on the parent's thread
            assert parent["thread"] == x["thread"]
            assert parent["t0_ns"] <= x["t0_ns"]
            assert x["t0_ns"] + x["dur_ns"] <= \
                parent["t0_ns"] + parent["dur_ns"]
        main = threading.get_ident()
        on_main = [x for x in spans if x["thread"] == main
                   and x["name"].startswith(("srt.exec", "srt.flush",
                                             "srt.pull", "srt.obs"))]
        assert on_main and all(x["parent"] in by_id for x in on_main)
        plan = next(x for x in spans if x["name"] == "srt.plan")
        assert plan["args"]["cache"] in ("hit", "miss")

    def test_second_collect_takes_a_new_query_number(self):
        s = _session()
        df = s.sql(_join_group_by(s))
        df.collect()
        df.collect()
        by_query = {}
        for x in trace.coarse_spans():
            by_query.setdefault(x["query"], []).append(x["name"])
        assert len(by_query) == 2
        first, second = (by_query[q] for q in sorted(by_query))
        assert "srt.sql.parse" in first and "srt.query" in first
        assert "srt.sql.parse" not in second and "srt.query" in second

    def test_eager_launches_are_counted_per_query(self):
        s = _session()
        text = _join_group_by(s)
        s.sql(text).collect()
        s.sql(text).collect()
        spans = trace.coarse_spans()
        numbers = sorted({x["query"] for x in spans})
        counts = trace.coarse_counts()
        assert set(counts) <= set(numbers)
        # every counter belongs to a documented family
        # (docs/observability.md); jit_build.* appears whenever a
        # neighbour dropped the jit caches, join.* with every hash join,
        # scan.* with every file scan, agg.* with every aggregate,
        # exchange.* with every in-process shuffle, window.* with every
        # window operator, expand.* with every grouping set, launch.* /
        # launch_ns.* / lanes.* / eager_lanes.* with every launch,
        # pull.* with every declared transfer, compile.* with every
        # compile, str.* with every LIKE launch, plan.* with every ON
        # conjunct pushed below an outer join, gather.* with every eager
        # gather of a batch's columns
        for tbl in counts.values():
            assert all(k.startswith(("eager.", "jit_build.", "join.",
                                     "scan.", "agg.", "exchange.",
                                     "window.", "expand.", "launch.",
                                     "launch_ns.", "lanes.",
                                     "eager_lanes.", "pull.", "compile.",
                                     "str.", "plan.", "gather."))
                       and v > 0 for k, v in tbl.items())
        assert any(k.startswith("launch.")
                   for tbl in counts.values() for k in tbl)
        # under a jit trace the same sites launch nothing
        from spark_rapids_tpu.columnar import dtypes as T
        from spark_rapids_tpu.columnar.column import Column
        import jax.numpy as jnp
        trace.reset()
        trace.begin_query()
        col = Column(T.INT64, jnp.arange(8), jnp.ones(8, bool))
        col.gather(jnp.arange(4))
        counts = trace.coarse_counts()
        assert list(counts) == [trace.current_query()]
        # (and the compiles of the small shapes' programs)
        table = {k: v for k, v in counts[trace.current_query()].items()
                 if "batch_gather@" in k}
        assert table.pop("launch_ns.batch_gather@-") > 0
        assert table == {"launch.batch_gather@-": 1,
                         "lanes.batch_gather@-": 4}
        jax.jit(lambda i: col.gather(i).data)(jnp.arange(4))
        assert trace.coarse_counts()[trace.current_query()][
            "launch.batch_gather@-"] == 1

    def test_count_tables_are_bounded(self):
        for _ in range(trace.COUNT_QUERIES + 5):
            trace.begin_query()
            trace.count("eager.site")
        counts = trace.coarse_counts()
        assert len(counts) == trace.COUNT_QUERIES
        assert trace.current_query() in counts

    def test_threads_lose_no_span_and_no_count(self):
        """More writers than cores on a short switch interval: every
        span gets its own ring slot and every count is added."""
        import sys
        workers, each = 16, 400
        tr = trace.get_tracer()
        trace.begin_query()
        qno = trace.current_query()
        start = threading.Barrier(workers)

        def work():
            trace.adopt_query(qno)
            start.wait(timeout=30)
            for _ in range(each):
                with trace.span("srt.exec.stress", "exec", True):
                    trace.count("eager.stress")
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work) for _ in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        assert tr.ring_written() == workers * each
        spans = trace.coarse_spans()
        assert len(spans) == workers * each
        assert len({x["id"] for x in spans}) == workers * each
        assert trace.coarse_counts() == {qno: {"eager.stress":
                                               workers * each}}

    def test_timed_ticks_the_flight_recorder(self):
        """The watchdog's progress signal is the calling thread's
        flight count: it still advances at timed() boundaries."""
        from spark_rapids_tpu.exec.base import Metric, timed
        m = Metric("opTime")
        before = flight.thread_counts().get(threading.get_ident(), 0)
        with timed(m):
            time.sleep(0.001)
        assert flight.thread_counts()[threading.get_ident()] == before + 2
        span = trace.coarse_spans()[-1]
        assert span["name"] == "srt.exec.opTime"
        assert m.value == span["dur_ns"] >= 1_000_000

    def test_span_file_is_not_written_per_query(self, tmp_path):
        path = str(tmp_path / "spans.json")
        s = _session({"spark.rapids.tpu.obs.trace.enabled": True,
                      "spark.rapids.tpu.obs.trace.path": path})
        s.sql(_join_group_by(s)).collect()
        assert not os.path.exists(path)
        s.close()
        assert os.path.exists(path)


# ---------------------------------------------------------------------------
# (4) the ring wraps at capacity, does not grow, and says so
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("written,wrapped", [(8, False), (9, True),
                                             (50, True)])
def test_ring_wraps_without_growing(monkeypatch, capsys, written, wrapped):
    tr = trace.SpanTracer(ring_slots=8)
    monkeypatch.setattr(trace, "_TRACER", tr)
    t_first = time.perf_counter_ns()
    for i in range(written):
        with trace.span(f"srt.s{i}", "engine", True):
            pass
    assert len(tr._ring) == 8 and tr.ring_written() == written
    got = trace.coarse_spans(t_first)
    if wrapped:
        assert got is None
        assert "wrapped" in capsys.readouterr().err
        # a window that starts after the oldest surviving span is whole
        later = tr.coarse_spans(time.perf_counter_ns())
        assert later == []
    else:
        assert [x["name"] for x in got] == [f"srt.s{i}"
                                            for i in range(written)]


# ---------------------------------------------------------------------------
# (5) a profiler session puts the coarse spans on the xplane's host plane
# ---------------------------------------------------------------------------

def test_profiler_session_sees_coarse_spans(tmp_path):
    s = _session()
    text = _join_group_by(s)
    s.sql(text).collect()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        s.sql(text).collect()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert len(found) == 1
    planes = jax.profiler.ProfileData.from_file(found[0]).planes
    host = {e.name for p in planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events}
    assert {"srt.query", "srt.flush", "srt.plan"} <= host, \
        sorted(n for n in host if n.startswith("srt."))
    # and the ring got the same spans: one code path
    names = {x["name"] for x in trace.coarse_spans()}
    assert {"srt.query", "srt.flush", "srt.plan"} <= names


# ---------------------------------------------------------------------------
# (6) jit_builds counts every jax.jit the engine constructs
# ---------------------------------------------------------------------------

def test_jit_builds_equals_constructions(monkeypatch):
    import importlib
    import pkgutil
    import spark_rapids_tpu
    # decorators build their jit at import: have every module imported
    # before counting
    for m in pkgutil.walk_packages(spark_rapids_tpu.__path__,
                                   "spark_rapids_tpu."):
        try:
            importlib.import_module(m.name)
        except Exception:  # noqa: BLE001 - optional dependencies
            pass
    s = _session()
    text = _join_group_by(s)
    s.sql(text).collect()
    _drop_engine_jit_caches()
    built = []
    real = jax.jit

    def counting(fn, *a, **k):
        built.append(getattr(fn, "__name__", "?"))
        return real(fn, *a, **k)
    monkeypatch.setattr(jax, "jit", counting)
    before = compile_watch.jit_builds()
    trace.reset()
    s.sql(text).collect()                                   # cold
    cold = compile_watch.jit_builds() - before
    assert cold == len(built) and cold >= 3, (cold, built)
    # the building query's counter table names each construction
    (table,) = trace.coarse_counts().values()
    counted = {k[len("jit_build."):]: v for k, v in table.items()
               if k.startswith("jit_build.")}
    assert sum(counted.values()) == cold and set(counted) == set(built)
    assert all(n.startswith(PREFIXES) for n in built), built
    s.sql(text).collect()                                   # warm
    assert compile_watch.jit_builds() - before == len(built)
    spans = [x for x in trace.coarse_spans()
             if x["name"] == "srt.jit_build"]
    assert spans and all(x["args"]["cache"] and
                         x["args"]["site"].startswith(PREFIXES)
                         for x in spans)
    recs = compile_watch.records_since(0)
    assert recs and all(r["t0_ns"] is not None and
                        r["t0_ns"] <= r["end_ns"] for r in recs)
