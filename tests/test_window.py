"""Window function tests — reference: window_function_test.py pattern."""
import pytest

from spark_rapids_tpu.api import functions as F

from harness import assert_tpu_and_cpu_are_equal_collect
from data_gen import IntGen, FloatGen, KeyGen, gen_df

N = 200


class TestWindow:
    def test_row_number(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=8),
                                 "v": IntGen(lo=-100, hi=100)}, N)
            .with_window("rn", F.row_number(), partition_by=["k"],
                         order_by=["v", "k"]))

    def test_rank_dense_rank(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=6),
                                 "v": KeyGen(cardinality=10,
                                             null_ratio=0.0)}, N)
            .with_window("rk", F.rank(), partition_by=["k"],
                         order_by=["v"]))

    def test_lead_lag(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=5),
                                 "v": IntGen(lo=0, hi=1000,
                                             null_ratio=0.0),
                                 "x": FloatGen(null_ratio=0.2)}, N)
            .with_window("ld", F.lead("x"), partition_by=["k"],
                         order_by=["v", "x"])
            .with_window("lg", F.lag("x"), partition_by=["k"],
                         order_by=["v", "x"]))

    def test_partition_aggregate(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=7),
                                 "v": FloatGen(no_nans=True)}, N)
            .with_window("s", F.sum("v"), partition_by=["k"],
                         frame=("rows", None, None))
            .with_window("c", F.count("v"), partition_by=["k"],
                         frame=("rows", None, None)))

    def test_running_sum(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=4),
                                 "o": IntGen(lo=0, hi=10**6,
                                             null_ratio=0.0),
                                 "v": IntGen(lo=-50, hi=50)}, N)
            .with_window("rs", F.sum("v"), partition_by=["k"],
                         order_by=["o"], frame=("rows", None, 0)))

    def test_global_window(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"v": IntGen(lo=0, hi=100,
                                             null_ratio=0.0)}, 50)
            .with_window("rn", F.row_number(), partition_by=[],
                         order_by=["v"]))


class TestFrames:
    """Bounded ROWS and RANGE frames vs the exact CPU oracle."""

    def _df(self, s, n=60):
        import numpy as np
        rng = np.random.default_rng(9)
        return s.create_dataframe({
            "g": rng.integers(0, 5, n).astype(np.int64),
            "o": rng.integers(0, 40, n).astype(np.int64),
            "v": rng.integers(-50, 50, n).astype(np.int64),
        })

    def test_bounded_rows_frame(self):
        from spark_rapids_tpu.api import functions as F
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: self._df(s).with_window(
                "w", F.sum("v"), partition_by=["g"], order_by=["o"],
                frame=("rows", -2, 1)))

    def test_range_frame_sum(self):
        from spark_rapids_tpu.api import functions as F
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: self._df(s).with_window(
                "w", F.sum("v"), partition_by=["g"], order_by=["o"],
                frame=("range", -5, 5)))

    def test_range_frame_count_avg(self):
        from spark_rapids_tpu.api import functions as F
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: self._df(s)
            .with_window("c", F.count("v"), partition_by=["g"],
                         order_by=["o"], frame=("range", None, 0))
            .with_window("a", F.avg("v"), partition_by=["g"],
                         order_by=["o"], frame=("range", -3, 3)))

    def test_range_frame_desc(self):
        from spark_rapids_tpu.api import functions as F
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: self._df(s).with_window(
                "w", F.sum("v"), partition_by=["g"],
                order_by=[F.col("o").desc()], frame=("range", -4, 2)))

    def test_range_frame_with_null_order(self):
        from spark_rapids_tpu.api import functions as F

        def fn(s):
            df = s.create_dataframe({
                "g": [1, 1, 1, 1, 2, 2],
                "o": [1, None, 3, None, 2, 5],
                "v": [10, 20, 30, 40, 50, 60],
            })
            return df.with_window(
                "w", F.sum("v"), partition_by=["g"], order_by=["o"],
                frame=("range", -2, 2))
        assert_tpu_and_cpu_are_equal_collect(fn)

    def test_range_frame_half_unbounded_with_null_order(self):
        """UNBOUNDED sides reach the partition edge and take the
        null-order block in with them (Spark RANGE semantics)."""
        from spark_rapids_tpu.api import functions as F

        def fn(frame, order_desc=False):
            def run(s):
                df = s.create_dataframe({
                    "g": [1, 1, 1, 1, 2, 2],
                    "o": [1, None, 3, None, 2, 5],
                    "v": [10, 20, 30, 40, 50, 60],
                })
                ob = [F.col("o").desc()] if order_desc else ["o"]
                return df.with_window(
                    "w", F.sum("v"), partition_by=["g"], order_by=ob,
                    frame=frame)
            return run
        assert_tpu_and_cpu_are_equal_collect(fn(("range", None, 0)))
        assert_tpu_and_cpu_are_equal_collect(fn(("range", -1, None)))
        assert_tpu_and_cpu_are_equal_collect(
            fn(("range", None, 1), order_desc=True))


class TestWindowCompleteness:
    """Round-4 window breadth (GpuWindowExpression.scala parity):
    ntile / percent_rank / cume_dist, bounded min/max frames, RANGE
    min/max, collect_list over windows."""

    def test_ntile(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=5),
                                 "v": IntGen(lo=0, hi=1000,
                                             null_ratio=0.0)}, N)
            .with_window("nt", F.ntile(4), partition_by=["k"],
                         order_by=["v"]))

    def test_percent_rank_cume_dist(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=5),
                                 "v": KeyGen(cardinality=12,
                                             null_ratio=0.0)}, N)
            .with_window("pr", F.percent_rank(), partition_by=["k"],
                         order_by=["v"])
            .with_window("cd", F.cume_dist(), partition_by=["k"],
                         order_by=["v"]))

    def test_bounded_min_max_rows(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=6),
                                 "o": IntGen(lo=0, hi=10000,
                                             null_ratio=0.0),
                                 "v": IntGen(lo=-500, hi=500,
                                             null_ratio=0.15)}, N)
            .with_window("mn", F.min("v"), partition_by=["k"],
                         order_by=["o", "v"], frame=("rows", -3, 2))
            .with_window("mx", F.max("v"), partition_by=["k"],
                         order_by=["o", "v"], frame=("rows", -2, None)))

    def test_range_min_max(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=4),
                                 "o": IntGen(lo=0, hi=60,
                                             null_ratio=0.1),
                                 "v": IntGen(lo=-500, hi=500,
                                             null_ratio=0.1)}, N)
            .with_window("mn", F.min("v"), partition_by=["k"],
                         order_by=["o"], frame=("range", -5, 5))
            .with_window("mx", F.max("v"), partition_by=["k"],
                         order_by=["o"], frame=("range", None, 3)))

    def test_collect_list_window(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=5),
                                 "o": IntGen(lo=0, hi=100000,
                                             null_ratio=0.0),
                                 "v": IntGen(lo=0, hi=50,
                                             null_ratio=0.2)}, N)
            .with_window("cl", F.collect_list("v"), partition_by=["k"],
                         order_by=["o", "v"], frame=("rows", -2, 1)))

    def test_collect_list_window_unbounded(self):
        assert_tpu_and_cpu_are_equal_collect(
            lambda s: gen_df(s, {"k": KeyGen(cardinality=4),
                                 "o": IntGen(lo=0, hi=100000,
                                             null_ratio=0.0),
                                 "v": IntGen(lo=0, hi=50,
                                             null_ratio=0.1)}, N)
            .with_window("cl", F.collect_list("v"), partition_by=["k"],
                         order_by=["o", "v"],
                         frame=("rows", None, None)))

    def test_sql_window_completeness(self):
        """ntile/percent_rank/cume_dist + bounded ROWS min + bounded
        RANGE max + windowed collect_list through session.sql()."""
        import numpy as np
        from harness import with_cpu_session, with_tpu_session
        rng = np.random.default_rng(5)
        data = {"k": rng.integers(0, 5, 200).astype(np.int64),
                "o": rng.integers(0, 50, 200).astype(np.int64),
                "v": rng.integers(-50, 50, 200).astype(np.int64)}
        sql = """
          select k, o, v,
                 ntile(3) over (partition by k order by o, v) nt,
                 percent_rank() over (partition by k order by o) pr,
                 cume_dist() over (partition by k order by o) cd,
                 min(v) over (partition by k order by o, v
                              rows between 3 preceding and 2 following)
                   mn,
                 max(v) over (partition by k order by o
                              range between 5 preceding and 5 following)
                   mx,
                 collect_list(v) over (partition by k order by o, v
                              rows between 2 preceding and current row)
                   cl
          from t order by k, o, v"""

        def run(s):
            s.create_dataframe(data).create_or_replace_temp_view("t")
            return s.sql(sql).collect()
        cpu = with_cpu_session(run)
        tpu = with_tpu_session(run)
        assert len(cpu) == len(tpu) == 200
        for a, b in zip(tpu, cpu):
            for x, y in zip(a, b):
                if isinstance(x, float):
                    assert abs(x - y) < 1e-9
                else:
                    assert x == y

    def test_mixed_key_window_collapse_warns(self):
        """A plan that coalesces to one partition for mixed-key windows
        must say so (round-3 Weak #9), not silently go single-stream."""
        import numpy as np
        from harness import with_tpu_session
        rng = np.random.default_rng(3)

        def run(s):
            df = s.create_dataframe(
                {"a": rng.integers(0, 5, 100).astype(np.int64),
                 "b": rng.integers(0, 5, 100).astype(np.int64),
                 "v": rng.integers(0, 50, 100).astype(np.int64)},
                num_partitions=4)
            df.create_or_replace_temp_view("t")
            # ONE window node with MIXED partition keys -> the planner
            # coalesces to a single stream and must warn
            s.sql("""
              select a, b, v,
                     row_number() over (partition by a order by v) r1,
                     row_number() over (partition by b order by v) r2
              from t""").collect()
            return s._last_planner.parallelism_warnings
        warnings = with_tpu_session(run)
        assert any("single-stream" in w for w in warnings)

    def test_rank_descending_with_nulls(self):
        """DESC single-key rank through BOTH engines (the CPU oracle
        previously ranked by ascending value, inverting DESC ranks)."""
        import numpy as np
        from harness import with_cpu_session, with_tpu_session
        k = [0, 0, 0, 1, 1, 1, 1]
        v = [3, 1, 1, None, 5, 5, 2]

        def run(s):
            df = s.create_dataframe({"k": np.array(k, dtype=np.int64),
                                     "v": v})
            df.create_or_replace_temp_view("t")
            return sorted(s.sql(
                "select k, v, rank() over (partition by k "
                "order by v desc) r, dense_rank() over (partition by k "
                "order by v desc) d from t").collect(),
                key=lambda r: (r[0], r[2]))
        cpu = with_cpu_session(run)
        tpu = with_tpu_session(run)
        assert cpu == tpu
        # spot-check Spark semantics: [3,1,1] desc -> ranks [1,2,2]
        g0 = [(r[1], r[2], r[3]) for r in cpu if r[0] == 0]
        assert g0 == [(3, 1, 1), (1, 2, 2), (1, 2, 2)]


# ---------------------------------------------------------------------------
# PR 34: the operator as named programs (exec/tpu_window.py,
# kernels/window.py).  Every function family against a plain python
# window, the programs one spec launches, and what keys a program.
# ---------------------------------------------------------------------------

def _window_rows(n, one_partition=False, seed=11):
    """Seeded rows with NULL partition keys, ties and NULLs in the order
    key, NULL values, a key with a single row; ``one_partition``: one
    key for every row, so the partition fills the batch."""
    import numpy as np
    rng = np.random.default_rng(seed + n)
    k = [None if x == 3 else int(x) for x in rng.integers(0, 4, n)]
    if one_partition:
        k = [7] * n
    else:
        k[0] = 99                                   # a partition of one row
    o = [None if x == 0 else int(x) for x in rng.integers(0, 9, n)]
    v = [None if x < -30 else int(x) for x in rng.integers(-50, 50, n)]
    return {"id": list(range(n)), "k": k, "o": o, "v": v}


def _plain_window(data, kind, frame=None, by_id=True, arg=None):
    """{id: value} by loops over python lists: partitions by ``k`` (NULL
    is a key), order by ``o`` ascending NULLs first (then ``id`` when
    ``by_id``), ``frame`` in ROWS offsets (None = unbounded)."""
    parts = {}
    for i, k in enumerate(data["k"]):
        parts.setdefault(k, []).append(i)

    def okey(i):
        o = data["o"][i]
        return ((0, 0) if o is None else (1, o)) + ((i,) if by_id else ())
    out = {}
    for rows in parts.values():
        rows = sorted(rows, key=lambda i: (okey(i), i))
        n = len(rows)
        for at, i in enumerate(rows):
            less = sum(1 for j in rows if okey(j) < okey(i))
            upto = sum(1 for j in rows if okey(j) <= okey(i))
            if kind == "row_number":
                out[i] = at + 1
            elif kind == "rank":
                out[i] = less + 1
            elif kind == "dense_rank":
                out[i] = len({okey(j) for j in rows if okey(j) < okey(i)}) + 1
            elif kind == "percent_rank":
                out[i] = less / (n - 1) if n > 1 else 0.0
            elif kind == "cume_dist":
                out[i] = upto / n
            elif kind == "ntile":
                base, rem = divmod(n, arg)
                cut = rem * (base + 1)
                out[i] = (at // (base + 1) if at < cut
                          else rem + (at - cut) // max(base, 1)) + 1
            elif kind in ("lead", "lag"):
                to = at + (arg if kind == "lead" else -arg)
                out[i] = data["v"][rows[to]] if 0 <= to < n else None
            else:
                lo, hi = frame
                a = 0 if lo is None else max(at + lo, 0)
                b = n - 1 if hi is None else min(at + hi, n - 1)
                vals = [data["v"][j] for j in rows[a:b + 1]
                        if data["v"][j] is not None]
                if kind == "count":
                    out[i] = len(vals)
                elif kind == "collect_list":
                    out[i] = vals
                elif not vals:
                    out[i] = None
                elif kind == "sum":
                    out[i] = sum(vals)
                elif kind == "avg":
                    out[i] = sum(vals) / len(vals)
                else:
                    out[i] = min(vals) if kind == "min" else max(vals)
    return out


_WHOLE, _RUNNING = (None, None), (None, 0)
FAMILIES = [
    # (name, function, order by id too, frame, plain kind, its argument)
    ("row_number", lambda: F.row_number(), True, None, "row_number", None),
    ("rank", lambda: F.rank(), False, None, "rank", None),
    ("dense_rank", lambda: F.dense_rank(), False, None, "dense_rank", None),
    ("percent_rank", lambda: F.percent_rank(), False, None,
     "percent_rank", None),
    ("cume_dist", lambda: F.cume_dist(), False, None, "cume_dist", None),
    ("ntile", lambda: F.ntile(3), True, None, "ntile", 3),
    ("lead", lambda: F.lead("v", 2), True, None, "lead", 2),
    ("lag", lambda: F.lag("v"), True, None, "lag", 1),
    ("sum_partition", lambda: F.sum("v"), True, _WHOLE, "sum", None),
    ("count_partition", lambda: F.count("v"), True, _WHOLE, "count", None),
    ("avg_partition", lambda: F.avg("v"), True, _WHOLE, "avg", None),
    ("min_partition", lambda: F.min("v"), True, _WHOLE, "min", None),
    ("max_partition", lambda: F.max("v"), True, _WHOLE, "max", None),
    ("sum_running", lambda: F.sum("v"), True, _RUNNING, "sum", None),
    ("max_running", lambda: F.max("v"), True, _RUNNING, "max", None),
    ("avg_rows", lambda: F.avg("v"), True, (-2, 1), "avg", None),
    ("min_rows", lambda: F.min("v"), True, (-1, 1), "min", None),
    ("max_to_end", lambda: F.max("v"), True, (-1, None), "max", None),
    ("count_rows", lambda: F.count("v"), True, (0, 3), "count", None),
    ("collect_list_rows", lambda: F.collect_list("v"), True, (-1, 0),
     "collect_list", None),
]


@pytest.mark.parametrize("rows,one_partition", [
    (50, False),       # capacity 64: four keys, NULL among them, one single
    (200, False),      # capacity 256
    (64, True),        # one partition that fills the capacity
])
@pytest.mark.parametrize("family", FAMILIES, ids=[f[0] for f in FAMILIES])
def test_family_against_a_plain_window(family, rows, one_partition):
    from harness import with_tpu_session
    _, func, by_id, frame, kind, arg = family
    data = _window_rows(rows, one_partition)
    want = _plain_window(data, kind, frame, by_id, arg)

    def run(s):
        df = s.create_dataframe(dict(data))
        return df.with_window(
            "w", func(), partition_by=["k"],
            order_by=["o", "id"] if by_id else ["o"],
            frame=("rows",) + (frame or (None, 0))).collect()
    got = {r[0]: r[-1] for r in with_tpu_session(run)}
    assert set(got) == set(want)
    for i in want:
        if isinstance(want[i], float):
            assert got[i] == pytest.approx(want[i], rel=1e-12), (i, kind)
        else:
            assert got[i] == want[i], (i, kind)


def _two_functions_one_spec(s, n):
    s.create_dataframe(_window_rows(n)).create_or_replace_temp_view("t")
    return s.sql(
        "select id, rank() over (partition by k order by o) r, "
        "sum(v) over (partition by k order by o "
        "rows between unbounded preceding and unbounded following) t "
        "from t")


def test_two_functions_over_one_spec_share_one_sort(monkeypatch):
    """One ``window_plan`` for the spec, one program a function, and
    neither ``jnp.cumsum`` nor ``jnp.argsort`` anywhere on the way."""
    import jax.numpy as jnp
    from harness import with_tpu_session
    from spark_rapids_tpu.exec.tpu_window import TpuWindow
    from spark_rapids_tpu.obs import compile_watch, trace

    def banned(*a, **kw):
        raise AssertionError("jnp.cumsum / jnp.argsort on the window path")
    monkeypatch.setattr(jnp, "cumsum", banned)
    monkeypatch.setattr(jnp, "argsort", banned)
    monkeypatch.setattr(TpuWindow, "_PROGRAMS", {})
    data = _window_rows(50)

    def run(s):
        trace.reset()
        before = dict(compile_watch.jit_build_sites())
        rows = _two_functions_one_spec(s, 50).collect()
        built = {k: v - before.get(k, 0)
                 for k, v in compile_watch.jit_build_sites().items()
                 if k.startswith("window_") and v != before.get(k, 0)}
        (counts,) = trace.coarse_counts().values()
        return rows, built, counts
    rows, built, counts = with_tpu_session(run)
    assert built == {"window_plan": 1, "window_rank": 1,
                     "window_part_agg": 1}
    assert counts["window.specs"] == 1 and counts["window.funcs"] == 2
    assert counts["window.batches"] == 1 and counts["window.rows"] == 64
    rank = _plain_window(data, "rank", by_id=False)
    total = _plain_window(data, "sum", (None, None))
    assert {r[0]: r[1:] for r in rows} == \
        {i: (rank[i], total[i]) for i in rank}


def test_programs_are_keyed_by_capacity_not_by_row_count():
    """Two row counts under one capacity: the second query builds no
    program and compiles nothing."""
    import jax.monitoring as mon
    from harness import with_tpu_session
    from spark_rapids_tpu.obs import compile_watch, trace
    compiles = []
    mon.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    def run(s):
        out = []
        for n in (50, 41, 64):
            trace.reset()
            builds, seen = compile_watch.jit_builds(), len(compiles)
            rows = _two_functions_one_spec(s, n).collect()
            (counts,) = trace.coarse_counts().values()
            out.append((len(rows), counts["window.rows"],
                        compile_watch.jit_builds() - builds,
                        len(compiles) - seen))
        return out
    first, fewer, full = with_tpu_session(run)
    assert first[:2] == (50, 64)
    assert fewer == (41, 64, 0, 0)
    assert full == (64, 64, 0, 0)


@pytest.mark.parametrize("words,rows", [(2, 64), (8, 256), (13, 1000),
                                        (16, 4096)])
def test_the_rolled_sort_chain_is_the_unrolled_one(words, rows):
    """``kernels/sort.py``: inside a traced program a chain of
    ``ROLL_FROM`` passes or more is one loop over the stacked words;
    the permutation is the unrolled chain's and numpy's lexsort's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.kernels import sort as ksort
    rng = np.random.default_rng(words * rows)
    ws = [rng.integers(0, 3 if i % 2 else 1 << 62, rows).astype(np.uint64)
          for i in range(words)]
    # words every row shares (a rolled-up key's are zero throughout)
    ws[-1] = np.zeros(rows, np.uint64)
    ws[words // 2] = np.full(rows, 7, np.uint64)
    want = np.lexsort(ws[::-1])
    dev = [jnp.asarray(w) for w in ws]
    eager = ksort.sort_permutation(dev)              # launches, no loop
    rolled = jax.jit(lambda *w: ksort.sort_permutation(list(w), 2))(*dev)
    traced = jax.jit(lambda *w: ksort.sort_permutation(list(w)))(*dev)
    for got in (eager, rolled, traced):
        np.testing.assert_array_equal(np.asarray(got), want)
    text = jax.jit(lambda *w: ksort.sort_permutation(list(w))) \
        .lower(*dev).as_text()
    # a loop from ROLL_FROM words on, the chain as it always was below
    assert ("stablehlo.while" in text) == (words >= ksort.ROLL_FROM)
