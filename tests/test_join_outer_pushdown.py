"""Two planner rules of ``plan/logical_opt.py``:

- a WHERE conjunct over a LEFT (RIGHT) OUTER join that reads only the
  left (right) child filters that child first
  (``_rewrite_filter_outer``); a conjunct that reads the null-supplying
  side (``IS NULL`` on it too), or both sides, stays above, and a FULL
  join takes none;
- a two-sided conjunct that is not an equi-key becomes an inner equi
  join's condition (``_residual_condition``), while a join with no key
  keeps it as a Filter above.

Rows come from the device path (``sql.test.enabled``) and are compared
with plain Python or pyarrow, not with the CPU engine: both engines share
the planner.  Last, the optimized plans of the fourteen texts the
benchmark's five older cells run are the same with and without the two
rules."""
import importlib
import os
import sys

import pyarrow as pa
import pyarrow.compute as pc
import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import trace
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan import logical_opt

T = {"k": [1, 2, 2, 3, 4, None, 6, 7],
     "a": [5, 1, 7, None, 3, 9, 2, 8]}
U = {"k2": [2, 2, 3, 4, 4, 5, None, 7],
     "b": [1, 6, 4, None, 8, 2, 5, 3]}
V = {"k3": [5, 7, 7, 1, 2], "c": [10, 20, 30, 40, 50]}


def _session():
    s = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": True,
                            "spark.rapids.tpu.sql.test.enabled": True}))
    for name, data in (("t", T), ("u", U), ("v", V)):
        s.create_dataframe(data, num_partitions=2) \
            .create_or_replace_temp_view(name)
    return s


def _outer(how):
    """The join of t and u on k = k2 in plain Python, NULL-extended."""
    lrows = list(zip(T["k"], T["a"]))
    rrows = list(zip(U["k2"], U["b"]))
    out, lhit, rhit = [], set(), set()
    for i, (k, a) in enumerate(lrows):
        for j, (k2, b) in enumerate(rrows):
            if k is not None and k == k2:
                out.append((k, a, k2, b))
                lhit.add(i)
                rhit.add(j)
    if how in ("left", "full"):
        out += [(k, a, None, None) for i, (k, a) in enumerate(lrows)
                if i not in lhit]
    if how in ("right", "full"):
        out += [(None, None, k2, b) for j, (k2, b) in enumerate(rrows)
                if j not in rhit]
    return out


def _gt2(v):
    return v is not None and v > 2


def _lt(x, y):
    return x is not None and y is not None and x < y


CASES = {
    # (join, conjunct): (its SQL, its Python over (k, a, k2, b), the
    # child it filters after the rewrite: 0 left, 1 right, None neither)
    ("left", "left"): ("a > 2", lambda r: _gt2(r[1]), 0),
    ("left", "right"): ("b > 2", lambda r: _gt2(r[3]), None),
    ("left", "right_is_null"): ("b is null", lambda r: r[3] is None, None),
    ("left", "both"): ("a < b", lambda r: _lt(r[1], r[3]), None),
    ("right", "right"): ("b > 2", lambda r: _gt2(r[3]), 1),
    ("right", "left"): ("a > 2", lambda r: _gt2(r[1]), None),
    ("right", "left_is_null"): ("k is null", lambda r: r[0] is None, None),
    ("full", "left"): ("a > 2", lambda r: _gt2(r[1]), None),
    ("full", "right"): ("b > 2", lambda r: _gt2(r[3]), None),
}


def _key(r):
    return tuple((v is None, v if v is not None else 0) for v in r)


def _optimized(s, sql):
    return logical_opt.optimize(s.sql(sql)._plan)


def _find(plan, kind):
    if isinstance(plan, kind):
        yield plan
    for c in plan.children:
        yield from _find(c, kind)


def _filter_under(join, side):
    """The conditions of the Filters directly over ``join``'s child."""
    child = join.children[side]
    return [repr(child.condition)] if isinstance(child, L.Filter) else []


@pytest.mark.parametrize("how,which", sorted(CASES))
def test_where_over_an_outer_join(how, which):
    text, fn, filtered = CASES[(how, which)]
    sql = f"select k, a, k2, b from t {how} outer join u on k = k2 " \
          f"where {text}"
    s = _session()
    plan = _optimized(s, sql)
    (j,) = _find(plan, L.Join)
    above = [f for f in _find(plan, L.Filter) if f.children[0] is j]
    for side in (0, 1):
        assert bool(_filter_under(j, side)) == (side == filtered)
    assert bool(above) == (filtered is None)
    trace.reset()
    got = s.sql(sql).collect()
    (counts,) = [c for q, c in trace.coarse_counts().items()
                 if q is not None]
    assert counts.get("plan.pushdown.outer", 0) == \
        (0 if filtered is None else 1)
    want = [r for r in _outer(how) if fn(r)]
    assert sorted(got, key=_key) == sorted(want, key=_key)


def test_a_where_passes_two_outer_joins_to_the_inner_join():
    """TPC-DS q72's shape: the WHERE's conjuncts on the inner chain reach
    it through both LEFT OUTER joins and the inner join's two-sided
    inequality becomes its condition; ``v.c is null`` passes the second
    join (it reads that join's preserved side) and stays over the first,
    whose NULLs it tests."""
    sql = ("select k, a, k2, b, v.c from t join u on k = k2 "
           "left outer join v on v.k3 = k "
           "left outer join v v2 on v2.k3 = k2 "
           "where a < b and a > 1 and v.c is null")
    s = _session()
    plan = _optimized(s, sql)
    joins = list(_find(plan, L.Join))
    assert [j.join_type for j in joins] == ["left", "left", "inner"]
    inner = joins[2]
    assert type(inner.condition).__name__ == "LessThan"
    assert isinstance(inner.children[0], L.Filter)      # a > 1 on t
    (on_first,) = [f for f in _find(plan, L.Filter)
                   if f.children[0] is joins[1]]
    assert joins[0].children[0] is on_first
    assert type(on_first.condition).__name__ == "IsNull"
    want = []
    for k, a in zip(T["k"], T["a"]):
        for k2, b in zip(U["k2"], U["b"]):
            if k is None or k != k2 or not _lt(a, b) or a <= 1:
                continue
            if k in V["k3"]:                  # v.c is not NULL
                continue
            want += [(k, a, k2, b, None)] * max(1, V["k3"].count(k2))
    assert want
    got = s.sql(sql).collect()
    assert sorted(got, key=_key) == sorted(want, key=_key)


def _pyarrow_join(cond=None):
    t = pa.table(T).filter(pc.is_valid(pa.table(T)["k"]))
    j = t.join(pa.table(U), keys="k", right_keys="k2", join_type="inner")
    j = j.append_column("k2", j["k"])
    if cond is not None:
        j = j.filter(pc.fill_null(cond(j["a"], j["b"]), False))
    return sorted(zip(*(j[c].to_pylist() for c in ("k", "a", "k2", "b"))),
                  key=_key)


@pytest.mark.parametrize("op,cond", [
    ("<", pc.less), ("<>", pc.not_equal), (">=", pc.greater_equal)],
    ids=["lt", "ne", "ge"])
def test_a_two_sided_inequality_is_the_inner_joins_condition(op, cond):
    sql = f"select k, a, k2, b from t, u where k = k2 and a {op} b"
    s = _session()
    plan = _optimized(s, sql)
    (j,) = _find(plan, L.Join)
    assert j.join_type == "inner" and j.left_keys and \
        j.condition is not None
    assert not list(_find(plan, L.Filter))
    trace.reset()
    got = sorted(s.sql(sql).collect(), key=_key)
    assert got == _pyarrow_join(cond)
    (counts,) = [c for q, c in trace.coarse_counts().items()
                 if q is not None]
    assert counts["join.residual.pairs"] == len(_pyarrow_join())


def test_a_join_with_no_key_keeps_its_filter():
    s = _session()
    plan = _optimized(s, "select k, a, k2, b from t, u where a < b")
    (j,) = _find(plan, L.Join)
    assert not j.left_keys and j.condition is None
    (f,) = _find(plan, L.Filter)
    assert f.children[0] is j


def test_an_existing_on_condition_is_kept_beside_the_new_conjunct():
    s = _session()
    plan = _optimized(s, "select k, a, k2, b from t join u "
                         "on k = k2 and a <> 5 + b where a < b + 3")
    (j,) = _find(plan, L.Join)
    assert repr(j.condition) == \
        "(NOT (col(a) = (lit(5) + col(b))) AND (col(a) < (col(b) + lit(3))))"
    got = s.sql("select k, a, k2, b from t join u on k = k2 and "
                "a <> 5 + b where a < b + 3").collect()
    want = _pyarrow_join(lambda a, b: pc.and_(
        pc.not_equal(a, pc.add(b, 5)), pc.less(a, pc.add(b, 3))))
    assert sorted(got, key=_key) == want and want


# ---------------------------------------------------------------------------
# the fourteen texts of the five older cells: plans unchanged
# ---------------------------------------------------------------------------

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
OLD_CELLS = {"tpcds_sf1_store.power": 0.01, "tpch_sf5_q1q6.power": 0.001,
             "tpch_q3q18.power": 0.002, "tpcds_sf1_olap.power": 0.01,
             "tpch_q13q21.power": 0.002}


def _plan_text(p, depth=0) -> str:
    """Every node's type, and what a Join or Filter decides with."""
    line = type(p).__name__
    if isinstance(p, L.Join):
        same = logical_opt._same_as
        cond = None if p.condition is None else same(p.condition)
        line += (f"[{p.join_type}] {[same(k) for k in p.left_keys]} = "
                 f"{[same(k) for k in p.right_keys]} if {cond}")
    elif isinstance(p, L.Filter):
        line += f" {logical_opt._same_as(p.condition)}"
    elif isinstance(p, L.Scan):
        line += f" {sorted(p.schema.names)}"
    return "\n".join(["  " * depth + line] +
                     [_plan_text(c, depth + 1) for c in p.children])


@pytest.fixture(scope="module")
def old_texts(tmp_path_factory):
    sys.path.insert(0, CHIPBENCH)
    try:
        import run as harness
        out = []
        for cell_name, scale in OLD_CELLS.items():
            cell = harness.load_cell(cell_name)
            config = cell["config"]
            d = str(tmp_path_factory.mktemp(cell["config_name"]))
            gen = importlib.import_module(f"datagen.{config['schema']}")
            gen.generate(d, scale, 7, sorted(config["tables"]))
            out.append((cell, d))
        yield out
    finally:
        sys.path.remove(CHIPBENCH)
        for name in [m for m in sys.modules
                     if m in ("run", "reference") or m == "datagen"
                     or m.startswith("datagen.")]:
            sys.modules.pop(name, None)


def test_the_older_cells_plans_are_unchanged(old_texts, monkeypatch):
    seen = []
    for cell, d in old_texts:
        s = TpuSession(TpuConf(cell["config"]["engine_conf"]))
        for t in cell["config"]["tables"]:
            s.read.parquet(os.path.join(d, f"{t}.parquet")) \
                .create_or_replace_temp_view(t)
        for q in cell["config"]["queries"]:
            with_rules = _plan_text(s.sql(cell["texts"][q])._plan)
            with monkeypatch.context() as m:
                _rules_off(m)
                without = _plan_text(s.sql(cell["texts"][q])._plan)
            assert with_rules == without, (cell["name"], q)
            seen.append(q)
    assert len(seen) == 14


def _rules_off(m):
    """The planner as it was before the two rules."""
    m.setattr(logical_opt, "_rewrite_filter_outer", lambda f: f)
    m.setattr(logical_opt, "_residual_condition",
              lambda cond, keys, both: (cond, both))


def test_the_rules_do_move_q72s_plan(monkeypatch):
    """The comparison above can see a change: the same two rules off
    move the q72 shape's plan (the inequality and ``a > 1`` back above
    the outer join)."""
    s = _session()
    sql = ("select k, a, k2, b, c from t join u on k = k2 "
           "left outer join v on k3 = k where a < b and a > 1")
    with_rules = _plan_text(s.sql(sql)._plan)
    _rules_off(monkeypatch)
    without = s.sql(sql)._plan
    assert _plan_text(without) != with_rules
    assert isinstance(without.children[0], L.Filter)
