"""An outer join's ON conjunct that reads one side alone
(``plan/logical_opt._push_on_conjuncts``): under LEFT OUTER a right-only
conjunct becomes a Filter on the right child, under RIGHT OUTER a
left-only one a Filter on the left; the preserved side is never
filtered, and a FULL OUTER join keeps its condition whole.  Rows equal
the pyarrow engine's and a plain Python evaluation of the ON clause
(the two engines share the planner, so only the second can see a wrong
rewrite)."""
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


def _oracle(how, cond):
    """The join in plain Python: pairs with equal non-NULL keys whose
    ON predicate is true (NULL is not), then the preserved sides' rows
    that matched nothing, NULL-extended."""
    lrows = list(zip(T["k"], T["a"]))
    rrows = list(zip(U["k2"], U["b"]))
    out, lhit, rhit = [], set(), set()
    for i, (k, a) in enumerate(lrows):
        for j, (k2, b) in enumerate(rrows):
            if k is not None and k == k2 and cond(a, b):
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


CASES = {
    # (join, the conjunct's side): (its SQL, its Python, the child it
    # filters after the rewrite: 0 left, 1 right, None neither)
    ("left", "right"): ("b > 2", lambda a, b: _gt2(b), 1),
    ("left", "left"): ("a > 2", lambda a, b: _gt2(a), None),
    ("right", "left"): ("a > 2", lambda a, b: _gt2(a), 0),
    ("right", "right"): ("b > 2", lambda a, b: _gt2(b), None),
    ("full", "right"): ("b > 2", lambda a, b: _gt2(b), None),
    ("full", "left"): ("a > 2", lambda a, b: _gt2(a), None),
}


def _session(enabled):
    s = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": enabled}))
    s.create_dataframe(T, num_partitions=2).create_or_replace_temp_view("t")
    s.create_dataframe(U, num_partitions=2).create_or_replace_temp_view("u")
    return s


def _key(r):
    return tuple((v is None, v if v is not None else 0) for v in r)


@pytest.mark.parametrize("how,side", sorted(CASES))
def test_one_sided_on_conjunct(how, side):
    text, fn, filtered = CASES[(how, side)]
    sql = (f"select k, a, k2, b from t {how} outer join u "
           f"on k = k2 and {text}")
    want = sorted(_oracle(how, fn), key=_key)
    cpu = sorted(_session(False).sql(sql).collect(), key=_key)
    s = _session(True)
    trace.reset()
    df = s.sql(sql)          # parsing runs the logical rewrites
    got = sorted(df.collect(), key=_key)
    assert got == want and cpu == want
    counts = {k: v for t in trace.coarse_counts().values()
              for k, v in t.items()}
    assert counts.get("plan.join.on_pushdown", 0) == \
        (0 if filtered is None else 1)
    opt = logical_opt.optimize(df._plan)
    join, = [n for n in _walk(opt) if isinstance(n, L.Join)]
    under = [isinstance(c, L.Filter) for c in join.children]
    assert under == [filtered == 0, filtered == 1]
    assert (join.condition is None) == (filtered is not None)


def _walk(p):
    yield p
    for c in p.children:
        yield from _walk(c)
