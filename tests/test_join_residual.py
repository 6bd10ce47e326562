"""Hash joins with a residual (non-equi) condition
(``exec/tpu_join._residual_batches``): every join type against the
pyarrow engine on wide inputs; an inner join whose condition is one
integer or date comparison of a build value with a stream value is
decided by bounds (``join.residual.range``), every other join and
condition pair by pair; and the semi / anti path's shape: one
``join_residual_core`` launch a stream batch that gathers the
condition's two columns and nothing else (``lanes.`` = 2 x the pairs'
capacity), no pair column materialized, no count pulled to the host
inside the join, the candidate and surviving pairs counted."""
import datetime

import numpy as np
import pytest

from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.obs import trace
from tests.harness import canon_rows, with_cpu_session

N, M = 300, 200


def _frames(s):
    rng = np.random.default_rng(7)
    left = {"k": [int(v) for v in rng.integers(0, 60, N)],
            "x": [int(v) for v in rng.integers(0, 5, N)],
            "d": [float(v) for v in rng.normal(size=N)],
            "s": [f"s{v}" for v in rng.integers(0, 4, N)],
            "p1": list(range(N)), "p2": [f"pad-{i:04d}" for i in range(N)],
            "p3": [float(i) / 7 for i in range(N)]}
    right = {"k2": [int(v) for v in rng.integers(0, 60, M)],
             "y": [int(v) for v in rng.integers(0, 5, M)],
             "e": [float(v) for v in rng.normal(size=M)],
             "t": [f"s{v}" for v in rng.integers(0, 4, M)],
             "q1": [f"q-{i}" for i in range(M)]}
    left["x"][3] = None
    right["y"][5] = None
    day = datetime.date(1999, 2, 1)
    left["dt"] = [day + datetime.timedelta(days=int(v))
                  for v in rng.integers(0, 12, N)]
    right["du"] = [day + datetime.timedelta(days=int(v))
                   for v in rng.integers(0, 12, M)]
    left["dt"][8] = None
    right["du"][2] = None
    return (s.create_dataframe(left, num_partitions=1),
            s.create_dataframe(right, num_partitions=1))


#: decided by bounds in an inner join: one comparison, an integer or
#: date on each side, with the build's value on either side of it (x and
#: y hold five values each, so a key's run repeats them, and a NULL each)
RANGE = {"x_lt_y": "x < y", "y_lt_x": "y < x", "x_le_y": "x <= y",
         "y_le_x": "y <= x", "x_gt_y": "x > y", "y_gt_x": "y > x",
         "x_ge_y": "x >= y", "y_ge_x": "y >= x", "expr_lt": "x + 1 < y",
         "date_gt": "du > dt + interval 3 days"}
CONDITIONS = {"int_ne": "y <> x", "double_lt": "e > d",
              "string_ne": "t <> s", "conj": "y > x and e > d", **RANGE}


def _query(how, cond):
    """The join through SQL: an EXISTS (semi) or NOT EXISTS (anti) whose
    correlated subquery holds the residual, or an ON clause."""
    if how in ("semi", "anti"):
        sql = (f"select * from l where {'not ' if how == 'anti' else ''}"
               f"exists (select * from r where k2 = k and "
               f"{CONDITIONS[cond]})")
    else:
        sql = (f"select * from l {how} join r on k = k2 and "
               f"{CONDITIONS[cond]}")

    def fn(s):
        left, right = _frames(s)
        left.create_or_replace_temp_view("l")
        right.create_or_replace_temp_view("r")
        return s.sql(sql)
    return fn


def _device_session():
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.config import TpuConf
    return TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": True,
                               "spark.rapids.tpu.sql.test.enabled": True}))


@pytest.mark.parametrize("cond", sorted(CONDITIONS))
@pytest.mark.parametrize("how", ["semi", "anti", "inner", "left outer"])
def test_residual_join_matches_the_cpu_engine(how, cond):
    """On the device (test mode: no CPU operator) and on the pyarrow
    engine, whose CpuJoin decides the pairs by its own code; by bounds
    exactly where the join is inner and the condition one comparison."""
    from spark_rapids_tpu.analysis import residency
    cpu = with_cpu_session(lambda s: _query(how, cond)(s).collect())
    df = _query(how, cond)(_device_session())
    trace.reset()
    with residency.declared_transfer(site="oracle_compare"):
        got = df.collect()
    assert canon_rows(got) == canon_rows(cpu) and len(got) > 0
    ranged = sum(t.get("join.residual.range", 0)
                 for t in trace.coarse_counts().values())
    assert (ranged > 0) == (how == "inner" and cond in RANGE)


def _pairs():
    rng = np.random.default_rng(7)
    k = rng.integers(0, 60, N)
    rng.integers(0, 5, N), rng.normal(size=N), rng.integers(0, 4, N)
    k2 = rng.integers(0, 60, M)
    return int(sum((k2 == v).sum() for v in k))


@pytest.mark.parametrize("how", ["semi", "anti"])
def test_semi_and_anti_gather_the_condition_alone(how):
    df = _query(how, "int_ne")(_device_session())
    trace.reset()
    rows = df.collect()
    assert len(rows) > 0
    counts = {k: v for t in trace.coarse_counts().values()
              for k, v in t.items()}
    pairs = _pairs()
    assert counts["join.residual.pairs"] == pairs
    assert 0 < counts["join.residual.kept"] < pairs
    launches = {k: v for k, v in counts.items()
                if k.startswith("launch.join_residual_core@")}
    lanes = {k: v for k, v in counts.items()
             if k.startswith("lanes.join_residual_core@")}
    assert sum(launches.values()) == 1
    # x and y, out_cap indices each: the stream's other six columns and
    # the build's other three are never gathered for the pairs
    assert sum(lanes.values()) == 2 * bucket_capacity(pairs)
    assert not any(k.startswith("pull.join_verify") for k in counts)


def test_a_string_condition_takes_the_eager_steps():
    """Strings size their buffers on the host: the same steps run
    eagerly over the condition's columns, and no program is built."""
    df = _query("semi", "string_ne")(_device_session())
    trace.reset()
    df.collect()
    counts = {k: v for t in trace.coarse_counts().values()
              for k, v in t.items()}
    assert counts["join.residual.pairs"] == _pairs()
    assert not any(k.startswith("launch.join_residual_core@")
                   for k in counts)
