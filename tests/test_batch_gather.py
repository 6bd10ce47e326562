"""One row gather of packed 32-bit lanes for a batch's columns
(``columnar/gather.py``, ``kernels/gather.py``): bit for bit what two
takes a column gave, for every fixed width, every validity, a string
view's map (its bytes stay lazy), out-of-range indices and ``live``
masks; nested columns keep their own gathers; one ``batch_gather``
launch a call, its columns counted; the slice's results; the aggregate
cores' row gather, whose program moved module and did not change."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import gather as cgather
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.binary64 import Binary64Column
from spark_rapids_tpu.columnar.column import (Column, GatheredStringColumn,
                                              ListColumn, StringColumn)
from spark_rapids_tpu.kernels import gather as gather_k
from spark_rapids_tpu.obs import trace

CAP = 64


@pytest.fixture(autouse=True)
def _clean():
    trace.reset()
    yield
    trace.reset()


def _values(dtype, rng, n=CAP):
    """Every value a column of ``dtype`` can hold, the edges first."""
    if dtype in (T.FLOAT32, T.FLOAT64):
        vals = rng.standard_normal(n).astype(dtype.np_dtype)
        tiny = np.finfo(dtype.np_dtype).smallest_subnormal
        vals[:8] = [0.0, -0.0, np.inf, -np.inf, np.nan, tiny, -tiny * 3,
                    np.finfo(dtype.np_dtype).max]
        return vals
    if dtype == T.BOOL:
        return rng.random(n) < 0.5
    info = np.iinfo(dtype.np_dtype)
    vals = rng.integers(info.min, info.max, n, dtype=dtype.np_dtype,
                        endpoint=True)
    vals[:3] = [info.min, info.max, 0]
    return vals


def _indices(rng, out_cap, in_cap=CAP):
    """A shuffle with repeats, and indices past both ends."""
    idx = rng.integers(0, in_cap, out_cap)
    idx[:3] = [-5, in_cap, in_cap + 1000]
    return idx.astype(np.int32)


def _reference(col, idx, live):
    """What two takes of a column (data, then validity) give."""
    take = np.clip(idx, 0, col.capacity - 1)
    valid = np.asarray(col.validity)[take]
    if live is not None:
        valid = valid & np.asarray(live)
    return np.asarray(col.data)[take], valid


FIXED = [T.INT8, T.INT16, T.INT32, T.INT64, T.DATE, T.FLOAT32, T.FLOAT64,
         T.BOOL]


@pytest.mark.parametrize("dtype", FIXED, ids=lambda d: d.name)
@pytest.mark.parametrize("out_cap", [CAP // 4, CAP, 4 * CAP])
@pytest.mark.parametrize("with_live", [False, True])
def test_every_width_moves_bit_for_bit(dtype, out_cap, with_live):
    rng = np.random.default_rng(out_cap + with_live)
    valid = rng.random(CAP) < 0.3                   # null-heavy
    col = Column(dtype, jnp.asarray(_values(dtype, rng)),
                 jnp.asarray(valid))
    idx = _indices(rng, out_cap)
    live = jnp.asarray(rng.random(out_cap) < 0.7) if with_live else None
    (got,) = cgather.gather_columns([col], jnp.asarray(idx), live)
    data, valid = _reference(col, idx, live)
    assert type(got) is Column and got.dtype == dtype
    assert np.asarray(got.data).dtype == data.dtype
    assert np.asarray(got.data).tobytes() == data.tobytes()
    assert np.asarray(got.validity).tolist() == valid.tolist()


def test_a_batch_of_every_width_is_one_launch_and_counted():
    rng = np.random.default_rng(7)
    cols = [Column(dt, jnp.asarray(_values(dt, rng)),
                   jnp.asarray(rng.random(CAP) < 0.5)) for dt in FIXED]
    cols.append(Binary64Column(jnp.asarray(_values(T.INT64, rng)),
                               jnp.ones(CAP, bool)))
    cols.append(StringColumn.from_pylist(
        [None if i % 5 == 0 else "s%d" % i for i in range(CAP)], CAP))
    idx = _indices(rng, 2 * CAP)
    live = jnp.asarray(rng.random(2 * CAP) < 0.8)
    with trace.span("srt.exec.TpuHashJoin", "exec", True):
        prev = trace.operator("TpuHashJoin")
        got = ColumnarBatch(_schema(cols), cols, CAP).gather(
            jnp.asarray(idx), 2 * CAP, live=live)
        trace.operator(prev)
    (tbl,) = trace.coarse_counts().values()
    assert tbl["launch.batch_gather@TpuHashJoin"] == 1
    assert tbl["lanes.batch_gather@TpuHashJoin"] == 2 * CAP
    assert tbl["gather.batch.columns"] == len(cols)
    assert not any(k.startswith("eager.") for k in tbl)
    for c, g in zip(cols[:-1], got.columns[:-1]):
        data, valid = _reference(c, idx, live)
        assert type(g) is type(c)
        assert np.asarray(g.data).tobytes() == data.tobytes()
        assert np.asarray(g.validity).tolist() == valid.tolist()


def _schema(cols):
    from spark_rapids_tpu.columnar.schema import Field, Schema
    return Schema([Field("c%d" % i, c.dtype) for i, c in enumerate(cols)])


def test_a_wide_batch_moves_in_several_matrices_of_one_launch():
    rng = np.random.default_rng(11)
    cols = [Column(T.INT64, jnp.asarray(_values(T.INT64, rng)),
                   jnp.asarray(rng.random(CAP) < 0.5)) for _ in range(20)]
    idx = jnp.asarray(_indices(rng, CAP))
    runs = cgather._chunks([a for c in cols for a in (c.data, c.validity)])
    lanes = [sum(gather_k.lane_count(a.dtype) for a in run) +
             -(-sum(a.dtype == jnp.bool_ for a in run) // 32)
             for run in runs]
    assert len(runs) == 3 and max(lanes) <= cgather.MAX_LANES
    got = cgather.gather_columns(cols, idx)
    (tbl,) = trace.coarse_counts().values()
    assert tbl["launch.batch_gather@-"] == 1
    for c, g in zip(cols, got):
        data, valid = _reference(c, np.asarray(idx), None)
        assert np.asarray(g.data).tobytes() == data.tobytes()
        assert np.asarray(g.validity).tolist() == valid.tolist()


def _strings():
    return StringColumn.from_pylist(
        [None if i % 7 == 3 else ("w%d" % i) * (i % 4) for i in range(CAP)],
        CAP)


@pytest.mark.parametrize("with_live", [False, True])
def test_a_string_column_becomes_a_view_and_no_byte_moves(with_live):
    rng = np.random.default_rng(3)
    src = _strings()
    idx = _indices(rng, 2 * CAP)
    live = jnp.asarray(rng.random(2 * CAP) < 0.6) if with_live else None
    (got,) = cgather.gather_columns([src], jnp.asarray(idx), live,
                                    unique=False)
    assert type(got) is GatheredStringColumn and got._mat is None
    assert got.src is src
    assert np.asarray(got.idx).tolist() == \
        np.clip(idx, 0, CAP - 1).tolist()
    take = np.clip(idx, 0, CAP - 1)
    valid = np.asarray(src.validity)[take]
    if with_live:
        valid = valid & np.asarray(live)
    assert np.asarray(got.validity).tolist() == valid.tolist()
    texts = np.array(src.to_pylist(CAP), object)
    want = [t if ok else None for t, ok in zip(texts[take], valid)]
    assert got.to_pylist(2 * CAP) == want


def test_a_view_of_a_view_composes_its_map_in_the_matrix():
    rng = np.random.default_rng(4)
    src = _strings()
    first = _indices(rng, CAP)
    view = src.gather(jnp.asarray(first), unique=True)
    trace.reset()
    second = _indices(rng, CAP // 2)
    (got,) = cgather.gather_columns([view], jnp.asarray(second))
    (tbl,) = trace.coarse_counts().values()
    assert tbl["launch.batch_gather@-"] == 1
    assert not any("compose" in k for k in tbl)
    assert got.src is src and got._mat is None and got._unique is False
    want_idx = np.clip(first, 0, CAP - 1)[np.clip(second, 0, CAP - 1)]
    assert np.asarray(got.idx).tolist() == want_idx.tolist()
    texts = np.array(src.to_pylist(CAP), object)
    valid = np.asarray(view.validity)[np.clip(second, 0, CAP - 1)]
    assert got.to_pylist(CAP // 2) == [t if ok else None for t, ok in
                                    zip(texts[want_idx], valid)]


def test_a_materialized_view_gathers_from_its_bytes():
    rng = np.random.default_rng(5)
    view = _strings().gather(jnp.asarray(_indices(rng, CAP)))
    mat = view._materialize()
    idx = _indices(rng, CAP)
    (got,) = cgather.gather_columns([view], jnp.asarray(idx))
    assert got.src is mat
    assert np.asarray(got.idx).tolist() == np.clip(idx, 0, CAP - 1).tolist()
    assert got.to_pylist(CAP) == [
        view.to_pylist(CAP)[i] if ok else None
        for i, ok in zip(np.clip(idx, 0, CAP - 1),
                         np.asarray(view.validity)[np.clip(idx, 0, CAP - 1)])]


def test_nested_columns_keep_their_own_gather():
    lst = ListColumn.from_pylist([[i, i + 1] if i % 3 else None
                                  for i in range(CAP)], capacity=CAP)
    plain = Column(T.INT32, jnp.arange(CAP, dtype=jnp.int32),
                   jnp.ones(CAP, bool))
    idx = jnp.asarray(np.arange(CAP)[::-1].astype(np.int32))
    live = jnp.arange(CAP) < CAP // 2
    got = cgather.gather_columns([lst, plain], idx, live)
    (tbl,) = trace.coarse_counts().values()
    # the plain column, then the list's element child inside the list's
    # own gather (offsets first, then its elements by their indices)
    assert tbl["gather.batch.columns"] == 2
    assert tbl["launch.batch_gather@-"] == 2
    assert type(got[0]) is ListColumn
    want = lst.gather(idx).mask_validity(live)
    assert got[0].to_pylist(CAP) == want.to_pylist(CAP)
    assert got[1].to_pylist(CAP) == \
        [CAP - 1 - i if i < CAP // 2 else None for i in range(CAP)]


def _program(fn, *args):
    """``fn``'s lowered program without its name or source locations."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=False)
    return text.split("\n", 1)[1]


def test_under_a_trace_each_column_gathers_as_before():
    """Inside a program the gathers are the cores' per-column takes: no
    launch, no count, and the program equals the two takes written
    out."""
    col = Column(T.INT64, jnp.arange(CAP), jnp.arange(CAP) % 3 > 0)
    idx = jnp.asarray(np.arange(CAP)[::-1].astype(np.int32))

    def engine(i, live):
        g = col.gather(i, live=live)
        return g.data, g.validity

    def two_takes(i, live):
        valid = jnp.take(col.validity, i, axis=0, mode="clip")
        data = jnp.take(col.data, i, axis=0, mode="clip")
        return data, valid & live
    live = jnp.ones(CAP, bool)
    assert _program(engine, idx, live) == _program(two_takes, idx, live)
    # (the compiles of the two lowerings are counted, nothing else)
    assert not [k for tbl in trace.coarse_counts().values() for k in tbl
                if not k.startswith("compile.")]


@pytest.mark.parametrize("start,length", [(0, 16), (5, 20), (50, 40),
                                          (63, 1), (64, 8)])
def test_a_slice_reads_what_it_read(start, length):
    rng = np.random.default_rng(start)
    cols = [Column(dt, jnp.asarray(_values(dt, rng)),
                   jnp.asarray(rng.random(CAP) < 0.6))
            for dt in (T.INT64, T.FLOAT64, T.INT32, T.BOOL)]
    cols.append(_strings())
    cols.append(ListColumn.from_pylist([[i] for i in range(CAP)],
                                       capacity=CAP))
    rows = 60
    b = ColumnarBatch(_schema(cols), cols, rows).slice(start, length)
    keep = max(0, min(length, rows - start))
    assert b.num_rows == keep
    for c, g in zip(cols, b.columns):
        want = c.to_pylist(CAP)[start:start + keep]
        assert g.to_pylist(keep) == want
        # rows past the slice's live ones are invalid
        assert not np.asarray(g.validity)[keep:].any()


def _gather_rows_once_as_it_was(perm, arrays):
    """The aggregate cores' row gather as the aggregate module wrote it."""
    distinct = list({id(a): a for a in arrays}.values())
    flags = [a for a in distinct if a.dtype == jnp.bool_]
    lanes, joins = [], []
    for a in distinct:
        if a.dtype != jnp.bool_:
            mine, join = gather_k.as_lanes(a)
            joins.append((a, len(lanes), len(mine), join))
            lanes.extend(mine)
    flag_lane0 = len(lanes)
    for at in range(0, len(flags), 32):
        word = jnp.zeros(perm.shape[0], jnp.uint32)
        for bit, v in enumerate(flags[at:at + 32]):
            word = word | (v.astype(jnp.uint32) << jnp.uint32(bit))
        lanes.append(word)
    if not lanes:
        return {}
    got = jnp.take(jnp.stack(lanes, 1), perm, axis=0)
    moved = {id(a): (a, join([got[:, at + i] for i in range(n)]))
             for a, at, n, join in joins}
    for i, v in enumerate(flags):
        bit = (got[:, flag_lane0 + i // 32] >> jnp.uint32(i % 32)) \
            & jnp.uint32(1)
        moved[id(v)] = (v, bit != jnp.uint32(0))
    return moved


def test_the_aggregate_cores_row_gather_traces_the_same_program():
    def prog(fn):
        def body(perm, a, b, c, v1, v2):
            moved = fn(perm, [a, v1, b, c, v2, a])
            return [moved[id(x)][1] for x in (a, b, c, v1, v2)]
        return body
    args = (jnp.arange(CAP, dtype=jnp.int32)[::-1], jnp.arange(CAP),
            jnp.linspace(-1.0, 1.0, CAP), jnp.arange(CAP, dtype=jnp.int16),
            jnp.arange(CAP) % 2 > 0, jnp.arange(CAP) % 3 > 0)
    assert _program(prog(gather_k.gather_rows_once), *args) == \
        _program(prog(_gather_rows_once_as_it_was), *args)


def test_a_grouped_aggregate_reads_the_same_after_the_move():
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.config import TpuConf
    rng = np.random.default_rng(9)
    n = 500
    k = rng.integers(0, 7, n)
    v = rng.standard_normal(n)
    w = rng.integers(-50, 50, n)
    s = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": True}))
    df = s.create_dataframe({"k": k.tolist(), "v": v.tolist(),
                             "w": w.tolist()}, num_partitions=1)
    df.create_or_replace_temp_view("t")
    trace.reset()
    rows = s.sql("select k, sum(v), count(w), min(w), max(v) from t "
                 "group by k order by k").collect()
    counts = {name: c for tbl in trace.coarse_counts().values()
              for name, c in tbl.items()}
    assert counts.get("agg.batches.fused", 0) >= 1
    for key, sv, cw, mw, mv in rows:
        sel = k == key
        assert sv == pytest.approx(v[sel].sum(), rel=1e-12)
        assert (cw, mw, mv) == (sel.sum(), w[sel].min(), v[sel].max())
