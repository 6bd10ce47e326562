"""A residual join past ``spark.rapids.tpu.sql.join.gather.chunkRows``
candidate pairs decides them in chunks of that many, one launch each
(``exec/tpu_join.py`` ``_residual_batches``): a probe row's run of pairs
may be cut at a chunk's edge, its survival is the OR over the chunks,
and an inner join yields each chunk's survivors as a batch of its own.
With the budget set tiny here, inner, left outer, semi and anti joins
equal a plain Python join and the same join in one chunk;
``join.residual.chunks`` counts the launches; a join under the budget
keeps the program and cache key it had before chunking existed; a
chunk's window of stream rows holds every row whose pairs meet it.  An
inner join under ``a < b`` is decided by bounds instead (one launch a
stream batch, ``join.residual.range``), and its survivors come out in
batches of the budget; under ``a <> b`` it keeps the chunks."""
import numpy as np
import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec.tpu_join import TpuHashJoinBase
from spark_rapids_tpu.obs import trace

_rng = np.random.default_rng(41)
#: 16 probe rows on 3 keys (one NULL), 40 build rows: 5..17 pairs a
#: probe row, so a chunk of 7 cuts most rows' runs
T = {"k": [1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2, 3, 1, None, 2, 3],
     "a": _rng.integers(0, 20, 16).tolist()}
U = {"k2": _rng.integers(1, 4, 40).tolist(),
     "b": _rng.integers(0, 20, 40).tolist()}
#: probe row 0 (k 1, a 19) has one surviving pair in a run longer
#: than a chunk: its survival rests on one chunk of two or three
T["a"][0] = 19
U["b"][len(U["k2"]) - 1 - U["k2"][::-1].index(1)] = 25

SQL = {
    "inner": "select k, a, k2, b from t join u on k = k2 and a < b",
    "inner_ne": "select k, a, k2, b from t join u on k = k2 and a <> b",
    "left": "select k, a, k2, b from t left outer join u "
            "on k = k2 and a < b",
    "semi": "select k, a from t left semi join u on k = k2 and a < b",
    "anti": "select k, a from t left anti join u on k = k2 and a < b",
}


def _oracle(how):
    out = []
    for k, a in zip(T["k"], T["a"]):
        hits = [(k2, b) for k2, b in zip(U["k2"], U["b"])
                if k is not None and k == k2 and
                (a != b if how == "inner_ne" else a < b)]
        if how in ("inner", "inner_ne"):
            out += [(k, a, k2, b) for k2, b in hits]
        elif how == "left":
            out += [(k, a, k2, b) for k2, b in hits] or [(k, a, None, None)]
        elif (how == "semi") == bool(hits):
            out.append((k, a))
    return out


def _pairs():
    return sum(U["k2"].count(k) for k in T["k"] if k is not None)


def _key(r):
    return tuple((v is None, v if v is not None else 0) for v in r)


def _session(chunk_rows=None):
    conf = {"spark.rapids.tpu.sql.enabled": True,
            "spark.rapids.tpu.sql.test.enabled": True}
    if chunk_rows is not None:
        conf["spark.rapids.tpu.sql.join.gather.chunkRows"] = chunk_rows
    s = TpuSession(TpuConf(conf))
    s.create_dataframe(T, num_partitions=1).create_or_replace_temp_view("t")
    s.create_dataframe(U, num_partitions=1).create_or_replace_temp_view("u")
    return s


def _run(s, how):
    trace.reset()
    rows = sorted(s.sql(SQL[how]).collect(), key=_key)
    (counts,) = [c for q, c in trace.coarse_counts().items()
                 if q is not None]
    return rows, counts


def test_the_data_cuts_runs_at_chunk_edges():
    runs = [U["k2"].count(k) for k in T["k"] if k is not None]
    edges = np.cumsum(runs)
    starts = edges - runs
    cut = (starts // 7) != ((edges - 1) // 7)
    assert cut.sum() >= 5 and _pairs() > 3 * 7
    assert runs[0] > 7 and sum(
        1 for k2, b in zip(U["k2"], U["b"]) if k2 == 1 and b > 19) == 1


@pytest.mark.parametrize("how", sorted(SQL))
@pytest.mark.parametrize("chunk_rows", [7, 13])
def test_chunked_equals_one_chunk_and_python(how, chunk_rows):
    want = sorted(_oracle(how), key=_key)
    one, one_counts = _run(_session(), how)
    many, counts = _run(_session(chunk_rows), how)
    assert one == want and many == want
    pairs = _pairs()
    assert counts["join.residual.pairs"] == one_counts[
        "join.residual.pairs"] == pairs
    assert one_counts["join.residual.chunks"] == 1
    # an inner a < b: one launch of bounds for the one stream batch
    ranged = int(how == "inner")
    assert counts.get("join.residual.range", 0) == \
        one_counts.get("join.residual.range", 0) == ranged
    assert counts["join.residual.chunks"] == \
        (1 if ranged else -(-pairs // chunk_rows))
    assert counts["join.residual.kept"] == one_counts["join.residual.kept"]
    # the pairs' bytes: two int64 columns (8 + 1 bytes each), two int32
    # maps and a flag a pair, whatever the chunking
    assert counts["join.residual.bytes"] == \
        one_counts["join.residual.bytes"] == pairs * (2 * 9 + 8 + 1)


def test_an_inner_join_yields_a_batch_a_chunk(monkeypatch):
    sizes = []
    real = TpuHashJoinBase._chunk_pairs

    def spy(self, *args, **kw):
        out = real(self, *args, **kw)
        sizes.append(None if out is None else out.capacity)
        return out
    monkeypatch.setattr(TpuHashJoinBase, "_chunk_pairs", spy)
    rows, counts = _run(_session(7), "inner_ne")
    assert rows == sorted(_oracle("inner_ne"), key=_key)
    assert len(sizes) == counts["join.residual.chunks"]
    # each chunk's batch is cut to its survivors' bucket, under the chunk
    assert all(c is None or c <= bucket_capacity(7) for c in sizes)


def test_chunk_windows_hold_every_pair_once():
    """Each chunk's window of stream rows holds every row whose run
    meets the chunk, and the chunks tile the batch's pairs."""
    counts = np.array([0, 5, 0, 0, 17, 1, 0, 9, 3, 0, 0, 0, 0, 0, 0, 0])
    got = list(TpuHashJoinBase._chunk_windows(counts, 7))
    assert [g[0] for g in got] == list(range(0, int(counts.sum()), 7))
    incl = np.cumsum(counts)
    for base, first, rows, off0 in got:
        meet = np.nonzero((incl > base) & (incl - counts < base + 7))[0]
        assert first <= meet.min() and meet.max() < first + rows
        assert first + rows <= counts.shape[0]
        assert off0 == int(incl[first] - counts[first]) - base


def test_a_join_under_the_budget_keeps_its_cache_key():
    """The key a one-chunk residual program had before chunking: the
    condition's signature, semi or not, ``bucket_capacity(pairs)``, the
    two capacities, the condition columns' dtypes and how many are the
    stream's; past the budget the chunk's own program runs at the
    budget, beside the one that sorts the build side's condition
    columns once."""
    TpuHashJoinBase._RESIDUAL_JIT.clear()
    _run(_session(), "inner_ne")
    (key,) = TpuHashJoinBase._RESIDUAL_JIT
    assert len(key) == 8 and key[0] == "residual" and key[2] is True
    assert key[3] == bucket_capacity(_pairs())
    assert key[6] == ("bigint", "bigint") and key[7] == 1
    TpuHashJoinBase._RESIDUAL_JIT.clear()
    _run(_session(7), "inner_ne")
    keys = {k[0]: k for k in TpuHashJoinBase._RESIDUAL_JIT}
    assert sorted(keys) == ["residual_chunk", "residual_sorted"]
    assert keys["residual_chunk"][3] == 7


def test_a_range_join_yields_bounded_batches(monkeypatch):
    """An inner join decided by bounds whose survivors exceed the
    budget: the equi join's chunks, each at most the budget, the rows
    the one-launch answer's, ``join.residual.kept`` the survivors; the
    build's runs sorted by one program and the bounds by another, and
    no pair decided one by one."""
    sizes = []
    real = TpuHashJoinBase._expand_phase

    def spy(self, *args, **kw):
        out = real(self, *args, **kw)
        sizes.append(int(out.num_rows))
        return out
    one, one_counts = _run(_session(), "inner")
    TpuHashJoinBase._RESIDUAL_JIT.clear()
    monkeypatch.setattr(TpuHashJoinBase, "_expand_phase", spy)
    rows, counts = _run(_session(7), "inner")
    want = sorted(_oracle("inner"), key=_key)
    assert rows == one == want and len(want) > 3 * 7
    assert len(sizes) == -(-len(want) // 7) and max(sizes) == 7
    assert sum(sizes) == len(want)
    assert counts["join.residual.kept"] == \
        one_counts["join.residual.kept"] == len(want)
    assert counts["join.residual.range"] == counts["join.residual.chunks"] \
        == 1
    assert sorted(k[0] for k in TpuHashJoinBase._RESIDUAL_JIT) == \
        ["residual_range", "residual_range_sorted"]


def _wide(kind):
    """2,000 probe rows on 100 keys against 300 build rows (3 a key):
    6,000 pairs, so a chunk of 1,024 meets about 340 probe rows, and
    past the first chunks its window of rows starts inside the batch.
    The condition's columns carry NULLs on both sides."""
    rng = np.random.default_rng(4100)

    def vals(n):
        if kind == "double":
            v = rng.normal(0, 10, n).tolist()
        elif kind == "string":
            v = [f"s{x:03d}" for x in rng.integers(0, 200, n)]
        else:
            v = rng.integers(-50, 50, n).tolist()
        return [None if x % 17 == 3 else y for x, y in enumerate(v)]
    t = {"k": [int(x) for x in rng.integers(0, 100, 2000)], "a": vals(2000)}
    u = {"k2": [i // 3 for i in range(300)], "b": vals(300)}
    return t, u


@pytest.mark.parametrize("kind", ["bigint", "double", "string",
                                  "bigint_ne"])
def test_windows_inside_a_large_batch(kind):
    """A bigint ``a < b`` is decided by bounds (one launch); under
    ``<>`` it takes the chunks' windows like a DOUBLE or a string."""
    ne = kind.endswith("_ne")
    t, u = _wide(kind.split("_")[0])
    by_key = {}
    for k2, b in zip(u["k2"], u["b"]):
        by_key.setdefault(k2, []).append(b)
    want = sorted(((k, a, b) for k, a in zip(t["k"], t["a"])
                   for b in by_key.get(k, [])
                   if a is not None and b is not None and
                   (a != b if ne else a < b)), key=_key)
    got = []
    for chunk_rows in (None, 1024):
        conf = {"spark.rapids.tpu.sql.enabled": True,
                "spark.rapids.tpu.sql.test.enabled": True}
        if chunk_rows:
            conf["spark.rapids.tpu.sql.join.gather.chunkRows"] = chunk_rows
        s = TpuSession(TpuConf(conf))
        s.create_dataframe(t, num_partitions=1) \
            .create_or_replace_temp_view("t")
        s.create_dataframe(u, num_partitions=1) \
            .create_or_replace_temp_view("u")
        trace.reset()
        got.append(sorted(s.sql(
            "select k, a, b from t join u on k = k2 and "
            f"a {'<>' if ne else '<'} b").collect(), key=_key))
        (counts,) = [c for q, c in trace.coarse_counts().items()
                     if q is not None]
        assert counts["join.residual.pairs"] == 6000
        ranged = int(kind == "bigint")
        assert counts.get("join.residual.range", 0) == ranged
        assert counts["join.residual.chunks"] == \
            (1 if chunk_rows is None or ranged else -(-6000 // chunk_rows))
    assert got[0] == want and got[1] == want
