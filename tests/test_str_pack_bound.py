"""A string key is packed by its byte bound (``kernels/strings.py``
``str_pack_words(..., num_bytes)`` through ``pack_words``): a caller
that holds ``key_byte_bound`` pays one gathered index a byte of the
bound (rounded up to a power of two), not eight a word, and gets the
same bits.  Each case holds the byte-bound program against a plain numpy
packer and against the full-width program; a structural case counts the
gathered indices in the jaxpr; two drive ``session.sql(...).collect()``
against the pyarrow engine and read the ``str.pack.*`` counters; the
last hold the benchmark metric that reads the program's device time
(``chipbench/metrics/str_pack_device_ms_per_query.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from harness import _compare_rows
# the jaxpr walker and the ``chipbench/`` fixture of the sibling file
from test_agg_string_keys_fused import _equations, bench  # noqa: F401
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.columnar.column import (GatheredStringColumn,
                                              StringColumn)
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec import tpu_aggregate as TA
from spark_rapids_tpu.kernels import canon, strings as skern
from spark_rapids_tpu.obs import trace


@pytest.fixture(autouse=True)
def _trace_clean():
    trace.reset()
    yield
    trace.reset()


NARROW = (0, 1, 2, 3, 4)            # gathers 1, 1, 2, 4, 4 bytes a row
FULL = (5, 7, 8, 9, 16, 17)         # rounds up to the words' own width
CAP = 64


def pack_counts(prefix):
    """The counters named ``prefix*`` since the last ``trace.reset()``,
    over every query's table (and the one of no query)."""
    out = {}
    for table in trace.coarse_counts().values():
        for name, n in table.items():
            if name.startswith(prefix):
                out[name] = out.get(name, 0) + n
    return out


def packs():
    """``(launches, lanes)`` of ``str_pack_words`` since the last reset,
    over every operator: the lanes are rows x the bytes a row gathers."""
    return (sum(pack_counts("launch.str_pack_words@").values()),
            sum(pack_counts("lanes.str_pack_words@").values()))


def width(bound, num_words):
    """The bytes a row the pack gathers under a byte bound."""
    return min(1 << max(0, bound - 1).bit_length(), 8 * num_words)


def numpy_words(strings, num_words, cap):
    """Big-endian uint64 words of each string's UTF-8 bytes, zero
    padded; a NULL packs as the empty string."""
    out = np.zeros((cap, num_words), np.uint64)
    for r, s in enumerate(strings):
        raw = (s or "").encode()[:8 * num_words].ljust(8 * num_words, b"\0")
        out[r] = np.frombuffer(raw, ">u8")
    return out


def column(bound, n=50, seed=0, tight=True):
    """``n`` strings of 0 to ``bound`` bytes: empty ones, NULLs, one of
    exactly ``bound`` bytes in the last row, which (``tight``) ends at
    the byte buffer's last byte."""
    rng = np.random.default_rng(seed + bound)
    strings = ["".join(chr(c) for c in rng.integers(33, 127,
                                                    rng.integers(0, bound + 1)))
               for _ in range(n)]
    strings[1] = ""
    strings[2] = None
    strings[-1] = "z" * bound
    col = StringColumn.from_pylist(strings, CAP)
    if tight:
        total = int(np.asarray(col.offsets)[-1])
        col = StringColumn(col.offsets, col.data[:max(total, 1)],
                           col.validity, max_bytes=bound)
        assert col.data.shape[0] == max(total, 1)
    return strings, col


@pytest.mark.parametrize("bound", NARROW + FULL)
def test_byte_bound_pack_is_the_full_pack_bit_for_bit(bound):
    strings, col = column(bound)
    assert skern.key_byte_bound(col, len(strings)) == bound
    num_words = skern.needed_key_words(col, len(strings))
    trace.reset()
    got = np.asarray(skern.pack_words(col, num_words, bound))
    assert packs() == (1, CAP * width(bound, num_words))
    assert (width(bound, num_words) < 8 * num_words) == (bound in NARROW)
    full = np.asarray(skern.str_pack_words(col.offsets, col.data, num_words))
    want = numpy_words(strings, num_words, CAP)
    assert got.dtype == np.uint64 and got.shape == (CAP, num_words)
    assert got.tobytes() == want.tobytes()
    assert got.tobytes() == full.tobytes()
    # the key words a sort or a join reads, bound found by the callee
    words = skern.string_key_words(col, len(strings))
    assert len(words) == num_words + 1
    assert np.stack(words[:-1], 1).tobytes() == want.tobytes()
    assert np.asarray(words[-1]).tolist() == \
        [len((s or "").encode()) for s in strings] + \
        [0] * (CAP - len(strings))


def test_no_bound_is_the_full_program():
    strings, col = column(1)
    trace.reset()
    got = skern.pack_words(col, 2)
    assert packs() == (1, CAP * 16)
    assert np.asarray(got).tobytes() == \
        numpy_words(strings, 2, CAP).tobytes()
    # a bound that fills the agreed words takes the same program
    again = skern.pack_words(col, 1, 8)
    assert np.asarray(again).tobytes() == \
        numpy_words(strings, 1, CAP).tobytes()
    assert packs() == (2, CAP * 16 + CAP * 8)


def test_stale_rows_past_num_rows_may_be_longer():
    """A shrunk batch: live rows of at most one byte, then stale strings
    of six.  The live bound sizes the gather; the stale rows' words are
    cut, deterministic, and zeroed by ``column_key_words``."""
    n = 20
    strings = [("A", "N", "", None)[i % 4] for i in range(n)] + \
        ["stale%d" % (i % 10) for i in range(12)]
    col = StringColumn.from_pylist(strings, 32)
    col.max_bytes = None                       # derived on the device
    assert skern.key_byte_bound(col, n) == 1
    trace.reset()
    words = skern.string_key_words(col, n)
    assert packs() == (1, 32 * 1)
    want = numpy_words(strings, 1, 32)
    got = np.asarray(words[0])
    assert got[:n].tolist() == want[:n, 0].tolist()
    # one byte of each stale string, not eight
    assert got[n:].tolist() == (want[n:, 0] & np.uint64(0xFF << 56)).tolist()
    assert np.asarray(skern.string_key_words(col, n)[0]).tolist() == \
        got.tolist()
    keyed = canon.column_key_words(col, n)
    plain = canon.column_key_words(col, n, str_words=1)     # no bound
    assert [np.asarray(w).tolist() for w in keyed] == \
        [np.asarray(w).tolist() for w in plain]
    assert not np.asarray(keyed[1])[n:].any()


@pytest.mark.parametrize("bound", [1, 4, 12])
def test_lazy_view_packs_its_source_by_the_bound(bound):
    strings, src = column(bound, tight=False)
    rng = np.random.default_rng(bound)
    idx = rng.integers(0, len(strings), 40).astype(np.int32)
    valid = np.asarray(src.validity)[idx]
    view = GatheredStringColumn(src, jnp.asarray(np.pad(idx, (0, 24))),
                                jnp.asarray(np.pad(valid, (0, 24))))
    trace.reset()
    (words, validity), got_bound = TA._pack_string_key(view, 40)
    assert view._mat is None, "packing materialized the view's bytes"
    assert got_bound == 1 << max(0, bound - 1).bit_length()
    num_words = max(1, -(-got_bound // 8))
    assert len(words) == num_words + 1
    want = numpy_words(strings, num_words, CAP)[np.pad(idx, (0, 24))]
    assert np.stack(words[:-1], 1).tobytes() == want.tobytes()
    assert packs() == (1, src.capacity * width(got_bound, num_words))
    # and with nothing handed in: the source's own bound
    plain = canon.value_words(view, 40)
    assert [np.asarray(w).tolist() for w in plain] == \
        [np.asarray(w).tolist() for w in words]


@pytest.mark.parametrize("src_rows,view_rows,packs_the_view", [
    (1 << 16, 1 << 12, True),       # a sixteenth of a large source
    (1 << 16, 64, True),            # a semi join's survivors
    (1 << 16, 1 << 13, False),      # an eighth: the source's words
    (1 << 15, 64, False),           # a dimension table: as before
])
def test_few_rows_of_a_large_source_pack_their_own_bytes(
        src_rows, view_rows, packs_the_view):
    """A view of a few rows packs those rows' strings, not every row
    of its source (Q18's partial aggregate behind the semi join: one
    source-sized pack a surviving batch); the words are the same."""
    rng = np.random.default_rng(src_rows + view_rows)
    names = np.array([f"Customer#{i:09d}" for i in range(1000)] +
                     ["", "x"], object)
    strings = names[rng.integers(0, len(names), src_rows)].tolist()
    strings[7] = None
    src = StringColumn.from_pylist(strings, src_rows)
    src = StringColumn(src.offsets, src.data, src.validity, max_bytes=18)
    live = view_rows - 5
    idx = np.pad(rng.integers(0, src_rows, live).astype(np.int32),
                 (0, view_rows - live))
    idx[3] = 7                                              # the NULL
    valid = np.asarray(src.validity)[idx] & (np.arange(view_rows) < live)
    view = GatheredStringColumn(src, jnp.asarray(idx), jnp.asarray(valid))
    assert canon._few_rows_of_a_large_source(view) is packs_the_view
    (words, validity), bound = TA._pack_string_key(view, live)
    assert bound == 32 and len(words) == 5
    assert (view._mat is not None) is packs_the_view
    want = numpy_words(strings, 4, src_rows)[idx]
    got = np.stack([np.asarray(w) for w in words[:-1]], 1)
    assert got[valid].tobytes() == want[valid].tobytes()
    lens = np.array([len((strings[i] or "").encode()) for i in idx])
    assert np.asarray(words[-1])[valid].tolist() == lens[valid].tolist()
    # the caller masks the dead lanes by the validity it is handed
    assert np.asarray(validity).tolist() == valid.tolist()
    if packs_the_view:
        assert not got[~valid].any()


@pytest.mark.parametrize("num_words,num_bytes,indices_a_row", [
    (1, 1, 1), (1, 2, 2), (1, 4, 4), (1, None, 8), (1, 8, 8),
    (2, None, 16), (2, 4, 4)])
def test_the_gather_is_as_wide_as_the_bound(num_words, num_bytes,
                                            indices_a_row):
    cap, nbytes = 4096, 10000
    jaxpr = jax.make_jaxpr(
        lambda o, d: skern.str_pack_words(o, d, num_words, num_bytes))(
        jax.ShapeDtypeStruct((cap + 1,), np.int32),
        jax.ShapeDtypeStruct((nbytes,), np.uint8)).jaxpr
    gathers = [e for e in _equations(jaxpr) if e.primitive.name == "gather"]
    assert all(e.invars[0].aval.shape == (nbytes,) for e in gathers)
    # every gather's index array is [cap, ..., 1]: one index an element
    shapes = [e.invars[1].aval.shape for e in gathers]
    assert all(s[0] == cap and s[-1] == 1 for s in shapes), shapes
    assert sum(int(np.prod(s[1:])) for s in shapes) == indices_a_row
    if num_bytes is None:
        # the program every caller without a bound runs: one index matrix
        assert shapes == [(cap, 8 * num_words, 1)]


# -- through the session ------------------------------------------------------

def _lineitem(n=700, seed=31):
    rng = np.random.default_rng(seed)
    return pa.table({
        "l_returnflag": pa.array([("A", "N", "R")[i]
                                  for i in rng.integers(0, 3, n)]),
        "l_linestatus": pa.array([("F", "O")[i]
                                  for i in rng.integers(0, 2, n)]),
        "l_shipmode": pa.array([("REG AIR FAST", "TRUCK GROUND", "MAIL",
                                 "SHIP BY SEA")[i]
                                for i in rng.integers(0, 4, n)]),
        "l_quantity": pa.array(rng.integers(1, 51, n).astype(float)),
        "l_extendedprice": pa.array(rng.uniform(900.0, 1e5, n)),
        "l_discount": pa.array(rng.integers(0, 11, n) / 100.0),
        "l_shipdate": pa.array(rng.integers(8000, 10600, n), pa.int32()),
    })


Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       avg(l_discount) as avg_disc, count(*) as count_order
from lineitem where l_shipdate <= 10471
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus"""
BY_MODE = """
select l_shipmode, sum(l_quantity) as sum_qty, count(*) as n
from lineitem group by l_shipmode order by l_shipmode"""


def _run(enabled, sql, conf):
    settings = {"spark.rapids.tpu.sql.enabled": enabled}
    settings.update(conf)
    s = TpuSession(TpuConf(settings))
    s.create_dataframe(_lineitem(), num_partitions=1) \
        .create_or_replace_temp_view("lineitem")
    return s, s.sql(sql).collect()


@pytest.mark.parametrize("sql,narrow", [
    # three batches, two one-byte keys each: six narrow packs before the
    # merge's and the final sort's own, and no key without its bound
    (Q1, True),
    # a 12-byte key is two full words
    (BY_MODE, False),
], ids=["q1_flags", "key_of_12_bytes"])
def test_group_by_counts_its_packs(sql, narrow, monkeypatch):
    conf = {"spark.rapids.tpu.sql.batchSizeRows": 256}
    _, want = _run(False, sql, conf)
    widths = []
    program = skern.str_pack_words

    def spy(offsets, data, num_words, num_bytes=None):
        widths.append(num_bytes is not None)
        return program(offsets, data, num_words, num_bytes)
    monkeypatch.setattr(skern, "str_pack_words", spy)
    trace.reset()
    s, got = _run(True, sql, conf)
    _compare_rows(want, got)
    assert "Cpu" not in s.last_physical_plan.tree_string()
    assert not any(t.get("agg.batches.eager")
                   for t in trace.coarse_counts().values())
    # every launch is counted, under the operator that packed
    assert packs()[0] == len(widths)
    assert all("@Tpu" in k for k in pack_counts("launch.str_pack_words@"))
    if narrow:
        assert len(widths) >= 6 and all(widths), widths
    else:
        assert len(widths) >= 3 and not any(widths), widths


# -- the benchmark metric that reads the program's device time ----------------

@pytest.mark.parametrize("trace_block,want", [
    (None, None),                                   # a run without --trace
    ({"queries": [], "device_ops": []}, None),
    # an engine that names no program
    ({"queries": ["q1", "q6"], "device_ops": [["fusion.3", 1.0]]}, None),
    # the parent: one program, 4.059 s over two queries
    ({"queries": ["q1", "q6"],
      "device_ops": [["jit_str_pack_words", 4.059],
                     ["jit_agg_global_core", 0.797],
                     ["jit_str_materialize_bytes", 0.005]]}, 2029.5),
    # every program under the prefix is summed
    ({"queries": ["q1", "q6"],
      "device_ops": [["jit_agg_whole_stage_core", 0.668],
                     ["jit_str_pack_words", 0.5],
                     ["jit_str_pack_key_words", 0.25]]}, 375.0),
    # fallen off the top ten: 0 is a reading
    ({"queries": ["q3"], "device_ops": [["jit_join_probe_core", 0.9]]}, 0.0),
])
def test_str_pack_device_ms_metric(bench, trace_block, want):
    read = bench.harness.metric_reader("str_pack_device_ms_per_query")
    got = read({"trace": trace_block})
    assert got == want if want is None else got == pytest.approx(want)
