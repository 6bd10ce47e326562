"""Gathered string bytes are laid out in one pass over the lanes
(``kernels/strings.py`` ``str_materialize_bytes``): every row scatters
the step of its shift at its first lane, a running sum hands each lane
its row's shift, one gathered byte a lane.  Each case holds the program
against a plain numpy layout and against the program it replaced (a
binary search over the row ends a lane, kept here as the oracle); a
structural case counts the per-lane gathers in the jaxpr; one holds
``gather_strings``, ``substring`` and ``concat`` to the columns the old
program gave them; the last read the ``str.materialize.*`` counters and
the benchmark metric that reads the program's device time
(``chipbench/metrics/str_materialize_device_ms_per_query.py``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the jaxpr walker and the ``chipbench/`` fixture of the sibling file
from test_agg_string_keys_fused import _equations, bench  # noqa: F401
from test_str_pack_bound import _trace_clean, pack_counts  # noqa: F401
import spark_rapids_tpu.expr as E
from spark_rapids_tpu.columnar import ColumnarBatch
from spark_rapids_tpu.columnar.column import StringColumn
from spark_rapids_tpu.kernels import strings as skern


@functools.partial(jax.jit, static_argnames=("out_bytes",))
def searchsorted_program(data, new_offsets, src_starts, out_bytes: int):
    """``str_materialize_bytes`` as it was up to PR 32: the oracle."""
    j = jnp.arange(out_bytes, dtype=jnp.int32)
    row = jnp.searchsorted(new_offsets[1:], j, side="right").astype(jnp.int32)
    row = jnp.clip(row, 0, new_offsets.shape[0] - 2)
    within = j - new_offsets[row]
    src_idx = jnp.take(src_starts, row) + within
    live = j < new_offsets[-1]
    return jnp.where(live,
                     jnp.take(data, jnp.clip(src_idx, 0, data.shape[0] - 1)),
                     jnp.uint8(0))


def numpy_layout(data, new_offsets, src_starts, out_bytes):
    """Row ``r``'s bytes ``data[src_starts[r]:]`` (an index past the
    buffer reads its last byte) at ``new_offsets[r]``, zeros after."""
    out = np.zeros(out_bytes, np.uint8)
    for r in range(len(src_starts)):
        lo, hi = int(new_offsets[r]), min(int(new_offsets[r + 1]), out_bytes)
        if hi > lo:
            src = np.clip(src_starts[r] + np.arange(hi - lo), 0, len(data) - 1)
            out[lo:hi] = data[src]
    return out


def launch(source, picks, slots=None, out_bytes=None, pad=0):
    """The operands of one launch: ``source`` strings (None: a NULL, no
    bytes) back to back in a byte buffer with ``pad`` spare bytes, and
    the output rows ``picks`` (source row numbers; None: a NULL output
    row of no bytes) in ``slots`` row slots, the rest dead."""
    raw = [(s or "").encode() for s in source]
    src_offsets = np.concatenate([[0], np.cumsum([len(b) for b in raw])])
    data = np.frombuffer(b"".join(raw) + b"\xff" * pad, np.uint8)
    if data.size == 0:
        data = np.zeros(1, np.uint8)
    slots = slots or len(picks)
    lens = np.zeros(slots, np.int64)
    src_starts = np.zeros(slots, np.int32)
    for r, p in enumerate(picks):
        if p is not None:
            lens[r] = len(raw[p])
            src_starts[r] = src_offsets[p]
    new_offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    total = int(new_offsets[-1])
    return data, new_offsets, src_starts, out_bytes or max(1, total)


WORDS = ["alpha", "", "be", None, "gamma delta", "e", "", "zeta!"]

CASES = {
    "plain": lambda: launch(["ab", "cde", "f"], [0, 1, 2]),
    "empty_strings": lambda: launch(WORDS, [0, 1, 2, 6, 4, 1, 7]),
    "null_rows": lambda: launch(WORDS, [0, None, 3, 2, None, 4]),
    "empty_run_at_start": lambda: launch(WORDS, [1, 6, None, 1, 0, 2, 4]),
    "empty_run_in_the_middle": lambda: launch(WORDS, [0, 1, 6, None, 3, 1, 4,
                                                      7]),
    "empty_run_at_end": lambda: launch(WORDS, [0, 4, 2, 1, None, 6, 3]),
    "repeated_source_rows": lambda: launch(WORDS, [4, 4, 0, 4, 0, 0, 7, 4]),
    "dead_lanes_past_the_total": lambda: launch(WORDS, [0, 2, 4], slots=8,
                                                out_bytes=64),
    "out_bytes_is_the_total": lambda: launch(WORDS, [7, 0, 4, 2]),
    "one_row": lambda: launch(["solo"], [0]),
    "one_empty_row": lambda: launch([""], [0], out_bytes=4),
    "every_row_empty": lambda: launch(WORDS, [1, None, 6, 3], out_bytes=8),
    "multi_byte_utf8": lambda: launch(["naïve", "日本語", "", "🙂 ok", "ß"],
                                      [1, 3, 0, 2, 4, 1]),
    "source_larger_than_output": lambda: launch(
        ["x" * 40, "tail", "y" * 90, "z"], [1, 3], pad=100),
    "source_smaller_than_output": lambda: launch(
        ["ab", "c"], [0, 1] * 20, slots=64, out_bytes=256),
    "rows_past_out_bytes": lambda: launch(WORDS, [4, 0, 7, 4], out_bytes=13),
}


def _substring_like():
    """Source windows that start inside a row and end before its end
    (``substring``, ``trim``)."""
    data, new_offsets, src_starts, out_bytes = launch(
        ["abcdefgh", "ijklmnop", "qrstuvwx"], [0, 1, 2, 1])
    lens = np.array([3, 0, 5, 2])
    src_starts = src_starts + np.array([2, 1, 3, 6], np.int32)
    new_offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    return data, new_offsets, src_starts, 16


def _starts_past_the_buffer():
    """``case when`` lays every branch's buffer out by one set of
    offsets: a row that chose another branch points past this buffer's
    end, and both programs read its last byte there."""
    data, new_offsets, _, out_bytes = launch(["abc", "de"], [0, 1, 0, 1])
    return data, new_offsets, np.array([0, 40, -7, 3], np.int32), out_bytes


CASES["substring_windows"] = _substring_like
CASES["starts_past_the_buffer"] = _starts_past_the_buffer


@pytest.mark.parametrize("case", sorted(CASES))
def test_same_bytes_as_numpy_and_as_the_old_program(case):
    data, new_offsets, src_starts, out_bytes = CASES[case]()
    got = np.asarray(skern.str_materialize_bytes(
        jnp.asarray(data), jnp.asarray(new_offsets), jnp.asarray(src_starts),
        out_bytes))
    assert got.dtype == np.uint8 and got.shape == (out_bytes,)
    want = numpy_layout(data, new_offsets, src_starts, out_bytes)
    assert got.tobytes() == want.tobytes()
    old = np.asarray(searchsorted_program(
        jnp.asarray(data), jnp.asarray(new_offsets), jnp.asarray(src_starts),
        out_bytes))
    assert got.tobytes() == old.tobytes()
    assert not got[int(new_offsets[-1]):].any()


@pytest.mark.parametrize("seed", range(6))
def test_random_gathers(seed):
    """A join-like gather: repeated source rows, a tenth of the output
    rows NULL, a tenth of the source rows empty, dead row slots and dead
    lanes after them."""
    rng = np.random.default_rng(seed)
    source = ["".join(chr(c) for c in rng.integers(33, 127, n))
              for n in rng.integers(0, 30, 200)]
    for i in rng.integers(0, 200, 20):
        source[i] = ""
    picks = [None if rng.random() < 0.1 else int(p)
             for p in rng.integers(0, 200, 900)]
    data, new_offsets, src_starts, _ = launch(source, picks, slots=1024)
    out_bytes = 1 << int(new_offsets[-1]).bit_length()
    operands = (jnp.asarray(data), jnp.asarray(new_offsets),
                jnp.asarray(src_starts))
    got = np.asarray(skern.str_materialize_bytes(*operands, out_bytes))
    assert got.tobytes() == numpy_layout(
        data, new_offsets, src_starts, out_bytes).tobytes()
    assert got.tobytes() == np.asarray(
        searchsorted_program(*operands, out_bytes)).tobytes()


def test_one_gathered_byte_a_lane_and_no_search():
    slots, nbytes, lanes = 4096, 10000, 1 << 16
    jaxpr = jax.make_jaxpr(
        lambda d, o, s: skern.str_materialize_bytes(d, o, s, lanes))(
        jax.ShapeDtypeStruct((nbytes,), np.uint8),
        jax.ShapeDtypeStruct((slots + 1,), np.int32),
        jax.ShapeDtypeStruct((slots,), np.int32)).jaxpr
    eqns = list(_equations(jaxpr))
    # the search was a loop of dependent gathers a lane
    assert not [e for e in eqns if e.primitive.name in ("while", "scan")]
    per_lane = [e for e in eqns if e.primitive.name == "gather"
                and e.outvars[0].aval.shape == (lanes,)]
    assert [e.invars[0].aval.shape for e in per_lane] == [(nbytes,)]
    scatters = [e for e in eqns
                if e.primitive.name in ("scatter-add", "scatter_add")]
    # one scatter, over the rows
    assert [e.invars[2].aval.shape for e in scatters] == [(slots,)]
    assert scatters[0].params["indices_are_sorted"]


def _mixed_batch():
    rng = np.random.default_rng(33)
    strings = ["".join(chr(c) for c in rng.integers(97, 123, n))
               for n in rng.integers(0, 12, 60)]
    strings[0] = ""
    strings[1] = None
    strings[7] = "日本語のテキスト"
    strings[30] = None
    strings[31] = ""
    strings[59] = None
    other = [None if i % 11 == 0 else f"<{i}>" for i in range(60)]
    return strings, ColumnarBatch.from_pydict({"s": strings, "t": other})


def _columns_through(program, monkeypatch):
    """``gather_strings``, ``substring`` and ``concat`` over the mixed
    batch with ``program`` as the engine's materializer: every output
    column's (offsets, bytes, validity) as host arrays."""
    monkeypatch.setattr(skern, "str_materialize_bytes", program)
    strings, batch = _mixed_batch()
    col = batch.column("s")
    rng = np.random.default_rng(7)
    idx = jnp.asarray(rng.integers(0, 60, 128).astype(np.int32))
    live = jnp.asarray(np.arange(128) < 100)
    out = {}
    new_offsets, buf, gvalid = skern.gather_strings(
        col.offsets, col.data, col.validity, idx, live)
    out["gather"] = (new_offsets, buf, gvalid)
    new_offsets, buf, gvalid = skern.gather_strings(
        col.offsets, col.data, col.validity, idx, live, max_bytes=24)
    out["gather_by_max_bytes"] = (new_offsets, buf, gvalid)
    sub = skern.substring(col, 2, 5)
    out["substring"] = (sub.offsets, sub.data, sub.validity)
    tail = skern.substring(col, -3, None)
    out["substring_from_the_end"] = (tail.offsets, tail.data, tail.validity)
    for name, expr in [
            ("concat", E.ConcatStrings(E.AttributeReference("s"),
                                       E.AttributeReference("t"))),
            ("concat_literal", E.ConcatStrings(E.AttributeReference("s"),
                                               E.lit("_x")))]:
        c = E.eval_as_column(expr.bind(batch.schema), batch)
        out[name] = (c.offsets, c.data, c.validity)
    return strings, {k: tuple(np.asarray(a) for a in v)
                     for k, v in out.items()}


def test_gather_substring_concat_give_the_columns_they_gave(monkeypatch):
    strings, now = _columns_through(skern.str_materialize_bytes, monkeypatch)
    _, before = _columns_through(searchsorted_program, monkeypatch)
    assert sorted(now) == sorted(before) and len(now) == 6
    for name in now:
        for a, b in zip(now[name], before[name]):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
    # and the strings are the right ones (the kernel counts bytes)
    offsets, data, validity = now["substring"]
    got = [bytes(data[offsets[r]:offsets[r + 1]]) if validity[r] else None
           for r in range(60)]
    assert got == [None if s is None else s.encode()[1:6] for s in strings]


def materialize_counts():
    """The program's launches and lanes (its host ns left out)."""
    got = pack_counts("launch.str_materialize_bytes@")
    got.update(pack_counts("lanes.str_materialize_bytes@"))
    return {k.split(".")[0]: v for k, v in got.items()}


def test_counters_read_launches_and_lanes():
    data, new_offsets, src_starts, _ = CASES["empty_strings"]()
    operands = (jnp.asarray(data), jnp.asarray(new_offsets),
                jnp.asarray(src_starts))
    assert materialize_counts() == {}
    skern.str_materialize_bytes(*operands, 64)
    assert materialize_counts() == {"launch": 1, "lanes": 64}
    skern.str_materialize_bytes(*operands, 256)
    skern.str_materialize_bytes(*operands, out_bytes=64)
    assert materialize_counts() == {"launch": 3, "lanes": 384}


def test_counters_count_nothing_under_a_jit_trace():
    data, new_offsets, src_starts, _ = CASES["empty_strings"]()

    @jax.jit
    def outer(d, o, s):
        return skern.str_materialize_bytes(d, o, s, 64)

    got = outer(jnp.asarray(data), jnp.asarray(new_offsets),
                jnp.asarray(src_starts))
    assert materialize_counts() == {}
    assert np.asarray(got).tobytes() == numpy_layout(
        data, new_offsets, src_starts, 64).tobytes()


def test_a_materialized_view_counts_its_launch():
    col = StringColumn.from_pylist(["ab", None, "", "cdef"] * 8, 32)
    view = col.gather(jnp.asarray(np.arange(32)[::-1].astype(np.int32)))
    assert materialize_counts() == {}           # a view launches nothing
    assert view.to_pylist(32) == (["ab", None, "", "cdef"] * 8)[::-1]
    counts = materialize_counts()
    assert counts["launch"] == 1
    assert counts["lanes"] == view.data.shape[0]


def test_the_program_keeps_its_name():
    lowered = skern.str_materialize_bytes.__wrapped__.lower(
        jax.ShapeDtypeStruct((16,), np.uint8),
        jax.ShapeDtypeStruct((5,), np.int32),
        jax.ShapeDtypeStruct((4,), np.int32), out_bytes=32)
    assert "@jit_str_materialize_bytes" in lowered.as_text()


@pytest.mark.parametrize("trace_block,want", [
    (None, None),                                   # a run without --trace
    ({"queries": [], "device_ops": []}, None),
    # an engine that names no program
    ({"queries": ["q3", "q18"], "device_ops": [["fusion.3", 1.0]]}, None),
    # the parent (ledger, PR 32): 18.353 s over two queries
    ({"queries": ["q3", "q18"],
      "device_ops": [["jit_str_materialize_bytes", 18.353],
                     ["jit_join_expand_matches", 10.821],
                     ["jit_str_pack_words", 0.5]]}, 9176.5),
    # fallen off the top ten: 0 is a reading
    ({"queries": ["q1", "q6"],
      "device_ops": [["jit_agg_global_core", 0.827]]}, 0.0),
])
def test_str_materialize_device_ms_metric(bench, trace_block, want):
    read = bench.harness.metric_reader("str_materialize_device_ms_per_query")
    got = read({"trace": trace_block})
    assert got == want if want is None else got == pytest.approx(want)
