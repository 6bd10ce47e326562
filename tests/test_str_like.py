"""``kernels/strings.str_like_match``, the one device program behind
every LIKE of literal bytes and ``%`` (and ``StartsWith`` / ``EndsWith``
/ ``Contains``), against Python's ``re`` on seeded random strings: empty
strings, NULLs, patterns at a row's start and end, matches that would
run across two rows, repeated and overlapping pieces, ``NOT LIKE``; and
what the program holds and counts."""
import re

import jax
import numpy as np
import pytest

from spark_rapids_tpu.columnar import Field, Schema, dtypes as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import StringColumn
from spark_rapids_tpu.expr.core import AttributeReference, Literal
from spark_rapids_tpu.expr.predicates import Not
from spark_rapids_tpu.expr.string_ops import Like
from spark_rapids_tpu.kernels import strings as skern
from spark_rapids_tpu.obs import trace

PATTERNS = ["%ab%", "ab%", "%ab", "a%b", "%a%b%", "ab%ba", "aa%aa", "%aa%aa%",
            "%ab%ab%ab%", "a%b%a%b", "%", "%%", "b%%a", "%abab%", "x%x%x",
            "%ba%ab%", "abx%", "%xab"]


def _strings(seed, n=600):
    """Short strings over a three-letter alphabet, a tenth NULL, some
    empty: every piece of PATTERNS occurs often, at starts and ends."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        k = int(rng.integers(0, 9))
        out.append(None if rng.random() < 0.1 else
                   "".join(rng.choice(list("abx"), k)))
    return out


def _rx(pattern):
    return re.compile("".join(".*" if c == "%" else re.escape(c)
                              for c in pattern), re.DOTALL)


def _eval(expr, vals):
    col = StringColumn.from_pylist(vals)
    batch = ColumnarBatch(Schema([Field("s", T.STRING)]), [col], len(vals))
    got = expr.bind(batch.schema).columnar_eval(batch)
    return (np.asarray(got.data).astype(bool)[:len(vals)],
            np.asarray(got.validity).astype(bool)[:len(vals)])


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("negated", [False, True], ids=["like", "not_like"])
def test_like_matches_re(pattern, seed, negated):
    vals = _strings(seed)
    ref = AttributeReference("s", T.STRING, True)
    expr = Like(ref, Literal(pattern, T.STRING))
    data, valid = _eval(Not(expr) if negated else expr, vals)
    rx = _rx(pattern)
    for i, v in enumerate(vals):
        # NULL stays NULL, and NOT LIKE of NULL is NULL
        assert valid[i] == (v is not None), (pattern, v)
        if v is not None:
            assert data[i] == ((rx.fullmatch(v) is not None) != negated), \
                (pattern, v)


@pytest.mark.parametrize("pattern,vals,want", [
    # pieces that would only match by running on into the next row
    ("%ab%", ["xa", "bx", "a", "b"], [False] * 4),
    ("%a%b%", ["xxa", "bxx", "", "ab"], [False, False, False, True]),
    ("a%b", ["a", "b", "ab", "a", "", "b"],
     [False, False, True, False, False, False]),
    ("%ba", ["b", "a", "xba", ""], [False, False, True, False]),
    # overlapping head and tail, repeated pieces
    ("aa%aa", ["aaa", "aaaa", "aa", "aaxaa"], [False, True, False, True]),
    ("%aba%bab%", ["ababab", "abab", "abaxbab", "babab"],
     [True, False, True, False]),
    # empty strings and an all-empty column
    ("%", ["", "", ""], [True, True, True]),
    ("%a%", ["", "", ""], [False, False, False]),
    ("a%", [""], [False]),
])
def test_like_edges(pattern, vals, want):
    data, valid = _eval(Like(AttributeReference("s", T.STRING, True),
                             Literal(pattern, T.STRING)), vals)
    assert valid.all() and data.tolist() == want


@pytest.mark.parametrize("fn,arg,pattern", [
    (skern.starts_with, b"ab", "ab%"),
    (skern.ends_with, b"ba", "%ba"),
    (skern.contains, b"bab", "%bab%"),
    (skern.contains, b"", "%"),
])
def test_starts_ends_contains_are_the_like_program(fn, arg, pattern):
    vals = [v or "" for v in _strings(3, 300)]
    col = StringColumn.from_pylist(vals)
    got = np.asarray(fn(col, arg))[:len(vals)]
    rx = _rx(pattern)
    assert got.tolist() == [rx.fullmatch(v) is not None for v in vals]


def _primitives(segs, rows=64, nbytes=512):
    found = {}

    def walk(jaxpr):
        for e in jaxpr.eqns:
            found.setdefault(e.primitive.name, []).append(
                [v.aval.shape for v in e.outvars])
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    off = jax.ShapeDtypeStruct((rows + 1,), np.int32)
    data = jax.ShapeDtypeStruct((nbytes,), np.uint8)
    walk(jax.make_jaxpr(lambda o, d: skern.str_like_match.__wrapped__(
        o, d, segs=segs))(off, data).jaxpr)
    return found


@pytest.mark.parametrize("segs", [
    (b"", b"special", b"requests", b""), (b"", b"special", b""),
    (b"ab", b""), (b"", b"ba"), (b"ab", b"c", b"d", b"ef")])
def test_the_program_gathers_a_row_never_a_byte(segs):
    """No search of a byte's row (``searchsorted``'s loop or sort), no
    ``cumsum``, and every gather is of the rows' own count: the bytes
    are compared as shifted slices, not gathered by a bytes x pattern
    index matrix."""
    found = _primitives(segs)
    assert not {"cumsum", "cummax", "reduce_window", "reduce_window_max",
                "while", "sort", "scatter-add"} & set(found)
    for shapes in found.get("gather", []):
        assert all(s == (64,) for s in shapes)
    assert len(found.get("scatter", [])) <= 1     # the row starts, once


def test_a_launch_counts_its_bytes_and_rows():
    vals = _strings(4, 100)
    col = StringColumn.from_pylist(vals)
    trace.reset()
    skern.like(col, (b"", b"ab", b""))
    counts = trace.coarse_counts()
    tbl = {k: v for c in counts.values() for k, v in c.items()}
    assert tbl["str.like.bytes"] == col.data.shape[0]
    assert tbl["str.like.rows"] == col.capacity
    assert tbl["launch.str_like_match@-"] == 1
    assert tbl["lanes.str_like_match@-"] == col.data.shape[0]
