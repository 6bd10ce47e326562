"""CPU expression interpreter over pyarrow compute.

Role parity: in the reference, operators that stay on CPU run as stock
Spark JVM expressions; here the CPU engine evaluates the same Expression
trees with pyarrow kernels (proper SQL null semantics).  This is both the
fallback path for untagged operators and the oracle for the
CPU-vs-TPU equality test harness (reference asserts.py:
assert_gpu_and_cpu_are_equal_collect).
"""
from __future__ import annotations

from typing import Any

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..columnar import dtypes as T
from ..columnar.arrow import to_arrow_type
from . import (core, arithmetic as A, predicates as P, conditional as C,
               cast as castmod, string_ops as S, datetime as DT, misc as M)


def cpu_eval(expr: core.Expression, table: pa.Table):
    """Evaluate an expression against a pa.Table -> pa.Array or pa.Scalar."""
    fn = _DISPATCH.get(type(expr))
    if fn is None:
        return _fallback_rowwise(expr, table)
    return fn(expr, table)


def _arr(x, n):
    if isinstance(x, (pa.Array, pa.ChunkedArray)):
        return x
    # scalar -> broadcast array
    if isinstance(x, pa.Scalar):
        return pa.repeat(x, n) if x.is_valid else pa.nulls(n, x.type)
    return pa.repeat(x, n)


def _ev(e, t):
    return cpu_eval(e, t)


def _attr(e: core.AttributeReference, t):
    return t.column(e.col_name)


def _bound(e: core.BoundReference, t):
    return t.column(e.ordinal)


def _lit(e: core.Literal, t):
    if e.value is None:
        at = to_arrow_type(e._dtype) if e._dtype != T.NULL else pa.bool_()
        return pa.scalar(None, type=at)
    if e._dtype == T.DATE:
        import datetime
        v = e.value
        if isinstance(v, int):
            v = datetime.date(1970, 1, 1) + datetime.timedelta(days=v)
        return pa.scalar(v, type=pa.date32())
    return pa.scalar(e.value, type=to_arrow_type(e._dtype))


def _alias(e: core.Alias, t):
    return _ev(e.children[0], t)


def _num(kind):
    def f(e, t):
        a = _ev(e.children[0], t)
        b = _ev(e.children[1], t)
        out_t = e.dtype()
        at = to_arrow_type(out_t)
        a = pc.cast(a, at, safe=False)
        b = pc.cast(b, at, safe=False)
        if kind == "add":
            return pc.add_checked(a, b) if False else pc.add(a, b)
        if kind == "sub":
            return pc.subtract(a, b)
        if kind == "mul":
            return pc.multiply(a, b)
        raise AssertionError(kind)
    return f


def _div(e, t):
    a = pc.cast(_ev(e.children[0], t), pa.float64(), safe=False)
    b = pc.cast(_ev(e.children[1], t), pa.float64(), safe=False)
    bz = pc.if_else(pc.equal(b, 0.0), pa.scalar(None, pa.float64()), b)
    return pc.divide(a, bz)


def _intdiv(e, t):
    a = pc.cast(_ev(e.children[0], t), pa.float64(), safe=False)
    b = pc.cast(_ev(e.children[1], t), pa.float64(), safe=False)
    bz = pc.if_else(pc.equal(b, 0.0), pa.scalar(None, pa.float64()), b)
    return pc.cast(pc.trunc(pc.divide(a, bz)), pa.int64(), safe=False)


def _remainder(e, t):
    # java-style remainder: a - trunc(a/b)*b
    a0 = _ev(e.children[0], t)
    b0 = _ev(e.children[1], t)
    out_t = e.dtype()
    a = pc.cast(a0, pa.float64(), safe=False)
    b = pc.cast(b0, pa.float64(), safe=False)
    bz = pc.if_else(pc.equal(b, 0.0), pa.scalar(None, pa.float64()), b)
    r = pc.subtract(a, pc.multiply(pc.trunc(pc.divide(a, bz)), bz))
    return pc.cast(r, to_arrow_type(out_t), safe=False)


def _nan_flags(x, n):
    if isinstance(x, pa.Scalar):
        is_f = pa.types.is_floating(x.type)
        v = pa.repeat(x, n) if x.is_valid else pa.nulls(n, x.type)
    else:
        is_f = pa.types.is_floating(x.type)
        v = x
    if not is_f:
        return pa.array([False] * n)
    return pc.coalesce(pc.is_nan(v), pa.scalar(False))


def _nested_eq(x, y) -> bool:
    """Recursive equality with Spark ordering semantics: NaN == NaN,
    nulls inside containers compare equal to each other."""
    import math
    if x is None and y is None:
        return True
    if x is None or y is None:
        return False
    if isinstance(x, float) and isinstance(y, float):
        if math.isnan(x) and math.isnan(y):
            return True
        return x == y
    if isinstance(x, (list, tuple)) and isinstance(y, (list, tuple)):
        return len(x) == len(y) and all(
            _nested_eq(a, b) for a, b in zip(x, y))
    if isinstance(x, dict) and isinstance(y, dict):
        return set(x) == set(y) and all(
            _nested_eq(v, y[k]) for k, v in x.items())
    return x == y


def _cmp(op):
    def f(e, t):
        a = _ev(e.children[0], t)
        b = _ev(e.children[1], t)
        a_t = a.type
        b_t = b.type
        if op == "equal" and (pa.types.is_nested(a_t) or
                              pa.types.is_nested(b_t)):
            # pyarrow has no nested equality kernel; row-wise python
            # (Spark supports struct/array equality)
            av = _arr(a, t.num_rows).to_pylist()
            bv = _arr(b, t.num_rows).to_pylist()
            return pa.array(
                [None if (x is None or y is None) else _nested_eq(x, y)
                 for x, y in zip(av, bv)], type=pa.bool_())
        if a_t != b_t:
            target = _common_arrow(a_t, b_t)
            a = pc.cast(a, target, safe=False)
            b = pc.cast(b, target, safe=False)
        raw = getattr(pc, op)(a, b)
        # Spark total order for floats: NaN == NaN, NaN > everything else
        if pa.types.is_floating(a.type if hasattr(a, 'type') else b.type):
            n = t.num_rows
            an = _nan_flags(a, n)
            bn = _nan_flags(b, n)
            both = pc.and_(an, bn)
            if op == "equal":
                raw = pc.if_else(pc.or_(an, bn), both, raw)
            elif op == "less":
                raw = pc.if_else(an, pa.scalar(False),
                                 pc.if_else(bn, pc.invert(an), raw))
            elif op == "less_equal":
                raw = pc.if_else(bn, pa.scalar(True),
                                 pc.if_else(an, both, raw))
            elif op == "greater":
                raw = pc.if_else(bn, pa.scalar(False),
                                 pc.if_else(an, pc.invert(bn), raw))
            elif op == "greater_equal":
                raw = pc.if_else(an, pa.scalar(True),
                                 pc.if_else(bn, both, raw))
            # preserve nulls from original inputs
            valid = pc.and_(pc.is_valid(_arr(a, n)), pc.is_valid(_arr(b, n)))
            raw = pc.if_else(valid, raw, pa.scalar(None, pa.bool_()))
        return raw
    return f


def _common_arrow(at, bt):
    order = [pa.int8(), pa.int16(), pa.int32(), pa.int64(), pa.float32(),
             pa.float64()]
    if at in order and bt in order:
        return order[max(order.index(at), order.index(bt))]
    if pa.types.is_decimal(at) or pa.types.is_decimal(bt):
        # compare in a wide decimal so mixed scales/ints always fit
        sa = at.scale if pa.types.is_decimal(at) else 0
        sb = bt.scale if pa.types.is_decimal(bt) else 0
        return pa.decimal128(38, max(sa, sb))
    return at


def _eq_null_safe(e, t):
    a = _arr(_ev(e.children[0], t), t.num_rows).to_pylist()
    b = _arr(_ev(e.children[1], t), t.num_rows).to_pylist()
    return pa.array([_nested_eq(x, y) for x, y in zip(a, b)],
                    type=pa.bool_())


def _in_set(e, t):
    vals = [v for v in e.values if v is not None]
    has_null = any(v is None for v in e.values)
    a = _arr(_ev(e.children[0], t), t.num_rows).to_pylist()
    out = []
    for x in a:
        if x is None:
            out.append(None)
        elif any(_nested_eq(x, v) for v in vals):
            out.append(True)
        else:
            out.append(None if has_null else False)
    return pa.array(out, type=pa.bool_())


def _and(e, t):
    return pc.and_kleene(
        pc.cast(_ev(e.children[0], t), pa.bool_()),
        pc.cast(_ev(e.children[1], t), pa.bool_()))


def _or(e, t):
    return pc.or_kleene(
        pc.cast(_ev(e.children[0], t), pa.bool_()),
        pc.cast(_ev(e.children[1], t), pa.bool_()))


def _not(e, t):
    return pc.invert(pc.cast(_ev(e.children[0], t), pa.bool_()))


def _isnull(e, t):
    return pc.is_null(_arr(_ev(e.children[0], t), t.num_rows))


def _isnotnull(e, t):
    return pc.is_valid(_arr(_ev(e.children[0], t), t.num_rows))


def _isnan(e, t):
    v = _ev(e.children[0], t)
    if pa.types.is_floating(v.type):
        return pc.coalesce(pc.is_nan(v), pa.scalar(False))
    return pa.array([False] * t.num_rows)


def _if(e, t):
    cond = pc.coalesce(pc.cast(_ev(e.children[0], t), pa.bool_()),
                       pa.scalar(False))
    a = _ev(e.children[1], t)
    b = _ev(e.children[2], t)
    at = to_arrow_type(e.dtype()) if e.dtype() != T.NULL else None
    if at is not None:
        a = pc.cast(a, at, safe=False)
        b = pc.cast(b, at, safe=False)
    return pc.if_else(cond, a, b)


def _case(e: C.CaseWhen, t):
    at = to_arrow_type(e.dtype())
    result = pc.cast(_ev(e.else_value, t), at, safe=False) \
        if e.else_value is not None else pa.scalar(None, at)
    for cond, val in reversed(e.branches):
        c = pc.coalesce(pc.cast(_ev(cond, t), pa.bool_()), pa.scalar(False))
        v = pc.cast(_ev(val, t), at, safe=False)
        result = pc.if_else(c, v, result)
    return result


def _coalesce(e, t):
    vals = [_arr(_ev(c, t), t.num_rows) for c in e.children]
    at = to_arrow_type(e.dtype())
    vals = [pc.cast(v, at, safe=False) for v in vals]
    return pc.coalesce(*vals)


def _cast(e: castmod.Cast, t):
    v = _ev(e.children[0], t)
    src_t = e.children[0].dtype()
    to = e.to
    if to == T.STRING:
        if src_t == T.BOOL:
            return pc.if_else(pc.cast(v, pa.bool_()), pa.scalar("true"),
                              pa.scalar("false"))
        if src_t.is_fractional:
            vals = _arr(v, t.num_rows).to_pylist()
            return pa.array(
                [None if x is None else castmod._format_float(x)
                 for x in vals], pa.string())
        if src_t in (T.DATE, T.TIMESTAMP):
            vals = _arr(v, t.num_rows).to_pylist()
            return pa.array([None if x is None else
                             str(x).replace("T", " ") for x in vals],
                            pa.string())
        return pc.cast(v, pa.string())
    if src_t == T.STRING:
        n = t.num_rows
        vals = _arr(v, n).to_pylist()
        out = []
        for s in vals:
            if s is None:
                out.append(None)
                continue
            s = s.strip()
            try:
                if to.is_integral:
                    out.append(int(s))
                elif to.is_fractional:
                    out.append(float(s))
                elif to == T.BOOL:
                    sl = s.lower()
                    out.append(True if sl in ("true", "t", "yes", "y", "1")
                               else False if sl in ("false", "f", "no", "n",
                                                    "0") else None)
                elif to == T.DATE:
                    from .cast import parse_date
                    out.append(parse_date(s))
                elif to == T.TIMESTAMP:
                    out.append(np.datetime64(s, "us").item())
                else:
                    out.append(None)
            except (ValueError, OverflowError):
                out.append(None)
        return pa.array(out, to_arrow_type(to))
    if to.is_integral and src_t.is_fractional:
        info = np.iinfo(to.np_dtype)
        clipped = pc.if_else(pc.coalesce(pc.is_nan(v), pa.scalar(False)),
                             pa.scalar(0.0), v)
        # float(int64.max) rounds UP to 2^63, which then WRAPS in the
        # integer cast; clamp to the largest double strictly below it
        # so +inf / 1e300 saturate to Long.Max (Spark semantics)
        hi = float(info.max)
        if float(np.float64(hi)) > info.max:
            hi = float(np.nextafter(np.float64(hi), 0.0))
        clipped = pc.min_element_wise(
            pc.max_element_wise(clipped, pa.scalar(float(info.min)),
                                skip_nulls=False),
            pa.scalar(hi), skip_nulls=False)
        out = pc.cast(pc.trunc(clipped), to_arrow_type(to), safe=False)
        if info.bits < 64:
            return out        # float(info.max) exact: clamp saturates
        # 64-bit: values at/above 2^63 must saturate to Long.Max (the
        # nextafter clamp alone would give 2^63-1024)
        return pc.if_else(
            pc.greater_equal(pc.coalesce(v, pa.scalar(0.0)),
                             pa.scalar(2.0 ** 63)),
            pa.scalar(info.max, to_arrow_type(to)), out)
    if src_t == T.DATE and to == T.TIMESTAMP:
        return pc.cast(v, pa.timestamp("us"))
    if src_t == T.TIMESTAMP and to == T.DATE:
        return pc.cast(v, pa.date32())
    if src_t.is_integral and to == T.DATE:
        return pc.cast(pc.cast(v, pa.int32(), safe=False), pa.date32())
    if src_t.is_integral and to == T.TIMESTAMP:
        return pc.cast(pc.cast(v, pa.int64(), safe=False), pa.timestamp("us"))
    return pc.cast(v, to_arrow_type(to), safe=False)


def _math1(fn, cast_f64=True):
    def f(e, t):
        v = _ev(e.children[0], t)
        if cast_f64:
            v = pc.cast(v, pa.float64(), safe=False)
        return fn(v)
    return f


def _upper(e, t):
    return pc.utf8_upper(_ev(e.children[0], t))


def _lower(e, t):
    return pc.utf8_lower(_ev(e.children[0], t))


def _length(e, t):
    return pc.cast(pc.utf8_length(_ev(e.children[0], t)), pa.int32())


def _substring(e: S.Substring, t):
    v = _ev(e.children[0], t)
    pos = e.children[1].value
    length = e.children[2].value if len(e.children) > 2 else None
    start = pos - 1 if pos > 0 else pos
    if pos > 0:
        if length is None:
            return pc.utf8_slice_codeunits(v, start)
        return pc.utf8_slice_codeunits(v, start, start + length)
    # negative start: python-style from end
    vals = _arr(v, t.num_rows).to_pylist()
    out = []
    for s in vals:
        if s is None:
            out.append(None)
        else:
            st = len(s) + pos if pos < 0 else 0
            st = max(st, 0)
            out.append(s[st: st + length] if length is not None else s[st:])
    return pa.array(out, pa.string())


def _starts(e, t):
    return pc.starts_with(_ev(e.children[0], t),
                          pattern=e.children[1].value)


def _ends(e, t):
    return pc.ends_with(_ev(e.children[0], t), pattern=e.children[1].value)


def _contains(e, t):
    return pc.match_substring(_ev(e.children[0], t),
                              pattern=e.children[1].value)


def _like(e: S.Like, t):
    return pc.match_like(_ev(e.children[0], t), pattern=e.children[1].value)


def _rlike(e, t):
    return pc.match_substring_regex(_ev(e.children[0], t),
                                    pattern=e.children[1].value)


def _concat(e, t):
    vals = [_arr(_ev(c, t), t.num_rows) for c in e.children]
    vals = [pc.cast(v, pa.string()) for v in vals]
    return pc.binary_join_element_wise(*vals, "",
                                       null_handling="emit_null")


def _trim(side):
    def f(e, t):
        v = _ev(e.children[0], t)
        if side == "both":
            return pc.utf8_trim(v, characters=" ")
        if side == "left":
            return pc.utf8_ltrim(v, characters=" ")
        return pc.utf8_rtrim(v, characters=" ")
    return f


def _dt_field(fn, out=pa.int32()):
    def f(e, t):
        v = _ev(e.children[0], t)
        return pc.cast(fn(v), out)
    return f


def _day_of_week(e, t):
    v = _ev(e.children[0], t)
    # pc.day_of_week: Monday=0; Spark: Sunday=1..Saturday=7
    # Monday=0..Sunday=6 -> Spark Sunday=1..Saturday=7
    dow = pc.day_of_week(v, count_from_zero=True, week_start=1)
    shifted = pc.subtract(pc.add(dow, 2), pc.multiply(
        pc.cast(pc.greater_equal(dow, 6), pa.int64()), pa.scalar(7)))
    return pc.cast(shifted, pa.int32())


def _weekday(e, t):
    v = _ev(e.children[0], t)
    return pc.cast(pc.day_of_week(v, count_from_zero=True, week_start=1),
                   pa.int32())


def _date_add(e, t):
    import datetime
    v = _ev(e.children[0], t)
    d = _ev(e.children[1], t)
    days_i = pc.cast(_arr(d, t.num_rows), pa.int64())
    dur = pc.multiply(days_i, pa.scalar(86_400_000_000, pa.int64()))
    ts = pc.cast(pc.cast(v, pa.timestamp("us")), pa.int64())
    out = pc.add(ts, dur)
    return pc.cast(pc.cast(out, pa.timestamp("us")), pa.date32())


def _date_sub(e, t):
    from .core import Literal
    import copy
    neg = DT.DateAdd(e.children[0],
                     A.UnaryMinus(e.children[1]))
    return _date_add(neg, t)


def _date_diff(e, t):
    a = pc.cast(pc.cast(_ev(e.children[0], t), pa.date32()), pa.int32())
    b = pc.cast(pc.cast(_ev(e.children[1], t), pa.date32()), pa.int32())
    return pc.subtract(a, b)


def _round(e: A.Round, t):
    v = pc.cast(_ev(e.children[0], t), pa.float64(), safe=False)
    return pc.round(v, ndigits=e.scale,
                    round_mode="half_towards_infinity")


def _fallback_rowwise(expr, table: pa.Table):
    """Last resort: evaluate via the device path on the CPU backend.

    Keeps the CPU engine total; exotic expressions (hash, rand) share one
    implementation with the TPU path by construction.
    """
    from ..columnar.arrow import from_arrow, column_to_arrow
    from .core import eval_as_column
    batch = from_arrow(table)
    bound = expr.bind(batch.schema) if not _is_bound(expr) else expr
    col = eval_as_column(bound, batch)
    return column_to_arrow(col, batch.num_rows)


def _is_bound(expr) -> bool:
    attrs = expr.collect(lambda e: isinstance(e, core.AttributeReference))
    return not attrs


_DISPATCH = {
    core.AttributeReference: _attr,
    core.BoundReference: _bound,
    core.Literal: _lit,
    core.Alias: _alias,
    A.Add: _num("add"),
    A.Subtract: _num("sub"),
    A.Multiply: _num("mul"),
    A.Divide: _div,
    A.IntegralDivide: _intdiv,
    A.Remainder: _remainder,
    A.UnaryMinus: _math1(pc.negate, cast_f64=False),
    A.Abs: _math1(pc.abs, cast_f64=False),
    A.Sqrt: _math1(pc.sqrt),
    A.Exp: _math1(pc.exp),
    A.Log: _math1(pc.ln),
    A.Log2: _math1(pc.log2),
    A.Log10: _math1(pc.log10),
    A.Sin: _math1(pc.sin),
    A.Cos: _math1(pc.cos),
    A.Tan: _math1(pc.tan),
    A.Asin: _math1(pc.asin),
    A.Acos: _math1(pc.acos),
    A.Atan: _math1(pc.atan),
    A.Floor: lambda e, t: pc.cast(
        pc.floor(pc.cast(_ev(e.children[0], t), pa.float64(), safe=False)),
        to_arrow_type(e.dtype()), safe=False),
    A.Ceil: lambda e, t: pc.cast(
        pc.ceil(pc.cast(_ev(e.children[0], t), pa.float64(), safe=False)),
        to_arrow_type(e.dtype()), safe=False),
    A.Round: _round,
    A.Pow: lambda e, t: pc.power(
        pc.cast(_ev(e.children[0], t), pa.float64(), safe=False),
        pc.cast(_ev(e.children[1], t), pa.float64(), safe=False)),
    A.Signum: lambda e, t: pc.cast(
        pc.sign(pc.cast(_ev(e.children[0], t), pa.float64(), safe=False)),
        pa.float64()),
    P.EqualTo: _cmp("equal"),
    P.LessThan: _cmp("less"),
    P.LessThanOrEqual: _cmp("less_equal"),
    P.GreaterThan: _cmp("greater"),
    P.GreaterThanOrEqual: _cmp("greater_equal"),
    P.And: _and,
    P.Or: _or,
    P.Not: _not,
    P.IsNull: _isnull,
    P.IsNotNull: _isnotnull,
    P.IsNaN: _isnan,
    C.If: _if,
    C.CaseWhen: _case,
    C.Coalesce: _coalesce,
    castmod.Cast: _cast,
    S.Upper: _upper,
    S.Lower: _lower,
    S.Length: _length,
    S.Substring: _substring,
    S.StartsWith: _starts,
    S.EndsWith: _ends,
    S.Contains: _contains,
    S.Like: _like,
    S.RLike: _rlike,
    S.ConcatStrings: _concat,
    S.StringTrim: _trim("both"),
    S.StringTrimLeft: _trim("left"),
    S.StringTrimRight: _trim("right"),
    S.Replace: lambda e, t: pc.replace_substring(
        _ev(e.children[0], t), pattern=e.children[1].value,
        replacement=e.children[2].value),
    S.Reverse: lambda e, t: pc.utf8_reverse(_ev(e.children[0], t)),
    S.Lpad: lambda e, t: pc.utf8_lpad(
        pc.utf8_slice_codeunits(_ev(e.children[0], t), 0,
                                e.children[1].value),
        width=e.children[1].value, padding=e.children[2].value),
    S.Rpad: lambda e, t: pc.utf8_rpad(
        pc.utf8_slice_codeunits(_ev(e.children[0], t), 0,
                                e.children[1].value),
        width=e.children[1].value, padding=e.children[2].value),
    S.StringRepeat: lambda e, t: pc.binary_repeat(
        _ev(e.children[0], t), e.children[1].value),
    S.StringLocate: lambda e, t: pc.cast(
        pc.add(pc.find_substring(_ev(e.children[1], t),
                                 pattern=e.children[0].value), 1),
        pa.int32()),
    S.RegexpReplace: lambda e, t: pc.replace_substring_regex(
        _ev(e.children[0], t), pattern=e.children[1].value,
        replacement=e.children[2].value),
    DT.Year: _dt_field(pc.year),
    DT.Month: _dt_field(pc.month),
    DT.DayOfMonth: _dt_field(pc.day),
    DT.Quarter: _dt_field(pc.quarter),
    DT.DayOfWeek: _day_of_week,
    DT.WeekDay: _weekday,
    DT.DayOfYear: _dt_field(pc.day_of_year),
    DT.Hour: _dt_field(pc.hour),
    DT.Minute: _dt_field(pc.minute),
    DT.Second: _dt_field(pc.second),
    DT.DateAdd: _date_add,
    DT.DateSub: _date_sub,
    DT.DateDiff: _date_diff,
}


# -- collection expressions (independent pylist oracle) ----------------------

def _pylist_of(e, t):
    v = _arr(cpu_eval(e, t), t.num_rows)
    if isinstance(v, pa.ChunkedArray):
        v = v.combine_chunks()
    return v.to_pylist()


def _coll_create_array(e, t):
    from . import collections as CO
    kids = [_pylist_of(c, t) for c in e.children]
    n = t.num_rows
    rows = [[k[i] for k in kids] for i in range(n)]
    return pa.array(rows, type=to_arrow_type(e.dtype()))


def _coll_size(e, t):
    vals = _pylist_of(e.children[0], t)
    return pa.array([(-1 if v is None else len(v)) for v in vals],
                    type=pa.int32())


def _coll_get_item(e, t):
    arrs = _pylist_of(e.children[0], t)
    idxs = _pylist_of(e.children[1], t)
    out = []
    for a, i in zip(arrs, idxs):
        if a is None or i is None or i < 0 or i >= len(a):
            out.append(None)
        else:
            out.append(a[i])
    return pa.array(out, type=to_arrow_type(e.dtype()))


def _coll_element_at(e, t):
    arrs = _pylist_of(e.children[0], t)
    idxs = _pylist_of(e.children[1], t)
    out = []
    for a, i in zip(arrs, idxs):
        if a is None or i is None or i == 0:
            out.append(None)
            continue
        j = i - 1 if i > 0 else len(a) + i
        out.append(a[j] if 0 <= j < len(a) else None)
    return pa.array(out, type=to_arrow_type(e.dtype()))


def _coll_contains(e, t):
    arrs = _pylist_of(e.children[0], t)
    needles = _pylist_of(e.children[1], t)
    out = []
    for a, nd in zip(arrs, needles):
        if a is None or nd is None:
            out.append(None)
        elif nd in [x for x in a if x is not None]:
            out.append(True)
        elif any(x is None for x in a):
            out.append(None)
        else:
            out.append(False)
    return pa.array(out, type=pa.bool_())


def _coll_sort_array(e, t):
    import math

    def key(x):
        if isinstance(x, float):
            if math.isnan(x):
                return (1, 0.0)
            return (0, x + 0.0)
        return (0, x)

    arrs = _pylist_of(e.children[0], t)
    out = []
    for a in arrs:
        if a is None:
            out.append(None)
            continue
        vals = sorted([x for x in a if x is not None], key=key,
                      reverse=not e.asc)
        nulls = [None] * (len(a) - len(vals))
        out.append(nulls + vals if e.asc else vals + nulls)
    return pa.array(out, type=to_arrow_type(e.dtype()))


def _coll_minmax(is_min):
    import math

    def key(x):
        # Spark float total order: NaN greatest, -0.0 == 0.0
        if isinstance(x, float):
            if math.isnan(x):
                return (1, 0.0)
            return (0, x + 0.0)
        return (0, x)

    def f(e, t):
        arrs = _pylist_of(e.children[0], t)
        out = []
        for a in arrs:
            vals = [x for x in (a or []) if x is not None]
            if a is None or not vals:
                out.append(None)
            else:
                out.append(min(vals, key=key) if is_min
                           else max(vals, key=key))
        return pa.array(out, type=to_arrow_type(e.dtype()))
    return f


def _register_collections():
    from . import collections as CO
    _DISPATCH[CO.CreateArray] = _coll_create_array
    _DISPATCH[CO.Size] = _coll_size
    _DISPATCH[CO.GetArrayItem] = _coll_get_item
    _DISPATCH[CO.ElementAt] = _coll_element_at
    _DISPATCH[CO.ArrayContains] = _coll_contains
    _DISPATCH[CO.SortArray] = _coll_sort_array
    _DISPATCH[CO.ArrayMin] = _coll_minmax(True)
    _DISPATCH[CO.ArrayMax] = _coll_minmax(False)


_register_collections()


def _coll_named_struct(e, t):
    kids = [_pylist_of(c, t) for c in e.children]
    n = t.num_rows
    rows = [dict(zip(e.names, [k[i] for k in kids])) for i in range(n)]
    return pa.array(rows, type=to_arrow_type(e.dtype()))


def _coll_get_field(e, t):
    rows = _pylist_of(e.children[0], t)
    out = [None if r is None else r.get(e.field_name) for r in rows]
    return pa.array(out, type=to_arrow_type(e.dtype()))


def _coll_create_map(e, t):
    kids = [_pylist_of(c, t) for c in e.children]
    n = t.num_rows
    rows = []
    for i in range(n):
        items = [(kids[j][i], kids[j + 1][i])
                 for j in range(0, len(kids), 2)]
        rows.append(items)
    return pa.array(rows, type=to_arrow_type(e.dtype()))


def _as_map_dict(v):
    if v is None or isinstance(v, dict):
        return v
    return dict(v)  # pyarrow map pylist is [(k, v), ...]


def _coll_get_map_value(e, t):
    rows = [_as_map_dict(v) for v in _pylist_of(e.children[0], t)]
    keys = _pylist_of(e.children[1], t)
    out = [None if (r is None or k is None) else r.get(k)
           for r, k in zip(rows, keys)]
    return pa.array(out, type=to_arrow_type(e.dtype()))


def _coll_map_keys(e, t):
    rows = [_as_map_dict(v) for v in _pylist_of(e.children[0], t)]
    out = [None if r is None else list(r.keys()) for r in rows]
    return pa.array(out, type=to_arrow_type(e.dtype()))


def _coll_map_values(e, t):
    rows = [_as_map_dict(v) for v in _pylist_of(e.children[0], t)]
    out = [None if r is None else list(r.values()) for r in rows]
    return pa.array(out, type=to_arrow_type(e.dtype()))


def _coll_size_any(e, t):
    # arrays arrive as lists, maps as entry-lists/dicts; len covers all
    vals = _pylist_of(e.children[0], t)
    return pa.array([(-1 if v is None else len(v)) for v in vals],
                    type=pa.int32())


def _register_struct_map():
    from . import collections as CO
    _DISPATCH[CO.CreateNamedStruct] = _coll_named_struct
    _DISPATCH[CO.GetStructField] = _coll_get_field
    _DISPATCH[CO.CreateMap] = _coll_create_map
    _DISPATCH[CO.GetMapValue] = _coll_get_map_value
    _DISPATCH[CO.MapKeys] = _coll_map_keys
    _DISPATCH[CO.MapValues] = _coll_map_values
    _DISPATCH[CO.Size] = _coll_size_any
    # element_at over maps routes through the map lookup
    _elem_arr = _DISPATCH[CO.ElementAt]

    def _element_at_any(e, t):
        from ..columnar import dtypes as TT
        if isinstance(e.children[0].dtype(), TT.MapType):
            return _coll_get_map_value(e, t)
        return _elem_arr(e, t)

    _DISPATCH[CO.ElementAt] = _element_at_any
    _DISPATCH[CO.ExtractValue] = lambda e, t: cpu_eval(e._resolved(), t)


_register_struct_map()


def _register_predicates():
    _DISPATCH[P.EqualNullSafe] = _eq_null_safe
    _DISPATCH[P.In] = _in_set

    def _hash_guard(e, t):
        for c in e.children:
            if c.dtype().is_nested:
                raise NotImplementedError(
                    "hash over nested types is not supported on either "
                    "engine yet")
        return _fallback_rowwise(e, t)

    _DISPATCH[M.Murmur3Hash] = _hash_guard


_register_predicates()
