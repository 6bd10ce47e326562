"""Predicates, comparisons, null tests, three-valued logic.

Reference analogue: predicates.scala, nullExpressions.scala and
GpuEqualTo/GpuLessThan... registrations in GpuOverrides.scala.

Comparisons on strings and floats route through the canonical key-word
encoding (kernels/canon.py) so ordering matches sorts/joins exactly.
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column, StringColumn
from ..kernels import canon
from .core import Expression, Scalar, eval_data_valid, as_column


def _comparable_words(expr: Expression, batch):
    col = as_column(expr.columnar_eval(batch), batch.capacity, batch.num_rows)
    words = canon.value_words(col, batch.num_rows)
    return words, col.validity, isinstance(col, StringColumn)


def coerce_date_string(left: Expression, right: Expression):
    """Spark's comparison of a DATE with a STRING: the string is cast to
    a date (TPC-DS q95's ``d_date between '1999-2-01' and ...``).  Any
    other pair is returned as it is."""
    try:
        lt_, rt_ = left.dtype(), right.dtype()
    except (ValueError, NotImplementedError):
        return left, right
    from .cast import Cast
    if lt_ == T.DATE and rt_ == T.STRING:
        return left, Cast(right, T.DATE)
    if rt_ == T.DATE and lt_ == T.STRING:
        return Cast(left, T.DATE), right
    return left, right


def promote_comparison_sides(left: Expression, right: Expression):
    """Insert casts so both sides share one dtype before key-word
    encoding (the Spark analyzer's binary-comparison coercion).

    The canonical word encodings are only ordered WITHIN a type family:
    an int64 bias word and a float sign-flip word (let alone the
    on-chip f64 triple word) are not mutually comparable, so mixed
    int/float comparisons must promote first.
    """
    try:
        lt_, rt_ = left.dtype(), right.dtype()
    except (ValueError, NotImplementedError):
        return left, right
    if lt_ == rt_:
        return left, right
    dec_l = isinstance(lt_, T.DecimalType)
    dec_r = isinstance(rt_, T.DecimalType)
    if dec_l and dec_r:
        if lt_.scale == rt_.scale:
            # same scale: unscaled words compare exactly as-is
            return left, right
        # widen both to the max scale — exact int64 rescale when the
        # widened precision still fits DECIMAL64, else compare as double
        smax = max(lt_.scale, rt_.scale)
        pmax = max(lt_.precision + smax - lt_.scale,
                   rt_.precision + smax - rt_.scale)
        if pmax <= T.DecimalType.MAX_PRECISION:
            common = T.DecimalType(pmax, smax)
        else:
            common = T.FLOAT64
    elif (dec_l and rt_.is_fractional) or (dec_r and lt_.is_fractional):
        # decimal vs float: Spark's decimal/double coercion
        common = T.FLOAT64
    else:
        try:
            common = T.common_type(lt_, rt_)
        except ValueError:
            # date vs timestamp: compare in timestamp space
            if {type(lt_), type(rt_)} == {T.DateType, T.TimestampType}:
                common = T.TIMESTAMP
            else:
                return left, right
    from .cast import Cast
    if lt_ != common:
        left = Cast(left, common)
    if rt_ != common:
        right = Cast(right, common)
    return left, right


class BinaryComparison(Expression):
    symbol = "?"

    def __init__(self, left: Expression, right: Expression):
        self.children = [left, right]
        self._promoted = None

    def with_children(self, children):
        return type(self)(children[0], children[1])

    def dtype(self):
        return T.BOOL

    def compare(self, lt, eq):
        raise NotImplementedError

    @staticmethod
    def _cmp_family(dt):
        """Comparison family for the native fast path; None = word path."""
        if isinstance(dt, T.DecimalType):
            return ("dec", dt.scale)
        if dt == T.BOOL or dt.is_integral or dt in (T.DATE, T.TIMESTAMP):
            return ("int",)
        if dt.is_fractional:
            return ("float",)
        return None

    def _native_cmp(self, batch):
        """Direct-dtype comparison for numeric primitives.

        The general path encodes both sides as canonical u64 key words —
        on a chip with no 64-bit ALU every word op is an emulated u32
        pair, which made a single f64 ``x > lit`` cost ~60ms/M rows.
        Numeric comparisons instead compare natively with Spark's
        ordering pinned explicitly: NaN is greatest and equal to itself,
        -0.0 == 0.0 (IEEE == already), decimals compare unscaled at equal
        scale.  Strings and exotic types keep the word path, which is
        what sorts/joins use (ordering stays mutually consistent).
        """
        left, right = self._promoted
        try:
            lf = self._cmp_family(left.dtype())
            rf = self._cmp_family(right.dtype())
        except (ValueError, NotImplementedError):
            return None
        if lf is None or lf != rf:
            return None
        lc = as_column(left.columnar_eval(batch), batch.capacity,
                       batch.num_rows)
        rc = as_column(right.columnar_eval(batch), batch.capacity,
                       batch.num_rows)
        from ..columnar.binary64 import Binary64Column, require_same_kind
        if isinstance(lc, Binary64Column) or isinstance(rc, Binary64Column):
            require_same_kind(lc, rc)
            from ..kernels import binary64 as b64
            lt = b64.lt(lc.data, rc.data)
            eq = b64.eq(lc.data, rc.data)
            return lt, ~lt & ~eq, eq, lc.validity, rc.validity
        a, b = lc.data, rc.data
        if lf[0] == "float":
            if a.dtype != b.dtype:
                common = jnp.promote_types(a.dtype, b.dtype)
                a, b = a.astype(common), b.astype(common)
            an, bn = jnp.isnan(a), jnp.isnan(b)
            lt = jnp.where(an, False, (a < b) | bn)
            eq = (a == b) | (an & bn)
        else:
            if a.dtype != b.dtype:
                a, b = a.astype(jnp.int64), b.astype(jnp.int64)
            lt = a < b
            eq = a == b
        return lt, ~lt & ~eq, eq, lc.validity, rc.validity

    def _ordered_words(self, batch):
        """Shared preamble: promote once (cached per plan node), then a
        native numeric compare when dtypes allow, else encode both sides
        as canonical words and compare (lt, gt, eq, valid)."""
        if self._promoted is None:
            self._promoted = promote_comparison_sides(*self.children)
        native = self._native_cmp(batch)
        if native is not None:
            return native
        left, right = self._promoted
        lw, lv, l_str = _comparable_words(left, batch)
        rw, rv, r_str = _comparable_words(right, batch)
        # unify word counts (strings of different max widths): the
        # string encoding is [content words..., length word], so the
        # zero padding must insert BEFORE the trailing length word — a
        # shorter string's missing content words are zero by
        # construction, and padding after the length word would compare
        # content words against length words
        n = max(len(lw), len(rw))

        def _pad(ws, is_str):
            if len(ws) == n:
                return ws
            fill = [jnp.zeros_like(ws[0])] * (n - len(ws))
            if is_str and len(ws) > 1:
                return ws[:-1] + fill + ws[-1:]
            return ws + fill
        lw = _pad(lw, l_str)
        rw = _pad(rw, r_str)
        idx = jnp.arange(lw[0].shape[0])
        lt = canon.words_less(lw, idx, rw, idx)
        gt = canon.words_less(rw, idx, lw, idx)
        return lt, gt, ~lt & ~gt, lv, rv

    def columnar_eval(self, batch):
        lt, gt, eq, lv, rv = self._ordered_words(batch)
        return Column(T.BOOL, self.compare(lt, eq), lv & rv)

    def __repr__(self):
        return f"({self.children[0]!r} {self.symbol} {self.children[1]!r})"


class EqualTo(BinaryComparison):
    symbol = "="

    def compare(self, lt, eq):
        return eq


class LessThan(BinaryComparison):
    symbol = "<"

    def compare(self, lt, eq):
        return lt


class LessThanOrEqual(BinaryComparison):
    symbol = "<="

    def compare(self, lt, eq):
        return lt | eq


class GreaterThan(BinaryComparison):
    symbol = ">"

    def compare(self, lt, eq):
        return ~lt & ~eq


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="

    def compare(self, lt, eq):
        return ~lt


class EqualNullSafe(BinaryComparison):
    """<=>: null <=> null is true; never returns null."""
    symbol = "<=>"

    def columnar_eval(self, batch):
        lt, gt, eq, lv, rv = self._ordered_words(batch)
        both_null = ~lv & ~rv
        result = jnp.where(both_null, True, eq & lv & rv)
        return Column(T.BOOL, result, jnp.ones_like(result))


class Not(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, children):
        return Not(children[0])

    def dtype(self):
        return T.BOOL

    def columnar_eval(self, batch):
        a, v, _ = eval_data_valid(self.children[0], batch)
        return Column(T.BOOL, ~a.astype(bool), v)

    def __repr__(self):
        return f"NOT {self.children[0]!r}"


class And(Expression):
    """3-valued AND: false & null = false."""

    def __init__(self, left, right):
        self.children = [left, right]

    def with_children(self, children):
        return And(children[0], children[1])

    def dtype(self):
        return T.BOOL

    def columnar_eval(self, batch):
        la, lv, _ = eval_data_valid(self.children[0], batch)
        ra, rv, _ = eval_data_valid(self.children[1], batch)
        la = la.astype(bool)
        ra = ra.astype(bool)
        result = la & ra
        # null unless: both valid, or one side is a valid False
        valid = (lv & rv) | (lv & ~la) | (rv & ~ra)
        return Column(T.BOOL, result & valid, valid)

    def __repr__(self):
        return f"({self.children[0]!r} AND {self.children[1]!r})"


class Or(Expression):
    """3-valued OR: true | null = true."""

    def __init__(self, left, right):
        self.children = [left, right]

    def with_children(self, children):
        return Or(children[0], children[1])

    def dtype(self):
        return T.BOOL

    def columnar_eval(self, batch):
        la, lv, _ = eval_data_valid(self.children[0], batch)
        ra, rv, _ = eval_data_valid(self.children[1], batch)
        la = la.astype(bool) & lv
        ra = ra.astype(bool) & rv
        result = la | ra
        valid = (lv & rv) | la | ra
        return Column(T.BOOL, result, valid)

    def __repr__(self):
        return f"({self.children[0]!r} OR {self.children[1]!r})"


class IsNull(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, children):
        return IsNull(children[0])

    def dtype(self):
        return T.BOOL

    @property
    def nullable(self):
        return False

    def columnar_eval(self, batch):
        _, v, _ = eval_data_valid(self.children[0], batch)
        in_range = jnp.arange(batch.capacity) < batch.num_rows
        return Column(T.BOOL, ~v & in_range, jnp.ones_like(v))


class IsNotNull(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, children):
        return IsNotNull(children[0])

    def dtype(self):
        return T.BOOL

    @property
    def nullable(self):
        return False

    def columnar_eval(self, batch):
        _, v, _ = eval_data_valid(self.children[0], batch)
        return Column(T.BOOL, v, jnp.ones_like(v))


class IsNaN(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, children):
        return IsNaN(children[0])

    def dtype(self):
        return T.BOOL

    @property
    def nullable(self):
        return False

    def columnar_eval(self, batch):
        a, v, t = eval_data_valid(self.children[0], batch)
        isnan = jnp.isnan(a) if t.is_fractional else jnp.zeros_like(v)
        return Column(T.BOOL, isnan & v, jnp.ones_like(v))


class In(Expression):
    """IN over a literal list (reference: GpuInSet)."""

    def __init__(self, child: Expression, values: List):
        self.children = [child]
        self.values = values

    def with_children(self, children):
        return In(children[0], self.values)

    def dtype(self):
        return T.BOOL

    def columnar_eval(self, batch):
        from .core import Literal
        child = self.children[0]
        acc_data = None
        acc_valid = None
        has_null_item = any(v is None for v in self.values)
        cdt = child.dtype()
        for v in self.values:
            if v is None:
                continue
            # fractional values against a non-fractional child must keep
            # their own type so EqualTo's promotion coerces the CHILD up
            # (forcing the child dtype would truncate 0.5 -> 0)
            if isinstance(v, float) and not cdt.is_fractional:
                lit_v = Literal(v)
            else:
                lit_v = Literal(v, cdt)
            eq = EqualTo(child, lit_v)
            a, va, _ = eval_data_valid(eq, batch)
            a = a.astype(bool) & va
            acc_data = a if acc_data is None else (acc_data | a)
            acc_valid = va if acc_valid is None else (acc_valid | va)
        if acc_data is None:
            acc_data = jnp.zeros(batch.capacity, bool)
            acc_valid = jnp.ones(batch.capacity, bool)
        _, cv, _ = eval_data_valid(child, batch)
        # SQL: x IN (..null..) is null when no match; match wins
        valid = jnp.where(acc_data, True,
                          cv & (not has_null_item))
        return Column(T.BOOL, acc_data, valid)
