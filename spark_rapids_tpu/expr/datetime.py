"""Date/time expressions — reference analogue: datetimeExpressions.scala.

Dates are days-since-epoch int32; timestamps microseconds-since-epoch int64
(UTC).  Civil-calendar decomposition uses the days-from-civil algorithm
(Howard Hinnant's public-domain arithmetic) vectorized in jnp — pure integer
ops, fully on device.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column
from .core import Expression, eval_data_valid

US_PER_DAY = 86_400_000_000


def _civil_from_days(z):
    """days since 1970-01-01 -> (year, month, day), vectorized int ops."""
    z = z.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097                                   # [0, 146096]
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)          # [0, 365]
    mp = (5 * doy + 2) // 153                                # [0, 11]
    d = doy - (153 * mp + 2) // 5 + 1                        # [1, 31]
    m = jnp.where(mp < 10, mp + 3, mp - 9)                   # [1, 12]
    y = y + (m <= 2)
    return y, m, d


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _to_days(a, t: T.DType):
    if t == T.DATE:
        return a.astype(jnp.int64)
    # timestamp: floor toward -inf for pre-epoch correctness
    return jnp.floor_divide(a.astype(jnp.int64), US_PER_DAY)


class _DateField(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return type(self)(c[0])

    def dtype(self):
        return T.INT32

    def field(self, y, m, d, a, t):
        raise NotImplementedError

    def columnar_eval(self, batch):
        a, v, t = eval_data_valid(self.children[0], batch)
        days = _to_days(a, t)
        y, m, d = _civil_from_days(days)
        return Column(T.INT32, self.field(y, m, d, a, t).astype(jnp.int32), v)


class Year(_DateField):
    def field(self, y, m, d, a, t):
        return y


class Month(_DateField):
    def field(self, y, m, d, a, t):
        return m


class DayOfMonth(_DateField):
    def field(self, y, m, d, a, t):
        return d


class Quarter(_DateField):
    def field(self, y, m, d, a, t):
        return (m - 1) // 3 + 1


class DayOfWeek(_DateField):
    """Spark: Sunday=1 .. Saturday=7."""

    def field(self, y, m, d, a, t):
        days = _to_days(a, t)
        return ((days + 4) % 7) + 1  # 1970-01-01 was Thursday


class WeekDay(_DateField):
    """Spark weekday(): Monday=0 .. Sunday=6."""

    def field(self, y, m, d, a, t):
        days = _to_days(a, t)
        return (days + 3) % 7


class DayOfYear(_DateField):
    def field(self, y, m, d, a, t):
        days = _to_days(a, t)
        jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
        return (days - jan1 + 1)


class LastDay(Expression):
    """last_day(date) -> date of last day of that month."""

    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return LastDay(c[0])

    def dtype(self):
        return T.DATE

    def columnar_eval(self, batch):
        a, v, t = eval_data_valid(self.children[0], batch)
        days = _to_days(a, t)
        y, m, d = _civil_from_days(days)
        ny = jnp.where(m == 12, y + 1, y)
        nm = jnp.where(m == 12, 1, m + 1)
        nxt = _days_from_civil(ny, nm, jnp.ones_like(d))
        return Column(T.DATE, (nxt - 1).astype(jnp.int32), v)


class _TimeField(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return type(self)(c[0])

    def dtype(self):
        return T.INT32

    def field(self, us_in_day):
        raise NotImplementedError

    def columnar_eval(self, batch):
        a, v, t = eval_data_valid(self.children[0], batch)
        us = a.astype(jnp.int64)
        us_in_day = us - jnp.floor_divide(us, US_PER_DAY) * US_PER_DAY
        return Column(T.INT32, self.field(us_in_day).astype(jnp.int32), v)


class Hour(_TimeField):
    def field(self, us_in_day):
        return us_in_day // 3_600_000_000


class Minute(_TimeField):
    def field(self, us_in_day):
        return (us_in_day // 60_000_000) % 60


class Second(_TimeField):
    def field(self, us_in_day):
        return (us_in_day // 1_000_000) % 60


class DateAdd(Expression):
    def __init__(self, start, days):
        self.children = [start, days]

    def with_children(self, c):
        return DateAdd(c[0], c[1])

    def dtype(self):
        return T.DATE

    def columnar_eval(self, batch):
        a, av, _ = eval_data_valid(self.children[0], batch)
        b, bv, _ = eval_data_valid(self.children[1], batch)
        return Column(T.DATE,
                      (a.astype(jnp.int64) + b.astype(jnp.int64)).astype(
                          jnp.int32), av & bv)


class DateSub(Expression):
    def __init__(self, start, days):
        self.children = [start, days]

    def with_children(self, c):
        return DateSub(c[0], c[1])

    def dtype(self):
        return T.DATE

    def columnar_eval(self, batch):
        a, av, _ = eval_data_valid(self.children[0], batch)
        b, bv, _ = eval_data_valid(self.children[1], batch)
        return Column(T.DATE,
                      (a.astype(jnp.int64) - b.astype(jnp.int64)).astype(
                          jnp.int32), av & bv)


def date_plus_days(left: Expression, right: Expression, op: str):
    """Spark's coercion of ``date + int``, ``int + date`` and ``date -
    int`` (the analyzer's DateTimeOperations rule): ``date_add`` /
    ``date_sub`` by that many days.  None for any other operands, which
    stay arithmetic."""
    try:
        lt, rt = left.dtype(), right.dtype()
    except Exception:  # noqa: BLE001 - an unresolved side: not a date
        return None
    if lt == T.DATE and rt.is_integral:
        return (DateAdd if op == "+" else DateSub)(left, right)
    if op == "+" and rt == T.DATE and lt.is_integral:
        return DateAdd(right, left)
    return None


class DateDiff(Expression):
    def __init__(self, end, start):
        self.children = [end, start]

    def with_children(self, c):
        return DateDiff(c[0], c[1])

    def dtype(self):
        return T.INT32

    def columnar_eval(self, batch):
        a, av, ta = eval_data_valid(self.children[0], batch)
        b, bv, tb = eval_data_valid(self.children[1], batch)
        return Column(T.INT32,
                      (_to_days(a, ta) - _to_days(b, tb)).astype(jnp.int32),
                      av & bv)


class UnixTimestampToSeconds(Expression):
    """unix_timestamp(ts): seconds since epoch."""

    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return UnixTimestampToSeconds(c[0])

    def dtype(self):
        return T.INT64

    def columnar_eval(self, batch):
        a, v, _ = eval_data_valid(self.children[0], batch)
        return Column(T.INT64,
                      jnp.floor_divide(a.astype(jnp.int64), 1_000_000), v)


class ToDate(Expression):
    def __init__(self, child):
        self.children = [child]

    def with_children(self, c):
        return ToDate(c[0])

    def dtype(self):
        return T.DATE

    def columnar_eval(self, batch):
        from .cast import Cast
        return Cast(self.children[0], T.DATE).columnar_eval(batch)
