"""Cast matrix — the GpuCast role.

Reference analogue: GpuCast.scala:166 (1,301 LoC) + per-pair CastChecks
(TypeChecks.scala:879).  Non-ANSI semantics: numeric narrowing wraps,
float->int saturates-then-wraps per Spark, invalid string parses -> null.
ANSI mode (conf spark.rapids.tpu.sql.ansi.enabled) raises on overflow.
"""
from __future__ import annotations

import datetime as _dt
import re

import numpy as np
import jax.numpy as jnp

from ..columnar import dtypes as T
from ..columnar.column import Column, StringColumn
from .core import Expression, eval_data_valid, as_column


class Cast(Expression):
    def __init__(self, child: Expression, to: T.DType, ansi: bool = False):
        self.children = [child]
        self.to = to
        self.ansi = ansi

    def with_children(self, c):
        return Cast(c[0], self.to, self.ansi)

    def dtype(self):
        return self.to

    @property
    def name(self):
        return f"Cast({self.to.name})"

    def columnar_eval(self, batch):
        src_t = self.children[0].dtype()
        to = self.to
        if src_t == to:
            return self.children[0].columnar_eval(batch)
        if src_t == T.STRING:
            col = as_column(self.children[0].columnar_eval(batch),
                            batch.capacity, batch.num_rows)
            return _cast_from_string(col, to, batch.num_rows)
        b64_out = self._binary64_cast(batch, src_t, to)
        if b64_out is not None:
            return b64_out
        a, v, vt = eval_data_valid(self.children[0], batch)
        if to == T.STRING:
            return _cast_to_string(a, v, vt, batch.num_rows)
        return _cast_numeric(a, v, vt, to)

    def _binary64_cast(self, batch, src_t, to):
        """exactDouble: casts in/out of bits-typed DOUBLE columns
        (kernels/binary64.py from_i64/from_f32/to_int/to_f32)."""
        from ..columnar.binary64 import (Binary64Column,
                                         exact_double_enabled)
        if to == T.FLOAT64:
            if not exact_double_enabled():
                return None
            from ..kernels import binary64 as b64
            c = as_column(self.children[0].columnar_eval(batch),
                          batch.capacity, batch.num_rows)
            if isinstance(c, Binary64Column):
                return c
            if src_t.is_integral or src_t == T.BOOL or \
                    src_t in (T.DATE, T.TIMESTAMP):
                import jax.numpy as jnp
                return Binary64Column(
                    b64.from_i64(c.data.astype(jnp.int64)), c.validity)
            if src_t == T.FLOAT32:
                return Binary64Column(b64.from_f32(c.data), c.validity)
            return None
        if src_t == T.FLOAT64:
            if not exact_double_enabled():
                return None     # cheap guard: no double child eval
            c = as_column(self.children[0].columnar_eval(batch),
                          batch.capacity, batch.num_rows)
            if not isinstance(c, Binary64Column):
                return None
            from ..kernels import binary64 as b64
            if to.is_integral:
                data = b64.to_int(c.data, to.np_dtype)
                valid = c.validity & ~b64.is_nan(c.data)
                return Column(to, data, valid)
            if to == T.FLOAT32:
                return Column(to, b64.to_f32(c.data), c.validity)
            raise NotImplementedError(
                f"exactDouble: CAST(DOUBLE AS {to.name}) not wired; "
                f"disable spark.rapids.tpu.sql.exactDouble")
        return None

    def __repr__(self):
        return f"CAST({self.children[0]!r} AS {self.to.name})"


def _float_to_i64_exact(x) -> jnp.ndarray:
    """float -> int64, guarded against out-of-range UB.

    f64 -> s64 is exact on both backends (verified on chip with x64
    enabled), but values at/beyond +-2^63 are undefined in the
    conversion, so clamp in float space at the nearest safely
    representable bound first; the caller's integer clamp handles the
    target-type saturation.  Note the subtlety this replaces: clamping
    in FLOAT space at a narrower type's bound (e.g. 2147483647.0) then
    converting via s32 lands one ulp short on chip — saturate with
    integer comparisons instead.
    """
    from ..kernels.canon import _f64_bitcast_supported
    if _f64_bitcast_supported():
        # real f64 backend: every double below 2^63 converts exactly
        lim = 9223372036854774784.0   # largest double below 2^63
    else:
        # on chip the (hi, lo) f32-pair representation needs hi strictly
        # inside s64 range; values in the last 2^39-wide window saturate
        # (documented incompat — emulated f64 ulp there is 2^15 anyway)
        lim = 9223371487098961920.0   # 2^63 - 2^39, exact in f32 and f64
    i64 = jnp.clip(x, -lim, lim).astype(jnp.int64)
    i64 = jnp.where(x > lim, np.int64(2 ** 63 - 1), i64)
    i64 = jnp.where(x < -lim, np.int64(-(2 ** 63)), i64)
    return i64


def _cast_numeric(a, v, src_t: T.DType, to: T.DType) -> Column:
    if isinstance(to, T.DecimalType):
        if isinstance(src_t, T.DecimalType):
            # decimal -> decimal rescale: exact int64 arithmetic
            ds = to.scale - src_t.scale
            if ds >= 0:
                scaled = a.astype(jnp.int64) * np.int64(10 ** ds)
            else:
                # round-half-up toward nearest on scale reduction; jnp //
                # floors, so divide magnitudes and reapply the sign
                div = np.int64(10 ** (-ds))
                x = a.astype(jnp.int64)
                mag = (jnp.abs(x) + div // 2) // div
                scaled = jnp.where(x < 0, -mag, mag)
            return Column(to, scaled, v)
        if src_t.is_integral or src_t == T.BOOL:
            # int -> decimal: exact int64 multiply (no float round-trip)
            scaled = a.astype(jnp.int64) * np.int64(10 ** to.scale)
            return Column(to, scaled, v)
        # float -> decimal: value * 10^scale via float (inherent rounding)
        scaled = jnp.round(a.astype(jnp.float64) * (10.0 ** to.scale))
        return Column(to, _float_to_i64_exact(scaled), v)
    if isinstance(src_t, T.DecimalType):
        if to.is_integral:
            # decimal -> int: exact truncating integer division
            div = np.int64(10 ** src_t.scale)
            q = a.astype(jnp.int64) // div
            r = a.astype(jnp.int64) % div
            # python floordiv rounds toward -inf; SQL truncates toward 0
            q = jnp.where((a.astype(jnp.int64) < 0) & (r != 0), q + 1, q)
            info = np.iinfo(to.np_dtype)
            q = jnp.clip(q, np.int64(info.min), np.int64(info.max))
            return Column(to, q.astype(to.np_dtype), v)
        f = a.astype(jnp.float64) / (10.0 ** src_t.scale)
        if to.is_fractional:
            return Column(to, f.astype(to.np_dtype), v)
        return _cast_numeric(f, v, T.FLOAT64, to)
    if to == T.BOOL:
        return Column(T.BOOL, a.astype(bool) if a.dtype != bool else a, v)
    if src_t == T.BOOL:
        return Column(to, a.astype(to.np_dtype), v)
    if to.is_integral and src_t.is_fractional:
        # Spark float->int: NaN casts to 0 and values saturate to type
        # bounds (non-ANSI).  Convert to int64 exactly, then clamp and
        # narrow with INTEGER comparisons.
        info = np.iinfo(to.np_dtype)
        x = jnp.trunc(jnp.nan_to_num(a, nan=0.0))
        i64 = _float_to_i64_exact(x)
        i64 = jnp.clip(i64, np.int64(info.min), np.int64(info.max))
        return Column(to, i64.astype(to.np_dtype), v)
    if to in (T.DATE, T.TIMESTAMP):
        if src_t == T.TIMESTAMP and to == T.DATE:
            days = jnp.floor_divide(a, 86_400_000_000)
            return Column(T.DATE, days.astype(jnp.int32), v)
        if src_t == T.DATE and to == T.TIMESTAMP:
            return Column(T.TIMESTAMP,
                          a.astype(jnp.int64) * 86_400_000_000, v)
        return Column(to, a.astype(to.np_dtype), v)
    if src_t in (T.DATE, T.TIMESTAMP) and to.is_numeric:
        return Column(to, a.astype(to.np_dtype), v)
    return Column(to, a.astype(to.np_dtype), v)


# -- string parse/format (host-assisted v0; device text kernels are a later
#    milestone — reference gates these with conf flags too, e.g.
#    spark.rapids.sql.castStringToFloat.enabled) -----------------------------

def _cast_from_string(col: StringColumn, to: T.DType, num_rows: int) -> Column:
    vals, valid = col.to_numpy(num_rows)
    out = np.zeros(col.capacity, dtype=to.np_dtype if to.np_dtype else object)
    ok = np.zeros(col.capacity, dtype=bool)
    for i in range(num_rows):
        if not valid[i]:
            continue
        s = vals[i].strip()
        try:
            if to.is_integral:
                out[i] = int(s)
            elif to.is_fractional:
                out[i] = float(s)
            elif to == T.BOOL:
                sl = s.lower()
                if sl in ("true", "t", "yes", "y", "1"):
                    out[i] = True
                elif sl in ("false", "f", "no", "n", "0"):
                    out[i] = False
                else:
                    continue
            elif to == T.DATE:
                out[i] = np.datetime64(parse_date(s), "D").astype(np.int32)
            elif to == T.TIMESTAMP:
                out[i] = np.datetime64(s, "us").astype(np.int64)
            elif isinstance(to, T.DecimalType):
                out[i] = int(round(float(s) * 10 ** to.scale))
            else:
                continue
            ok[i] = True
        except (ValueError, OverflowError):
            continue
    return Column(to, jnp.asarray(out.astype(to.np_dtype)), jnp.asarray(ok))


_DATE_TEXT = re.compile(r"([0-9]{4})(?:-([0-9]{1,2})(?:-([0-9]{1,2}))?)?"
                        r"(?:[ T].*)?")


def parse_date(text: str) -> _dt.date:
    """A string as Spark's cast to DATE reads it: ``yyyy``,
    ``yyyy-[m]m`` or ``yyyy-[m]m-[d]d``, optionally followed by a space
    or ``T`` and anything (TPC-DS q95's ``'1999-2-01'``).  ValueError
    where Spark's cast gives NULL."""
    m = _DATE_TEXT.fullmatch(text.strip())
    if m is None:
        raise ValueError(f"not a date: {text!r}")
    return _dt.date(int(m.group(1)), int(m.group(2) or 1),
                    int(m.group(3) or 1))


def _format_float(x: float) -> str:
    if np.isnan(x):
        return "NaN"
    if np.isinf(x):
        return "Infinity" if x > 0 else "-Infinity"
    if x == int(x) and abs(x) < 1e16:
        return f"{x:.1f}"
    return repr(float(x))


def _cast_to_string(a, v, src_t: T.DType, num_rows: int) -> StringColumn:
    an = np.asarray(a)[:num_rows]
    vn = np.asarray(v)[:num_rows]
    out = []
    for i in range(num_rows):
        if not vn[i]:
            out.append(None)
        elif src_t == T.BOOL:
            out.append("true" if an[i] else "false")
        elif src_t.is_integral:
            out.append(str(int(an[i])))
        elif src_t.is_fractional:
            out.append(_format_float(float(an[i])))
        elif isinstance(src_t, T.DecimalType):
            unscaled = int(an[i])
            s = src_t.scale
            if s == 0:
                out.append(str(unscaled))
            else:
                sign = "-" if unscaled < 0 else ""
                digits = str(abs(unscaled)).rjust(s + 1, "0")
                out.append(f"{sign}{digits[:-s]}.{digits[-s:]}")
        elif src_t == T.DATE:
            out.append(str(np.datetime64(int(an[i]), "D")))
        elif src_t == T.TIMESTAMP:
            ts = np.datetime64(int(an[i]), "us")
            out.append(str(ts).replace("T", " "))
        else:
            out.append(str(an[i]))
    cap = int(np.asarray(a).shape[0])
    return StringColumn.from_pylist(out + [None] * (cap - num_rows),
                                    capacity=cap)
