"""TypeSig — the type-support algebra driving tagging and docs.

Reference: TypeChecks.scala:367 (TypeSig), ExecChecks/ExprChecks, and the
generated docs/supported_ops.md.  A TypeSig describes which dtypes an op
supports; tagging intersects the actual plan types against it and records
human-readable reasons on mismatch (RapidsMeta.explain role).
"""
from __future__ import annotations

from typing import Iterable, Optional, Set, Type

from ..columnar import dtypes as T


class TypeSig:
    def __init__(self, kinds: Iterable[type] = (), decimal: bool = False,
                 note: str = ""):
        self.kinds: Set[type] = set(kinds)
        self.decimal = decimal
        self.note = note

    def __add__(self, other: "TypeSig") -> "TypeSig":
        out = TypeSig(self.kinds | other.kinds,
                      self.decimal or other.decimal)
        return out

    def supports(self, dt: T.DType) -> bool:
        if isinstance(dt, T.DecimalType):
            return self.decimal
        # nested types are supported when listed AND their leaves are
        if isinstance(dt, T.ArrayType):
            return T.ArrayType in self.kinds and self.supports(
                dt.element_type)
        if isinstance(dt, T.StructType):
            return T.StructType in self.kinds and all(
                self.supports(f.dtype) for f in dt.fields)
        if isinstance(dt, T.MapType):
            return T.MapType in self.kinds and self.supports(dt.key_type) \
                and self.supports(dt.value_type)
        return type(dt) in self.kinds

    def reason(self, dt: T.DType, context: str) -> Optional[str]:
        if self.supports(dt):
            return None
        return f"{context}: type {dt.name} is not supported on TPU"

    def describe(self) -> str:
        names = sorted(k().name if k not in (T.DecimalType,) else "decimal"
                       for k in self.kinds)
        if self.decimal:
            names.append("decimal64")
        return ", ".join(names)


BOOLEAN = TypeSig([T.BooleanType])
INTEGRAL = TypeSig([T.ByteType, T.ShortType, T.IntegerType, T.LongType])
FP = TypeSig([T.FloatType, T.DoubleType])
NUMERIC = INTEGRAL + FP
DECIMAL_64 = TypeSig([], decimal=True)
NUMERIC_WITH_DECIMAL = NUMERIC + DECIMAL_64
STRING_SIG = TypeSig([T.StringType])
DATETIME = TypeSig([T.DateType, T.TimestampType])
NULL_SIG = TypeSig([T.NullType])

# scalar types every op can handle
ALL_SUPPORTED = (BOOLEAN + NUMERIC + DECIMAL_64 + STRING_SIG + DATETIME +
                 NULL_SIG)
ARRAY_SIG = TypeSig([T.ArrayType])
STRUCT_SIG = TypeSig([T.StructType])
MAP_SIG = TypeSig([T.MapType])
# scalars + arrays of them: only for ops that understand ListColumn
# (references, aliases, the collection expressions)
WITH_ARRAYS = ALL_SUPPORTED + ARRAY_SIG
# everything device-resident incl. structs and maps (nested leaves must
# themselves be supported — TypeSig.supports recurses)
WITH_NESTED = WITH_ARRAYS + STRUCT_SIG + MAP_SIG
# orderable == groupable == joinable (canonical key words cover scalars
# only; nested types cannot be sort/join keys yet)
ORDERABLE = ALL_SUPPORTED


# ---------------------------------------------------------------------------
# per-parameter signatures (ExprChecks role, TypeChecks.scala:879)
# ---------------------------------------------------------------------------

class ParamSig:
    """One named parameter's accepted types (+ partial-support note)."""

    def __init__(self, name: str, sig: TypeSig, note: str = ""):
        self.name = name
        self.sig = sig
        self.note = note


class ExprSig:
    """Per-parameter + output type contract for one expression class.

    Reference: ExprChecks (TypeChecks.scala:879) — each GPU expression
    declares what each input parameter accepts and what it produces;
    tagging walks ACTUAL child dtypes against the matching parameter
    instead of only checking the output type.  ``repeat_last`` covers
    variadic tails (Coalesce, Least, CreateArray...).
    """

    def __init__(self, params: list, output: TypeSig,
                 repeat_last: bool = False, note: str = "",
                 check_params: bool = True):
        self.params = list(params)
        self.output = output
        self.repeat_last = repeat_last
        self.note = note
        self.check_params = check_params

    @classmethod
    def uniform(cls, sig: TypeSig) -> "ExprSig":
        """Back-compat wrapper: output-type check only (legacy rules
        never constrained parameters; per-param contracts register an
        explicit ExprSig instead)."""
        return cls([ParamSig("input", sig)], sig, repeat_last=True,
                   check_params=False)

    def _param_for(self, i: int) -> Optional[ParamSig]:
        if i < len(self.params):
            return self.params[i]
        if self.repeat_last and self.params:
            return self.params[-1]
        return None

    def describe(self) -> str:
        if not self.check_params:
            return self.output.describe()
        parts = [f"{p.name}: {p.sig.describe()}" for p in self.params]
        return "; ".join(parts) + f" -> {self.output.describe()}"

    def reasons_for(self, expr) -> list:
        out = []
        cls_name = type(expr).__name__
        try:
            dt = expr.dtype()
        except (ValueError, NotImplementedError) as e:
            return [f"{cls_name}: {e}"]
        r = self.output.reason(dt, f"{cls_name} output")
        if r:
            out.append(r)
        if not self.check_params:
            return out
        for i, c in enumerate(expr.children):
            p = self._param_for(i)
            if p is None:
                out.append(f"{cls_name}: unexpected argument {i}")
                continue
            try:
                cdt = c.dtype()
            except (ValueError, NotImplementedError):
                continue
            if not p.sig.supports(cdt):
                note = f" ({p.note})" if p.note else ""
                out.append(f"{cls_name} parameter '{p.name}': type "
                           f"{cdt.name} is not supported on TPU{note}")
        return out


#: a string predicate with a literal pattern (LIKE, StartsWith, EndsWith,
#: Contains): a STRING and its pattern in, a BOOLEAN out.  The uniform
#: string rule checked the BOOLEAN output against the string signature,
#: which refused every such predicate on the device, in a Filter and in
#: a join's condition alike (TPC-H Q13's ON clause)
PATTERN_PREDICATE = ExprSig([ParamSig("str", STRING_SIG),
                             ParamSig("pattern", STRING_SIG)], BOOLEAN)


# ---------------------------------------------------------------------------
# cast-pair support matrix (CastChecks role, TypeChecks.scala:367)
# ---------------------------------------------------------------------------

def _family(dt: T.DType) -> str:
    if isinstance(dt, T.DecimalType):
        return "decimal"
    if isinstance(dt, (T.ArrayType, T.StructType, T.MapType)):
        return "nested"
    if dt == T.BOOL:
        return "bool"
    if dt.is_integral:
        return "integral"
    if dt.is_fractional:
        return "fp"
    if dt == T.STRING:
        return "string"
    if dt == T.DATE:
        return "date"
    if dt == T.TIMESTAMP:
        return "timestamp"
    if dt == T.NULL:
        return "null"
    return "other"


#: (from_family, to_family) -> None (supported) | reason note.
#: Mirrors the reference's sparse cast matrix: everything listed as a
#: key is a cast the engine has an implementation for; absent pairs tag
#: the plan node to the CPU engine.
CAST_MATRIX = {}


def _allow(src: str, dsts: str, note: str = ""):
    for d in dsts.split():
        CAST_MATRIX[(src, d)] = note or None


_allow("bool", "bool integral fp string")
_allow("integral", "bool integral fp decimal string timestamp")
_allow("fp", "bool integral fp decimal string",
       "fp->string formats with Spark's toString rules")
_allow("decimal", "integral fp decimal string")
_allow("string", "bool integral fp decimal date timestamp string",
       "string->fp/date/timestamp follow Spark parsing; malformed "
       "values become NULL")
_allow("date", "date timestamp string integral")
_allow("timestamp", "date timestamp string integral fp")
_allow("null", "bool integral fp decimal string date timestamp null "
               "nested")


def cast_reason(src: T.DType, dst: T.DType) -> Optional[str]:
    """None when CAST(src AS dst) runs on the TPU; else the reason."""
    key = (_family(src), _family(dst))
    if key[0] == key[1] and key[0] == "nested":
        return "nested-to-nested casts are not supported on TPU"
    if key in CAST_MATRIX:
        return None
    return (f"Cast {src.name} -> {dst.name} is not supported on TPU")


def cast_note(src: T.DType, dst: T.DType) -> Optional[str]:
    return CAST_MATRIX.get((_family(src), _family(dst)))
