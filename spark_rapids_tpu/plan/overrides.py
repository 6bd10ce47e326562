"""The planner: wrap -> tag -> convert, with explain and CPU fallback.

Reference: GpuOverrides.scala:3100 (apply/applyOverrides), RapidsMeta.scala
(wrapping/tagging framework), GpuTransitionOverrides.scala (transition
insertion).  Differences are structural, not conceptual: the logical plan
is ours (no Catalyst), and the CPU engine is the pyarrow fallback rather
than stock Spark.

Pipeline:
  1. wrap every logical node in a PlanMeta; every expression in ExprMeta
  2. tag: type checks (TypeSig), conf enables, per-op constraints; record
     human-readable reasons (spark.rapids.tpu.sql.explain)
  3. convert: tagged-ok nodes become TPU execs with exchanges inserted
     (partial/final aggregation, hash-partitioned joins, range-partitioned
     global sorts); tagged-out nodes become CPU execs with
     RowToColumnar/ColumnarToRow transitions fused at the boundaries
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Type

from ..columnar import dtypes as T
from ..config import (TpuConf, SQL_ENABLED, EXPLAIN, SHUFFLE_PARTITIONS,
                      TEST_ENABLED, DECIMAL_ENABLED, CAST_STRING_TO_FLOAT,
                      BATCH_SIZE_ROWS, SHUFFLE_MODE, ADAPTIVE_ENABLED,
                      ADAPTIVE_TARGET_PARTITION_BYTES,
                      ADAPTIVE_BROADCAST_BYTES, ADAPTIVE_SKEW_FACTOR,
                      ADAPTIVE_SKEW_MIN_BYTES)
from ..expr import core as ec
from ..expr import (aggregates as eagg, arithmetic as ea, cast as ecast,
                    conditional as econd, datetime as edt, misc as emisc,
                    predicates as ep, string_ops as es)
from . import logical as L
from . import typesig as TS
from ..exec import cpu as X
from ..exec import tpu_basic as TB
from ..exec import tpu_aggregate as TA
from ..exec import tpu_join as TJ
from ..exec import tpu_sort as TSOR
from ..exec import exchange as EX
from ..exec.base import PhysicalPlan
from ..shuffle.partitioners import (HashPartitioner, RangePartitioner,
                                    RoundRobinPartitioner,
                                    SinglePartitioner)

BROADCAST_ROW_THRESHOLD = 1 << 20  # rows; stand-in for byte-size stats


def _scan_row_estimate(p, conf=None) -> "Optional[int]":
    """Row-count estimate for file scans (parquet metadata is cheap)."""
    if getattr(p, "_row_estimate", None) is not None:
        return p._row_estimate
    try:
        if p.fmt == "parquet":
            import pyarrow.parquet as papq
            from ..io.readers import expand_paths
            total = 0
            for f in expand_paths(p.paths, conf):
                total += papq.ParquetFile(f).metadata.num_rows
            p._row_estimate = total
            return total
    except Exception:
        pass
    p._row_estimate = None
    return None


# ---------------------------------------------------------------------------
# expression rules (the expr[...] registry, GpuOverrides.scala:773)
# ---------------------------------------------------------------------------

_EXPR_RULES: Dict[Type[ec.Expression], "TS.ExprSig"] = {}


def expr_rule(cls, sig):
    """Register an expression rule: a plain TypeSig (uniform across
    params, back-compat) or a per-parameter ExprSig
    (TypeChecks.scala:879 ExprChecks role)."""
    _EXPR_RULES[cls] = sig if isinstance(sig, TS.ExprSig) else \
        TS.ExprSig.uniform(sig)


for _cls in [ec.AttributeReference, ec.BoundReference, ec.Literal, ec.Alias]:
    expr_rule(_cls, TS.WITH_NESTED)
for _cls in [ea.Add, ea.Subtract, ea.Multiply, ea.Divide, ea.IntegralDivide,
             ea.Remainder, ea.Pmod, ea.UnaryMinus, ea.UnaryPositive, ea.Abs,
             ea.Least, ea.Greatest, ea.Round]:
    expr_rule(_cls, TS.NUMERIC_WITH_DECIMAL)
for _cls in [ea.Sqrt, ea.Exp, ea.Expm1, ea.Log, ea.Log1p, ea.Log2, ea.Log10,
             ea.Sin, ea.Cos, ea.Tan, ea.Asin, ea.Acos, ea.Atan, ea.Sinh,
             ea.Cosh, ea.Tanh, ea.Asinh, ea.Acosh, ea.Atanh, ea.Cbrt,
             ea.ToDegrees, ea.ToRadians, ea.Rint, ea.Signum, ea.Floor,
             ea.Ceil, ea.Pow, ea.Atan2]:
    expr_rule(_cls, TS.NUMERIC)
for _cls in [ea.BitwiseAnd, ea.BitwiseOr, ea.BitwiseXor, ea.BitwiseNot,
             ea.ShiftLeft, ea.ShiftRight, ea.ShiftRightUnsigned]:
    expr_rule(_cls, TS.INTEGRAL)
for _cls in [ep.EqualTo, ep.EqualNullSafe, ep.LessThan, ep.LessThanOrEqual,
             ep.GreaterThan, ep.GreaterThanOrEqual, ep.In]:
    expr_rule(_cls, TS.ORDERABLE)
for _cls in [ep.Not, ep.And, ep.Or]:
    expr_rule(_cls, TS.BOOLEAN)
for _cls in [ep.IsNull, ep.IsNotNull]:
    expr_rule(_cls, TS.ALL_SUPPORTED)
expr_rule(ep.IsNaN, TS.FP)
for _cls in [econd.If, econd.CaseWhen, econd.Coalesce, econd.NaNvl]:
    expr_rule(_cls, TS.ALL_SUPPORTED)
expr_rule(ecast.Cast, TS.ALL_SUPPORTED)
for _cls in [es.Upper, es.Lower, es.Length, es.Substring, es.StartsWith,
             es.EndsWith, es.Contains, es.Like, es.RLike, es.ConcatStrings,
             es.StringTrim, es.StringTrimLeft, es.StringTrimRight,
             es.Replace, es.Reverse, es.StringRepeat, es.Lpad, es.Rpad,
             es.InitCap, es.StringLocate, es.ConcatWs, es.RegexpReplace,
             es.RegexpExtract]:
    expr_rule(_cls, TS.STRING_SIG)
for _cls in [edt.Year, edt.Month, edt.DayOfMonth, edt.Quarter, edt.DayOfWeek,
             edt.WeekDay, edt.DayOfYear, edt.LastDay, edt.Hour, edt.Minute,
             edt.Second, edt.DateAdd, edt.DateSub, edt.DateDiff,
             edt.UnixTimestampToSeconds, edt.ToDate]:
    expr_rule(_cls, TS.DATETIME + TS.INTEGRAL)
for _cls in [emisc.Murmur3Hash, emisc.Md5, emisc.MonotonicallyIncreasingID,
             emisc.SparkPartitionID, emisc.Rand]:
    expr_rule(_cls, TS.ALL_SUPPORTED)

# -- refined per-parameter contracts (ExprChecks role, the rules above
# keep the legacy output-only check; these override with full param
# signatures like TypeChecks.scala:879 declares per GPU expression) ----
_P = TS.ParamSig
expr_rule(es.Substring, TS.ExprSig(
    [_P("str", TS.STRING_SIG), _P("pos", TS.INTEGRAL),
     _P("len", TS.INTEGRAL)], TS.STRING_SIG))
expr_rule(es.StringLocate, TS.ExprSig(
    [_P("substr", TS.STRING_SIG), _P("str", TS.STRING_SIG),
     _P("start", TS.INTEGRAL)], TS.INTEGRAL))
expr_rule(es.Lpad, TS.ExprSig(
    [_P("str", TS.STRING_SIG), _P("len", TS.INTEGRAL),
     _P("pad", TS.STRING_SIG)], TS.STRING_SIG,
    note="pad runs on the host string path"))
expr_rule(es.Rpad, TS.ExprSig(
    [_P("str", TS.STRING_SIG), _P("len", TS.INTEGRAL),
     _P("pad", TS.STRING_SIG)], TS.STRING_SIG,
    note="pad runs on the host string path"))
for _cls in [es.Like, es.StartsWith, es.EndsWith, es.Contains]:
    expr_rule(_cls, TS.PATTERN_PREDICATE)
expr_rule(es.StringRepeat, TS.ExprSig(
    [_P("str", TS.STRING_SIG), _P("n", TS.INTEGRAL)], TS.STRING_SIG))
expr_rule(es.RegexpExtract, TS.ExprSig(
    [_P("str", TS.STRING_SIG), _P("regexp", TS.STRING_SIG),
     _P("idx", TS.INTEGRAL)], TS.STRING_SIG,
    note="pattern must be a literal; host regex engine"))
expr_rule(es.RegexpReplace, TS.ExprSig(
    [_P("str", TS.STRING_SIG), _P("regexp", TS.STRING_SIG),
     _P("rep", TS.STRING_SIG)], TS.STRING_SIG,
    note="pattern must be a literal; host regex engine"))
expr_rule(edt.DateAdd, TS.ExprSig(
    [_P("start", TS.DATETIME), _P("days", TS.INTEGRAL)], TS.DATETIME))
expr_rule(edt.DateSub, TS.ExprSig(
    [_P("start", TS.DATETIME), _P("days", TS.INTEGRAL)], TS.DATETIME))
expr_rule(edt.DateDiff, TS.ExprSig(
    [_P("end", TS.DATETIME), _P("start", TS.DATETIME)], TS.INTEGRAL))
expr_rule(ep.And, TS.ExprSig(
    [_P("lhs", TS.BOOLEAN), _P("rhs", TS.BOOLEAN)], TS.BOOLEAN))
expr_rule(ep.Or, TS.ExprSig(
    [_P("lhs", TS.BOOLEAN), _P("rhs", TS.BOOLEAN)], TS.BOOLEAN))
expr_rule(ep.Not, TS.ExprSig([_P("input", TS.BOOLEAN)], TS.BOOLEAN))
expr_rule(econd.If, TS.ExprSig(
    [_P("predicate", TS.BOOLEAN), _P("trueValue", TS.ALL_SUPPORTED),
     _P("falseValue", TS.ALL_SUPPORTED)], TS.ALL_SUPPORTED))
for _cls in [eagg.Sum, eagg.Count, eagg.Min, eagg.Max, eagg.Average,
             eagg.First, eagg.Last, eagg.StddevSamp, eagg.StddevPop,
             eagg.VarianceSamp, eagg.VariancePop, eagg.PivotFirst]:
    expr_rule(_cls, TS.ALL_SUPPORTED)
# device collect: lists assemble from the sort+segment plan; set dedupe
# needs single-word value encoding, so string elements stay on CPU
expr_rule(eagg.CollectList, TS.ExprSig(
    [TS.ParamSig("input", TS.ALL_SUPPORTED)], TS.WITH_ARRAYS))
expr_rule(eagg.CollectSet, TS.ExprSig(
    [TS.ParamSig("input", TS.BOOLEAN + TS.NUMERIC + TS.DATETIME +
                 TS.DECIMAL_64,
                 note="string elements run on the CPU engine")],
    TS.WITH_ARRAYS))
# collection expressions (collectionOperations.scala registrations,
# GpuOverrides.scala:773+)
from ..expr import collections as ecoll  # noqa: E402
for _cls in [ecoll.CreateArray, ecoll.SortArray, ecoll.Explode]:
    expr_rule(_cls, TS.WITH_ARRAYS)
expr_rule(ecoll.GetArrayItem, TS.ExprSig(
    [_P("array", TS.WITH_ARRAYS), _P("ordinal", TS.INTEGRAL)],
    TS.WITH_ARRAYS + TS.ALL_SUPPORTED))
expr_rule(ecoll.ElementAt, TS.ExprSig(
    [_P("array", TS.WITH_ARRAYS), _P("index", TS.INTEGRAL)],
    TS.WITH_ARRAYS + TS.ALL_SUPPORTED))
expr_rule(ecoll.Size, TS.WITH_ARRAYS + TS.INTEGRAL)
# struct/map expressions (complexTypeCreator/Extractors.scala)
for _cls in [ecoll.CreateNamedStruct, ecoll.GetStructField,
             ecoll.CreateMap, ecoll.GetMapValue, ecoll.MapKeys,
             ecoll.MapValues, ecoll.ExtractValue]:
    expr_rule(_cls, TS.WITH_NESTED)
expr_rule(ecoll.ArrayContains, TS.BOOLEAN)
expr_rule(ecoll.ArrayMin, TS.NUMERIC + TS.DATETIME + TS.BOOLEAN)
expr_rule(ecoll.ArrayMax, TS.NUMERIC + TS.DATETIME + TS.BOOLEAN)

# Python UDFs stay on the columnar plan with an Arrow host exchange,
# the GpuArrowEvalPythonExec model (SURVEY.md §2.8)
from ..udf.python_udf import PythonUDF as _PyUDF, PandasUDF as _PdUDF  # noqa: E402
expr_rule(_PyUDF, TS.ALL_SUPPORTED)
expr_rule(_PdUDF, TS.ALL_SUPPORTED)
# native device UDFs (RapidsUDF.java / GpuScalaUDF role)
from ..udf.native_udf import TpuUDFExpression as _TpuUDF  # noqa: E402
expr_rule(_TpuUDF, TS.WITH_NESTED)
from ..expr import window_funcs as _wfn  # noqa: E402
for _cls in [_wfn.RowNumber, _wfn.Rank, _wfn.DenseRank, _wfn.Lead,
             _wfn.Lag]:
    expr_rule(_cls, TS.ALL_SUPPORTED)


class ExprMeta:
    """Per-expression tagging (BaseExprMeta role, RapidsMeta.scala:686)."""

    def __init__(self, expr: ec.Expression, conf: TpuConf):
        self.expr = expr
        self.conf = conf
        self.reasons: List[str] = []
        self.children = [ExprMeta(c, conf) for c in expr.children]

    # ops that canonical-key-encode their inputs: inputs must be ORDERABLE
    # scalars (the per-param TypeSig role of the reference's ExprChecks)
    _KEY_ENCODING = (ep.EqualTo, ep.EqualNullSafe, ep.LessThan,
                     ep.LessThanOrEqual, ep.GreaterThan,
                     ep.GreaterThanOrEqual, ep.In, emisc.Murmur3Hash)

    def tag(self):
        cls = type(self.expr)
        rule = _EXPR_RULES.get(cls)
        if rule is None:
            self.reasons.append(
                f"expression {cls.__name__} has no TPU implementation")
        else:
            self.reasons.extend(rule.reasons_for(self.expr))
        if isinstance(self.expr, self._KEY_ENCODING):
            for c in self.expr.children:
                try:
                    cdt = c.dtype()
                except (ValueError, NotImplementedError):
                    continue
                if not TS.ORDERABLE.supports(cdt):
                    self.reasons.append(
                        f"{cls.__name__}: input type {cdt.name} cannot be "
                        f"key-encoded on TPU")
        if isinstance(self.expr, ecast.Cast):
            src = self.expr.children[0].dtype()
            # cast-pair matrix (CastChecks role, TypeChecks.scala:367):
            # pairs absent from the matrix tag the node to the CPU
            r = TS.cast_reason(src, self.expr.to)
            if r:
                self.reasons.append(r)
            if (src == T.STRING and self.expr.to.is_fractional and
                    not self.conf.get(CAST_STRING_TO_FLOAT)):
                self.reasons.append(
                    "Cast string->float disabled: set "
                    "spark.rapids.tpu.sql.castStringToFloat.enabled=true")
        if isinstance(self.expr.dtype() if not self.reasons else None,
                      T.DecimalType) and not self.conf.get(DECIMAL_ENABLED):
            self.reasons.append("decimal support disabled by conf")
        for c in self.children:
            c.tag()

    @property
    def can_replace(self) -> bool:
        return not self.reasons and all(c.can_replace for c in self.children)

    def all_reasons(self) -> List[str]:
        out = list(self.reasons)
        for c in self.children:
            out.extend(c.all_reasons())
        return out


# ---------------------------------------------------------------------------
# plan metas
# ---------------------------------------------------------------------------

class PlanMeta:
    """SparkPlanMeta role (RapidsMeta.scala:512)."""

    def __init__(self, plan: L.LogicalPlan, conf: TpuConf):
        self.plan = plan
        self.conf = conf
        self.reasons: List[str] = []
        self.children = [PlanMeta(c, conf) for c in plan.children]
        self.expr_metas: List[ExprMeta] = [
            ExprMeta(e, conf) for e in self._expressions()]

    def _expressions(self) -> List[ec.Expression]:
        p = self.plan
        if isinstance(p, L.Project):
            return list(p.exprs)
        if isinstance(p, L.Filter):
            return [p.condition]
        if isinstance(p, L.Aggregate):
            return list(p.group_exprs) + [a.func for a in p.aggs]
        if isinstance(p, L.Join):
            out = list(p.left_keys) + list(p.right_keys)
            if p.condition is not None:
                out.append(p.condition)
            return out
        if isinstance(p, L.Sort):
            return [o.expr for o in p.orders]
        if isinstance(p, L.Repartition):
            return list(p.by_exprs or [])
        if isinstance(p, L.Generate):
            return [p.generator]
        if isinstance(p, L.GroupedMapInPandas):
            return list(p.keys)
        if isinstance(p, L.Expand):
            return [e for proj in p.projections for e in proj]
        if isinstance(p, L.Window):
            out = []
            for wf in p.window_funcs:
                out.append(wf.func)
                out.extend(wf.spec.partition_by)
                out.extend(o.expr for o in wf.spec.order_by)
            return out
        return []

    def tag(self):
        if not self.conf.get(SQL_ENABLED):
            self.reasons.append("spark.rapids.tpu.sql.enabled is false")
        for em in self.expr_metas:
            em.tag()
            self.reasons.extend(em.all_reasons())
        # per-node checks
        p = self.plan
        for f in p.schema:
            if not TS.WITH_NESTED.supports(f.dtype) and \
                    f.dtype.is_nested:
                self.reasons.append(
                    f"output column {f.name}: nested type {f.dtype.name} "
                    f"not yet device-resident")
        # array columns may flow through, but cannot be sort/group/join/
        # partition keys (canonical key words cover scalars only)
        def _keys_orderable(exprs, what):
            for e in exprs:
                try:
                    dt = e.dtype()
                except (ValueError, NotImplementedError):
                    continue
                if not TS.ORDERABLE.supports(dt):
                    self.reasons.append(
                        f"{what} key of type {dt.name} not supported on TPU")
        if isinstance(p, L.Aggregate):
            _keys_orderable(p.group_exprs, "group-by")
        if isinstance(p, L.Distinct):
            _keys_orderable(
                [ec.AttributeReference(f.name, f.dtype, f.nullable)
                 for f in p.schema], "distinct")
        if isinstance(p, L.Sort):
            _keys_orderable([o.expr for o in p.orders], "sort")
        if isinstance(p, L.Join):
            _keys_orderable(list(p.left_keys) + list(p.right_keys), "join")
        if isinstance(p, L.Repartition):
            _keys_orderable(list(p.by_exprs or []), "partition")
        if isinstance(p, L.Window):
            for wf in p.window_funcs:
                _keys_orderable(wf.spec.partition_by, "window partition")
                _keys_orderable([o.expr for o in wf.spec.order_by],
                                "window order")
        if isinstance(p, L.Window):
            from ..expr import window_funcs as wfn
            for wf in p.window_funcs:
                f = wf.func
                ok = isinstance(f, (wfn.RowNumber, wfn.Rank, wfn.DenseRank,
                                    wfn.Lead, wfn.Lag, wfn.NTile,
                                    wfn.PercentRank, wfn.CumeDist,
                                    eagg.Sum, eagg.Count,
                                    eagg.Min, eagg.Max, eagg.Average,
                                    eagg.CollectList))
                if not ok:
                    self.reasons.append(
                        f"window function {f.name} not implemented on TPU")
                if f.children and f.children[0].dtype() == T.STRING and \
                        isinstance(f, (eagg.Sum, eagg.Min, eagg.Max,
                                       eagg.Average)):
                    self.reasons.append(
                        "string window aggregates not on TPU yet")
                kind, lo, hi = wf.spec.frame
                if kind == "range" and not (lo is None and hi is None) \
                        and isinstance(f, eagg.AggregateFunction):
                    # frames only bind aggregate window functions;
                    # the rank family ignores them (Spark semantics) —
                    # SQL's default RANGE frame must not knock
                    # row_number/rank/lead/lag off the TPU
                    # bounded RANGE: rank-search covers a single
                    # integral/decimal/date/timestamp order key with
                    # sum/count/avg/min/max/collect_list
                    # (tpu_window._range_positions; the reference's own
                    # bounded-RANGE support is one numeric key,
                    # GpuWindowExpression.scala)
                    ok_range = (
                        len(wf.spec.order_by) == 1 and
                        isinstance(f, (eagg.Sum, eagg.Count,
                                       eagg.Average, eagg.Min, eagg.Max,
                                       eagg.CollectList)))
                    if ok_range:
                        odt = wf.spec.order_by[0].expr.dtype()
                        ok_range = odt.is_integral or odt in (
                            T.DATE, T.TIMESTAMP) or isinstance(
                            odt, T.DecimalType)
                    if not ok_range:
                        self.reasons.append(
                            "RANGE frame limited to one "
                            "integral/decimal/date order key on TPU")
        for c in self.children:
            c.tag()

    @property
    def can_replace(self) -> bool:
        return not self.reasons

    # -- explain (RapidsMeta.explain role) ---------------------------------
    def explain(self, all_nodes: bool = False, indent: int = 0) -> str:
        pad = "  " * indent
        mark = "*" if self.can_replace else "!"
        line = f"{pad}{mark} {self.plan._node_string()}"
        if not self.can_replace:
            for r in self.reasons:
                line += f"\n{pad}    cannot run on TPU: {r}"
        out = [line] if (all_nodes or not self.can_replace) else []
        for c in self.children:
            sub = c.explain(all_nodes, indent + 1)
            if sub:
                out.append(sub)
        return "\n".join(out)


# ---------------------------------------------------------------------------
# conversion
# ---------------------------------------------------------------------------

def _as_columnar(p: PhysicalPlan) -> PhysicalPlan:
    return p if p.columnar else TB.RowToColumnar(p)


def _as_cpu(p: PhysicalPlan) -> PhysicalPlan:
    return TB.ColumnarToRow(p) if p.columnar else p


class Planner:
    """applyOverrides + transitions, producing an executable physical plan."""

    def __init__(self, conf: TpuConf):
        self.conf = conf
        self.default_partitions = conf.get(SHUFFLE_PARTITIONS)
        self.batch_rows = conf.get(BATCH_SIZE_ROWS)
        self.fallbacks: List[str] = []
        # plan decisions that silently REDUCE parallelism (a coalesce
        # to one partition): surfaced in explain + logged, so a query
        # that just went single-stream says so (round-3 Weak #9)
        self.parallelism_warnings: List[str] = []
        self._placement = None

    def _warn_collapse(self, why: str):
        self.parallelism_warnings.append(why)
        import logging
        logging.getLogger(__name__).warning(
            "parallelism collapse: %s (plan coalesces to ONE "
            "partition)", why)

    def plan(self, logical: L.LogicalPlan, *,
             skip_verify: bool = False) -> PhysicalPlan:
        # ``skip_verify=True`` is the plan cache's certificate-replay
        # path (cache/plan_cache.py): the full structural pipeline
        # still runs on the INCOMING logical plan (fresh literals are
        # correct by construction), but the invariant verifier passes
        # are skipped because the cached entry carries the verdict of a
        # fingerprint-identical plan — the caller MUST validate the
        # rebuilt plan_fingerprint against the stored one before
        # trusting the result.
        #
        # ColumnPruning (Catalyst does this before the reference plugin
        # sees the plan): narrow file scans to referenced columns so the
        # readers neither decode nor upload dead columns
        from .logical_opt import prune_scan_columns
        logical = prune_scan_columns(logical)
        meta = PlanMeta(logical, self.conf)
        meta.tag()
        from ..config import CBO_ENABLED
        self._placement = None
        if self.conf.get(CBO_ENABLED):
            from .cbo import choose_placement
            self._placement = choose_placement(logical, self.conf)
        mode = self.conf.get(EXPLAIN).upper()
        explain_on = mode in ("NOT_ON_TPU", "ALL")
        if explain_on:
            text = meta.explain(all_nodes=(mode == "ALL"))
            if text:
                print(text)
        phys = self._convert(meta)
        if explain_on:
            for w in self.parallelism_warnings:
                print(f"! parallelism: {w}")
        phys = self._collapse_stages(phys)
        self._mark_deferred_verify(phys, parent=None)
        if self.conf.get(TEST_ENABLED):
            self._assert_all_tpu(phys)
        from ..config import PLAN_VERIFY
        verify_on = (not skip_verify) and (
            self.conf.get(PLAN_VERIFY) or os.environ.get(
                "SPARK_RAPIDS_TPU_FORCE_PLAN_VERIFY"))
        if verify_on:
            from ..analysis.plan_verify import verify_or_raise
            verify_or_raise(phys)
        # superstage carving is a post-pass over the VERIFIED plan: it
        # only rearranges dispatch (wrappers + sync-free flags), so the
        # invariant passes above see the uncarved operator tree and the
        # PV-STAGE re-verify below checks the carving contracts
        from ..config import SUPERSTAGE
        if self.conf.get(SUPERSTAGE):
            from ..compile import carve_plan
            phys = carve_plan(phys, self.conf)
            if verify_on:
                from ..analysis.plan_verify import STAGE, verify_or_raise
                verify_or_raise(phys, passes=[STAGE])
        return phys

    # -- deferred-verification marking ------------------------------------
    def _mark_deferred_verify(self, node: PhysicalPlan, parent):
        """Allow a FINAL/COMPLETE aggregate to hand its speculative fit
        flag and unresolved group count to the NEXT flush barrier
        instead of forcing a round trip of its own — but only when its
        direct consumer provably verifies: the session collect (root),
        an exchange (verify-at-flush), or a hash join (verifies stream
        batches after its phase-A flush).  Everything else — including
        projections, which re-evaluate columns into fresh batches and
        would silently DROP the speculative flag — consumes the batch
        without verifying, so the aggregate keeps its own barrier
        there."""
        from ..exec import tpu_aggregate as TA
        from ..exec import tpu_join as TJ
        from ..exec import exchange as TX
        from ..exec import tpu_sort as TS
        safe_types = [TX.TpuShuffleExchange,
                      TX.TpuBroadcastExchange,
                      TJ.TpuHashJoinBase,
                      # TopN re-attaches the speculative
                      # flag to its own (sorted, head-n)
                      # output with a redo chain, so the
                      # verify rides the NEXT barrier
                      TS.TpuTopN]
        from ..config import SUPERSTAGE
        if self.conf.get(SUPERSTAGE):
            # superstage mode: TpuSort resolves speculative inputs at
            # its own count pull (same fused flush), so an aggregate
            # under a sort may defer too — the quartet's agg->sort edge
            safe_types.append(TS.TpuSort)
        safe = parent is None or isinstance(parent, tuple(safe_types))
        if isinstance(node, TA.TpuHashAggregate) and \
                node.mode in (TA.FINAL, TA.COMPLETE):
            node.allow_deferred_verify = safe
        for c in node.children:
            self._mark_deferred_verify(c, parent=node)

    # -- whole-stage collapse (GpuTransitionOverrides-style post-pass) ----
    def _collapse_stages(self, node: PhysicalPlan) -> PhysicalPlan:
        """Fuse TpuFilter/TpuProject chains into TpuStagedCompute, and
        fold a leading chain into the hash aggregate's fused core — one
        program launch per batch per stage (exec/staged.py)."""
        from ..exec.staged import TpuStagedCompute
        from ..exec import tpu_aggregate as TA
        node.children = [self._collapse_stages(c) for c in node.children]
        chain = []
        cur = node
        while isinstance(cur, (TB.TpuFilter, TB.TpuProject)):
            chain.append(cur)
            cur = cur.children[0]
        # children were collapsed first, so an already-built staged node
        # below the chain merges in (a 3+-op chain must stay ONE launch)
        absorbed = None
        if chain and isinstance(cur, TpuStagedCompute):
            absorbed = cur
            cur = cur.children[0]
        if len(chain) >= 2 or (chain and absorbed is not None):
            ops = list(absorbed.ops) if absorbed is not None else []
            for n in reversed(chain):
                src = n.children[0].output_schema
                if isinstance(n, TB.TpuFilter):
                    ops.append(("filter", n.condition.bind(src),
                                n.output_schema))
                else:
                    ops.append(("project",
                                [e.bind(src) for e in n.exprs],
                                n.output_schema))
            node = TpuStagedCompute(cur, ops, cur.output_schema)
        if isinstance(node, TA.TpuHashAggregate) and \
                node.mode in (TA.PARTIAL, TA.COMPLETE):
            child = node.children[0]
            ops = None
            if isinstance(child, TpuStagedCompute):
                ops = child.ops
                src = child.children[0]
            elif isinstance(child, (TB.TpuFilter, TB.TpuProject)):
                s = child.children[0].output_schema
                if isinstance(child, TB.TpuFilter):
                    ops = [("filter", child.condition.bind(s),
                            child.output_schema)]
                else:
                    ops = [("project", [e.bind(s) for e in child.exprs],
                            child.output_schema)]
                src = child.children[0]
            if ops is not None:
                node.pre_ops = ops
                node.children = [src]
        return node

    # ------------------------------------------------------------------
    def _convert(self, meta: PlanMeta) -> PhysicalPlan:
        p = meta.plan
        if not meta.can_replace:
            self.fallbacks.append(
                f"{p.name}: {'; '.join(meta.reasons[:3])}")
            return self._convert_cpu(meta)
        if self._placement is not None and \
                self._placement.get(id(p)) == "cpu":
            self.fallbacks.append(
                f"{p.name}: cost model placed this subtree on CPU "
                f"(transition-aware placement)")
            return self._convert_cpu(meta)
        children = [self._convert(c) for c in meta.children]
        return self._convert_tpu(meta, p, children)

    def _convert_cpu(self, meta: PlanMeta) -> PhysicalPlan:
        """Run this node on the CPU engine; children still plan normally."""
        p = meta.plan
        children = [_as_cpu(self._convert(c)) for c in meta.children]
        if isinstance(p, L.LocalRelation):
            return X.CpuLocalScan(p.table, p.num_partitions)
        if isinstance(p, L.Range):
            return X.CpuRange(p.start, p.end, p.step, p.num_partitions)
        if isinstance(p, L.Project):
            return X.CpuProject(p.exprs, children[0])
        if isinstance(p, L.Filter):
            return X.CpuFilter(p.condition, children[0])
        if isinstance(p, L.Aggregate):
            return X.CpuAggregate(p.group_exprs, p.aggs, children[0])
        if isinstance(p, L.Join):
            return X.CpuJoin(p, children[0], children[1])
        if isinstance(p, L.Sort):
            return X.CpuSort(p.orders, children[0], p.is_global)
        if isinstance(p, L.Limit):
            return X.CpuLimit(p.n, children[0], p.offset)
        if isinstance(p, L.Union):
            return X.CpuUnion(*children)
        if isinstance(p, L.Distinct):
            agg = L.Aggregate(
                [ec.AttributeReference(f.name, f.dtype, f.nullable)
                 for f in p.schema], [], p.children[0])
            return X.CpuAggregate(agg.group_exprs, [], children[0])
        if isinstance(p, L.Repartition):
            return X.CpuShuffleExchange(children[0], p.num_partitions,
                                        p.by_exprs)
        if isinstance(p, L.Window):
            from ..exec.cpu_window import CpuWindow
            return CpuWindow(p, children[0])
        if isinstance(p, L.Generate):
            return X.CpuGenerate(p, children[0])
        if isinstance(p, L.Expand):
            return X.CpuExpand(p, children[0])
        if isinstance(p, L.CachedRelation):
            from ..exec.cache import CpuCachedExec
            return CpuCachedExec(p.storage, children[0])
        if isinstance(p, L.MapInPandas):
            from ..exec.python_exec import CpuMapInPandas
            return CpuMapInPandas(p, children[0])
        if isinstance(p, L.GroupedMapInPandas):
            from ..exec.python_exec import CpuGroupedMapInPandas
            return CpuGroupedMapInPandas(p, children[0])
        if isinstance(p, L.CogroupedMapInPandas):
            from ..exec.python_exec import CpuCogroupedMapInPandas
            return CpuCogroupedMapInPandas(p, children[0], children[1])
        if isinstance(p, L.WindowInPandas):
            from ..exec.python_exec import CpuWindowInPandas
            return CpuWindowInPandas(p, children[0])
        if isinstance(p, L.Scan):
            from ..io.planner import cpu_scan_exec
            return cpu_scan_exec(p, self.conf)
        if isinstance(p, L.WriteFile):
            from ..io.planner import cpu_write_exec
            return cpu_write_exec(p, _as_cpu(children[0]), self.conf)
        raise NotImplementedError(f"no CPU conversion for {p.name}")

    # ------------------------------------------------------------------
    def _convert_tpu(self, meta: PlanMeta, p: L.LogicalPlan,
                     children: List[PhysicalPlan]) -> PhysicalPlan:
        children = [_as_columnar(c) for c in children]
        if isinstance(p, L.LocalRelation):
            return TB.TpuLocalScan(p.table, p.num_partitions,
                                   self.batch_rows)
        if isinstance(p, L.Range):
            return TB.TpuRange(p.start, p.end, p.step, p.num_partitions,
                               self.batch_rows)
        if isinstance(p, L.Scan):
            from ..io.planner import tpu_scan_exec
            return tpu_scan_exec(p, self.conf)
        if isinstance(p, L.Project):
            return TB.TpuProject(p.exprs, children[0])
        if isinstance(p, L.Filter):
            child = children[0]
            if isinstance(p.children[0], L.Scan) and \
                    p.children[0].fmt == "parquet":
                from ..io.pushdown import to_arrow_filters
                pushed = to_arrow_filters(p.condition)
                if pushed and hasattr(child, "set_pushed_filters"):
                    child.set_pushed_filters(pushed)
            return TB.TpuFilter(p.condition, child)
        if isinstance(p, L.Aggregate):
            return self._plan_aggregate(p, children[0])
        if isinstance(p, L.Distinct):
            keys = [ec.AttributeReference(f.name, f.dtype, f.nullable)
                    for f in p.schema]
            agg = L.Aggregate(keys, [], p.children[0])
            return self._plan_aggregate(agg, children[0])
        if isinstance(p, L.Join):
            return self._plan_join(p, children[0], children[1])
        if isinstance(p, L.Sort):
            return self._plan_sort(p, children[0])
        if isinstance(p, L.Limit):
            child = p.children[0]
            if isinstance(child, L.Sort) and child.is_global and \
                    p.offset == 0:
                # fuse into TopN over the sort's input
                return TSOR.TpuTopN(p.n, child.orders, children[0].children[0]
                                    if isinstance(children[0], TSOR.TpuSort)
                                    else children[0])
            local = TB.TpuLocalLimit(p.n + p.offset, children[0])
            return TB.TpuGlobalLimit(p.n, EX.TpuCoalescePartitions(local),
                                     p.offset)
        if isinstance(p, L.Union):
            return TB.TpuUnion(*children)
        if isinstance(p, L.Repartition):
            if p.by_exprs:
                part = HashPartitioner(p.by_exprs, p.num_partitions)
            else:
                part = RoundRobinPartitioner(p.num_partitions)
            return EX.TpuShuffleExchange(children[0], part)
        if isinstance(p, L.WriteFile):
            from ..io.planner import tpu_write_exec
            return tpu_write_exec(p, children[0], self.conf)
        if isinstance(p, L.Window):
            return self._plan_window(p, children[0])
        if isinstance(p, L.Expand):
            from ..exec.tpu_expand import TpuExpand
            return TpuExpand(p, children[0])
        if isinstance(p, L.Generate):
            from ..exec.tpu_generate import TpuGenerate
            return TpuGenerate(p, children[0])
        if isinstance(p, L.CachedRelation):
            from ..exec.cache import TpuCachedExec
            return TpuCachedExec(p.storage, children[0])
        if isinstance(p, L.MapInPandas):
            from ..exec.python_exec import TpuMapInPandas
            return TpuMapInPandas(p, children[0])
        if isinstance(p, L.CogroupedMapInPandas):
            from ..exec.python_exec import TpuCogroupedMapInPandas
            return TpuCogroupedMapInPandas(p, children[0], children[1])
        if isinstance(p, L.WindowInPandas):
            from ..exec.python_exec import TpuWindowInPandas
            return TpuWindowInPandas(p, children[0])
        if isinstance(p, L.GroupedMapInPandas):
            from ..exec.python_exec import TpuGroupedMapInPandas
            return TpuGroupedMapInPandas(p, children[0])
        raise NotImplementedError(f"no TPU conversion for {p.name}")

    def _plan_window(self, p: L.Window, child: PhysicalPlan) -> PhysicalPlan:
        from ..exec.tpu_window import TpuWindow
        nparts = child.num_partitions_hint()
        pby = p.window_funcs[0].spec.partition_by
        same_keys = all(
            [repr(e) for e in wf.spec.partition_by] ==
            [repr(e) for e in pby] for wf in p.window_funcs)
        if nparts > 1:
            if pby and same_keys:
                part = HashPartitioner(pby, min(self.default_partitions,
                                                nparts))
                child = self._aqe_read(EX.TpuShuffleExchange(child, part))
            else:
                self._warn_collapse(
                    "window functions with "
                    + ("mixed partition keys" if pby else
                       "no PARTITION BY")
                    + " run single-stream")
                child = EX.TpuCoalescePartitions(child)
        return TpuWindow(p, child)

    def _aqe_read(self, exchange):
        """Wrap an exchange in a coalescing AQE read when enabled
        (GpuCustomShuffleReaderExec insertion, GpuTransitionOverrides
        role)."""
        if not self.conf.get(ADAPTIVE_ENABLED):
            return exchange
        from ..exec.adaptive import TpuAQEShuffleRead
        return TpuAQEShuffleRead(
            exchange, self.conf.get(ADAPTIVE_TARGET_PARTITION_BYTES))

    # -- aggregate: partial -> exchange -> final (aggregate.scala modes) ---
    def _plan_aggregate_mesh(self, p: L.Aggregate, child):
        """shuffle.mode=mesh: the whole group-by as one SPMD program
        (exec/tpu_mesh_aggregate.py) when the shapes allow it."""
        import jax
        from ..exec.tpu_mesh_aggregate import (TpuMeshAggregate,
                                               mesh_aggregate_supported)
        if self.conf.get(SHUFFLE_MODE) != "mesh":
            return None
        try:
            n_dev = jax.device_count()
        except Exception:
            return None
        if not mesh_aggregate_supported(p, n_dev):
            return None
        return TpuMeshAggregate(p, child)

    def _plan_aggregate(self, p: L.Aggregate,
                        child: PhysicalPlan) -> PhysicalPlan:
        mesh_plan = self._plan_aggregate_mesh(p, child)
        if mesh_plan is not None:
            return mesh_plan
        nparts = child.num_partitions_hint()
        if nparts <= 1:
            return TA.TpuHashAggregate(p.group_exprs, p.aggs, child,
                                       mode=TA.COMPLETE)
        partial = TA.TpuHashAggregate(p.group_exprs, p.aggs, child,
                                      mode=TA.PARTIAL)
        buf_schema = partial.output_schema
        if p.group_exprs:
            keys = [ec.AttributeReference(f.name, f.dtype, f.nullable)
                    for f in list(buf_schema)[:len(p.group_exprs)]]
            n = min(self._pick_partitions(p), nparts)
            part = HashPartitioner(keys, n)
            shuffled: PhysicalPlan = self._aqe_read(
                EX.TpuShuffleExchange(partial, part))
        else:
            shuffled = EX.TpuCoalescePartitions(partial)
        return TA.TpuHashAggregate(p.group_exprs, p.aggs, shuffled,
                                   mode=TA.FINAL)

    # -- join strategy selection (GpuOverrides join metas role) ------------
    def _plan_join(self, p: L.Join, left: PhysicalPlan,
                   right: PhysicalPlan) -> PhysicalPlan:
        if p.join_type == "cross" or not p.left_keys:
            return TJ.TpuNestedLoopJoin(p, left, right)
        mesh_plan = self._plan_join_mesh(p, left, right)
        if mesh_plan is not None:
            return mesh_plan
        lsize = self._estimate_rows(p.children[0])
        rsize = self._estimate_rows(p.children[1])
        build_right = p.join_type != "right"
        # inner joins may build on EITHER side: pick the smaller one
        # (GpuShuffledHashJoinMeta's buildSide choice) — building the
        # fact side of a star join forces a full fact-table shuffle
        # where building the dimension side broadcasts it
        if p.join_type == "inner" and lsize is not None and \
                rsize is not None:
            build_right = rsize <= lsize
        # broadcast the build side when it is provably small
        build_size = rsize if build_right else lsize
        if build_size is not None and build_size <= BROADCAST_ROW_THRESHOLD \
                and p.join_type not in ("full",):
            if build_right:
                bcast = EX.TpuBroadcastExchange(right)
                return TJ.TpuBroadcastHashJoin(p, left, bcast,
                                               build_right=True)
            bcast = EX.TpuBroadcastExchange(left)
            return TJ.TpuBroadcastHashJoin(p, bcast, right,
                                           build_right=False)
        n = self._pick_partitions(p.children[0], p.children[1])
        if self.conf.get(ADAPTIVE_ENABLED):
            from ..exec.adaptive import TpuAdaptiveShuffledJoin
            return TpuAdaptiveShuffledJoin(
                p, left, right, build_right=build_right, num_partitions=n,
                broadcast_bytes=self.conf.get(ADAPTIVE_BROADCAST_BYTES),
                target_bytes=self.conf.get(ADAPTIVE_TARGET_PARTITION_BYTES),
                skew_factor=self.conf.get(ADAPTIVE_SKEW_FACTOR),
                skew_min_bytes=self.conf.get(ADAPTIVE_SKEW_MIN_BYTES))
        lpart = HashPartitioner(p.left_keys, n)
        rpart = HashPartitioner(p.right_keys, n)
        lex = EX.TpuShuffleExchange(left, lpart)
        rex = EX.TpuShuffleExchange(right, rpart)
        return TJ.TpuShuffledHashJoin(p, lex, rex, build_right=build_right)

    def _estimate_rows(self, p: L.LogicalPlan) -> Optional[int]:
        if isinstance(p, L.LocalRelation):
            return p.table.num_rows
        if isinstance(p, L.Range):
            return max(0, -(-(p.end - p.start) // p.step))
        if isinstance(p, (L.Project, L.Filter, L.Sort, L.Window)):
            return self._estimate_rows(p.children[0])
        if isinstance(p, L.Limit):
            return p.n
        if isinstance(p, L.Scan):
            return _scan_row_estimate(p, self.conf)
        if isinstance(p, L.Join):
            l = self._estimate_rows(p.children[0])
            r = self._estimate_rows(p.children[1])
            if l is None or r is None:
                return None
            return max(l, r)
        if isinstance(p, L.Aggregate):
            return self._estimate_rows(p.children[0])
        return None

    def _pick_partitions(self, *plans: L.LogicalPlan) -> int:
        """Exchange width from size estimates: avoid many tiny partitions

        (each distinct slice size is a separate XLA compilation)."""
        est = 0
        for p in plans:
            r = self._estimate_rows(p)
            if r is None:
                return self.default_partitions
            est = max(est, r)
        need = max(1, -(-est // max(self.batch_rows, 1)))
        return max(1, min(self.default_partitions, need))

    # -- mesh-collective join/sort (shuffle.mode=mesh) ---------------------
    def _plan_join_mesh(self, p: L.Join, left, right):
        """shuffle.mode=mesh: the whole shuffled equi-join as one SPMD
        program (exec/tpu_mesh_join.py) when the shapes allow it."""
        if self.conf.get(SHUFFLE_MODE) != "mesh":
            return None
        import jax
        from ..exec.tpu_mesh_join import (TpuMeshShuffledJoin,
                                          mesh_join_supported)
        n_dev = len(jax.devices())
        if not mesh_join_supported(p, n_dev):
            return None
        return TpuMeshShuffledJoin(p, left, right)

    def _plan_sort_mesh(self, p: L.Sort, child):
        """shuffle.mode=mesh: sample-splitter global sort as one SPMD
        program (exec/tpu_mesh_sort.py) when the shapes allow it."""
        if self.conf.get(SHUFFLE_MODE) != "mesh":
            return None
        import jax
        from ..exec.tpu_mesh_sort import TpuMeshSort, mesh_sort_supported
        n_dev = len(jax.devices())
        if not mesh_sort_supported(p, n_dev):
            return None
        return TpuMeshSort(p.orders, child)

    # -- global sort: range exchange + local sort --------------------------
    def _plan_sort(self, p: L.Sort, child: PhysicalPlan) -> PhysicalPlan:
        if p.is_global:
            mesh_plan = self._plan_sort_mesh(p, child)
            if mesh_plan is not None:
                return mesh_plan
        nparts = child.num_partitions_hint()
        if not p.is_global or nparts <= 1:
            return TSOR.TpuSort(p.orders, child)
        part = RangePartitioner(p.orders, nparts)
        ex = EX.TpuShuffleExchange(child, part)
        return TSOR.TpuSort(p.orders, ex)

    # -- test-mode assertion (spark.rapids.sql.test.enabled role) ----------
    def _assert_all_tpu(self, phys: PhysicalPlan):
        allowed = set(self.conf.allowed_non_tpu)
        bad = [n.name for n in phys.collect_nodes()
               if not n.columnar and n.name not in allowed
               and not isinstance(n, TB.ColumnarToRow)]
        if bad:
            raise AssertionError(
                f"test mode: operators fell back to CPU: {bad}; "
                f"fallback reasons: {self.fallbacks}")
