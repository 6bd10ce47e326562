"""SQL front end: text -> AST -> logical plan.

Role note: the reference rides on Spark's SQL parser/analyzer and only
rewrites *physical* plans (SURVEY.md §1: "Everything else ... SQL parser,
optimizer ... is stock Spark").  Standalone, this module supplies that
front end: a hand-written lexer + recursive-descent/Pratt parser for the
SQL dialect the reference's integration tests exercise
(qa_nightly_select_test.py-style SELECTs, TPC-H/TPC-DS query shapes),
lowered onto the same logical IR the DataFrame API builds
(plan/logical.py), so both surfaces share one planner and both engines.

Supported: WITH (CTEs), SELECT [DISTINCT], expressions (arithmetic,
comparison, AND/OR/NOT, BETWEEN, IN (list | subquery), EXISTS, LIKE,
IS [NOT] NULL, CASE, CAST, ||, scalar subqueries), FROM with table
refs / subqueries / comma cross joins / explicit JOIN ... ON,
GROUP BY (exprs, ordinals, aliases) + HAVING, window functions with
OVER (PARTITION BY / ORDER BY / ROWS|RANGE frames), ORDER BY
(exprs, ordinals, aliases), LIMIT/OFFSET, UNION [ALL], INTERSECT, EXCEPT,
DATE/TIMESTAMP/INTERVAL literals.
"""
from __future__ import annotations

import dataclasses
import datetime as _dt
import re
from typing import Any, List, Optional, Tuple

from ..columnar import dtypes as T
from ..columnar.schema import Field, Schema
from ..expr import aggregates as eagg
from ..expr import arithmetic as ea
from ..expr import cast as ecast
from ..expr import conditional as econd
from ..expr import core as ec
from ..expr import datetime as edt
from ..expr import misc as emisc
from ..expr import predicates as ep
from ..expr import string_ops as es
from ..expr import window_funcs as ewin
from ..plan import logical as L


class SqlError(Exception):
    pass


# ---------------------------------------------------------------------------
# Lexer
# ---------------------------------------------------------------------------

_TOKEN_RE = re.compile(r"""
    (?P<ws>\s+|--[^\n]*\n?|/\*.*?\*/)
  | (?P<num>(\d+\.\d*|\.\d+|\d+)([eE][+-]?\d+)?)
  | (?P<str>'(?:[^']|'')*')
  | (?P<qid>"[^"]*"|`[^`]*`)
  | (?P<id>[A-Za-z_][A-Za-z_0-9]*)
  | (?P<op><>|!=|>=|<=|\|\||[(),.*+\-/%<>=])
""", re.VERBOSE | re.DOTALL)


@dataclasses.dataclass
class Tok:
    kind: str      # num | str | id | qid | op | end
    text: str
    pos: int


def _lex(sql: str) -> List[Tok]:
    out: List[Tok] = []
    i = 0
    while i < len(sql):
        m = _TOKEN_RE.match(sql, i)
        if not m:
            raise SqlError(f"unexpected character {sql[i]!r} at {i}")
        i = m.end()
        kind = m.lastgroup
        if kind == "ws":
            continue
        out.append(Tok(kind, m.group(), m.start()))
    out.append(Tok("end", "", len(sql)))
    return out


# ---------------------------------------------------------------------------
# AST (tuples everywhere so nodes compare structurally with ==)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Ast:
    pass


@dataclasses.dataclass(frozen=True)
class Lit(Ast):
    value: Any


@dataclasses.dataclass(frozen=True)
class Interval(Ast):
    n: int
    unit: str  # day | month | year


@dataclasses.dataclass(frozen=True)
class Ident(Ast):
    parts: Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class Star(Ast):
    table: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class Res(Ast):
    """A reference already resolved to an ACTUAL column name in the
    current plan's schema (produced by lowering, never by the parser)."""
    cname: str


@dataclasses.dataclass(frozen=True)
class Func(Ast):
    fname: str
    args: Tuple[Ast, ...]
    distinct: bool = False


@dataclasses.dataclass(frozen=True)
class Bin(Ast):
    op: str
    left: Ast
    right: Ast


@dataclasses.dataclass(frozen=True)
class Un(Ast):
    op: str
    operand: Ast


@dataclasses.dataclass(frozen=True)
class Between(Ast):
    operand: Ast
    lo: Ast
    hi: Ast
    negated: bool = False


@dataclasses.dataclass(frozen=True)
class InList(Ast):
    operand: Ast
    items: Tuple[Ast, ...]
    negated: bool = False


@dataclasses.dataclass(frozen=True)
class InSub(Ast):
    operand: Ast
    query: "SelectStmt"
    negated: bool = False


@dataclasses.dataclass(frozen=True)
class Exists(Ast):
    query: "SelectStmt"
    negated: bool = False


@dataclasses.dataclass(frozen=True)
class ScalarSub(Ast):
    query: "SelectStmt"


@dataclasses.dataclass(frozen=True)
class LikeE(Ast):
    operand: Ast
    pattern: str
    negated: bool = False


@dataclasses.dataclass(frozen=True)
class IsNullE(Ast):
    operand: Ast
    negated: bool = False


@dataclasses.dataclass(frozen=True)
class Case(Ast):
    operand: Optional[Ast]
    whens: Tuple[Tuple[Ast, Ast], ...]
    els: Optional[Ast]


@dataclasses.dataclass(frozen=True)
class CastE(Ast):
    operand: Ast
    typename: str
    p1: Optional[int] = None
    p2: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class OrderItem(Ast):
    e: Ast
    asc: bool = True
    nulls_first: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class WindowE(Ast):
    func: Func
    partition: Tuple[Ast, ...]
    order: Tuple[OrderItem, ...]
    # (kind, lo, hi): None = unbounded; ints relative to current row
    frame: Optional[Tuple[str, Optional[int], Optional[int]]] = None


@dataclasses.dataclass(frozen=True)
class SelectItem(Ast):
    e: Ast
    alias: Optional[str]


@dataclasses.dataclass(frozen=True)
class TableRef(Ast):
    tname: str
    alias: Optional[str]


@dataclasses.dataclass(frozen=True)
class SubqueryRef(Ast):
    query: "SelectStmt"
    alias: str


@dataclasses.dataclass(frozen=True)
class JoinItem(Ast):
    left: Ast
    right: Ast
    how: str                     # inner|left|right|full|cross
    on: Optional[Ast]


@dataclasses.dataclass(frozen=True)
class SelectStmt(Ast):
    ctes: Tuple[Tuple[str, "SelectStmt"], ...]
    distinct: bool
    items: Tuple[SelectItem, ...]
    from_item: Optional[Ast]
    where: Optional[Ast]
    group_by: Tuple[Ast, ...]
    having: Optional[Ast]
    order_by: Tuple[OrderItem, ...]
    limit: Optional[int]
    offset: Optional[int]
    parenthesized: bool = False
    # ROLLUP/CUBE/GROUPING SETS: tuples of subsets of group_by idents
    group_sets: Optional[Tuple[Tuple[Ast, ...], ...]] = None


@dataclasses.dataclass(frozen=True)
class SetOp(Ast):
    op: str                      # union|intersect|except
    all: bool
    left: Ast
    right: Ast
    order_by: Tuple[OrderItem, ...] = ()
    limit: Optional[int] = None
    offset: Optional[int] = None


# ---------------------------------------------------------------------------
# Parser
# ---------------------------------------------------------------------------

_KEYWORDS = {
    "select", "from", "where", "group", "by", "having", "order", "limit",
    "offset", "as", "and", "or", "not", "in", "is", "null", "like",
    "between", "case", "when", "then", "else", "end", "cast", "distinct",
    "join", "inner", "left", "right", "full", "outer", "cross", "on",
    "union", "all", "intersect", "except", "exists", "with", "asc", "desc",
    "nulls", "first", "last", "true", "false", "over", "partition", "rows",
    "range", "unbounded", "preceding", "following", "current", "row",
    "interval", "date", "timestamp", "semi", "anti",
}

_AGG_FUNCS = {"sum", "count", "min", "max", "avg", "mean", "first", "last",
              "first_value", "last_value", "collect_list", "collect_set",
              "count_distinct", "stddev", "stddev_samp", "std",
              "stddev_pop", "variance", "var_samp", "var_pop"}
_WINDOW_ONLY_FUNCS = {"row_number", "rank", "dense_rank", "ntile", "lead",
                      "lag", "percent_rank", "cume_dist"}


class _Parser:
    def __init__(self, sql: str):
        self.toks = _lex(sql)
        self.i = 0

    # -- token helpers ------------------------------------------------------
    def peek(self, ahead: int = 0) -> Tok:
        return self.toks[min(self.i + ahead, len(self.toks) - 1)]

    def next(self) -> Tok:
        t = self.toks[self.i]
        self.i += 1
        return t

    def at_kw(self, *kws: str) -> bool:
        t = self.peek()
        return t.kind == "id" and t.text.lower() in kws

    def eat_kw(self, *kws: str) -> bool:
        if self.at_kw(*kws):
            self.next()
            return True
        return False

    def expect_kw(self, kw: str):
        if not self.eat_kw(kw):
            raise SqlError(
                f"expected {kw.upper()} at {self.peek().pos}, "
                f"got {self.peek().text!r}")

    def at_op(self, *ops: str) -> bool:
        t = self.peek()
        return t.kind == "op" and t.text in ops

    def eat_op(self, *ops: str) -> bool:
        if self.at_op(*ops):
            self.next()
            return True
        return False

    def expect_op(self, op: str):
        if not self.eat_op(op):
            raise SqlError(
                f"expected {op!r} at {self.peek().pos}, "
                f"got {self.peek().text!r}")

    def ident(self) -> str:
        t = self.peek()
        if t.kind == "id":
            if t.text.lower() in _KEYWORDS:
                raise SqlError(f"unexpected keyword {t.text!r} at {t.pos}")
            self.next()
            return t.text
        if t.kind == "qid":
            self.next()
            return t.text[1:-1]
        raise SqlError(f"expected identifier at {t.pos}, got {t.text!r}")

    # -- statements ---------------------------------------------------------
    def parse(self) -> Ast:
        stmt = self.query_expr()
        if self.peek().kind != "end":
            raise SqlError(
                f"trailing input at {self.peek().pos}: {self.peek().text!r}")
        return stmt

    def query_expr(self) -> Ast:
        """select ((UNION [ALL] | INTERSECT | EXCEPT) select)* with an
        optional trailing ORDER BY/LIMIT owned by the whole set-op."""
        left = self.query_term()
        while self.at_kw("union", "intersect", "except"):
            op = self.next().text.lower()
            is_all = self.eat_kw("all")
            right = self.query_term()
            left = SetOp(op, is_all, left, right)
        if isinstance(left, SetOp):
            order = ()
            limit = offset = None
            if self.eat_kw("order"):
                self.expect_kw("by")
                order = tuple(self.order_items())
            if self.eat_kw("limit"):
                limit = int(self.next().text)
            if self.eat_kw("offset"):
                offset = int(self.next().text)
            # an unparenthesized final SELECT grabs the trailing ORDER
            # BY/LIMIT/OFFSET during its own parse; grammatically they
            # belong to the whole set operation — hoist them
            if not order and limit is None and offset is None:
                rb = left.right
                if isinstance(rb, SelectStmt) and not rb.parenthesized \
                        and (rb.order_by or rb.limit is not None or
                             rb.offset is not None):
                    order = rb.order_by
                    limit = rb.limit
                    offset = rb.offset
                    left = dataclasses.replace(
                        left, right=dataclasses.replace(
                            rb, order_by=(), limit=None, offset=None))
            left = dataclasses.replace(left, order_by=order, limit=limit,
                                       offset=offset)
        return left

    def query_term(self) -> Ast:
        if self.eat_op("("):
            q = self.query_expr()
            self.expect_op(")")
            if isinstance(q, SelectStmt):
                # remember the parens: a trailing ORDER BY/LIMIT inside
                # them belongs to this branch, not the enclosing set op
                q = dataclasses.replace(q, parenthesized=True)
            return q
        return self.select_stmt()

    def select_stmt(self) -> SelectStmt:
        ctes: List[Tuple[str, SelectStmt]] = []
        if self.eat_kw("with"):
            while True:
                name = self.ident()
                self.expect_kw("as")
                self.expect_op("(")
                sub = self.query_expr()
                self.expect_op(")")
                ctes.append((name, sub))
                if not self.eat_op(","):
                    break
        self.expect_kw("select")
        distinct = self.eat_kw("distinct")
        self.eat_kw("all")
        items = [self.select_item()]
        while self.eat_op(","):
            items.append(self.select_item())
        from_item = None
        if self.eat_kw("from"):
            from_item = self.from_clause()
        where = self.expr() if self.eat_kw("where") else None
        group_by: List[Ast] = []
        group_sets = None
        if self.eat_kw("group"):
            self.expect_kw("by")
            low = self.peek().text.lower()
            if self.peek().kind == "id" and low in ("rollup", "cube") and \
                    self.peek(1).text == "(":
                self.next()
                self.expect_op("(")
                cols = [self.expr()]
                while self.eat_op(","):
                    cols.append(self.expr())
                self.expect_op(")")
                group_by = cols
                from ..plan.logical import cube_sets, rollup_sets
                mk = rollup_sets if low == "rollup" else cube_sets
                group_sets = tuple(tuple(cols[i] for i in t)
                                   for t in mk(list(range(len(cols)))))
            elif self.peek().kind == "id" and low == "grouping" and \
                    self.peek(1).text.lower() == "sets":
                self.next()
                self.next()
                self.expect_op("(")
                sets = []
                cols_seen: List[Ast] = []
                while True:
                    if self.eat_op("("):
                        one = []
                        if not self.at_op(")"):
                            one.append(self.expr())
                            while self.eat_op(","):
                                one.append(self.expr())
                        self.expect_op(")")
                    else:
                        one = [self.expr()]   # bare member: SETS (k, ())
                    sets.append(tuple(one))
                    for c in one:
                        if c not in cols_seen:
                            cols_seen.append(c)
                    if not self.eat_op(","):
                        break
                self.expect_op(")")
                group_by = cols_seen
                group_sets = tuple(sets)
            else:
                group_by.append(self.expr())
                while self.eat_op(","):
                    group_by.append(self.expr())
        having = self.expr() if self.eat_kw("having") else None
        order_by: List[OrderItem] = []
        if self.eat_kw("order"):
            self.expect_kw("by")
            order_by = self.order_items()
        limit = offset = None
        if self.eat_kw("limit"):
            limit = int(self.next().text)
        if self.eat_kw("offset"):
            offset = int(self.next().text)
        return SelectStmt(tuple(ctes), distinct, tuple(items), from_item,
                          where, tuple(group_by), having, tuple(order_by),
                          limit, offset, group_sets=group_sets)

    def order_items(self) -> List[OrderItem]:
        out = [self.order_item()]
        while self.eat_op(","):
            out.append(self.order_item())
        return out

    def order_item(self) -> OrderItem:
        e = self.expr()
        asc = True
        if self.eat_kw("desc"):
            asc = False
        else:
            self.eat_kw("asc")
        nulls_first = None
        if self.eat_kw("nulls"):
            if self.eat_kw("first"):
                nulls_first = True
            else:
                self.expect_kw("last")
                nulls_first = False
        return OrderItem(e, asc, nulls_first)

    def select_item(self) -> SelectItem:
        if self.at_op("*"):
            self.next()
            return SelectItem(Star(), None)
        # t.*
        if (self.peek().kind in ("id", "qid") and
                self.peek().text.lower() not in _KEYWORDS and
                self.peek(1).kind == "op" and self.peek(1).text == "." and
                self.peek(2).kind == "op" and self.peek(2).text == "*"):
            t = self.ident()
            self.next()
            self.next()
            return SelectItem(Star(t.lower()), None)
        e = self.expr()
        alias = None
        if self.eat_kw("as"):
            alias = self.ident()
        elif (self.peek().kind in ("id", "qid") and
              self.peek().text.lower() not in _KEYWORDS):
            alias = self.ident()
        return SelectItem(e, alias)

    # -- FROM ---------------------------------------------------------------
    def from_clause(self) -> Ast:
        item = self.join_chain()
        while self.eat_op(","):
            right = self.join_chain()
            item = JoinItem(item, right, "cross", None)
        return item

    def join_chain(self) -> Ast:
        left = self.table_primary()
        while True:
            how = None
            if self.eat_kw("cross"):
                self.expect_kw("join")
                how = "cross"
            elif self.at_kw("join"):
                self.next()
                how = "inner"
            elif self.at_kw("inner") and \
                    self.peek(1).text.lower() == "join":
                self.next()
                self.next()
                how = "inner"
            elif self.at_kw("left", "right", "full"):
                how = self.next().text.lower()
                self.eat_kw("outer")
                if self.eat_kw("semi"):
                    how = "semi"
                elif self.eat_kw("anti"):
                    how = "anti"
                self.expect_kw("join")
            else:
                break
            right = self.table_primary()
            on = None
            if how != "cross":
                self.expect_kw("on")
                on = self.expr()
            left = JoinItem(left, right, how, on)
        return left

    def table_primary(self) -> Ast:
        if self.eat_op("("):
            q = self.query_expr()
            self.expect_op(")")
            self.eat_kw("as")
            alias = self.ident()
            return SubqueryRef(q, alias.lower())
        name = self.ident()
        alias = None
        if self.eat_kw("as"):
            alias = self.ident()
        elif (self.peek().kind in ("id", "qid") and
              self.peek().text.lower() not in _KEYWORDS):
            alias = self.ident()
        return TableRef(name.lower(), alias.lower() if alias else None)

    # -- expressions (precedence climbing) ----------------------------------
    def expr(self) -> Ast:
        return self.or_expr()

    def or_expr(self) -> Ast:
        left = self.and_expr()
        while self.eat_kw("or"):
            left = Bin("or", left, self.and_expr())
        return left

    def and_expr(self) -> Ast:
        left = self.not_expr()
        while self.eat_kw("and"):
            left = Bin("and", left, self.not_expr())
        return left

    def not_expr(self) -> Ast:
        if self.eat_kw("not"):
            return Un("not", self.not_expr())
        return self.predicate()

    def predicate(self) -> Ast:
        if self.at_kw("exists"):
            self.next()
            self.expect_op("(")
            q = self.query_expr()
            self.expect_op(")")
            return Exists(q)
        left = self.additive()
        while True:
            negated = False
            if self.at_kw("not") and self.peek(1).text.lower() in (
                    "in", "like", "between"):
                self.next()
                negated = True
            if self.eat_kw("between"):
                lo = self.additive()
                self.expect_kw("and")
                hi = self.additive()
                left = Between(left, lo, hi, negated)
                continue
            if self.eat_kw("in"):
                self.expect_op("(")
                if self.at_kw("select", "with"):
                    q = self.query_expr()
                    self.expect_op(")")
                    left = InSub(left, q, negated)
                else:
                    items = [self.expr()]
                    while self.eat_op(","):
                        items.append(self.expr())
                    self.expect_op(")")
                    left = InList(left, tuple(items), negated)
                continue
            if self.eat_kw("like"):
                pat = self.additive()
                if not isinstance(pat, Lit) or not isinstance(pat.value, str):
                    raise SqlError("LIKE pattern must be a string literal")
                left = LikeE(left, pat.value, negated)
                continue
            if self.eat_kw("is"):
                neg = self.eat_kw("not")
                self.expect_kw("null")
                left = IsNullE(left, neg)
                continue
            if negated:
                raise SqlError(f"dangling NOT at {self.peek().pos}")
            if self.at_op("=", "<>", "!=", "<", "<=", ">", ">="):
                op = self.next().text
                right = self.additive()
                left = Bin({"!=": "<>"}.get(op, op), left, right)
                continue
            return left

    def additive(self) -> Ast:
        left = self.multiplicative()
        while self.at_op("+", "-", "||"):
            op = self.next().text
            left = Bin(op, left, self.multiplicative())
        return left

    def multiplicative(self) -> Ast:
        left = self.unary()
        while self.at_op("*", "/", "%"):
            op = self.next().text
            left = Bin(op, left, self.unary())
        return left

    def unary(self) -> Ast:
        if self.eat_op("-"):
            return Un("-", self.unary())
        if self.eat_op("+"):
            return self.unary()
        return self.primary()

    def primary(self) -> Ast:
        t = self.peek()
        if t.kind == "num":
            self.next()
            txt = t.text
            if "." in txt or "e" in txt.lower():
                return Lit(float(txt))
            return Lit(int(txt))
        if t.kind == "str":
            self.next()
            return Lit(t.text[1:-1].replace("''", "'"))
        if self.eat_op("("):
            if self.at_kw("select", "with"):
                q = self.query_expr()
                self.expect_op(")")
                return ScalarSub(q)
            e = self.expr()
            self.expect_op(")")
            return e
        if t.kind not in ("id", "qid"):
            raise SqlError(f"unexpected token {t.text!r} at {t.pos}")
        low = t.text.lower()
        if low == "null":
            self.next()
            return Lit(None)
        if low in ("true", "false"):
            self.next()
            return Lit(low == "true")
        if low == "case":
            return self.case_expr()
        if low == "cast":
            self.next()
            self.expect_op("(")
            e = self.expr()
            self.expect_kw("as")
            tn = self.next().text.lower()
            p1 = p2 = None
            if self.eat_op("("):
                p1 = int(self.next().text)
                if self.eat_op(","):
                    p2 = int(self.next().text)
                self.expect_op(")")
            self.expect_op(")")
            return CastE(e, tn, p1, p2)
        if low == "interval":
            self.next()
            v = self.next()
            n = int(v.text[1:-1] if v.kind == "str" else v.text)
            unit = self.next().text.lower().rstrip("s")
            return Interval(n, unit)
        if low in ("date", "timestamp") and self.peek(1).kind == "str":
            self.next()
            s = self.next().text[1:-1]
            if low == "date":
                return Lit(_dt.date.fromisoformat(s))
            return Lit(_dt.datetime.fromisoformat(s))
        # function call?
        if (self.peek(1).kind == "op" and self.peek(1).text == "(" and
                (low not in _KEYWORDS or low in ("first", "last"))):
            fname = self.next().text.lower()
            self.expect_op("(")
            distinct = False
            args: List[Ast] = []
            if self.at_op("*"):
                self.next()
                args = [Star()]
            elif not self.at_op(")"):
                distinct = self.eat_kw("distinct")
                args.append(self.expr())
                while self.eat_op(","):
                    args.append(self.expr())
            self.expect_op(")")
            f = Func(fname, tuple(args), distinct)
            if self.at_kw("over"):
                return self.over_clause(f)
            return f
        # qualified / bare identifier
        parts = [self.ident()]
        while self.at_op(".") and self.peek(1).kind in ("id", "qid"):
            self.next()
            parts.append(self.ident())
        return Ident(tuple(p.lower() for p in parts))

    def case_expr(self) -> Ast:
        self.expect_kw("case")
        operand = None
        if not self.at_kw("when"):
            operand = self.expr()
        whens: List[Tuple[Ast, Ast]] = []
        while self.eat_kw("when"):
            c = self.expr()
            self.expect_kw("then")
            v = self.expr()
            whens.append((c, v))
        els = self.expr() if self.eat_kw("else") else None
        self.expect_kw("end")
        return Case(operand, tuple(whens), els)

    def over_clause(self, f: Func) -> WindowE:
        self.expect_kw("over")
        self.expect_op("(")
        partition: List[Ast] = []
        order: List[OrderItem] = []
        frame = None
        if self.eat_kw("partition"):
            self.expect_kw("by")
            partition.append(self.expr())
            while self.eat_op(","):
                partition.append(self.expr())
        if self.eat_kw("order"):
            self.expect_kw("by")
            order = self.order_items()
        if self.at_kw("rows", "range"):
            kind = self.next().text.lower()
            self.expect_kw("between")
            lo = self.frame_bound()
            self.expect_kw("and")
            hi = self.frame_bound()
            frame = (kind, lo, hi)
        self.expect_op(")")
        return WindowE(f, tuple(partition), tuple(order), frame)

    def frame_bound(self) -> Optional[int]:
        if self.eat_kw("unbounded"):
            if not self.eat_kw("preceding"):
                self.expect_kw("following")
            return None
        if self.eat_kw("current"):
            self.expect_kw("row")
            return 0
        n = int(self.next().text)
        if self.eat_kw("preceding"):
            return -n
        self.expect_kw("following")
        return n


def parse_sql(sql: str) -> Ast:
    return _Parser(sql).parse()


# ---------------------------------------------------------------------------
# Lowering: AST -> logical plan
# ---------------------------------------------------------------------------

class _Scope:
    """Name-resolution environment over the current plan's schema.

    entries: ordered (alias, {col_lower: (display_name, Field)}) — the
    Field carries the ACTUAL (possibly dedup-renamed) column name in the
    combined schema; display_name is what SELECT * / output shows.
    """

    def __init__(self, entries):
        self.entries = entries

    @staticmethod
    def of(schema: Schema, alias: Optional[str] = None) -> "_Scope":
        cols = {f.name.lower(): (f.name, f) for f in schema}
        return _Scope([(alias, cols)])

    def resolve(self, parts: Tuple[str, ...]) -> ec.AttributeReference:
        f = self.resolve_field(parts)
        return ec.AttributeReference(f.name, f.dtype, f.nullable)

    def resolve_actual(self, cname: str) -> ec.AttributeReference:
        for _, cols in self.entries:
            for _, (_, f) in cols.items():
                if f.name == cname:
                    return ec.AttributeReference(f.name, f.dtype, f.nullable)
        raise SqlError(f"unknown column {cname}")

    def resolve_field(self, parts: Tuple[str, ...]) -> Field:
        if len(parts) == 2:
            tab, col = parts
            for alias, cols in self.entries:
                if alias == tab and col in cols:
                    return cols[col][1]
            raise SqlError(f"unknown column {tab}.{col}")
        col = parts[-1]
        hits = [cols[col][1] for _, cols in self.entries if col in cols]
        if not hits:
            raise SqlError(f"unknown column {col}")
        if len(hits) > 1:
            raise SqlError(f"ambiguous column {col}")
        return hits[0]

    def star_fields(self, table: Optional[str]):
        out = []
        for alias, cols in self.entries:
            if table is not None and alias != table:
                continue
            for _, (display, f) in cols.items():
                out.append((display, f))
        if not out:
            raise SqlError(f"unknown table {table} in star")
        return out


def _walk(ast: Ast):
    """Yield ast and descendants, NOT descending into sub-query nodes."""
    yield ast
    if isinstance(ast, (ScalarSub, InSub, Exists)):
        if isinstance(ast, InSub):
            yield from _walk(ast.operand)
        return
    for fld in dataclasses.fields(ast):
        v = getattr(ast, fld.name)
        if isinstance(v, Ast) and not isinstance(v, SelectStmt):
            yield from _walk(v)
        elif isinstance(v, tuple):
            for x in v:
                if isinstance(x, Ast) and not isinstance(x, SelectStmt):
                    yield from _walk(x)
                elif (isinstance(x, tuple) and len(x) == 2 and
                      isinstance(x[0], Ast)):
                    yield from _walk(x[0])
                    yield from _walk(x[1])


def _transform(ast: Ast, fn) -> Ast:
    """Bottom-up rebuild; fn applied to every node (not into subqueries)."""
    if isinstance(ast, (ScalarSub, Exists)):
        return fn(ast)
    if isinstance(ast, InSub):
        return fn(dataclasses.replace(
            ast, operand=_transform(ast.operand, fn)))
    kw = {}
    changed = False
    for fld in dataclasses.fields(ast):
        v = getattr(ast, fld.name)
        if isinstance(v, Ast) and not isinstance(v, SelectStmt):
            nv = _transform(v, fn)
            changed |= nv is not v
            kw[fld.name] = nv
        elif isinstance(v, tuple) and any(isinstance(x, Ast) for x in v):
            nv = tuple(_transform(x, fn)
                       if isinstance(x, Ast) and not isinstance(x, SelectStmt)
                       else x for x in v)
            changed |= nv != v
            kw[fld.name] = nv
        elif (isinstance(v, tuple) and v and isinstance(v[0], tuple) and
              len(v[0]) == 2 and isinstance(v[0][0], Ast)):
            nv = tuple((_transform(a, fn), _transform(b, fn)) for a, b in v)
            changed |= nv != v
            kw[fld.name] = nv
    if changed:
        ast = dataclasses.replace(ast, **kw)
    return fn(ast)


def _display_name(ast: Ast, alias: Optional[str]) -> str:
    if alias:
        return alias
    if isinstance(ast, Ident):
        return ast.parts[-1]
    if isinstance(ast, Res):
        return ast.cname
    if isinstance(ast, Func):
        return f"{ast.fname}({', '.join(_display_name(a, None) for a in ast.args)})"
    if isinstance(ast, WindowE):
        return _display_name(ast.func, None)
    if isinstance(ast, Lit):
        return str(ast.value)
    if isinstance(ast, Star):
        return "*"
    if isinstance(ast, CastE):
        return _display_name(ast.operand, None)
    if isinstance(ast, Bin):
        return (f"({_display_name(ast.left, None)} {ast.op} "
                f"{_display_name(ast.right, None)})")
    if isinstance(ast, Un):
        return f"({ast.op} {_display_name(ast.operand, None)})"
    return type(ast).__name__.lower()


def _pyval(e: ec.Expression):
    if isinstance(e, ec.Literal):
        return e.value
    if isinstance(e, ec.Alias):
        return _pyval(e.children[0])
    raise SqlError("expected a literal argument")


_TYPE_MAP = {
    "boolean": T.BOOL, "bool": T.BOOL,
    "tinyint": T.INT8, "byte": T.INT8,
    "smallint": T.INT16, "short": T.INT16,
    "int": T.INT32, "integer": T.INT32,
    "bigint": T.INT64, "long": T.INT64,
    "float": T.FLOAT32, "real": T.FLOAT32,
    "double": T.FLOAT64,
    "string": T.STRING, "varchar": T.STRING, "char": T.STRING,
    "date": T.DATE, "timestamp": T.TIMESTAMP,
}


def _sql_type(name: str, p1, p2) -> T.DType:
    if name in ("decimal", "numeric"):
        return T.DecimalType(p1 if p1 is not None else 10,
                             p2 if p2 is not None else 0)
    if name in _TYPE_MAP:
        return _TYPE_MAP[name]
    raise SqlError(f"unsupported type {name}")


def _make_agg(f: Func, lower) -> eagg.AggregateFunction:
    n = f.fname
    if n == "count" and (not f.args or isinstance(f.args[0], Star)):
        if f.distinct:
            raise SqlError("COUNT(DISTINCT *) is not valid")
        return eagg.Count()
    arg = lower(f.args[0]) if f.args else None
    if n == "sum":
        return eagg.Sum(arg)
    if n == "count":
        return eagg.Count(arg)
    if n == "min":
        return eagg.Min(arg)
    if n == "max":
        return eagg.Max(arg)
    if n in ("avg", "mean"):
        return eagg.Average(arg)
    if n in ("first", "first_value"):
        return eagg.First(arg)
    if n in ("last", "last_value"):
        return eagg.Last(arg)
    if n == "collect_list":
        return eagg.CollectList(arg)
    if n == "collect_set":
        return eagg.CollectSet(arg)
    if n in ("stddev", "stddev_samp", "std"):
        return eagg.StddevSamp(arg)
    if n == "stddev_pop":
        return eagg.StddevPop(arg)
    if n in ("variance", "var_samp"):
        return eagg.VarianceSamp(arg)
    if n == "var_pop":
        return eagg.VariancePop(arg)
    raise SqlError(f"unknown aggregate {n}")




def _split_conjuncts(a: Ast) -> List[Ast]:
    """AND-flatten a predicate AST (shared by WHERE lowering and both
    decorrelators)."""
    if isinstance(a, Bin) and a.op == "and":
        return _split_conjuncts(a.left) + _split_conjuncts(a.right)
    return [a]


def _split_disjuncts(a: Ast) -> List[Ast]:
    """OR-flatten a predicate AST."""
    if isinstance(a, Bin) and a.op == "or":
        return _split_disjuncts(a.left) + _split_disjuncts(a.right)
    return [a]


def _conj(parts: List[Ast]) -> Optional[Ast]:
    if not parts:
        return None
    e = parts[0]
    for p in parts[1:]:
        e = Bin("and", e, p)
    return e


def _factor_or(a: Ast) -> Ast:
    """``(A and P1) or (A and P2) -> A and (P1 or P2)``, recursively.

    Exact in three-valued logic (AND distributes over OR).  TPC-DS q41
    hides its correlation equality ``i_manufact = i1.i_manufact`` inside
    both branches of a top-level OR; factoring it out lets the
    decorrelators see it as a plain correlation conjunct."""
    if isinstance(a, Bin) and a.op == "and":
        return Bin("and", _factor_or(a.left), _factor_or(a.right))
    if not (isinstance(a, Bin) and a.op == "or"):
        return a
    branches = [_split_conjuncts(_factor_or(d))
                for d in _split_disjuncts(a)]
    common = [c for c in branches[0]
              if all(any(c == d for d in b) for b in branches[1:])]
    if not common:
        return a
    rests = []
    for b in branches:
        rest = list(b)
        for c in common:
            for i, d in enumerate(rest):
                if c == d:
                    del rest[i]
                    break
        rests.append(_conj(rest))
    if any(r is None for r in rests):
        # (A) or (A and P) == A
        return _conj(common)
    disj = rests[0]
    for r in rests[1:]:
        disj = Bin("or", disj, r)
    return _conj(common + [disj])


def _canon_idents(scope_: "_Scope", ast: Ast) -> Ast:
    """Resolve raw Idents against a scope (raises SqlError on unknown
    columns) — shared by both decorrelators."""
    def fn(n):
        if isinstance(n, Ident):
            return Res(scope_.resolve_field(n.parts).name)
        return n
    return _transform(ast, fn)


class _Lowerer:
    def __init__(self, session, views):
        self.session = session
        self.views = dict(views)   # name_lower -> LogicalPlan
        self._uid = 0

    def fresh(self, prefix: str) -> str:
        self._uid += 1
        return f"__{prefix}{self._uid}"

    def _exec_sub(self, plan: L.LogicalPlan):
        """Eagerly execute a lowered subquery (scalar / IN / EXISTS
        position).  Runs the same logical optimizer as ``sql_to_plan``
        first — without it the plan is raw cross-joins + filters and a
        three-table subquery (TPC-DS q23's max_store_sales) explodes."""
        from ..plan.logical_opt import optimize
        return self.session.execute_to_arrow(optimize(plan))

    # -- statements ---------------------------------------------------------
    def lower(self, ast: Ast) -> L.LogicalPlan:
        if isinstance(ast, SetOp):
            return self.lower_setop(ast)
        assert isinstance(ast, SelectStmt), ast
        return self.lower_select(ast)

    def lower_setop(self, s: SetOp) -> L.LogicalPlan:
        # a WITH on the leftmost SELECT scopes over the entire set
        # operation; hoist its CTEs for the whole lowering
        leftmost = s.left
        while isinstance(leftmost, SetOp):
            leftmost = leftmost.left
        if isinstance(leftmost, SelectStmt) and leftmost.ctes:
            saved = self.views
            self.views = dict(saved)
            for name, sub in leftmost.ctes:
                self.views[name.lower()] = self.lower(sub)
            try:
                stripped = self._strip_leftmost_ctes(s)
                return self.lower_setop(stripped)
            finally:
                self.views = saved
        left = self.lower(s.left)
        right = self.lower(s.right)
        if len(left.schema) != len(right.schema):
            raise SqlError("set operation column counts differ")
        if s.op == "union":
            # align right's column names to left's
            if right.schema.names != left.schema.names:
                right = L.Project(
                    [ec.Alias(ec.AttributeReference(rf.name, rf.dtype,
                                                    rf.nullable), lf.name)
                     for lf, rf in zip(left.schema, right.schema)], right)
            plan = L.Union([left, right])
            if not s.all:
                plan = L.Distinct(plan)
        else:
            jt = "semi" if s.op == "intersect" else "anti"
            # null-safe comparison (IS NOT DISTINCT FROM): equi-join keys
            # reject nulls, so each column becomes (is-null flag,
            # null-defaulted value) — NULL rows then match each other
            either_nullable = [lf.nullable or rf.nullable for lf, rf in
                               zip(left.schema, right.schema)]

            def null_safe_keys(schema):
                keys = []
                for f, nullable in zip(schema, either_nullable):
                    ref = ec.AttributeReference(f.name, f.dtype, f.nullable)
                    if not nullable:
                        keys.append(ref)
                        continue
                    keys.append(ep.IsNull(ref))
                    default = f.dtype.default_value
                    if default is not None:
                        default = default.item() \
                            if hasattr(default, "item") else default
                    # the default must be a value of the column's PYTHON
                    # type — the CPU oracle evaluates the Coalesce with
                    # pyarrow, which rejects e.g. int fills on
                    # string/date columns
                    if f.dtype == T.STRING:
                        default = ""
                    elif f.dtype == T.DATE:
                        import datetime as _dt
                        default = _dt.date(1970, 1, 1)
                    elif f.dtype == T.TIMESTAMP:
                        import datetime as _dt
                        default = _dt.datetime(1970, 1, 1)
                    keys.append(econd.Coalesce(
                        ref, ec.Literal(default if default is not None
                                        else 0, f.dtype)))
                return keys
            plan = L.Distinct(L.Join(left, right, jt,
                                     null_safe_keys(left.schema),
                                     null_safe_keys(right.schema), None))
        if s.order_by:
            scope = _Scope.of(plan.schema)
            orders = [L.SortOrder(self.lower_expr(o.e, scope), o.asc,
                                  o.nulls_first) for o in s.order_by]
            plan = L.Sort(orders, plan, is_global=True)
        if s.limit is not None or s.offset:
            plan = L.Limit(s.limit if s.limit is not None else 1 << 60,
                           plan, offset=s.offset or 0)
        return plan

    @staticmethod
    def _strip_leftmost_ctes(s: SetOp) -> SetOp:
        if isinstance(s.left, SetOp):
            return dataclasses.replace(
                s, left=_Lowerer._strip_leftmost_ctes(s.left))
        return dataclasses.replace(
            s, left=dataclasses.replace(s.left, ctes=()))

    def lower_select(self, s: SelectStmt) -> L.LogicalPlan:
        views = self.views
        if s.ctes:
            self.views = dict(views)
            for name, sub in s.ctes:
                self.views[name.lower()] = self.lower(sub)
        try:
            return self._lower_select_body(s)
        finally:
            self.views = views

    def _lower_select_body(self, s: SelectStmt) -> L.LogicalPlan:
        # 1. FROM
        if s.from_item is None:
            plan: L.LogicalPlan = L.Range(0, 1)
            scope = _Scope([(None, {})])
        else:
            plan, scope = self.lower_from(s.from_item)

        # 2. canonicalize identifiers to actual column names
        def canon(ast: Ast) -> Ast:
            def fn(n):
                if isinstance(n, Ident):
                    return Res(scope.resolve_field(n.parts).name)
                return n
            return _transform(ast, fn)

        # expand stars; display names come from the ORIGINAL asts (the
        # join dedup-rename must not leak into output column names)
        items: List[SelectItem] = []
        display_names: List[str] = []
        for it in s.items:
            if isinstance(it.e, Star):
                for display, f in scope.star_fields(it.e.table):
                    items.append(SelectItem(Res(f.name), display))
                    display_names.append(display)
            else:
                items.append(SelectItem(canon(it.e), it.alias))
                display_names.append(it.alias or _display_name(it.e, None))
        seen: dict = {}
        for i, d in enumerate(display_names):
            if d in seen:
                seen[d] += 1
                display_names[i] = f"{d}_{seen[d]}"
            else:
                seen[d] = 0

        # 3. WHERE (incl. IN-subquery / EXISTS transforms)
        if s.where is not None:
            plan = self.lower_where(canon(s.where), plan, scope)
            scope = self._rescope(plan, scope)

        item_asts = [it.e for it in items]
        having_ast = canon(s.having) if s.having is not None else None
        # ORDER BY: ordinal / select-alias substitution BEFORE canon (an
        # alias is not a source column, canon would reject it)
        fixed_orders: List[OrderItem] = []
        for o in s.order_by:
            e = o.e
            if isinstance(e, Lit) and isinstance(e.value, int):
                if not (1 <= e.value <= len(item_asts)):
                    raise SqlError(f"ORDER BY ordinal {e.value} out of range")
                e = item_asts[e.value - 1]
            elif isinstance(e, Ident) and len(e.parts) == 1:
                for it, disp in zip(items, display_names):
                    if disp.lower() == e.parts[0].lower():
                        e = it.e
                        break
                else:
                    e = canon(e)
            else:
                try:
                    e = canon(e)
                except SqlError:
                    # select-list aliases may appear INSIDE an ORDER BY
                    # expression (TPC-DS q70: ``order by case when
                    # lochierarchy = 0 then s_state end``) — substitute
                    # aliases through the tree, then canonicalize
                    alias_map = {disp.lower(): it.e
                                 for it, disp in zip(items, display_names)}

                    def sub_alias(n):
                        if isinstance(n, Ident) and len(n.parts) == 1 \
                                and n.parts[0].lower() in alias_map:
                            return alias_map[n.parts[0].lower()]
                        return n
                    e = canon(_transform(e, sub_alias))
            fixed_orders.append(dataclasses.replace(o, e=e))
        order_asts = fixed_orders

        # GROUP BY keys: ordinals and select aliases allowed
        key_asts: List[Ast] = []
        for g in s.group_by:
            if isinstance(g, Lit) and isinstance(g.value, int):
                key_asts.append(item_asts[g.value - 1])
                continue
            if isinstance(g, Ident) and len(g.parts) == 1:
                matched = None
                for it, disp in zip(items, display_names):
                    if disp.lower() == g.parts[0].lower():
                        matched = it.e
                        break
                try:
                    key_asts.append(canon(g))
                except SqlError:
                    if matched is None:
                        raise
                    key_asts.append(matched)
                continue
            key_asts.append(canon(g))

        def has_agg(ast: Optional[Ast]) -> bool:
            if ast is None:
                return False
            return any(isinstance(n, Func) and n.fname in _AGG_FUNCS
                       for n in _walk(ast)
                       if not isinstance(n, WindowE))

        # a window func's direct Func node must not count as an aggregate
        def agg_calls(ast: Ast) -> List[Func]:
            out = []
            win_funcs = {id(n.func) for n in _walk(ast)
                         if isinstance(n, WindowE)}
            for n in _walk(ast):
                if (isinstance(n, Func) and n.fname in _AGG_FUNCS and
                        id(n) not in win_funcs):
                    out.append(n)
            return out

        need_agg = bool(key_asts) or any(
            agg_calls(a) for a in item_asts + ([having_ast] if having_ast
                                              else []))

        # 4. aggregation stage
        if need_agg:
            lower_in = lambda a: self.lower_expr(a, scope)  # noqa: E731
            key_names: List[str] = []
            group_exprs: List[ec.Expression] = []
            key_map: List[Tuple[Ast, str]] = []
            for k in key_asts:
                e = self.lower_expr(k, scope)
                if isinstance(k, Res):
                    name = k.cname
                else:
                    name = self.fresh("grp")
                    e = ec.Alias(e, name)
                key_names.append(name)
                group_exprs.append(e)
                key_map.append((k, name))
            aggs: List[L.AggExpr] = []
            agg_map: List[Tuple[Func, str]] = []
            roots = item_asts + ([having_ast] if having_ast else []) + \
                [o.e for o in order_asts]
            for root in roots:
                for call in agg_calls(root):
                    if any(call == c for c, _ in agg_map):
                        continue
                    name = self.fresh("agg")
                    aggs.append(L.AggExpr(
                        _make_agg(call, lower_in), name,
                        distinct=call.distinct))
                    agg_map.append((call, name))
            if s.group_sets is not None:
                # set members are the same ASTs as the GROUP BY columns,
                # which already went through alias/ordinal substitution
                # into key_asts — align by position
                subst = {g: k for g, k in zip(s.group_by, key_asts)}
                name_of = {k: n for (k, n) in key_map}
                sets = []
                for gset in s.group_sets:
                    members = []
                    for gcol in gset:
                        k = subst.get(gcol)
                        if k is None:
                            try:
                                k = canon(gcol)
                            except SqlError:
                                k = None
                        if k is None or k not in name_of:
                            raise SqlError(
                                "grouping set member must appear in "
                                "GROUP BY")
                        members.append(name_of[k])
                    sets.append(tuple(members))
                # grouping(col) -> 1 on subtotal rows where col is
                # rolled up, else 0 (computed from the expand set id)
                grouping_calls = []
                for root in item_asts + [o.e for o in order_asts] + \
                        ([having_ast] if having_ast is not None else []):
                    for nd in _walk(root):
                        if isinstance(nd, Func) and \
                                nd.fname == "grouping" and \
                                len(nd.args) == 1 and \
                                not any(nd == g for g in grouping_calls):
                            grouping_calls.append(nd)
                gsub = {}
                for gc in grouping_calls:
                    k = subst.get(gc.args[0])
                    if k is None:
                        k = canon(gc.args[0])
                    nm = name_of.get(k)
                    if nm is None:
                        raise SqlError(
                            "grouping() argument must be a GROUP BY key")
                    rolled = tuple(Lit(i) for i, st in enumerate(sets)
                                   if nm not in st)
                    gsub[gc] = Case(None, ((InList(Res("__gid"), rolled),
                                            Lit(1)),), Lit(0))

                def rwg(ast: Ast) -> Ast:
                    def fn(n):
                        return gsub.get(n, n)
                    return _transform(ast, fn)
                if gsub:
                    item_asts = [rwg(a) for a in item_asts]
                    order_asts = [dataclasses.replace(o, e=rwg(o.e))
                                  for o in order_asts]
                    if having_ast is not None:
                        having_ast = rwg(having_ast)
                plan = L.build_grouping_sets(group_exprs, sets, aggs,
                                             plan, keep_gid=bool(gsub))
            else:
                plan = L.build_aggregate(group_exprs, aggs, plan)
            scope = _Scope.of(plan.schema)

            def rw(ast: Ast) -> Ast:
                def fn(n):
                    for k, name in key_map:
                        if n == k:
                            return Res(name)
                    for c, name in agg_map:
                        if n == c:
                            return Res(name)
                    return n
                return _transform(ast, fn)

            item_asts = [rw(a) for a in item_asts]
            if having_ast is not None:
                having_ast = rw(having_ast)
            order_asts = [dataclasses.replace(o, e=rw(o.e))
                          for o in order_asts]

        # 5. HAVING
        if having_ast is not None:
            plan = L.Filter(self.lower_expr(having_ast, scope), plan)

        # 6. window functions
        win_nodes: List[Tuple[WindowE, str]] = []
        for root in item_asts + [o.e for o in order_asts]:
            for n in _walk(root):
                if isinstance(n, WindowE) and not any(
                        n == w for w, _ in win_nodes):
                    win_nodes.append((n, self.fresh("win")))
        if win_nodes:
            wfs = []
            for w, name in win_nodes:
                wfs.append(self.lower_window(w, name, scope))
            plan = L.Window(wfs, plan)
            scope = _Scope.of(plan.schema)

            def rww(ast: Ast) -> Ast:
                def fn(n):
                    for w, name in win_nodes:
                        if n == w:
                            return Res(name)
                    return n
                return _transform(ast, fn)
            item_asts = [rww(a) for a in item_asts]
            order_asts = [dataclasses.replace(o, e=rww(o.e))
                          for o in order_asts]

        # 7. sort below the final projection (hidden sort columns stay
        #    available), except DISTINCT which must sort its output
        if order_asts and not s.distinct:
            orders = [L.SortOrder(self.lower_expr(o.e, scope), o.asc,
                                  o.nulls_first) for o in order_asts]
            plan = L.Sort(orders, plan, is_global=True)

        # 8. final projection
        out_exprs = []
        for ast, disp in zip(item_asts, display_names):
            e = self.lower_expr(ast, scope)
            out_exprs.append(ec.Alias(e, disp))
        plan = L.Project(out_exprs, plan)

        if s.distinct:
            plan = L.Distinct(plan)
            if order_asts:
                oscope = _Scope.of(plan.schema)
                orders = []
                for o in order_asts:
                    orders.append(L.SortOrder(
                        self.lower_expr(o.e, oscope), o.asc, o.nulls_first))
                plan = L.Sort(orders, plan, is_global=True)

        # 9. limit / offset
        if s.limit is not None or s.offset:
            plan = L.Limit(s.limit if s.limit is not None else 1 << 60,
                           plan, offset=s.offset or 0)
        return plan

    def _rescope(self, plan: L.LogicalPlan, scope: _Scope) -> _Scope:
        """After a plan change that keeps the schema, keep the scope."""
        return scope

    # -- FROM ---------------------------------------------------------------
    def lower_from(self, item: Ast):
        if isinstance(item, TableRef):
            plan = self.views.get(item.tname)
            if plan is None:
                raise SqlError(f"unknown table {item.tname}")
            alias = item.alias or item.tname
            return plan, _Scope.of(plan.schema, alias)
        if isinstance(item, SubqueryRef):
            plan = self.lower(item.query)
            return plan, _Scope.of(plan.schema, item.alias)
        assert isinstance(item, JoinItem), item
        lplan, lscope = self.lower_from(item.left)
        rplan, rscope = self.lower_from(item.right)
        # dedup-rename right columns that collide with the left side
        taken = {f.name for f in lplan.schema}
        renames = {}
        for _, cols in rscope.entries:
            for low, (disp, f) in cols.items():
                if f.name in taken:
                    alias0 = next((a for a, c in rscope.entries
                                   if low in c and c[low][1] is f), None)
                    nn = f"__{alias0 or 'r'}_{f.name}"
                    while nn in taken:
                        nn += "_"
                    renames[f.name] = nn
                taken.add(renames.get(f.name, f.name))
        if renames:
            rplan = L.Project(
                [ec.Alias(ec.AttributeReference(f.name, f.dtype, f.nullable),
                          renames[f.name]) if f.name in renames else
                 ec.AttributeReference(f.name, f.dtype, f.nullable)
                 for f in rplan.schema], rplan)
            new_entries = []
            for alias, cols in rscope.entries:
                nc = {}
                for low, (disp, f) in cols.items():
                    nn = renames.get(f.name, f.name)
                    nc[low] = (disp, Field(nn, f.dtype, f.nullable))
                new_entries.append((alias, nc))
            rscope = _Scope(new_entries)
        combined = _Scope(lscope.entries + rscope.entries)
        how = item.how
        if how == "cross" or item.on is None:
            join = L.Join(lplan, rplan, "cross", [], [], None)
            return join, combined

        def canon_on(ast: Ast) -> Ast:
            def fn(n):
                if isinstance(n, Ident):
                    return Res(combined.resolve_field(n.parts).name)
                return n
            return _transform(ast, fn)
        cond = self.lower_expr(canon_on(item.on), combined)
        from .dataframe import _extract_equi_keys
        lkeys, rkeys, residual = _extract_equi_keys(
            cond, lplan.schema, rplan.schema)
        join = L.Join(lplan, rplan, how, lkeys, rkeys, residual)
        if how in ("semi", "anti"):
            return join, _Scope(lscope.entries)
        # outer joins make the other side nullable; rebuild the scope from
        # the join's output schema, preserving alias partitions
        out_fields = {f.name: f for f in join.schema}
        new_entries = []
        for alias, cols in combined.entries:
            nc = {low: (disp, out_fields[f.name])
                  for low, (disp, f) in cols.items()}
            new_entries.append((alias, nc))
        return join, _Scope(new_entries)

    # -- WHERE with subquery predicates -------------------------------------
    def lower_where(self, where: Ast, plan: L.LogicalPlan,
                    scope: _Scope) -> L.LogicalPlan:
        def conjuncts(a: Ast) -> List[Ast]:
            if isinstance(a, Bin) and a.op == "and":
                return conjuncts(a.left) + conjuncts(a.right)
            return [a]
        rest: List[ec.Expression] = []
        for c in conjuncts(where):
            # NOT EXISTS / NOT IN arrive as Un("not", ...) from the parser
            if isinstance(c, Un) and c.op == "not" and \
                    isinstance(c.operand, (InSub, Exists)):
                c = dataclasses.replace(c.operand,
                                        negated=not c.operand.negated)
            if isinstance(c, InSub):
                sub = self.lower(c.query)
                if len(sub.schema) != 1:
                    raise SqlError("IN subquery must return one column")
                sf = sub.schema.fields[0]
                lkey = self.lower_expr(c.operand, scope)
                rkey = ec.AttributeReference(sf.name, sf.dtype, sf.nullable)
                if c.negated:
                    # SQL three-valued NOT IN: empty set -> everything
                    # qualifies (even NULL); any NULL in the set ->
                    # nothing qualifies; else NULL operands never match
                    if self._exec_sub(
                            L.Limit(1, sub)).num_rows == 0:
                        continue
                    if sf.nullable:
                        nulls = self._exec_sub(L.Limit(
                            1, L.Filter(ep.IsNull(rkey), sub))).num_rows
                        if nulls:
                            plan = L.Filter(ec.Literal(False, T.BOOL), plan)
                            continue
                    if lkey.nullable:
                        plan = L.Filter(ep.IsNotNull(lkey), plan)
                    plan = L.Join(plan, sub, "anti", [lkey], [rkey], None)
                else:
                    plan = L.Join(plan, sub, "semi", [lkey], [rkey], None)
                continue
            disj = _split_disjuncts(c)
            if len(disj) > 1 and all(isinstance(d, Exists)
                                     and not d.negated for d in disj):
                plan = self._decorrelate_exists_or(disj, plan, scope)
                continue
            if isinstance(c, Exists):
                try:
                    sub = self.lower(c.query)
                except SqlError:
                    # outer references: decorrelate to a semi/anti join
                    plan = self._decorrelate_exists(c, plan, scope)
                    continue
                # uncorrelated EXISTS: evaluate eagerly to a constant
                n = self._exec_sub(
                    L.Limit(1, sub)).num_rows
                truth = (n > 0) != c.negated
                if not truth:
                    plan = L.Filter(ec.Literal(False, T.BOOL), plan)
                continue
            if isinstance(c, Bin) and c.op in ("<", "<=", ">", ">=",
                                               "=", "<>") and \
                    (isinstance(c.left, ScalarSub) ^
                     isinstance(c.right, ScalarSub)):
                sub_ast = c.right if isinstance(c.right, ScalarSub) \
                    else c.left
                try:
                    sub_plan = self.lower(sub_ast.query)
                except SqlError as probe_err:
                    plan = self._decorrelate_scalar_cmp(
                        c, plan, scope, probe_err)
                    continue
                # uncorrelated: fold the ALREADY-lowered plan to a
                # literal here (handing the raw AST to lower_expr
                # would lower + execute the whole subquery a second
                # time, including any nested subqueries)
                lit = self._scalar_literal(sub_plan)
                lhs = self.lower_expr(
                    c.left if isinstance(c.right, ScalarSub) else
                    c.right, scope)
                a, b = (lhs, lit) if isinstance(c.right, ScalarSub) \
                    else (lit, lhs)
                cmp_cls = {"<": ep.LessThan, "<=": ep.LessThanOrEqual,
                           ">": ep.GreaterThan,
                           ">=": ep.GreaterThanOrEqual, "=": ep.EqualTo}
                rest.append(ep.Not(ep.EqualTo(a, b)) if c.op == "<>"
                            else cmp_cls[c.op](a, b))
                continue
            rest.append(self.lower_expr(c, scope))
        if rest:
            cond = rest[0]
            for r in rest[1:]:
                cond = ep.And(cond, r)
            plan = L.Filter(cond, plan)
        return plan

    def _decorrelate_exists(self, c: Exists, plan: L.LogicalPlan,
                            outer_scope: _Scope) -> L.LogicalPlan:
        """Correlated [NOT] EXISTS -> semi/anti join.

        Reference shape: TPC-DS q16/q94 ``exists (select * from t2 where
        t1.k = t2.k and ...)``.  Equality conjuncts that straddle the
        scopes become join keys; purely-inner conjuncts stay as a filter
        under the join; anything else is unsupported."""
        outer_keys, inner_proj, rrefs, condition = \
            self._exists_parts(c, outer_scope)
        return L.Join(plan, inner_proj, "anti" if c.negated else "semi",
                      outer_keys, rrefs, condition)

    def _exists_parts(self, c: Exists, outer_scope: _Scope):
        """Split a correlated EXISTS into (outer_keys, projected inner
        plan, right key refs, residual condition).  Equality conjuncts
        that straddle the scopes become join keys; purely-inner
        conjuncts filter under the join; other straddling conjuncts
        (q16/q94's ``cs1.cs_warehouse_sk <> cs2.cs_warehouse_sk``)
        become a residual pair-level condition with the referenced
        inner columns projected alongside the keys."""
        sub = c.query
        if not isinstance(sub, SelectStmt) or sub.from_item is None or \
                sub.group_by or sub.having or sub.distinct or sub.ctes:
            raise SqlError("unsupported correlated EXISTS subquery")
        inner_plan, inner_scope = self.lower_from(sub.from_item)
        inner_rest: List[Ast] = []
        residual_asts: List[Ast] = []
        outer_keys: List[ec.Expression] = []
        inner_keys: List[ec.Expression] = []
        where_ast = _factor_or(sub.where) if sub.where is not None \
            else None
        for cj in (_split_conjuncts(where_ast)
                   if where_ast is not None else []):
            try:
                inner_rest.append(_canon_idents(inner_scope, cj))
                continue
            except SqlError:
                pass
            matched = False
            if isinstance(cj, Bin) and cj.op == "=":
                for a, b in ((cj.left, cj.right), (cj.right, cj.left)):
                    try:
                        ia = _canon_idents(inner_scope, a)
                        ob = _canon_idents(outer_scope, b)
                    except SqlError:
                        continue
                    inner_keys.append(self.lower_expr(ia, inner_scope))
                    outer_keys.append(self.lower_expr(ob, outer_scope))
                    matched = True
                    break
            if not matched:
                residual_asts.append(cj)
        if not inner_keys:
            raise SqlError("EXISTS subquery references unknown columns")
        if inner_rest:
            cond = self.lower_expr(inner_rest[0], inner_scope)
            for r in inner_rest[1:]:
                cond = ep.And(cond, self.lower_expr(r, inner_scope))
            inner_plan = L.Filter(cond, inner_plan)
        proj = [ec.Alias(k, f"__ck{i}")
                for i, k in enumerate(inner_keys)]
        # residual conjuncts: inner-resolvable idents are projected as
        # extra __rc columns; the rewritten predicate then lowers
        # against outer-scope + projected-inner and binds to the join's
        # pair schema at execution
        condition = None
        if residual_asts:
            extra: List[ec.Expression] = []
            extra_fields: List[Field] = []

            def sub_inner(n):
                if isinstance(n, Ident):
                    try:
                        ie = self.lower_expr(
                            _canon_idents(inner_scope, n), inner_scope)
                    except SqlError:
                        return n
                    name = f"__rc{len(extra)}"
                    extra.append(ec.Alias(ie, name))
                    extra_fields.append(Field(name, ie.dtype(), True))
                    return Res(name)
                return n
            lowered = []
            for r in residual_asts:
                r2 = _transform(r, sub_inner)
                comb = _Scope(outer_scope.entries + [
                    (None, {f.name.lower(): (f.name, f)
                            for f in extra_fields})])
                lowered.append(self.lower_expr(_canon_idents(comb, r2),
                                               comb))
            proj = proj + extra
            condition = lowered[0]
            for r in lowered[1:]:
                condition = ep.And(condition, r)
        inner_proj = L.Project(proj, inner_plan)
        rrefs = [ec.AttributeReference(f"__ck{i}", k.dtype(), True)
                 for i, k in enumerate(inner_keys)]
        return outer_keys, inner_proj, rrefs, condition

    def _decorrelate_exists_or(self, disj: List[Exists],
                               plan: L.LogicalPlan,
                               outer_scope: _Scope) -> L.LogicalPlan:
        """``exists(E1) or exists(E2) ...`` where every disjunct
        correlates on the SAME outer key expressions -> one semi join
        against the UNION ALL of the inner key sets (TPC-DS q10's
        web-or-catalog shape)."""
        parts = [self._exists_parts(d, outer_scope) for d in disj]
        ok0, _, rrefs0, cond0 = parts[0]
        if cond0 is not None or any(p[3] is not None for p in parts):
            raise SqlError("OR of EXISTS with residual conditions "
                           "unsupported")
        key_repr = [repr(k) for k in ok0]
        for ok, _, _, _ in parts[1:]:
            if [repr(k) for k in ok] != key_repr:
                raise SqlError(
                    "OR of EXISTS requires identical correlation keys "
                    "in every disjunct")
        inner = L.Union([p[1] for p in parts])
        return L.Join(plan, inner, "semi", ok0, rrefs0, None)

    def _scalar_literal(self, sub_plan: L.LogicalPlan) -> ec.Literal:
        """Execute an (already lowered) uncorrelated scalar subquery to
        a literal (at most one row, one column)."""
        if len(sub_plan.schema) != 1:
            raise SqlError("scalar subquery must return one column")
        tbl = self._exec_sub(sub_plan)
        if tbl.num_rows > 1:
            raise SqlError("scalar subquery returned more than one row")
        val = tbl.column(0)[0].as_py() if tbl.num_rows else None
        return ec.Literal(val, sub_plan.schema.fields[0].dtype)

    def _decorrelate_scalar_cmp(self, c: Bin, plan: L.LogicalPlan,
                                outer_scope: _Scope,
                                probe_err=None) -> L.LogicalPlan:
        """``x CMP (correlated scalar aggregate subquery)`` ->
        group-by-correlation-keys + inner join + comparison filter.

        Reference shape: TPC-DS q1/q6/q32/q81/q92 —
        ``where ctr_total_return > (select avg(ctr_total_return)*1.2
        from ctr ctr2 where ctr1.ctr_store_sk = ctr2.ctr_store_sk)``.
        The subquery becomes ``select k, AGG as __sv ... group by k``;
        each outer row joins its group's scalar and the comparison
        filters.  Rows with no group drop either way (NULL compare),
        so an inner join is exact."""
        sub_ast = c.right if isinstance(c.right, ScalarSub) else c.left
        outer_ast = c.left if isinstance(c.right, ScalarSub) else c.right
        op = c.op
        if isinstance(c.left, ScalarSub):
            op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)
        sub = sub_ast.query
        if not isinstance(sub, SelectStmt) or sub.from_item is None or \
                sub.group_by or sub.having or sub.distinct or sub.ctes \
                or len(sub.items) != 1:
            raise SqlError("unsupported correlated scalar subquery "
                           "(single aggregate item expected)")

        def has_agg(a: Ast) -> bool:
            found = []

            def fn(n):
                if isinstance(n, Func) and n.fname in _AGG_FUNCS:
                    found.append(n)
                return n
            _transform(a, fn)
            return bool(found)
        if not has_agg(sub.items[0].e):
            # a non-aggregate correlated scalar would need runtime
            # more-than-one-row enforcement; the group-by rewrite would
            # silently dedup instead — refuse
            raise SqlError(
                "correlated scalar subquery must select a single "
                "aggregate expression")
        # probe scope: which conjuncts are inner-only vs correlation
        # equalities (same split as _decorrelate_exists, but keeping
        # the RAW inner asts so the rewritten SelectStmt re-lowers)
        _, inner_scope = self.lower_from(sub.from_item)
        inner_rest: List[Ast] = []
        inner_key_asts: List[Ast] = []
        outer_keys: List[ec.Expression] = []
        for cj in (_split_conjuncts(_factor_or(sub.where))
                   if sub.where is not None else []):
            try:
                _canon_idents(inner_scope, cj)
                inner_rest.append(cj)
                continue
            except SqlError:
                pass
            matched = False
            if isinstance(cj, Bin) and cj.op == "=":
                for a, b in ((cj.left, cj.right), (cj.right, cj.left)):
                    try:
                        _canon_idents(inner_scope, a)
                        ob = _canon_idents(outer_scope, b)
                    except SqlError:
                        continue
                    inner_key_asts.append(a)
                    outer_keys.append(self.lower_expr(ob, outer_scope))
                    matched = True
                    break
            if not matched:
                raise SqlError(
                    "correlated scalar subquery predicates must be "
                    "equalities between inner and outer columns (plus "
                    "inner-only conjuncts)"
                    + (f"; original subquery error: {probe_err}"
                       if probe_err else ""))
        if not inner_key_asts:
            raise SqlError(
                "scalar subquery references unknown columns"
                + (f"; original subquery error: {probe_err}"
                   if probe_err else ""))
        # rebuild: select k0.., AGG as __sv from ... where inner_rest
        # group by k0.. — then re-lower through the normal pipeline
        where_ast = None
        for r in inner_rest:
            where_ast = r if where_ast is None else \
                Bin("and", where_ast, r)
        new_items = tuple(
            SelectItem(a, f"__ck{i}")
            for i, a in enumerate(inner_key_asts)
        ) + (SelectItem(sub.items[0].e, "__sv"),)
        new_sub = dataclasses.replace(
            sub, items=new_items, where=where_ast,
            group_by=tuple(inner_key_asts), group_sets=None,
            order_by=(), limit=None, offset=None)
        inner = self.lower(new_sub)
        fields = list(inner.schema)
        rrefs = [ec.AttributeReference(f.name, f.dtype, f.nullable)
                 for f in fields[:-1]]
        sv = fields[-1]
        sv_ref = ec.AttributeReference(sv.name, sv.dtype, sv.nullable)
        joined = L.Join(plan, inner, "inner", outer_keys, rrefs, None)
        lhs = self.lower_expr(outer_ast, outer_scope)
        cmp_cls = {"<": ep.LessThan, "<=": ep.LessThanOrEqual,
                   ">": ep.GreaterThan, ">=": ep.GreaterThanOrEqual,
                   "=": ep.EqualTo}
        cond = ep.Not(ep.EqualTo(lhs, sv_ref)) if op == "<>" else \
            cmp_cls[op](lhs, sv_ref)
        filtered = L.Filter(cond, joined)
        # restore the outer schema (the helper columns must not leak
        # into star expansion or set operations downstream)
        proj = [ec.AttributeReference(f.name, f.dtype, f.nullable)
                for f in plan.schema]
        return L.Project(proj, filtered)

    # -- window -------------------------------------------------------------
    def lower_window(self, w: WindowE, alias: str,
                     scope: _Scope) -> L.WindowFunc:
        f = w.func
        lower = lambda a: self.lower_expr(a, scope)  # noqa: E731
        n = f.fname
        if n == "row_number":
            func: ec.Expression = ewin.RowNumber()
        elif n == "rank":
            func = ewin.Rank()
        elif n == "dense_rank":
            func = ewin.DenseRank()
        elif n == "ntile":
            func = ewin.NTile(_pyval(lower(f.args[0])))
        elif n == "percent_rank":
            func = ewin.PercentRank()
        elif n == "cume_dist":
            func = ewin.CumeDist()
        elif n in ("lead", "lag"):
            off = _pyval(lower(f.args[1])) if len(f.args) > 1 else 1
            dflt = _pyval(lower(f.args[2])) if len(f.args) > 2 else None
            cls = ewin.Lead if n == "lead" else ewin.Lag
            func = cls(lower(f.args[0]), off, dflt)
        elif n in _AGG_FUNCS:
            func = _make_agg(f, lower)
        else:
            raise SqlError(f"unknown window function {n}")
        pb = [lower(p) for p in w.partition]
        ob = [L.SortOrder(lower(o.e), o.asc, o.nulls_first)
              for o in w.order]
        if w.frame is not None:
            frame = w.frame
        elif ob:
            frame = ("range", None, 0)
        else:
            frame = ("rows", None, None)
        return L.WindowFunc(func, L.WindowSpec(pb, ob, frame), alias)

    # -- expressions --------------------------------------------------------
    def lower_expr(self, ast: Ast, scope: _Scope) -> ec.Expression:
        lower = lambda a: self.lower_expr(a, scope)  # noqa: E731
        if isinstance(ast, Lit):
            return ec.Literal(ast.value)
        if isinstance(ast, Ident):
            return scope.resolve(ast.parts)
        if isinstance(ast, Res):
            return scope.resolve_actual(ast.cname)
        if isinstance(ast, Interval):
            raise SqlError("INTERVAL only valid next to +/- of a date")
        if isinstance(ast, Bin):
            return self.lower_bin(ast, scope)
        if isinstance(ast, Un):
            if ast.op == "not":
                return ep.Not(lower(ast.operand))
            return ea.UnaryMinus(lower(ast.operand))
        if isinstance(ast, Between):
            e = lower(ast.operand)
            cond = ep.And(
                ep.GreaterThanOrEqual(*ep.coerce_date_string(
                    e, lower(ast.lo))),
                ep.LessThanOrEqual(*ep.coerce_date_string(
                    e, lower(ast.hi))))
            return ep.Not(cond) if ast.negated else cond
        if isinstance(ast, InList):
            e = lower(ast.operand)
            vals = []
            all_lits = all(isinstance(i, Lit) for i in ast.items)
            if all_lits:
                vals = [i.value for i in ast.items]
                out: ec.Expression = ep.In(e, vals)
            else:
                out = ep.EqualTo(e, lower(ast.items[0]))
                for i in ast.items[1:]:
                    out = ep.Or(out, ep.EqualTo(e, lower(i)))
            return ep.Not(out) if ast.negated else out
        if isinstance(ast, LikeE):
            out = es.Like(lower(ast.operand), ec.Literal(ast.pattern))
            return ep.Not(out) if ast.negated else out
        if isinstance(ast, IsNullE):
            return (ep.IsNotNull if ast.negated else ep.IsNull)(
                lower(ast.operand))
        if isinstance(ast, Case):
            if ast.operand is not None:
                op = lower(ast.operand)
                branches = [(ep.EqualTo(op, lower(c)), lower(v))
                            for c, v in ast.whens]
            else:
                branches = [(lower(c), lower(v)) for c, v in ast.whens]
            els = lower(ast.els) if ast.els is not None else None
            return econd.CaseWhen(branches, els)
        if isinstance(ast, CastE):
            return ecast.Cast(lower(ast.operand),
                              _sql_type(ast.typename, ast.p1, ast.p2))
        if isinstance(ast, ScalarSub):
            sub = self.lower(ast.query)
            if len(sub.schema) != 1:
                raise SqlError("scalar subquery must return one column")
            tbl = self._exec_sub(sub)
            if tbl.num_rows > 1:
                raise SqlError("scalar subquery returned more than one row")
            val = tbl.column(0)[0].as_py() if tbl.num_rows else None
            return ec.Literal(val, sub.schema.fields[0].dtype)
        if isinstance(ast, InSub):
            # expression position (inside OR / SELECT / CASE): an
            # UNCORRELATED subquery evaluates eagerly to an IN-list
            # (the q45 shape: ``... or i_item_id in (select ...)``);
            # correlated ones only decorrelate as top-level conjuncts
            try:
                sub = self.lower(ast.query)
            except SqlError as err:
                raise SqlError(
                    "IN (subquery) in expression position must be "
                    "uncorrelated (correlated IN only decorrelates as "
                    f"a top-level WHERE conjunct); subquery error: "
                    f"{err}") from err
            if len(sub.schema) != 1:
                raise SqlError("IN subquery must return one column")
            tbl = self._exec_sub(sub)
            vals = tbl.column(0).to_pylist()
            has_null = any(v is None for v in vals)
            vals = [v for v in vals if v is not None]
            e = ep.In(self.lower_expr(ast.operand, scope), vals)
            if ast.negated:
                if has_null:
                    # Spark 3VL: x NOT IN (set with NULL) is FALSE when
                    # x matches a non-null member, else NULL — never
                    # TRUE.  (Folding to plain FALSE would flip under
                    # an enclosing NOT.)
                    return econd.CaseWhen(
                        [(e, ec.Literal(False, T.BOOL))],
                        ec.Literal(None, T.BOOL))
                return ep.Not(e)
            return e
        if isinstance(ast, Exists):
            raise SqlError(
                "EXISTS only supported as a top-level WHERE conjunct")
        if isinstance(ast, WindowE):
            raise SqlError("window functions only allowed in SELECT/ORDER BY")
        if isinstance(ast, Func):
            return self.lower_func(ast, scope)
        if isinstance(ast, Star):
            raise SqlError("* only allowed in SELECT list or COUNT(*)")
        raise SqlError(f"cannot lower {ast!r}")

    def lower_bin(self, ast: Bin, scope: _Scope) -> ec.Expression:
        lower = lambda a: self.lower_expr(a, scope)  # noqa: E731
        op = ast.op
        # date +/- interval
        if op in ("+", "-") and isinstance(ast.right, Interval):
            iv = ast.right
            if iv.unit != "day":
                raise SqlError(f"INTERVAL unit {iv.unit} not supported")
            base = lower(ast.left)
            return (edt.DateAdd if op == "+" else edt.DateSub)(
                base, ec.Literal(iv.n))
        if op == "+" and isinstance(ast.left, Interval):
            iv = ast.left
            if iv.unit != "day":
                raise SqlError(f"INTERVAL unit {iv.unit} not supported")
            return edt.DateAdd(lower(ast.right), ec.Literal(iv.n))
        l, r = lower(ast.left), lower(ast.right)
        if op == "or":
            return ep.Or(l, r)
        if op == "and":
            return ep.And(l, r)
        if op in ("=", "<>", "<", "<=", ">", ">="):
            l, r = ep.coerce_date_string(l, r)
        if op == "=":
            return ep.EqualTo(l, r)
        if op == "<>":
            return ep.Not(ep.EqualTo(l, r))
        if op == "<":
            return ep.LessThan(l, r)
        if op == "<=":
            return ep.LessThanOrEqual(l, r)
        if op == ">":
            return ep.GreaterThan(l, r)
        if op == ">=":
            return ep.GreaterThanOrEqual(l, r)
        if op in ("+", "-"):
            shifted = edt.date_plus_days(l, r, op)
            if shifted is not None:
                return shifted
        if op == "+":
            return ea.Add(l, r)
        if op == "-":
            return ea.Subtract(l, r)
        if op == "*":
            return ea.Multiply(l, r)
        if op == "/":
            return ea.Divide(l, r)
        if op == "%":
            return ea.Remainder(l, r)
        if op == "||":
            return es.ConcatStrings(l, r)
        raise SqlError(f"unknown operator {op}")

    def lower_func(self, f: Func, scope: _Scope) -> ec.Expression:
        from . import functions as F
        from .column import Col
        lower = lambda a: self.lower_expr(a, scope)  # noqa: E731
        n = f.fname
        if n in _AGG_FUNCS:
            raise SqlError(
                f"aggregate {n} not allowed here (no GROUP BY context)")
        args = [lower(a) for a in f.args]
        cargs = [Col(a) for a in args]

        def unwrap(x):
            return x.expr if isinstance(x, Col) else x

        simple = {
            "abs": F.abs, "sqrt": F.sqrt, "exp": F.exp, "ln": F.log,
            "log": F.log, "log2": F.log2, "log10": F.log10, "sin": F.sin,
            "cos": F.cos, "tan": F.tan, "asin": F.asin, "acos": F.acos,
            "atan": F.atan, "floor": F.floor, "ceil": F.ceil,
            "ceiling": F.ceil, "sign": F.signum, "signum": F.signum,
            "degrees": F.degrees, "radians": F.radians,
            "upper": F.upper, "ucase": F.upper, "lower": F.lower,
            "lcase": F.lower, "length": F.length,
            "char_length": F.length, "character_length": F.length,
            "trim": F.trim, "ltrim": F.ltrim, "rtrim": F.rtrim,
            "reverse": F.reverse, "initcap": F.initcap,
            "year": F.year, "month": F.month, "day": F.dayofmonth,
            "dayofmonth": F.dayofmonth, "quarter": F.quarter,
            "dayofweek": F.dayofweek, "weekday": F.weekday,
            "dayofyear": F.dayofyear, "hour": F.hour, "minute": F.minute,
            "second": F.second, "last_day": F.last_day,
            "to_date": F.to_date, "isnan": F.isnan, "md5": F.md5,
        }
        if n in simple:
            return unwrap(simple[n](*cargs))
        if n in ("pow", "power"):
            return unwrap(F.pow(cargs[0], cargs[1]))
        if n == "atan2":
            return ea.Atan2(args[0], args[1])
        if n in ("mod",):
            return ea.Remainder(args[0], args[1])
        if n == "pmod":
            return ea.Pmod(args[0], args[1])
        if n == "round":
            return unwrap(F.round(cargs[0],
                                  _pyval(args[1]) if len(args) > 1 else 0))
        if n == "greatest":
            return unwrap(F.greatest(*cargs))
        if n == "least":
            return unwrap(F.least(*cargs))
        if n in ("substring", "substr"):
            return unwrap(F.substring(cargs[0], _pyval(args[1]),
                                      _pyval(args[2])))
        if n == "concat":
            return unwrap(F.concat(*cargs))
        if n == "concat_ws":
            return unwrap(F.concat_ws(_pyval(args[0]), *cargs[1:]))
        if n == "replace":
            return unwrap(F.replace(cargs[0], _pyval(args[1]),
                                    _pyval(args[2])))
        if n == "repeat":
            return unwrap(F.repeat(cargs[0], _pyval(args[1])))
        if n == "lpad":
            return unwrap(F.lpad(cargs[0], _pyval(args[1]),
                                 _pyval(args[2]) if len(args) > 2 else " "))
        if n == "rpad":
            return unwrap(F.rpad(cargs[0], _pyval(args[1]),
                                 _pyval(args[2]) if len(args) > 2 else " "))
        if n == "instr":
            return unwrap(F.instr(cargs[0], _pyval(args[1])))
        if n == "locate":
            return unwrap(F.locate(_pyval(args[0]), cargs[1],
                                   _pyval(args[2]) if len(args) > 2 else 1))
        if n == "regexp_replace":
            return unwrap(F.regexp_replace(cargs[0], _pyval(args[1]),
                                           _pyval(args[2])))
        if n == "regexp_extract":
            return unwrap(F.regexp_extract(
                cargs[0], _pyval(args[1]),
                _pyval(args[2]) if len(args) > 2 else 1))
        if n == "date_add":
            return unwrap(F.date_add(cargs[0], _pyval(args[1])))
        if n == "date_sub":
            return unwrap(F.date_sub(cargs[0], _pyval(args[1])))
        if n == "datediff":
            return unwrap(F.datediff(cargs[0], cargs[1]))
        if n == "coalesce":
            return econd.Coalesce(*args)
        if n in ("nvl", "ifnull"):
            return econd.Coalesce(*args)
        if n == "nullif":
            return econd.If(ep.EqualTo(args[0], args[1]),
                            ec.Literal(None, args[0].dtype()), args[0])
        if n == "isnull":
            return ep.IsNull(args[0])
        if n == "isnotnull":
            return ep.IsNotNull(args[0])
        if n == "nanvl":
            return econd.NaNvl(args[0], args[1])
        if n == "if":
            return econd.If(args[0], args[1], args[2])
        if n == "hash":
            return emisc.Murmur3Hash(*args)
        raise SqlError(f"unknown function {n}")


def sql_to_plan(sql: str, session, views) -> L.LogicalPlan:
    from ..obs import trace as _trace
    from ..plan.logical_opt import optimize
    with _trace.span("srt.sql.parse", "front_end", True):
        ast = parse_sql(sql)
    with _trace.span("srt.sql.analyze", "front_end", True):
        return optimize(_Lowerer(session, views).lower(ast))
