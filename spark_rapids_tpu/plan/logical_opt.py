"""Logical-plan rewrites: predicate pushdown + equi-join extraction.

Role note: the reference plugs into Spark *after* Catalyst's optimizer has
already pushed predicates and chosen join keys (SparkPlan arrives
optimized; GpuOverrides.scala:3100 only re-maps physical ops).  This
standalone framework owns the front end, so the classical rewrites live
here: conjuncts of a Filter over an inner/cross Join are split into
per-side filters, cross-side equalities become hash-join keys (turning a
cross join into an equi join the TPU hash-join exec can run), the other
two-sided conjuncts become an equi join's residual condition, and what
is left stays as a Filter.  A WHERE conjunct that reads an outer join's
preserved side alone filters that side, and an outer join's ON
conjuncts that read its null-supplying side alone filter that side.
"""
from __future__ import annotations

import copy
from typing import List, Optional, Set

from ..expr import core as ec
from ..expr import predicates as ep
from ..obs import trace as _trace
from . import logical as L


def _flatten_and(e: ec.Expression) -> List[ec.Expression]:
    if isinstance(e, ep.And):
        return _flatten_and(e.children[0]) + _flatten_and(e.children[1])
    return [e]


def _and_all(conjuncts: List[ec.Expression]) -> ec.Expression:
    out = conjuncts[0]
    for c in conjuncts[1:]:
        out = ep.And(out, c)
    return out


def _refs(e: ec.Expression) -> Optional[Set[str]]:
    """Names of AttributeReferences in e; None if e contains anything
    (BoundReference, subquery-ish) that makes pushdown unsafe."""
    if isinstance(e, ec.BoundReference):
        return None
    if isinstance(e, ec.AttributeReference):
        return {e.col_name}
    out: Set[str] = set()
    for c in e.children:
        r = _refs(c)
        if r is None:
            return None
        out |= r
    return out


def _filter_over(conjuncts: List[ec.Expression],
                 plan: L.LogicalPlan) -> L.LogicalPlan:
    if not conjuncts:
        return plan
    return L.Filter(_and_all(conjuncts), plan)


def _flatten_or(e: ec.Expression) -> List[ec.Expression]:
    if isinstance(e, ep.Or):
        return _flatten_or(e.children[0]) + _flatten_or(e.children[1])
    return [e]


def _or_all(disjuncts: List[ec.Expression]) -> ec.Expression:
    out = disjuncts[0]
    for d in disjuncts[1:]:
        out = ep.Or(out, d)
    return out


def _same_as(e: ec.Expression):
    """A key two expressions share only when they are the same
    expression.  ``repr`` is not one: it leaves out what a node holds
    besides its children (``In``'s value list, a LIKE pattern, a cast's
    type), so ``c in ('a') and P or c in ('b') and Q`` read as two arms
    with ``c in (...)`` in common and lost both lists (TPC-DS q89's two
    category / class triples; found by PR 34's plain reference)."""
    state = tuple(sorted((k, repr(v)) for k, v in vars(e).items()
                         if k != "children"))
    return (type(e).__name__, state, tuple(_same_as(c) for c in e.children))


def _factor_or(e: ec.Expression) -> List[ec.Expression]:
    """Factor conjuncts common to every OR arm out of the disjunction:
    ``(A and B) or (A and C)  ->  A and (B or C)``.

    Sound in SQL's three-valued logic (Kleene distributivity), and
    load-bearing for the TPC-DS q13/q48 shape where the JOIN
    EQUALITIES live inside each OR arm — without factoring they never
    become hash-join keys and the plan degenerates to a cross join."""
    disjuncts = _flatten_or(e)
    if len(disjuncts) < 2:
        return [e]
    conj_lists = [_flatten_and(d) for d in disjuncts]
    first_keys = {_same_as(c): c for c in conj_lists[0]}
    common_keys = [k for k in first_keys
                   if all(any(_same_as(x) == k for x in cl)
                          for cl in conj_lists[1:])]
    if not common_keys:
        return [e]
    common_set = set(common_keys)
    remainders = []
    for cl in conj_lists:
        removed: Set[tuple] = set()
        rem = []
        for x in cl:
            rx = _same_as(x)
            if rx in common_set and rx not in removed:
                removed.add(rx)
                continue
            rem.append(x)
        remainders.append(_and_all(rem) if rem else
                          ec.Literal(True))
    return [first_keys[k] for k in common_keys] + [_or_all(remainders)]


def _rewrite_filter_join(f: L.Filter) -> L.LogicalPlan:
    j = f.children[0]
    if not isinstance(j, L.Join) or j.join_type not in ("inner", "cross"):
        return f
    left, right = j.children
    lnames = set(left.schema.names)
    rnames = set(right.schema.names)
    if lnames & rnames:
        return f  # ambiguous column names: leave untouched
    lpush: List[ec.Expression] = []
    rpush: List[ec.Expression] = []
    lkeys = list(j.left_keys)
    rkeys = list(j.right_keys)
    rest: List[ec.Expression] = []
    both: List[ec.Expression] = []    # two-sided, not an equi-key
    conjuncts = [x for c in _flatten_and(f.condition)
                 for x in _factor_or(c)]
    for c in conjuncts:
        refs = _refs(c)
        if refs is None or not refs:
            rest.append(c)
        elif refs <= lnames:
            lpush.append(c)
        elif refs <= rnames:
            rpush.append(c)
        elif isinstance(c, ep.EqualTo) and \
                (key := _equi_key(c, lnames, rnames)) is not None:
            lkeys.append(key[0])
            rkeys.append(key[1])
        elif refs <= lnames | rnames:
            both.append(c)
        else:
            rest.append(c)
    cond, above = _residual_condition(j.condition, lkeys, both)
    rest += above
    if not lpush and not rpush and len(lkeys) == len(j.left_keys) \
            and cond is j.condition:
        return f
    new_left = optimize(_filter_over(lpush, left))
    new_right = optimize(_filter_over(rpush, right))
    jt = "inner" if lkeys else j.join_type
    nj = L.Join(new_left, new_right, jt, lkeys, rkeys, cond)
    return _filter_over(rest, nj)


def _residual_condition(condition, keys, both):
    """-> (the inner join's condition, the conjuncts left above it).  An
    equi join decides a two-sided conjunct over its candidate pairs: for
    an inner join the rows a Filter above it would keep, without
    gathering every column of every pair first (TPC-DS q72's
    ``inv_quantity_on_hand < cs_quantity``).  A join with no key stays a
    cross join under the Filter."""
    if not keys or not both:
        return condition, both
    conds = ([condition] if condition is not None else []) + both
    return _and_all(conds), []


def _equi_key(c: ep.EqualTo, lnames: Set[str], rnames: Set[str]):
    """-> (left key, right key) when ``c`` equates an expression of the
    left side alone with one of the right side alone, else None."""
    a, b = c.children
    ra, rb = _refs(a), _refs(b)
    if ra and rb and ra <= lnames and rb <= rnames:
        return a, b
    if ra and rb and ra <= rnames and rb <= lnames:
        return b, a
    return None


#: outer join type -> the child whose rows it keeps whatever matches
#: (the preserved side), the one a WHERE conjunct may filter first
_PRESERVED = {"left": 0, "right": 1}


def _rewrite_filter_outer(f: L.Filter) -> L.LogicalPlan:
    """Filter over a LEFT (RIGHT) OUTER join: a conjunct that reads only
    the left (right) child filters that child first (Spark's
    PushPredicateThroughJoin).  A preserved row the conjunct drops would
    be dropped above the join with every row it makes, and the rows it
    keeps join as before.  A conjunct that reads the null-supplying side
    (``p_promo_sk IS NULL`` among them) stays above: the join's NULLs
    are what it tests.  A FULL join preserves both sides and takes
    none.  The pushed Filter is optimized in turn, so a WHERE passes a
    chain of outer joins down to the inner joins below them (TPC-DS
    q72)."""
    j = f.children[0]
    if not isinstance(j, L.Join) or j.join_type not in _PRESERVED:
        return f
    side = _PRESERVED[j.join_type]
    names = set(j.children[side].schema.names)
    if names & set(j.children[1 - side].schema.names):
        return f  # ambiguous column names: leave untouched
    push: List[ec.Expression] = []
    rest: List[ec.Expression] = []
    for c in _flatten_and(f.condition):
        refs = _refs(c)
        (push if refs and refs <= names else rest).append(c)
    if not push:
        return f
    _trace.count("plan.pushdown.outer", len(push))
    kids = list(j.children)
    kids[side] = optimize(_filter_over(push, kids[side]))
    nj = L.Join(kids[0], kids[1], j.join_type, j.left_keys, j.right_keys,
                j.condition)
    return _filter_over(rest, nj)


#: outer join type -> the child whose rows the join may drop (the
#: null-supplying side), the one an ON conjunct may filter
_NULL_SUPPLYING = {"left": 1, "right": 0}


def _push_on_conjuncts(j: L.Join) -> L.LogicalPlan:
    """A LEFT (RIGHT) OUTER join's ON conjuncts that read only its right
    (left) child become a Filter on that child (Spark's
    PushPredicateThroughJoin): a row there that fails one matches no
    row, which is all the join does with it.  Never the preserved side:
    its rows are in the output whatever the ON clause says, with NULLs
    where nothing matched (TPC-H Q13's ``o_comment not like ...``)."""
    side = _NULL_SUPPLYING.get(j.join_type)
    if side is None or j.condition is None:
        return j
    names = set(j.children[side].schema.names)
    if names & set(j.children[1 - side].schema.names):
        return j  # ambiguous column names: leave untouched
    push: List[ec.Expression] = []
    rest: List[ec.Expression] = []
    for c in _flatten_and(j.condition):
        refs = _refs(c)
        (push if refs and refs <= names else rest).append(c)
    if not push:
        return j
    _trace.count("plan.join.on_pushdown", len(push))
    kids = list(j.children)
    kids[side] = optimize(_filter_over(push, kids[side]))
    return L.Join(kids[0], kids[1], j.join_type, j.left_keys, j.right_keys,
                  _and_all(rest) if rest else None)


def _rewrite_filter_semi(f: L.Filter) -> L.LogicalPlan:
    """Filter over a semi/anti join: conjuncts that reference only the
    left side commute with the join (its output IS the left rows), so
    they push into the left child — where the inner/cross rewrite can
    then lift equalities into hash-join keys.  Load-bearing for the
    ``x IN (subquery)`` lowering, which stacks a semi join between the
    WHERE filter and the comma-join chain it must decompose."""
    j = f.children[0]
    if not isinstance(j, L.Join) or j.join_type not in ("semi", "anti"):
        return f
    left = j.children[0]
    lnames = set(left.schema.names)
    push: List[ec.Expression] = []
    rest: List[ec.Expression] = []
    for c in _flatten_and(f.condition):
        refs = _refs(c)
        if refs is not None and refs and refs <= lnames:
            push.append(c)
        else:
            rest.append(c)
    if not push:
        return f
    new_left = optimize(_filter_over(push, left))
    nj = L.Join(new_left, j.children[1], j.join_type, j.left_keys,
                j.right_keys, j.condition)
    return _filter_over(rest, nj)


def _rewrite_filter_project(f: L.Filter) -> L.LogicalPlan:
    """Push Filter conjuncts through a pass-through/renaming Project so
    they can keep sinking into the join below (the scalar-subquery
    decorrelation emits Project(Filter(Join(cross...))) shapes whose
    outer WHERE conjuncts must still reach the cross join)."""
    pj = f.children[0]
    if not isinstance(pj, L.Project):
        return f
    # out name -> source name, only for pure column pass-throughs
    mapping = {}
    for e in pj.exprs:
        src = e
        name = None
        if isinstance(e, ec.Alias):
            name = e.alias
            src = e.children[0]
        if isinstance(src, ec.AttributeReference):
            mapping[name or src.col_name] = src.col_name
    push: List[ec.Expression] = []
    rest: List[ec.Expression] = []

    def rewrite(e: ec.Expression):
        if isinstance(e, ec.AttributeReference):
            if e.col_name not in mapping:
                return None
            return ec.AttributeReference(mapping[e.col_name], e._dtype,
                                         e._nullable)
        kids = []
        for c in e.children:
            r = rewrite(c)
            if r is None:
                return None
            kids.append(r)
        return e.with_children(kids) if kids else e

    for c in _flatten_and(f.condition):
        refs = _refs(c)
        if refs is None:
            rest.append(c)
            continue
        r = rewrite(c)
        if r is not None:
            push.append(r)
        else:
            rest.append(c)
    if not push:
        return f
    new_child = optimize(_filter_over(push, pj.children[0]))
    npj = L.Project(pj.exprs, new_child)
    return _filter_over(rest, npj)


def _collect_cross_tree(p: L.LogicalPlan, rels: List[L.LogicalPlan]
                        ) -> bool:
    """Flatten a left-deep keyless cross/inner join tree into its
    relations; False if the tree has keys/conditions (already shaped)."""
    if isinstance(p, L.Join) and p.join_type in ("cross", "inner") and \
            not p.left_keys and p.condition is None:
        return _collect_cross_tree(p.children[0], rels) and \
            _collect_cross_tree(p.children[1], rels)
    rels.append(p)
    return True


def _reorder_cross_joins(f: L.Filter) -> L.Filter:
    """Connectivity-first join ordering over a FROM comma-list.

    The lowerer builds a left-deep cross-join tree in FROM order; when
    a relation's only equi predicates reference relations that appear
    LATER (TPC-DS q64 lists date_dim d2/d3 before customer), the
    pairwise rewrite leaves a cartesian behind and the plan explodes.
    Greedy fix (the classical heuristic): start from the first
    relation, repeatedly attach a relation linked to the joined set by
    an equality predicate; fall back to FROM order only when nothing
    connects.  The pairwise _rewrite_filter_join pass then distributes
    the predicates over the reordered tree."""
    j = f.children[0]
    rels: List[L.LogicalPlan] = []
    if not (isinstance(j, L.Join) and _collect_cross_tree(j, rels)) or \
            len(rels) < 3:
        return f
    names = [set(r.schema.names) for r in rels]
    if len(set().union(*names)) != sum(len(n) for n in names):
        return f                      # ambiguous columns: leave alone
    # equality edges between relation indices
    edges = []
    for c in _flatten_and(f.condition):
        if isinstance(c, ep.EqualTo):
            ra = _refs(c.children[0])
            rb = _refs(c.children[1])
            if not ra or not rb:
                continue
            ia = [i for i, n in enumerate(names) if ra <= n]
            ib = [i for i, n in enumerate(names) if rb <= n]
            if len(ia) == 1 and len(ib) == 1 and ia[0] != ib[0]:
                edges.append((ia[0], ib[0]))
    joined = {0}
    order = [0]
    remaining = list(range(1, len(rels)))
    while remaining:
        pick = None
        for i in remaining:           # FROM order among connected
            if any((a in joined) != (b in joined) and i in (a, b)
                   for a, b in edges):
                pick = i
                break
        if pick is None:
            pick = remaining[0]       # nothing connects: cross join
        joined.add(pick)
        order.append(pick)
        remaining.remove(pick)
    if order == list(range(len(rels))):
        return f
    tree: L.LogicalPlan = rels[order[0]]
    for i in order[1:]:
        tree = L.Join(tree, rels[i], "cross", [], [], None)
    return L.Filter(f.condition, tree)


# ---------------------------------------------------------------------------
# scan column pruning (Spark's ColumnPruning rule; the reference relies on
# Catalyst doing this before the plugin sees the plan — without it every
# file scan decodes AND uploads all columns, and host->device bandwidth is
# the scarcest resource on this backend)
# ---------------------------------------------------------------------------

def _u(*sets: "Optional[Set[str]]") -> "Optional[Set[str]]":
    """Union of required-name sets; None ("need everything") poisons."""
    out: Set[str] = set()
    for s in sets:
        if s is None:
            return None
        out |= s
    return out


def _refs_many(exprs) -> "Optional[Set[str]]":
    return _u(*[_refs(e) for e in exprs]) if exprs else set()


def _narrowest_field(fields):
    """Cheapest single column to keep for pure-count scans."""
    def width(f):
        w = getattr(f.dtype, "itemsize", None)
        if w is None:
            w = 16 if f.dtype.name in ("string", "binary") else 8
        return w
    return min(fields, key=width)


def prune_scan_columns(plan: L.LogicalPlan,
                       need: "Optional[Set[str]]" = None) -> L.LogicalPlan:
    """Top-down required-column propagation narrowing file scans.

    ``need=None`` means the parent requires every output column (the
    root, and any opaque consumer: pandas execs, writers, DISTINCT).
    Nodes are copied, never mutated — Scan nodes are shared across
    queries via registered views.
    """
    import copy as _copy

    def rec(p: L.LogicalPlan, need, parent=None):
        if isinstance(p, L.Scan):
            if need is None:
                return p
            kept = [f for f in p.schema.fields if f.name in need]
            if len(kept) == len(p.schema.fields):
                return p
            if not kept:
                kept = [_narrowest_field(p.schema.fields)]
            from ..columnar.schema import Schema
            out = _copy.copy(p)
            out._schema = Schema(kept)
            return out
        if isinstance(p, (L.LocalRelation, L.Range, L.CachedRelation)) or \
                not p.children:
            return p

        dropped = None            # replacement exprs/aggs when narrowed
        if isinstance(p, L.Filter):
            needs = [_u(need, _refs(p.condition))]
        elif isinstance(p, L.Project):
            kept = p.exprs if need is None else \
                [e for e in p.exprs if L.output_name(e) in need]
            if not kept:
                kept = p.exprs[:1]
            if len(kept) != len(p.exprs):
                dropped = ("exprs", kept)
            needs = [_refs_many(kept)]
        elif isinstance(p, L.Aggregate):
            kept_aggs = p.aggs if need is None else \
                [a for a in p.aggs if a.alias in need]
            if len(kept_aggs) != len(p.aggs):
                dropped = ("aggs", kept_aggs)
            needs = [_u(_refs_many(p.group_exprs),
                        _refs_many([a.func for a in kept_aggs]))]
        elif isinstance(p, L.Join):
            cn = _u(need, _refs_many(p.left_keys),
                    _refs_many(p.right_keys),
                    _refs(p.condition) if p.condition is not None
                    else set())
            needs = [cn, cn]
            if need is not None and isinstance(parent,
                                               (L.Project, L.Aggregate)):
                # record which OUTPUT columns the parent actually
                # consumes: execs that can emit a subset (the mesh
                # join routes fixed-width payloads) key off this —
                # e.g. a string JOIN KEY the parent projects away
                # stops blocking the mesh path.  Only name-binding
                # parents (Project/Aggregate) qualify: positional
                # consumers (another Join's output assembly) pair
                # columns with the full logical schema.  Never prune
                # to zero columns — batches need a capacity carrier.
                names = [f.name for f in p.schema.fields]
                if len(names) == len(set(names)):
                    req = sorted(n for n in need if n in set(names))
                    if not req:
                        req = [_narrowest_field(p.schema.fields).name]
                    if len(req) < len(names):
                        dropped = ("required_out", req)
        elif isinstance(p, L.Sort):
            needs = [_u(need, _refs_many([o.expr for o in p.orders]))]
        elif isinstance(p, L.Limit):
            needs = [need]
        elif isinstance(p, L.Repartition):
            needs = [_u(need, _refs_many(p.by_exprs or []))]
        elif isinstance(p, L.Window):
            aliases = {wf.alias for wf in p.window_funcs}
            base = None if need is None else \
                {n for n in need if n not in aliases}
            wrefs = []
            for wf in p.window_funcs:
                wrefs.append(_refs(wf.func))
                wrefs.append(_refs_many(wf.spec.partition_by))
                wrefs.append(_refs_many([o.expr for o in wf.spec.order_by]))
            needs = [_u(base, *wrefs)]
        elif isinstance(p, L.Expand):
            needs = [_refs_many([e for proj in p.projections for e in proj])]
        elif isinstance(p, L.Generate):
            gen_names = set(p.output_names)
            base = None if need is None else \
                {n for n in need if n not in gen_names}
            needs = [_u(base, _refs(p.generator))]
        elif isinstance(p, L.Union):
            if need is None:
                needs = [None] * len(p.children)
            else:
                try:
                    pos = [i for i, f in enumerate(p.schema.fields)
                           if f.name in need]
                    needs = [{c.schema.fields[i].name for i in pos}
                             for c in p.children]
                except Exception:
                    needs = [None] * len(p.children)
        else:
            # Distinct (whole-row semantics), writers, pandas execs,
            # and anything unknown: require every column below
            needs = [None] * len(p.children)

        new_children = [rec(c, n, parent=p)
                        for c, n in zip(p.children, needs)]
        if dropped is None and all(n is o for n, o in
                                   zip(new_children, p.children)):
            return p
        out = _copy.copy(p)
        out.children = new_children
        if dropped is not None:
            setattr(out, dropped[0], dropped[1])
        return out

    return rec(plan, need)


def optimize(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Bottom-up: push Filter conjuncts through inner/cross joins,
    promote cross-side equalities to join keys and make an equi join's
    other two-sided conjuncts its condition; push a WHERE conjunct on an
    outer join's preserved side into that side, and an outer join's
    one-sided ON conjuncts into its null-supplying side."""
    new_children = [optimize(c) for c in plan.children]
    if any(n is not o for n, o in zip(new_children, plan.children)):
        plan = copy.copy(plan)
        plan.children = new_children
    if isinstance(plan, L.Join):
        return _push_on_conjuncts(plan)
    if isinstance(plan, L.Filter):
        # collapse Filter(Filter(..)) so conjuncts see the join below
        child = plan.children[0]
        if isinstance(child, L.Filter):
            merged = L.Filter(
                ep.And(plan.condition, child.condition), child.children[0])
            return optimize(merged)
        plan = _reorder_cross_joins(plan)
        out = _rewrite_filter_join(plan)
        if out is not plan:
            return out
        out = _rewrite_filter_semi(plan)
        if out is not plan:
            return out
        out = _rewrite_filter_outer(plan)
        if out is not plan:
            return out
        out = _rewrite_filter_project(plan)
        if out is not plan:
            return out
    return plan
