"""Plain reference for q21.sql: numpy over the Parquet columns.  The
EXISTS and NOT EXISTS are read per order: a candidate line ``l1`` (late,
on an order of status F, of a supplier in SAUDI ARABIA) counts when its
order has a line of another supplier (the order's smallest and largest
``l_suppkey`` are not both ``l1``'s) and no late line of another supplier
(the smallest and largest ``l_suppkey`` over the order's late lines are
both ``l1``'s: ``l1`` is one of them).  Counts are integers and the names
strings: every cell is exact in either precision."""
import numpy as np

NATION = "SAUDI ARABIA"


def _per_order(okey, supp):
    """-> (order keys, smallest and largest supplier a key) over the
    lines given."""
    order = np.argsort(okey, kind="stable")
    k, s = okey[order], supp[order]
    starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    return (k[starts], np.minimum.reduceat(s, starts),
            np.maximum.reduceat(s, starts))


def _at(keys, lo, hi, probe):
    pos = np.clip(np.searchsorted(keys, probe), 0, len(keys) - 1)
    found = keys[pos] == probe
    return found, lo[pos], hi[pos]


def answer(t, num):
    s, li, o, n = t["supplier"], t["lineitem"], t["orders"], t["nation"]
    okey, supp = li["l_orderkey"], li["l_suppkey"]
    late = li["l_receiptdate"] > li["l_commitdate"]
    nation = n["n_nationkey"][n["n_name"].eq(NATION)]
    srow = num.lookup(s["s_suppkey"], supp)
    cand = late & (srow >= 0)
    cand &= np.isin(s["s_nationkey"][np.maximum(srow, 0)], nation)
    orow = num.lookup(o["o_orderkey"], okey)
    cand &= (orow >= 0) & o["o_orderstatus"].eq("F")[np.maximum(orow, 0)]
    line = np.flatnonzero(cand)
    mine = supp[line]
    keys, lo, hi = _per_order(okey, supp)
    _, lo_all, hi_all = _at(keys, lo, hi, okey[line])
    other = (lo_all != mine) | (hi_all != mine)
    lkeys, llo, lhi = _per_order(okey[late], supp[late])
    _, lo_late, hi_late = _at(lkeys, llo, lhi, okey[line])
    alone = (lo_late == mine) & (hi_late == mine)
    kept = srow[line][other & alone]
    rows, numwait = np.unique(kept, return_counts=True)
    names = s["s_name"].decode(rows)
    top = sorted(zip(names, numwait.tolist()), key=lambda r: (-r[1], r[0]))
    return [(name, int(c)) for name, c in top[:100]]
