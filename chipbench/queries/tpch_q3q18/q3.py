"""Plain reference for q3.sql: numpy over the Parquet columns.  The two
primary-key joins are look-ups (``c_custkey`` and ``o_orderkey`` are
unique), the sums run over the joined lines sorted by order, at most
seven addends a group, and ORDER BY is a stable sort."""
import numpy as np


def answer(t, num):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    crow = num.lookup(c["c_custkey"], o["o_custkey"])
    okeep = (crow >= 0) & (o["o_orderdate"] < 9204)
    okeep &= c["c_mktsegment"].eq("BUILDING")[np.maximum(crow, 0)]
    okey, odate = o["o_orderkey"][okeep], o["o_orderdate"][okeep]
    oprio = o["o_shippriority"][okeep]
    line = np.flatnonzero(li["l_shipdate"] > 9204)
    orow = num.lookup(okey, li["l_orderkey"][line])
    line, orow = line[orow >= 0], orow[orow >= 0]
    by_order = np.argsort(orow, kind="stable")
    line, orow = line[by_order], orow[by_order]
    revenue = li["l_extendedprice"][line] * (num.f(1) - li["l_discount"][line])
    if not len(orow):
        return []
    starts = np.flatnonzero(np.r_[True, orow[1:] != orow[:-1]])
    ends = np.r_[starts[1:], len(orow)]
    group = orow[starts]
    total = np.array([num.sum(revenue[a:b]) for a, b in zip(starts, ends)])
    top = np.lexsort((odate[group], -total))[:10]
    return [(int(okey[group[i]]), float(total[i]), int(odate[group[i]]),
             int(oprio[group[i]])) for i in top]
