"""Plain reference for q18.sql: numpy over the Parquet columns.  The
subquery is a sum over the lines sorted by order key; ``o_orderkey`` and
``c_custkey`` are unique, so the outer joins are look-ups, every outer
group is one order, and its ``sum(l_quantity)`` is the sum over all of
that order's lines.  Quantities are whole numbers up to 50: every sum
here is exact in either precision."""
import numpy as np


def answer(t, num):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    by_order = np.argsort(li["l_orderkey"], kind="stable")
    lkey, qty = li["l_orderkey"][by_order], li["l_quantity"][by_order]
    starts = np.flatnonzero(np.r_[True, lkey[1:] != lkey[:-1]])
    qsum = np.add.reduceat(qty, starts)
    big = qsum > num.f(300)
    orow = num.lookup(o["o_orderkey"], lkey[starts][big])
    qsum = qsum[big][orow >= 0]
    orow = orow[orow >= 0]
    crow = num.lookup(c["c_custkey"], o["o_custkey"][orow])
    orow, qsum, crow = orow[crow >= 0], qsum[crow >= 0], crow[crow >= 0]
    total, odate = o["o_totalprice"][orow], o["o_orderdate"][orow]
    top = np.lexsort((odate, -total))[:100]
    names = c["c_name"].decode(crow[top])
    return [(name, int(c["c_custkey"][crow[i]]), int(o["o_orderkey"][orow[i]]),
             int(odate[i]), float(total[i]), float(qsum[i]))
            for name, i in zip(names, top)]
