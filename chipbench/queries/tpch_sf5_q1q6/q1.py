"""Plain reference for q1.sql: numpy over the Parquet columns (rows
sorted by group once, so each group is one contiguous slice)."""
import numpy as np


def answer(t, num):
    li = t["lineitem"]
    rf, ls = li["l_returnflag"], li["l_linestatus"]
    code = np.where(li["l_shipdate"] <= 10471,
                    rf.codes.astype(np.int16) * len(ls.cats) + ls.codes,
                    -1).astype(np.int16)
    order = np.argsort(code, kind="stable")
    code = code[order]
    qty, price = li["l_quantity"][order], li["l_extendedprice"][order]
    disc, tax = li["l_discount"][order], li["l_tax"][order]
    one = num.f(1)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    rows = []
    for a in sorted(rf.cats):
        for b in sorted(ls.cats):
            k = rf.cats.index(a) * len(ls.cats) + ls.cats.index(b)
            lo, hi = np.searchsorted(code, [k, k + 1])
            if lo == hi:
                continue
            g = slice(lo, hi)
            rows.append((a, b, num.sum(qty[g]), num.sum(price[g]),
                         num.sum(disc_price[g]), num.sum(charge[g]),
                         num.avg(qty[g]), num.avg(price[g]),
                         num.avg(disc[g]), int(hi - lo)))
    return rows
