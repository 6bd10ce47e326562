"""Plain reference for q6.sql: numpy over the Parquet columns."""


def answer(t, num):
    li = t["lineitem"]
    disc = li["l_discount"]
    keep = (li["l_shipdate"] >= 8766) & (li["l_shipdate"] < 9131)
    keep &= (disc >= num.f(0.05)) & (disc <= num.f(0.07))
    keep &= li["l_quantity"] < num.f(24)
    return [(num.sum(li["l_extendedprice"][keep] * disc[keep]),)]
