"""Plain reference for q72.sql: numpy over the Parquet columns, in
another order than the text's.  The sales lines that pass the three
dimension filters and the ship-date step are found first; each is then
joined to the inventory rows of its item and its sold date's week (the
text's ``cs_item_sk = inv_item_sk`` and ``d1.d_week_seq =
d2.d_week_seq`` as one key), and the quantity test keeps a pair.  The
two outer joins add the promotion flag and, for a line with several
returns, one row a return.  Counts, names and weeks: every cell is
exact in either precision."""
import numpy as np

BUY_POTENTIAL = ">10000"
MARITAL_STATUS = "D"
YEAR = 1999
DAYS = 5


def _row(num, keys, probe):
    row = num.lookup(keys, probe)
    return row, row >= 0


def _days(dates):
    return np.asarray(dates).astype("datetime64[D]").astype(np.int64)


def answer(t, num):
    cs, inv, dd = t["catalog_sales"], t["inventory"], t["date_dim"]
    hd, cd = t["household_demographics"], t["customer_demographics"]
    item, wh = t["item"], t["warehouse"]
    day = _days(dd["d_date"])
    hrow, ok = _row(num, hd["hd_demo_sk"], cs["cs_bill_hdemo_sk"])
    ok &= hd["hd_buy_potential"].eq(BUY_POTENTIAL)[np.maximum(hrow, 0)]
    crow, found = _row(num, cd["cd_demo_sk"], cs["cs_bill_cdemo_sk"])
    ok &= found & cd["cd_marital_status"].eq(MARITAL_STATUS)[
        np.maximum(crow, 0)]
    d1, found = _row(num, dd["d_date_sk"], cs["cs_sold_date_sk"])
    d1 = np.maximum(d1, 0)
    ok &= found & (dd["d_year"][d1] == YEAR)
    d3, found = _row(num, dd["d_date_sk"], cs["cs_ship_date_sk"])
    ok &= found & (day[np.maximum(d3, 0)] > day[d1] + DAYS)
    irow, found = _row(num, item["i_item_sk"], cs["cs_item_sk"])
    ok &= found
    line = np.flatnonzero(ok)
    week1 = dd["d_week_seq"][d1[line]].astype(np.int64)
    # inventory rows by (item, week), each with its warehouse row
    d2, found = _row(num, dd["d_date_sk"], inv["inv_date_sk"])
    wrow, wfound = _row(num, wh["w_warehouse_sk"], inv["inv_warehouse_sk"])
    keep = np.flatnonzero(found & wfound)
    inv_key = inv["inv_item_sk"][keep] * (1 << 24) + \
        dd["d_week_seq"][d2[keep]].astype(np.int64)
    order = np.argsort(inv_key, kind="stable")
    inv_key, keep = inv_key[order], keep[order]
    sale_key = cs["cs_item_sk"][line] * (1 << 24) + week1
    lo = np.searchsorted(inv_key, sale_key, "left")
    hi = np.searchsorted(inv_key, sale_key, "right")
    n = hi - lo
    s = np.repeat(np.arange(len(line)), n)
    pos = np.repeat(lo - np.cumsum(n) + n, n) + np.arange(n.sum())
    r = keep[pos]
    q_ok = inv["inv_quantity_on_hand"][r] < cs["cs_quantity"][line[s]]
    s, r = s[q_ok], r[q_ok]
    sl = line[s]
    # left outer join promotion: a line's p_promo_sk is NULL where its
    # cs_promo_sk is NULL or names no promotion
    promo = cs["cs_promo_sk"]
    valid = ~np.isnan(promo) if promo.dtype.kind == "f" else \
        np.ones(len(promo), bool)
    pkey = np.where(valid, promo, -1).astype(np.int64)
    prow = num.lookup(t["promotion"]["p_promo_sk"], pkey)
    promoted = (valid & (prow >= 0))[sl]
    # left outer join catalog_returns: one row a matching return, one
    # row for a line with none
    cr = t["catalog_returns"]
    rkey = cr["cr_item_sk"] * (1 << 32) + cr["cr_order_number"]
    rkey.sort()
    lkey = cs["cs_item_sk"][sl] * (1 << 32) + cs["cs_order_number"][sl]
    mult = np.maximum(np.searchsorted(rkey, lkey, "right") -
                      np.searchsorted(rkey, lkey, "left"), 1)
    desc = item["i_item_desc"].codes[irow[sl]]
    wname = wh["w_warehouse_name"].codes[wrow[r]]
    groups = {}
    for g, m, p in zip(zip(desc.tolist(), wname.tolist(),
                           week1[s].tolist()),
                       mult.tolist(), promoted.tolist()):
        acc = groups.setdefault(g, [0, 0, 0])
        acc[1 if p else 0] += m
        acc[2] += m
    rows = [(item["i_item_desc"].cats[g[0]], wh["w_warehouse_name"].cats[g[1]],
             int(g[2]), a[0], a[1], a[2]) for g, a in groups.items()]
    rows.sort(key=lambda r: (-r[5], r[0], r[1], r[2]))
    return rows[:100]
