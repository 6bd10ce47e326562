"""Plain reference for q89.sql: numpy over the Parquet columns.  The
six-key groups are runs of a sort over one integer made of the keys'
codes; the partition average is a second grouping over the groups' sums
(``avg(sum(x)) over (partition by ...)``); the filter and the first
ORDER BY key are computed in the precision under test."""
import numpy as np


def ranks(col):
    """A StrCol's codes renumbered in the strings' sorted order, and
    the sorted strings."""
    order = sorted(range(len(col.cats)), key=lambda c: col.cats[c])
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order))
    return rank[col.codes], [col.cats[c] for c in order]


def deviating(t, num):
    """Every row the filter keeps, in the text's order (no LIMIT)."""
    ss, i, d, s = t["store_sales"], t["item"], t["date_dim"], t["store"]
    di = num.lookup(d["d_date_sk"], ss["ss_sold_date_sk"])
    ii = num.lookup(i["i_item_sk"], ss["ss_item_sk"])
    si = num.lookup(s["s_store_sk"], ss["ss_store_sk"])

    def among(col, values):
        hit = np.zeros(len(col.codes), bool)
        for v in values:
            hit |= col.eq(v)
        return hit
    i_ok = ((among(i["i_category"], ("Books", "Music", "Sports"))
             & among(i["i_class"], ("classical", "fishing", "football")))
            | (among(i["i_category"], ("Men", "Women", "Home"))
               & among(i["i_class"], ("pants", "shirts", "dresses"))))
    keep = (di >= 0) & (ii >= 0) & (si >= 0)
    keep &= i_ok[ii] & (d["d_year"] == 1999)[di]
    ii, di, si = ii[keep], di[keep], si[keep]
    price = ss["ss_sales_price"][keep]
    cat, cats = ranks(i["i_category"])
    cls, classes = ranks(i["i_class"])
    brand, brands = ranks(i["i_brand"])
    sname, snames = ranks(s["s_store_name"])
    company = s["s_company_id"].astype(np.int64)
    moy = d["d_moy"].astype(np.int64)
    # (category, brand, store name, company) is the window's partition:
    # it leads, so a partition is a run of groups
    parts = [(cat[ii], len(cats)), (brand[ii], len(brands)),
             (sname[si], len(snames)),
             (company[si] - company.min(), int(np.ptp(company)) + 1),
             (cls[ii], len(classes)), (moy[di], 13)]
    key = np.zeros(len(ii), np.int64)
    for codes, radix in parts:
        key = key * radix + codes
    order = np.argsort(key, kind="stable")
    key, price = key[order], price[order]
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    ends = np.r_[starts[1:], len(key)]
    sums = [num.sum(price[a:b]) for a, b in zip(starts, ends)]
    first = order[starts]
    part = key[starts] // (len(classes) * 13)
    pstarts = np.flatnonzero(np.r_[True, part[1:] != part[:-1]])
    pends = np.r_[pstarts[1:], len(part)]
    rows = []
    for a, b in zip(pstarts, pends):
        avg = num.avg(np.array(sums[a:b], num.f))
        for g in range(a, b):
            r = first[g]
            total = sums[g]
            if avg == 0 or not (abs(num.f(total) - num.f(avg))
                                / num.f(avg) > num.f(0.1)):
                continue
            rows.append((cats[cat[ii[r]]], classes[cls[ii[r]]],
                         brands[brand[ii[r]]], snames[sname[si[r]]],
                         int(company[si[r]]), int(moy[di[r]]), total, avg))
    rows.sort(key=lambda r: (float(num.f(r[6]) - num.f(r[7])), r[3], r[0],
                             r[1], r[2], r[5]))
    return rows


def answer(t, num):
    return deviating(t, num)[:100]
