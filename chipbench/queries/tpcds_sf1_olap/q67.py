"""Plain reference for q67.sql: numpy over the Parquet columns.  One
``np.lexsort`` over integer codes of the eight ROLLUP keys puts the
joined rows in an order in which every grouping set (a prefix of the
keys) is a run; each of the nine sets is summed from the joined rows,
not from a finer set's sums.  ``rank()`` is a stable sort on
(``i_category``, ``-sumsales``) with ties sharing the first position;
NULL (a rolled-up key, code -1) sorts first, as Spark orders ascending
keys."""
import numpy as np


def ranks(col):
    """A StrCol's codes renumbered in the strings' sorted order, and
    the sorted strings."""
    order = sorted(range(len(col.cats)), key=lambda c: col.cats[c])
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order))
    return rank[col.codes], [col.cats[c] for c in order]


def rollup(t, num):
    """-> (the eight keys' codes a group, -1 where rolled up; the sorted
    strings of each string key, None for an integer key; ``sumsales`` a
    group): all nine grouping sets, the finest first."""
    ss, i, d, s = t["store_sales"], t["item"], t["date_dim"], t["store"]
    di = num.lookup(d["d_date_sk"], ss["ss_sold_date_sk"])
    ii = num.lookup(i["i_item_sk"], ss["ss_item_sk"])
    si = num.lookup(s["s_store_sk"], ss["ss_store_sk"])
    d_ok = (d["d_month_seq"] >= 1200) & (d["d_month_seq"] <= 1211)
    keep = (di >= 0) & (ii >= 0) & (si >= 0)
    keep &= d_ok[di]
    ii, di, si = ii[keep], di[keep], si[keep]
    sales = ss["ss_sales_price"][keep] * ss["ss_quantity"][keep].astype(num.f)
    strs = []
    keys = []
    for table, row, name in ((i, ii, "i_category"), (i, ii, "i_class"),
                             (i, ii, "i_brand"), (i, ii, "i_product_name"),
                             (d, di, "d_year"), (d, di, "d_qoy"),
                             (d, di, "d_moy"), (s, si, "s_store_id")):
        col = table[name]
        if hasattr(col, "cats"):
            codes, names = ranks(col)
            strs.append(names)
        else:
            codes, names = col.astype(np.int64), None
            strs.append(None)
        keys.append(codes[row])
    order = np.lexsort(keys[::-1])
    keys = [k[order] for k in keys]
    sales = sales[order]
    n = len(sales)
    # a row starts a run of the first m keys if one of them changes
    change = np.zeros(n, bool)
    change[:1] = True
    starts_by_m = [np.flatnonzero(change)]
    for k in keys:
        change = change | np.r_[True, k[1:] != k[:-1]]
        starts_by_m.append(np.flatnonzero(change))
    group_keys = [[] for _ in keys]
    sums = []
    for m in range(len(keys), -1, -1):
        starts = starts_by_m[m]
        ends = np.r_[starts[1:], n]
        sums.extend(num.sum(sales[a:b]) for a, b in zip(starts, ends))
        for j, k in enumerate(keys):
            group_keys[j].append(k[starts] if j < m
                                 else np.full(len(starts), -1, np.int64))
    return ([np.concatenate(g) for g in group_keys], strs,
            np.array(sums, np.float64))


def ranked(group_keys, sums):
    """``rank() over (partition by i_category order by sumsales desc)``
    a group."""
    by = np.lexsort((-sums, group_keys[0]))
    cat, val = group_keys[0][by], sums[by]
    at = np.arange(len(by))
    new_part = np.r_[True, cat[1:] != cat[:-1]]
    new_val = new_part | np.r_[True, val[1:] != val[:-1]]
    part_start = np.maximum.accumulate(np.where(new_part, at, 0))
    val_start = np.maximum.accumulate(np.where(new_val, at, 0))
    rk = np.empty(len(by), np.int64)
    rk[by] = val_start - part_start + 1
    return rk


def answer(t, num):
    group_keys, strs, sums = rollup(t, num)
    rk = ranked(group_keys, sums)
    top = np.flatnonzero(rk <= 100)
    final = top[np.lexsort([rk[top], sums[top]]
                           + [g[top] for g in group_keys[::-1]])][:100]
    rows = []
    for g in final.tolist():
        key = tuple(None if k[g] < 0 else
                    (names[k[g]] if names is not None else int(k[g]))
                    for k, names in zip(group_keys, strs))
        rows.append(key + (float(sums[g]), int(rk[g])))
    return rows
