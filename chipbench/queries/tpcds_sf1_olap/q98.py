"""Plain reference for q98.sql: numpy over the Parquet columns.
``i_item_id`` is unique an item, so the five-key group is the item; the
class totals are a second grouping over the items' sums (what ``sum(sum(x))
over (partition by i_class)`` says), and the ratio is computed in the
precision under test."""
import numpy as np


def answer(t, num):
    ss, i, d = t["store_sales"], t["item"], t["date_dim"]
    di = num.lookup(d["d_date_sk"], ss["ss_sold_date_sk"])
    ii = num.lookup(i["i_item_sk"], ss["ss_item_sk"])
    cat_ok = (i["i_category"].eq("Sports") | i["i_category"].eq("Books")
              | i["i_category"].eq("Home"))
    d_ok = (d["d_year"] == 1999) & (d["d_moy"] >= 2) & (d["d_moy"] <= 3)
    keep = (di >= 0) & (ii >= 0)
    keep &= cat_ok[ii] & d_ok[di]
    item, price = ii[keep], ss["ss_ext_sales_price"][keep]
    order = np.argsort(item, kind="stable")
    item, price = item[order], price[order]
    starts = np.flatnonzero(np.r_[True, item[1:] != item[:-1]])
    ends = np.r_[starts[1:], len(item)]
    items = item[starts]
    revenue = [num.sum(price[a:b]) for a, b in zip(starts, ends)]
    klass = i["i_class"].decode(items)
    by_class = num.group(klass, revenue)
    total = {k: num.sum(np.array(v, num.f)) for k, v in by_class.items()}
    rows = []
    for row, k, rev in zip(items.tolist(), klass, revenue):
        ratio = num.f(rev) * num.f(100.0) / num.f(total[k])
        rows.append((i["i_item_id"].cats[i["i_item_id"].codes[row]],
                     i["i_item_desc"].cats[i["i_item_desc"].codes[row]],
                     i["i_category"].cats[i["i_category"].codes[row]], k,
                     float(i["i_current_price"][row]), rev, float(ratio)))
    rows.sort(key=lambda r: (r[2], r[3], r[0], r[1], r[6]))
    return rows[:100]
