"""Plain reference for q42.sql: numpy over the Parquet columns."""


def answer(t, num):
    ss, d, i = t["store_sales"], t["date_dim"], t["item"]
    di = num.lookup(d["d_date_sk"], ss["ss_sold_date_sk"])
    ii = num.lookup(i["i_item_sk"], ss["ss_item_sk"])
    keep = (di >= 0) & (ii >= 0)
    keep &= (i["i_manager_id"] == 1)[ii]
    keep &= ((d["d_moy"] == 11) & (d["d_year"] == 2000))[di]
    di, ii = di[keep], ii[keep]
    groups = num.group(
        zip(d["d_year"][di].tolist(), i["i_category_id"][ii].tolist(),
            i["i_category"].decode(ii)),
        ss["ss_ext_sales_price"][keep])
    rows = [(y, c, cn, num.sum(v)) for (y, c, cn), v in groups.items()]
    rows.sort(key=lambda r: (-r[3], r[0], r[1], r[2]))
    return rows[:100]
