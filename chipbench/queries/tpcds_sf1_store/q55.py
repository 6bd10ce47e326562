"""Plain reference for q55.sql: numpy over the Parquet columns."""


def answer(t, num):
    ss, d, i = t["store_sales"], t["date_dim"], t["item"]
    di = num.lookup(d["d_date_sk"], ss["ss_sold_date_sk"])
    ii = num.lookup(i["i_item_sk"], ss["ss_item_sk"])
    keep = (di >= 0) & (ii >= 0)
    keep &= (i["i_manager_id"] == 28)[ii]
    keep &= ((d["d_moy"] == 11) & (d["d_year"] == 1999))[di]
    ii = ii[keep]
    groups = num.group(
        zip(i["i_brand_id"][ii].tolist(), i["i_brand"].decode(ii)),
        ss["ss_ext_sales_price"][keep])
    rows = [(b, bn, num.sum(v)) for (b, bn), v in groups.items()]
    rows.sort(key=lambda r: (-r[2], r[0]))
    return rows[:100]
