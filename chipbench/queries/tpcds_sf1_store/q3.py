"""Plain reference for q3.sql: numpy over the Parquet columns."""


def answer(t, num):
    ss, d, i = t["store_sales"], t["date_dim"], t["item"]
    di = num.lookup(d["d_date_sk"], ss["ss_sold_date_sk"])
    ii = num.lookup(i["i_item_sk"], ss["ss_item_sk"])
    keep = (di >= 0) & (ii >= 0)
    keep &= (i["i_manufact_id"] == 128)[ii] & (d["d_moy"] == 11)[di]
    di, ii = di[keep], ii[keep]
    groups = num.group(
        zip(d["d_year"][di].tolist(), i["i_brand_id"][ii].tolist(),
            i["i_brand"].decode(ii)),
        ss["ss_ext_sales_price"][keep])
    rows = [(y, b, bn, num.sum(v)) for (y, b, bn), v in groups.items()]
    rows.sort(key=lambda r: (r[0], -r[3], r[1]))
    return rows[:100]
