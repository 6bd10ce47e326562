"""Plain reference for q7.sql: numpy over the Parquet columns."""


def answer(t, num):
    ss, cd, d = t["store_sales"], t["customer_demographics"], t["date_dim"]
    i, p = t["item"], t["promotion"]
    di = num.lookup(d["d_date_sk"], ss["ss_sold_date_sk"])
    ii = num.lookup(i["i_item_sk"], ss["ss_item_sk"])
    ci = num.lookup(cd["cd_demo_sk"], ss["ss_cdemo_sk"])
    pi = num.lookup(p["p_promo_sk"], ss["ss_promo_sk"])
    keep = (di >= 0) & (ii >= 0) & (ci >= 0) & (pi >= 0)
    cd_ok = (cd["cd_gender"].eq("M") & cd["cd_marital_status"].eq("S")
             & cd["cd_education_status"].eq("College"))
    p_ok = p["p_channel_email"].eq("N") | p["p_channel_event"].eq("N")
    keep &= cd_ok[ci] & p_ok[pi] & (d["d_year"] == 2000)[di]
    ids = i["i_item_id"].decode(ii[keep])
    cols = [ss[c][keep] for c in ("ss_quantity", "ss_list_price",
                                  "ss_coupon_amt", "ss_sales_price")]
    groups = [num.group(ids, c) for c in cols]
    rows = [(k,) + tuple(num.avg(g[k]) for g in groups)
            for k in sorted(groups[0])]
    return rows[:100]
