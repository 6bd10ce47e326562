"""Plain reference for q96.sql: numpy over the Parquet columns."""


def answer(t, num):
    ss, hd = t["store_sales"], t["household_demographics"]
    td, s = t["time_dim"], t["store"]
    ti = num.lookup(td["t_time_sk"], ss["ss_sold_time_sk"])
    hi = num.lookup(hd["hd_demo_sk"], ss["ss_hdemo_sk"])
    si = num.lookup(s["s_store_sk"], ss["ss_store_sk"])
    keep = (ti >= 0) & (hi >= 0) & (si >= 0)
    keep &= ((td["t_hour"] == 20) & (td["t_minute"] >= 30))[ti]
    keep &= (hd["hd_dep_count"] == 7)[hi] & s["s_store_name"].eq("ese")[si]
    return [(int(keep.sum()),)]
