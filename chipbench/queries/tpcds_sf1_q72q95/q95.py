"""Plain reference for q95.sql: numpy over the Parquet columns.
``ws_wh`` holds a row for each pair of lines of one order from two
warehouses, so an order is in it when its lines' smallest and largest
``ws_warehouse_sk`` differ; the second IN list is the returned orders
among those.  The lines that ship in the window to an address in IL
from a 'pri' site and whose order is in both lists give the distinct
order count and the two sums (exact in the reference, float32 in the
control)."""
import numpy as np

STATE = "IL"
COMPANY = "pri"
FIRST = np.datetime64("1999-02-01")
DAYS = 60


def answer(t, num):
    ws, dd = t["web_sales"], t["date_dim"]
    okey, wh = ws["ws_order_number"], ws["ws_warehouse_sk"]
    order = np.argsort(okey, kind="stable")
    k = okey[order]
    starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    lo = np.minimum.reduceat(wh[order], starts)
    hi = np.maximum.reduceat(wh[order], starts)
    multi = k[starts][lo != hi]
    returned = np.intersect1d(t["web_returns"]["wr_order_number"], multi)
    day = np.asarray(dd["d_date"]).astype("datetime64[D]")
    drow = num.lookup(dd["d_date_sk"], ws["ws_ship_date_sk"])
    ship = day[np.maximum(drow, 0)]
    ok = (drow >= 0) & (ship >= FIRST) & \
        (ship <= FIRST + np.timedelta64(DAYS, "D"))
    ca = t["customer_address"]
    arow = num.lookup(ca["ca_address_sk"], ws["ws_ship_addr_sk"])
    ok &= (arow >= 0) & ca["ca_state"].eq(STATE)[np.maximum(arow, 0)]
    site = t["web_site"]
    srow = num.lookup(site["web_site_sk"], ws["ws_web_site_sk"])
    ok &= (srow >= 0) & site["web_company_name"].eq(COMPANY)[
        np.maximum(srow, 0)]
    ok &= np.isin(okey, multi) & np.isin(okey, returned)
    line = np.flatnonzero(ok)
    return [(int(len(np.unique(okey[line]))),
             num.sum(ws["ws_ext_ship_cost"][line]),
             num.sum(ws["ws_net_profit"][line]))]
