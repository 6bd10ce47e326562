"""The least time the chip could take over the device time it took.

Least time: the Arrow bytes of the columns each traced query references
(``queries/<config>/<query>.json``, every row the tables hold, read once
at the ``peaks.json`` HBM bandwidth).  The count ignores pushdown,
pruning and caching on purpose: it is the same work whatever the engine
does with it.  HBM-bound by construction: the queries do a few
operations per byte."""


def read(run):
    t = run["trace"]
    if not t or not t["busy_s"] or not run["peaks"]:
        return None
    need = sum(run["bytes_by_query"][q] for q in t["queries"])
    least_s = need / (run["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / t["busy_s"]
