"""Mean pending-pool flushes (host<->device round trips) per query,
``session.last_query_flushes``."""


def read(run):
    v = [r["flushes"] for r in run["queries"]
         if r.get("flushes") is not None]
    return sum(v) / len(v) if v else None
