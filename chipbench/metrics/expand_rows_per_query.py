"""Row slots per query of the window that ``TpuExpand`` (ROLLUP / CUBE /
GROUPING SETS) handed the aggregate above it: the ``expand.rows``
counter (the capacity of every projected batch it yields) of the
window's query numbers (``obs.trace.coarse_counts()``).  Nothing
without an ``expand.batches`` count: an engine without the counters, or
a window of queries without a grouping set."""
import span_reduce


def read(run):
    if not span_reduce.counts_per_query(run, "expand.batches"):
        return None
    return span_reduce.counts_per_query(run, "expand.rows")
