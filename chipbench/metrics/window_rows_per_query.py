"""Row slots per query of the window that the window operator's
programs ran over: the ``window.rows`` counter (the capacity of each
concatenated partition batch ``TpuWindow`` evaluates) of the window's
query numbers (``obs.trace.coarse_counts()``).  Nothing without a
``window.batches`` count: an engine without the counters, or a window
of queries without a window function."""
import span_reduce


def read(run):
    if not span_reduce.counts_per_query(run, "window.batches"):
        return None
    return span_reduce.counts_per_query(run, "window.rows")
