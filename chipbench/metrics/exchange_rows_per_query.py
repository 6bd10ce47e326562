"""Rows per query of the window that crossed an in-process shuffle
exchange: the ``exchange.rows`` counter of the window's query numbers
(``obs.trace.coarse_counts()``), added to at each map batch's
``finalize_split``.  Nothing when no map batch was counted (an engine
without the ``exchange.*`` counters, or a window without a shuffle)."""
import span_reduce


def read(run):
    if not span_reduce.counts_per_query(run, "exchange.batches"):
        return None
    return span_reduce.counts_per_query(run, "exchange.rows")
