"""Batches per query of the window whose bucket-table aggregate did not
fit and was computed again on the sort path: the ``agg.table.misfit``
counter of the window's query numbers (``obs.trace.coarse_counts()``).
The engine adds 0 to it for every batch the table core takes, so a
window whose tables all fit reads 0; nothing where no table of the
window holds the counter (an engine without it, or no table batch)."""
import span_reduce


def read(run):
    w = span_reduce.window(run)
    if w is None or not any("agg.table.misfit" in tbl
                            for tbl in w["counts"].values()):
        return None
    return span_reduce.counts_per_query(run, "agg.table.misfit")
