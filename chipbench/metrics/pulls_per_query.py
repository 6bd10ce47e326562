"""Declared device-to-host transfers per query: the ``pull.<site>``
counters of the window's query numbers (``obs.trace.coarse_counts()``),
+1 at every ``analysis/residency.declared_transfer`` region, the span
``srt.pull``.  Each one drains the device's queue.  Nothing from an
engine that counts no pull."""
import span_reduce


def read(run):
    w = span_reduce.window(run)
    if w is None or not any(k.startswith("pull.")
                            for tbl in w["counts"].values() for k in tbl):
        return None
    return span_reduce.counts_per_query(run, "pull.")
