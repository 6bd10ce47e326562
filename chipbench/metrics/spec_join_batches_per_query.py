"""Stream batches per query of the window that a join ran through the
speculative unique-match probe, whose output keeps the stream's
capacity: the ``join.batches.spec`` counter of the window's query
numbers (``obs.trace.coarse_counts()``).  Nothing when no batch was
counted on either path (an engine without the ``join.*`` counters, or a
window without joins); with ``join.batches.sized`` alone, 0 is a
reading."""
import span_reduce


def read(run):
    if not span_reduce.counts_per_query(run, "join.batches."):
        return None
    return span_reduce.counts_per_query(run, "join.batches.spec")
