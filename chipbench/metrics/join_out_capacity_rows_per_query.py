"""Slots per query of the window in the batches the hash joins put out
(the ``join.out_capacity_rows`` counter, + each output batch's capacity
on either path): what every later gather, string gather and segment sum
runs over, whatever matched.  Nothing when none was counted (an engine
without the counter, or a window without joins)."""
import span_reduce


def read(run):
    return span_reduce.counts_per_query(run, "join.out_capacity_rows") or None
