"""64-bit key words a fused aggregate core sorted, per batch it took:
``agg.key_words`` / ``agg.batches.fused`` over the window's query
numbers (``obs.trace.coarse_counts()``).  Every word is one pass of the
LSD sort chain over the batch, so this is how wide a key the cores
sorted.  Nothing without both counters (an engine before
``agg.key_words``, or a window without a grouped aggregate)."""
import span_reduce


def read(run):
    words = span_reduce.counts_per_query(run, "agg.key_words")
    batches = span_reduce.counts_per_query(run, "agg.batches.fused")
    if not words or not batches:
        return None
    return words / batches
