"""Input batches per query of the window that a hash aggregate ran on
its eager grouped fallback, one jax program a gather and a segment sum
(string or nested aggregate inputs, ``collect_*``, ``exactDouble``):
the ``agg.batches.eager`` counter of the window's query numbers
(``obs.trace.coarse_counts()``).  Nothing when no batch was counted on
any path (an engine without the ``agg.batches.*`` counters, or a window
without aggregates); with ``agg.batches.fused`` or ``agg.batches.table``
alone, 0 is a reading."""
import span_reduce


def read(run):
    if not span_reduce.counts_per_query(run, "agg.batches."):
        return None
    return span_reduce.counts_per_query(run, "agg.batches.eager")
