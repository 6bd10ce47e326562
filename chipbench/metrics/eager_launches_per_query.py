"""One-op jax programs (``jit__take``, ``jit_scatter-add``, ...) the
engine launches eagerly per query: the ``eager.<site>`` counters of the
window's query numbers (``obs.trace.coarse_counts()``)."""
import span_reduce


def read(run):
    return span_reduce.counts_per_query(run, "eager.")
