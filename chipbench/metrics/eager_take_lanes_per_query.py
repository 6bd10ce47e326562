"""Indices per query the engine's one-op eager sites gather or scatter
(``jit__take``, ``jit_scatter-add``: the chip prices them per index):
the ``eager_lanes.<site>@<Operator>`` counters of the window's query
numbers (``obs.trace.coarse_counts()``).  0 is a reading; nothing from
an engine that times no launch."""
import span_reduce


def read(run):
    w = span_reduce.window(run)
    if w is None or not any(k.startswith("launch_ns.")
                            for tbl in w["counts"].values() for k in tbl):
        return None
    return span_reduce.counts_per_query(run, "eager_lanes.")
