"""Device ms per traced query in the residual join's programs
(``jit_join_residual*`` rows of the traced pass's ``device_ops``: the
expansion of a chunk of candidate pairs, the gather of the condition's
columns, the condition and the survivors' running sums).  That list is
a top ten: a lower bound when the program falls off it.  Nothing
without a ``join.residual.chunks`` count: an engine without the
counter, or a window without such a join."""
import span_reduce


def read(run):
    if not span_reduce.counts_per_query(run, "join.residual.chunks"):
        return None
    return span_reduce.device_ms_per_query(run, "jit_join_residual")
