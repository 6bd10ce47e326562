"""Candidate pairs per query of the window that hash joins with a
residual (non-equi) condition decided: the ``join.residual.pairs``
counter, + the pairs of every stream batch (the equi-key matches the
probe sized), each one an expanded row whose condition columns the
residual program gathers and evaluates.  Nothing when none was counted
(an engine without the counter, or a window without such a join)."""
import span_reduce


def read(run):
    return span_reduce.counts_per_query(run, "join.residual.pairs") or None
