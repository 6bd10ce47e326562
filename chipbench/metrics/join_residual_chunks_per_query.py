"""Launches per query of the window of the program that decides a
residual (non-equi) join's candidate pairs: the ``join.residual.chunks``
counter, +1 a launch.  A stream batch whose pairs fit
``join.gather.chunkRows`` takes one launch; one past it takes a launch
every that many pairs.  Nothing when none was counted (an engine
without the counter, or a window without such a join)."""
import span_reduce


def read(run):
    return span_reduce.counts_per_query(run, "join.residual.chunks") or None
