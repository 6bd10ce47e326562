"""Build-side slots per query of the window, summed over the partitions
the hash joins ran (the ``join.build_rows`` counter, + the build batch's
capacity once a partition): what every probe of that partition sorts or
searches against.  Nothing when none was counted (an engine without the
counter, or a window without joins)."""
import span_reduce


def read(run):
    return span_reduce.counts_per_query(run, "join.build_rows") or None
