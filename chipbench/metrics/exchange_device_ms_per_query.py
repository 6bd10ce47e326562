"""Device ms per traced query in the shuffle partitioner's programs
(``jit_partition_*`` rows of the traced pass's ``device_ops``).  That
list is a top ten: a lower bound when a partition program falls off it."""
import span_reduce


def read(run):
    return span_reduce.device_ms_per_query(run, "jit_partition_")
