"""Device ms per traced query in the join operator's programs
(``jit_join_*`` rows of the traced pass's ``device_ops``).  That list is
a top ten: a lower bound when a join program falls off it."""
import span_reduce


def read(run):
    return span_reduce.device_ms_per_query(run, "jit_join_")
