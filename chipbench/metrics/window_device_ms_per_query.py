"""Device ms per traced query in the window operator's programs
(``jit_window_*`` rows of the traced pass's ``device_ops``: the sort of
a spec, its partition runs and every function's scans).  That list is a
top ten: a lower bound when a window program falls off it.  Nothing on
an engine whose window runs eagerly (no such program exists)."""
import span_reduce


def read(run):
    if not span_reduce.counts_per_query(run, "window.batches"):
        return None
    return span_reduce.device_ms_per_query(run, "jit_window_")
