"""Device ms per traced query in the program that lays gathered string
bytes out (``jit_str_materialize*`` rows of the traced pass's
``device_ops``).  That list is a top ten: a lower bound when the program
falls off it."""
import span_reduce


def read(run):
    return span_reduce.device_ms_per_query(run, "jit_str_materialize")
