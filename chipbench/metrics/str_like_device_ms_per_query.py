"""Device ms per traced query in the LIKE program (``jit_str_like*``
rows of the traced pass's ``device_ops``: ``str_like_match``, one pass
over a string column's bytes for every piece of a pattern of literal
bytes and ``%``).  That list is a top ten: a lower bound when the
program falls off it.  Nothing from an engine that counts no
``str.like.bytes`` (its LIKE ran on the CPU, or as jax's one-op
programs)."""
import span_reduce


def read(run):
    if not span_reduce.counts_per_query(run, "str.like.bytes"):
        return None
    return span_reduce.device_ms_per_query(run, "jit_str_like")
