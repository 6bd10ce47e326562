"""The least time the chip could take over the window's columns, over
the device time its programs took.

Least time: ``bytes_per_query`` at the ``peaks.json`` HBM bandwidth.
The bytes are the engine's ``window.bytes`` counter, which adds, from
capacities and dtypes alone, for every distinct (partition, order) spec
of a window batch the device bytes of its partition and order columns
(data and validity; a string column by its byte buffer and offsets),
and for every function the bytes of the value column it reads and of
the result column it writes: each byte the operator must touch once,
whatever it does with it (the sort's passes, the permutation and the
scans are the engine's choice and are not counted).  HBM-bound by
construction: a sort and a few scans a byte.  Nothing without the
counter or without a ``jit_window_*`` program among the traced pass's
ten longest."""
import span_reduce


def bytes_per_query(run):
    return span_reduce.counts_per_query(run, "window.bytes")


def read(run):
    need = bytes_per_query(run)
    ms = span_reduce.device_ms_per_query(run, "jit_window_")
    if not need or not ms or not run["peaks"]:
        return None
    least_ms = need / (run["peaks"]["hbm_gbps"] * 1e9) * 1e3
    return 100.0 * least_ms / ms
