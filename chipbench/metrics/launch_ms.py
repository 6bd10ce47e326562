"""Mean host ms per query the engine spends in its eager device
launches: the ``launch_ns.<program>@<Operator>`` counters of the
window's query numbers (``obs.trace.coarse_counts()``), the host time of
every call of an engine program and every one-op eager site, jax's
dispatch and enqueue (which waits when the runtime's allocator does),
compiles left out.  The enqueue part of ``host_dispatch_ms``.  Nothing
from an engine that times no launch."""
import span_reduce


def read(run):
    w = span_reduce.window(run)
    if w is None or not any(k.startswith("launch_ns.")
                            for tbl in w["counts"].values() for k in tbl):
        return None
    return span_reduce.counts_per_query(run, "launch_ns.") / 1e6
