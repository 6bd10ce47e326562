"""``jax.jit`` objects the engine constructs per query of the window:
the ``jit_build.<program>`` counters of the window's query numbers,
which ``compile_watch.jit`` adds to at every construction, cached site or
not (``obs.trace.coarse_counts()``).  Each one is a re-trace and a compile
or persistent-cache load on a query's path; a warmed engine reads 0, and
0 is a reading."""
import span_reduce


def read(run):
    return span_reduce.counts_per_query(run, "jit_build.")
