"""Mean host CPU ms per query of the operators: the thread CPU time of
the ``srt.exec.<Node>`` spans (``cpu_ns``) less that of their
same-thread children, all threads.  ``host_dispatch_ms`` is the wall
self time of the same spans: the difference is the time the operators'
threads were blocked (in an enqueue the runtime's allocator holds, a
sync, a lock) or descheduled.  A retroactive child (``srt.compile``)
carries no CPU time and takes none off.  Nothing from an engine whose
spans carry no ``cpu_ns``."""
import span_reduce


def read(run):
    w = span_reduce.window(run)
    if w is None or not any("cpu_ns" in s for s in w["spans"]):
        return None
    kids = {}
    for s in w["spans"]:
        if s["cpu_ns"] is not None:
            at = (s["parent"], s["thread"])
            kids[at] = kids.get(at, 0) + s["cpu_ns"]
    total = sum(s["cpu_ns"] - kids.get((s["id"], s["thread"]), 0)
                for s in w["spans"]
                if s["name"].startswith("srt.exec.")
                and s["cpu_ns"] is not None)
    return total / 1e6 / w["n_queries"]
