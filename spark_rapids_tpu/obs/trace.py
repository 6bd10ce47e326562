"""Hierarchical span tracer — the NvtxRange role (NvtxWithMetrics /
nvtx_profiling.md in the reference, SURVEY.md §5) adapted to a
multi-tenant serving process.

One ``Span`` class, two levels:

- **coarse** spans (``span(..., coarse=True)``, names ``srt.*``) sit at
  the engine's layer boundaries (parse, plan, query, operator region,
  jit build, flush, pull, scan, semaphore wait, spill) and are ALWAYS
  recorded, on two clocks at once.  On enter a coarse span opens a
  ``jax.profiler.TraceAnnotation`` when a profiler session is live (the
  xplane's host plane, the device trace's clock; one ``is_enabled()``
  read otherwise) and reads ``time.perf_counter_ns``; on exit it writes
  one slot of the tracer's bounded ring: id, parent id, name, start,
  duration, thread, query number, and the thread's CPU time over the
  span (``time.thread_time_ns``: wall less CPU is the time the thread
  was blocked or descheduled).  ``coarse_spans()`` reads the ring back;
  ``chipbench/span_reduce.py`` turns it into host time by layer.
- **fine** spans (``span(...)``, today's behaviour) exist only while
  ``spark.rapids.tpu.obs.trace.enabled`` is set and buffer as Chrome
  trace events ("X" complete events) loadable in Perfetto /
  chrome://tracing; coarse spans add the same record then.  With fine
  tracing off ``span()`` returns a shared no-op (one flag read).

Spans nest per thread (each records its parent's id); every span of one
query shares a query number: the active
:class:`~..service.cancellation.CancelToken`'s ``query_id`` under the
service, else a sequence number taken at ``session.sql()`` /
``execute_to_arrow`` (``begin_query``) that pool workers adopt
(``adopt_query``).  ``count()`` keeps per-query counters of the same
key.

Every eager device launch the engine makes goes through one helper:
``Launcher`` (the programs ``compile_watch.jit`` builds and the
module-level jitted kernels) or ``launch()`` (the sites that launch
jax's one-op programs).  A launch reads ``perf_counter_ns`` around the
call, nothing else, and adds to its query's table under
``<name>@<Operator>``, the operator being the innermost
``srt.exec.<Node>`` open on the thread (``operator()``, kept by
``exec/base.timed``; ``-`` outside one): ``launch.`` / ``eager.`` (+n),
``launch_ns.`` (host ns of the call, less any compile inside it) and
``lanes.`` / ``eager_lanes.`` (the indices it gathers or scatters).
Under a ``jax.jit`` trace nothing is launched and nothing is counted.

The ring is the flight recorder's discipline: preallocated slots
mutated in place, overwrite-oldest, no lock (the slot index comes from
an ``itertools.count``, atomic under the GIL).  A reader racing a writer
can see one torn slot; the benchmark reads after its window has closed.
Stdlib-only at import (jax's profiler is bound at the first coarse
span): imported by exec/, memory/, shuffle/ and columnar/ layers.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

from ..service.cancellation import current_token

#: module-level fast-path flag for the FINE level.  Read directly
#: (``trace._ENABLED``) by hot call sites; everything else goes through
#: enable()/disable().  Coarse spans do not consult it to record.
_ENABLED = False

#: slots in the coarse ring: at least four times the coarse spans of
#: the largest benchmark window (PERF.md section 3 counts them a cell:
#: 100 store queries of 52 spans, 4 q3q18 queries of 550)
RING_SLOTS = 65_536
#: queries whose ``count()`` tables are kept: a benchmark window holds
#: at most about a hundred
COUNT_QUERIES = 1024

_PID = os.getpid()
_TLS = threading.local()
_IDS = itertools.count(1)
_QUERY_SEQ = itertools.count(1)
#: jax.profiler.TraceAnnotation and jax's "no trace is open on this
#: thread" check, bound at first use
_ANNOTATION = None
_EAGER = None


def _bind_jax():
    global _ANNOTATION, _EAGER
    from jax._src.core import trace_state_clean
    from jax.profiler import TraceAnnotation
    from . import compile_watch
    compile_watch.listen()
    _ANNOTATION, _EAGER = TraceAnnotation, trace_state_clean


class _NoopSpan:
    """Shared do-nothing span: the disabled-path return value of a fine
    ``span()``.  A singleton so the disabled fast path allocates
    nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()


def _query_number(tls: Dict, args: Optional[Dict] = None):
    """The query a span, emit or count on this thread belongs to; a
    service query's id also lands in ``args`` (the fine record's
    attribution)."""
    tok = current_token()
    if tok is not None and tok.query_id is not None:
        if args is not None and "query_id" not in args:
            args["query_id"] = tok.query_id
        return tok.query_id
    return tls.get("qno")


class Span:
    """One live span; finishes (records) on ``__exit__``.  ``dur_ns``
    holds the measured duration afterwards, so a site that needs the
    number (``exec/base.timed``, the flush observer) reads the clock
    once with the span."""
    __slots__ = ("name", "cat", "args", "coarse", "t0", "c0", "dur_ns",
                 "id", "parent", "qno", "_ann")

    def __init__(self, name: str, cat: str, args: Dict,
                 coarse: bool = False):
        self.name = name
        self.cat = cat
        self.args = args
        self.coarse = coarse
        self._ann = None

    def __enter__(self):
        d = _TLS.__dict__
        self.qno = _query_number(d, self.args)
        self.id = next(_IDS)
        self.parent = d.get("cur", 0)
        d["cur"] = self.id
        d["depth"] = d.get("depth", 0) + 1
        if self.coarse:
            if _ANNOTATION is None:
                _bind_jax()
            if _ANNOTATION.is_enabled():
                self._ann = _ANNOTATION(self.name, **self.args)
                self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        if self.coarse:
            # read inside the wall clock's interval: CPU <= wall
            self.c0 = time.thread_time_ns()
        return self

    def set(self, **attrs) -> "Span":
        self.args.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        cpu = time.thread_time_ns() - self.c0 if self.coarse else None
        dur = self.dur_ns = time.perf_counter_ns() - self.t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        d = _TLS.__dict__
        depth = d.get("depth", 1)
        d["depth"] = depth - 1
        d["cur"] = self.parent
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        tr = _TRACER or get_tracer()
        if self.coarse:
            tr.ring_write(self.id, self.parent, self.name, self.t0, dur,
                          self.qno, self.args, cpu)
        if _ENABLED:
            tr.record(self.name, self.cat, self.t0, dur, depth, self.args,
                      self.id, self.parent)
        return False


class SpanTracer:
    """Process-wide span store: the coarse ring (always written) and
    the fine buffer with its Chrome trace export.

    Both are bounded.  The ring overwrites its oldest slot; the fine
    buffer (``max_spans``) counts new spans as dropped past its limit
    instead of growing — a long service run with tracing left on must
    not OOM the host."""

    def __init__(self, max_spans: int = 100_000,
                 path: Optional[str] = None,
                 ring_slots: int = RING_SLOTS):
        self.max_spans = max_spans
        self.path = path
        self.epoch_ns = time.perf_counter_ns()
        self._lock = threading.Lock()
        self._events: List[Dict] = []
        self._thread_names: Dict[int, str] = {}
        self.dropped = 0
        self.ring_slots = ring_slots
        self.reset_ring()

    # -- coarse ring ---------------------------------------------------------
    def reset_ring(self):
        # slot: [seq, id, parent, name, t0_ns, dur_ns, thread, query,
        #        args, cpu_ns]
        self._ring = [[-1, 0, 0, "", 0, 0, 0, None, None, None]
                      for _ in range(self.ring_slots)]
        self._ring_seq = itertools.count()
        self._counts: Dict = {}

    def ring_write(self, sid: int, parent: int, name: str, t0_ns: int,
                   dur_ns: int, qno, args: Optional[Dict],
                   cpu_ns: Optional[int] = None):
        k = next(self._ring_seq)
        s = self._ring[k % self.ring_slots]
        s[1] = sid
        s[2] = parent
        s[3] = name
        s[4] = t0_ns
        s[5] = dur_ns
        s[6] = threading.get_ident()
        s[7] = qno
        s[8] = args
        s[9] = cpu_ns
        s[0] = k

    def coarse_spans(self, since_ns: Optional[int] = None
                     ) -> Optional[List[Dict]]:
        """The ring's spans in completion order, those that ended
        before ``since_ns`` left out; ``cpu_ns`` is the thread's CPU
        time over the span, None for a retroactive one (``emit``).
        None (and a line on stderr) when the ring has wrapped past
        ``since_ns``: spans of the asked-for interval may be
        overwritten, and a partial sum is worse than none."""
        rows = sorted((list(s) for s in self._ring if s[0] >= 0),
                      key=lambda s: s[0])
        if rows and rows[0][0] > 0:
            oldest_end = rows[0][4] + rows[0][5]
            if since_ns is None or oldest_end >= since_ns:
                print(f"obs.trace: the coarse ring ({self.ring_slots} "
                      f"slots) wrapped past the asked-for start; "
                      f"{rows[0][0]} spans overwritten", file=sys.stderr,
                      flush=True)
                return None
        return [{"id": s[1], "parent": s[2], "name": s[3], "t0_ns": s[4],
                 "dur_ns": s[5], "thread": s[6], "query": s[7],
                 "args": dict(s[8]) if s[8] else {}, "cpu_ns": s[9]}
                for s in rows
                if since_ns is None or s[4] + s[5] >= since_ns]

    def ring_written(self) -> int:
        """Spans written to the ring since its last reset (past
        ``ring_slots`` the oldest are gone)."""
        return max((s[0] for s in self._ring), default=-1) + 1

    def _table(self, qno) -> Dict:
        """``qno``'s counter table; the caller holds the lock."""
        tbl = self._counts.get(qno)
        if tbl is None:
            tbl = self._counts[qno] = {}
            while len(self._counts) > COUNT_QUERIES:
                # dicts keep insertion order: the first is the oldest
                del self._counts[next(iter(self._counts))]
        return tbl

    def count(self, qno, name: str, n: int = 1):
        with self._lock:
            tbl = self._table(qno)
            tbl[name] = tbl.get(name, 0) + n

    def count_launch(self, qno, keys, n: int, ns: int, lanes: int):
        """One launch's three counters (``_launch_keys``), one lock."""
        count_key, ns_key, lanes_key = keys
        with self._lock:
            tbl = self._table(qno)
            tbl[count_key] = tbl.get(count_key, 0) + n
            tbl[ns_key] = tbl.get(ns_key, 0) + ns
            if lanes:
                tbl[lanes_key] = tbl.get(lanes_key, 0) + lanes

    def coarse_counts(self) -> Dict:
        with self._lock:
            return {q: dict(t) for q, t in self._counts.items()}

    # -- fine buffer ---------------------------------------------------------
    def record(self, name: str, cat: str, t0_ns: int, dur_ns: int,
               depth: int, args: Dict, sid: int = 0, parent: int = 0):
        tid = threading.get_ident()
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": (t0_ns - self.epoch_ns) / 1e3,
              "dur": dur_ns / 1e3,
              "pid": _PID, "tid": tid,
              "args": dict(args, depth=depth, id=sid, parent=parent)}
        with self._lock:
            if len(self._events) >= self.max_spans:
                self.dropped += 1
                return
            self._events.append(ev)
            if tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name

    def num_spans(self) -> int:
        with self._lock:
            return len(self._events)

    def to_chrome_trace(self) -> Dict:
        """Perfetto/chrome://tracing-loadable trace object."""
        with self._lock:
            events = list(self._events)
            meta = [{"name": "thread_name", "ph": "M", "pid": _PID,
                     "tid": tid, "args": {"name": tname}}
                    for tid, tname in sorted(self._thread_names.items())]
            dropped = self.dropped
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms",
                "otherData": {"producer": "spark_rapids_tpu.obs.trace",
                              "dropped_spans": dropped}}

    def write(self, path: Optional[str] = None) -> str:
        path = path or self.path
        assert path, "no trace output path configured"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path

    def reset(self):
        """Drop the fine buffer (the ring has ``reset_ring``)."""
        with self._lock:
            self._events.clear()
            self._thread_names.clear()
            self.dropped = 0
            self.epoch_ns = time.perf_counter_ns()


_TRACER: Optional[SpanTracer] = None
_TRACER_LOCK = threading.Lock()


def get_tracer() -> SpanTracer:
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                _TRACER = SpanTracer()
    return _TRACER


def is_enabled() -> bool:
    return _ENABLED


def enable(path: Optional[str] = None,
           max_spans: Optional[int] = None) -> SpanTracer:
    """Turn fine tracing on (fresh fine buffer; the coarse ring is
    left as it is).  ``path`` is where ``flush()`` writes the Chrome
    trace JSON."""
    global _ENABLED
    tr = get_tracer()
    tr.reset()
    if path is not None:
        tr.path = path
    if max_spans is not None:
        tr.max_spans = max_spans
    _ENABLED = True
    return tr


def disable():
    global _ENABLED
    _ENABLED = False


def configure(conf) -> None:
    """Apply the ``spark.rapids.tpu.obs.trace.*`` conf group.  Only
    acts when the conf enables tracing — an unset conf must not tear
    down a tracer a test/tool enabled explicitly."""
    from ..config import (OBS_TRACE_ENABLED, OBS_TRACE_PATH,
                          OBS_TRACE_MAX_SPANS)
    if conf.get(OBS_TRACE_ENABLED):
        enable(path=conf.get(OBS_TRACE_PATH) or None,
               max_spans=conf.get(OBS_TRACE_MAX_SPANS))


def span(name: str, cat: str = "engine", coarse: bool = False, **args):
    """Open a span context.  A fine span with tracing off costs one
    flag read + the shared no-op singleton (call sites hotter than
    per-batch should guard with ``if trace._ENABLED`` to skip the kwargs
    dict too); a coarse span is always live."""
    if not (coarse or _ENABLED):
        return _NOOP
    return Span(name, cat, args, coarse)


def emit(name: str, cat: str, start_ns: int, dur_ns: int,
         coarse: bool = False, **args):
    """Record an already-elapsed region retroactively (e.g. a queue or
    semaphore wait measured by its own clock), as a child of the
    calling thread's open span.  ``start_ns`` is a
    time.perf_counter_ns() instant.  The profiler has no retroactive
    annotation: a coarse emit lands in the ring only, with no CPU time
    (``cpu_ns`` None)."""
    if not (coarse or _ENABLED):
        return
    d = _TLS.__dict__
    qno = _query_number(d, args)
    tr = _TRACER or get_tracer()
    sid, parent = next(_IDS), d.get("cur", 0)
    if coarse:
        tr.ring_write(sid, parent, name, start_ns, dur_ns, qno, args)
    if _ENABLED:
        tr.record(name, cat, start_ns, dur_ns, d.get("depth", 0) + 1, args,
                  sid, parent)


# ---------------------------------------------------------------------------
# query numbers and per-query counters
# ---------------------------------------------------------------------------

def begin_query(parsed: bool = False) -> int:
    """Give the calling thread's next query its number.
    ``session.sql()`` calls this with ``parsed=True``; the
    ``execute_to_arrow`` that follows keeps that number, so the front
    end's spans and the execution's share one.  An execution with no
    ``sql()`` before it (the DataFrame API, a second ``collect()``)
    takes a new one.  Under the service the token's ``query_id`` wins
    over this number in every span."""
    d = _TLS.__dict__
    if parsed or not d.pop("parsed", False):
        d["qno"] = next(_QUERY_SEQ)
    if parsed:
        d["parsed"] = True
    return d["qno"]


def current_query():
    """The calling thread's query number (token ``query_id`` first)."""
    return _query_number(_TLS.__dict__)


def adopt_query(qno) -> None:
    """A pool worker serving another thread's query takes its number."""
    _TLS.qno = qno


def count(name: str, n: int = 1) -> None:
    """One integer add, under the tracer's lock, into the calling
    query's counter table (kept for the last ``COUNT_QUERIES`` queries;
    ``coarse_counts()`` reads them)."""
    (_TRACER or get_tracer()).count(_query_number(_TLS.__dict__), name, n)


# ---------------------------------------------------------------------------
# launches: every eager device launch, timed and counted by operator
# ---------------------------------------------------------------------------

#: ``(prefix, name, operator)`` -> the launch's three counter names
_LAUNCH_KEYS: Dict = {}


def operator(name: str) -> str:
    """Make ``name`` the calling thread's operator (the innermost open
    ``srt.exec.<Node>``) and return the one it replaces: ``exec/base.timed``
    sets it on enter and puts the old one back on exit."""
    d = _TLS.__dict__
    prev = d.get("op", "-")
    d["op"] = name
    return prev


def _launch_keys(prefix: str, name: str, op: str):
    keys = _LAUNCH_KEYS.get((prefix, name, op))
    if keys is None:
        tail = f"{name}@{op}"
        lanes = "eager_lanes." if prefix == "eager." else "lanes."
        keys = _LAUNCH_KEYS[(prefix, name, op)] = (
            prefix + tail, "launch_ns." + tail, lanes + tail)
    return keys


def _launched(d: Dict, prefix: str, name: str, n: int, ns: int,
              lanes: int) -> None:
    (_TRACER or get_tracer()).count_launch(
        _query_number(d), _launch_keys(prefix, name, d.get("op", "-")),
        n, ns, lanes)


def eager() -> bool:
    """True when no ``jax.jit`` trace is open on this thread: a call
    launches.  Under a trace the ops join the program being built."""
    if _EAGER is None:
        _bind_jax()
    return _EAGER()


class _Launch:
    """One eager site's launch region (``launch()``)."""
    __slots__ = ("name", "n", "lanes", "t0", "c0")

    def __init__(self, name: str, n: int, lanes: int):
        self.name = name
        self.n = n
        self.lanes = lanes

    def __enter__(self):
        self.c0 = _TLS.__dict__.get("compile_ns", 0)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        ns = time.perf_counter_ns() - self.t0
        d = _TLS.__dict__
        _launched(d, "eager.", self.name, self.n,
                  ns - (d.get("compile_ns", 0) - self.c0), self.lanes)
        return False


def launch(site: str, n: int = 1, lanes: int = 0):
    """The region of a site that launches ``n`` of jax's one-op programs
    when it runs eagerly (``jit__take``, ``jit_scatter-add``: jax's
    names, not the engine's to change), gathering or scattering
    ``lanes`` indices in all.  Adds ``eager.<site>@<Op>`` (+n),
    ``launch_ns.<site>@<Op>`` and ``eager_lanes.<site>@<Op>``; under a
    ``jax.jit`` trace it is the shared no-op."""
    if not eager():
        return _NOOP
    return _Launch(site, n, lanes)


class Launcher:
    """A jitted program whose every eager call is a launch: adds
    ``launch.<name>@<Op>`` (+1), ``launch_ns.<name>@<Op>`` (the call's
    host ns: jax's dispatch and enqueue, which waits when the runtime's
    allocator does, less any compile inside it, which is ``srt.compile``)
    and, given ``lanes(*args, **kwargs)``, ``lanes.<name>@<Op>``.
    Called under a ``jax.jit`` trace it is the program and nothing more.
    Everything else (``lower``, ``__wrapped__``, ...) is the program's."""
    __slots__ = ("__wrapped__", "name", "lanes")

    def __init__(self, program, name: str, lanes=None):
        self.__wrapped__ = program
        self.name = name
        self.lanes = lanes

    def __call__(self, *args, **kwargs):
        if not eager():
            return self.__wrapped__(*args, **kwargs)
        d = _TLS.__dict__
        c0 = d.get("compile_ns", 0)
        t0 = time.perf_counter_ns()
        out = self.__wrapped__(*args, **kwargs)
        ns = time.perf_counter_ns() - t0 - (d.get("compile_ns", 0) - c0)
        _launched(d, "launch.", self.name, 1, ns,
                  self.lanes(*args, **kwargs) if self.lanes else 0)
        return out

    def __getattr__(self, attr):
        return getattr(self.__wrapped__, attr)


def launched(lanes=None):
    """Decorator form of ``Launcher`` for a module-level jitted kernel,
    named after its function."""
    return lambda program: Launcher(program, program.__name__, lanes)


def compiled(program: str, how: str, start_ns: int, dur_ns: int) -> None:
    """A backend compile (``how`` = ``compile``) or persistent-cache
    load (``cache_load``) of ``program`` that just ended on this thread:
    the retroactive coarse span ``srt.compile`` under the span open
    here, and its ns kept out of the enclosing launch's ``launch_ns``
    (``obs/compile_watch.py``'s jax.monitoring listener calls this)."""
    d = _TLS.__dict__
    d["compile_ns"] = d.get("compile_ns", 0) + dur_ns
    emit("srt.compile", "compile", start_ns, dur_ns, True, program=program,
         how=how)


def coarse_spans(since_ns: Optional[int] = None) -> Optional[List[Dict]]:
    """See :meth:`SpanTracer.coarse_spans`."""
    return get_tracer().coarse_spans(since_ns)


def coarse_counts() -> Dict:
    """``{query number: {name: count}}`` of the last queries."""
    return get_tracer().coarse_counts()


def flush(path: Optional[str] = None) -> Optional[str]:
    """Write the fine buffer to ``path`` (or the enable()-time path):
    called when a session or the service closes, or on request — never
    per query.  Returns the written path; None when tracing never
    started or no output path is configured (in-memory tracing:
    tests/tools read the buffer through ``get_tracer()`` instead)."""
    if _TRACER is None or not (path or _TRACER.path):
        return None
    return _TRACER.write(path)


def reset():
    """Test hook: drop the fine buffer, the ring and the counters."""
    if _TRACER is not None:
        _TRACER.reset()
        _TRACER.reset_ring()
    _TLS.__dict__.clear()
