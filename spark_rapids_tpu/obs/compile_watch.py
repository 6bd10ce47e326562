"""Compile telemetry — where multi-second inline XLA compiles land.

Seven engine JIT caches (fused project, staged compute, hash
aggregate, the three mesh SPMD programs, the hash-partition
program) already report hit/miss counts to Prometheus.  What they could
not answer is the question the AOT shape-bucketed compile cache
(ROADMAP item 4) will be built and judged against: *how long does each
miss actually cost, and did a query block on it?*

``wrap_miss(cache, fn, signature)`` is the single instrumentation
point: a cache miss wraps the freshly created callable so its FIRST
call — where ``jax.jit`` traces, lowers and compiles — is wall-timed
and recorded; afterwards the wrapper degenerates to one flag read per
call.  Each recorded compile carries:

- the cache name and a compact shape/dtype signature (from the cache
  key the miss was stored under);
- the wall duration (the same number lands in the
  ``tpu_compile_seconds{cache=...}`` histogram, the bounded top-N
  record store rendered by ``Service.stats()``, and — via the
  process-wide ns counter the session deltas around each execution —
  the victim query's event-log record, so all three surfaces agree
  exactly);
- an origin: ``inline`` means a query context (an active
  ``CancelToken``) was blocked on the compile, in which case the
  duration is also observed onto the token as ``inline_compile_ms``
  for the service's per-query metrics; ``warm`` means no query was
  waiting; ``warmup`` means the AOT warmup daemon compiled it in the
  background (``compile/aot.py warmup_scope`` — the scope outranks
  any ambient CancelToken, so a background compile can NEVER land on
  a tenant query's inline_compile_ms, and the utilization timeline
  classifies its window as process-idle, not ``inline_compile``);
  ``persistent`` means the first call was satisfied by the persistent
  executable cache (manifest hit from an earlier process run) — a
  deserialization, not a compile, so it is counted in
  ``tpu_compile_persistent_hits_total`` and kept OUT of the
  ``tpu_compile_seconds`` histogram and the inline/total ns counters;
- the capacity bucket the compile served (the thread's last
  ``aot.note_demand`` for that cache), rendered per-bucket by
  ``tools/report.py``.

``jit(fn, name)`` is the engine's one way to build a ``jax.jit`` object
at run time: it names the program after its operator and role (the
module XLA compiles is ``jit_<name>``, which is what a device trace
shows), and counts the construction in ``jit_builds()`` with its site,
cached site or not.  A warmed engine builds none per query; every one
it does build is a re-trace and a persistent-cache load (or a compile)
on some query's path.  The first call of a wrapped closure is the coarse
span ``srt.jit_build`` (obs/trace.py), and its record carries ``t0_ns``.
The program ``jit`` returns is a ``trace.Launcher``: every eager call is
a launch, timed and counted by operator.

Every compile inside a query is named: one ``jax.monitoring`` duration
listener takes jax's ``/jax/core/compile/backend_compile_duration``
event, which wraps ``compile_or_get_cached`` and carries the program's
name, and writes the retroactive coarse span ``srt.compile`` (args
``program``, ``how`` = ``compile`` or ``cache_load``: jax's nameless
``/jax/compilation_cache/cache_hits`` event fired on the same thread
inside it) under the span open there, and the counter
``compile.<program>`` (+1) in the query's table.

Hot-path discipline (this file is on the SYNC001/OBS002 lint scope):
the warm path is one list-index check; recording happens once per
compile (seconds-scale events) and allocates one small dict there.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

from . import flight
from . import trace as _trace
from .registry import COMPILE_SECONDS

_SIG_MAX = 160          #: stored signature strings are truncated here
_RECORD_CAP = 256       #: bounded record store (slowest kept on evict)

_ENABLED = True
_TOP_N = 20

_LOCK = threading.Lock()
_SEQ = 0                #: compile sequence — advances once per recorded
                        #: compile (warmup included); read lock-free by
                        #: obs/profile.py's dispatch_cold routing
_TOTAL_NS = 0           #: process-wide compile ns (session window deltas;
                        #: warmup + persistent loads deliberately excluded)
_INLINE_NS = 0          #: subset recorded under an active query context
_WARMUP_NS = 0          #: background warmup compiles (the pseudo-victim)
_PERSISTENT_NS = 0      #: persistent-cache deserializations (not compiles)
_PERSISTENT_HITS = 0
_RECORDS: List[Dict] = []
_JIT_BUILDS = 0         #: jax.jit objects built through ``jit()``
_JIT_BUILD_SITES: Dict[str, int] = {}   #: the same, by program name

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_HIT = threading.local()    #: a cache hit seen inside the open compile
_LISTENING = False


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _HIT.seen = True


def _on_duration(event: str, secs: float, fun_name: str = "?",
                 **_kw) -> None:
    if event != _BACKEND_COMPILE:
        return
    how = "cache_load" if _HIT.__dict__.pop("seen", False) else "compile"
    dur_ns = int(secs * 1e9)
    # jax names the module "jit(<fn>)"; XLA and the device trace "jit_<fn>"
    program = fun_name.replace("(", "_").rstrip(")")
    _trace.compiled(program, how, time.perf_counter_ns() - dur_ns, dur_ns)
    _trace.count("compile." + program)


def listen() -> None:
    """Register the compile listener, once a process: ``trace`` does
    when it first binds jax, before any span or launch of its own."""
    global _LISTENING
    if _LISTENING:
        return
    import jax.monitoring as mon
    with _LOCK:
        if not _LISTENING:
            mon.register_event_duration_secs_listener(_on_duration)
            mon.register_event_listener(_on_event)
            _LISTENING = True


def jit(fn: Callable, name: str, **jit_kwargs) -> Callable:
    """``jax.jit(fn, **jit_kwargs)`` with the program named ``name``
    (``<operator>_<role>``, unique across the engine, the same from
    query to query) and the construction counted.  Naming happens on
    the python function before ``jax.jit`` sees it: no run-time cost.
    The program comes back as a ``trace.Launcher``."""
    global _JIT_BUILDS
    import jax
    try:
        fn.__name__ = fn.__qualname__ = name
    except AttributeError:
        # a bound method has no settable name: jit a named forwarder
        # (traced once per shape, never called on the warm path)
        inner = fn

        def fn(*args, **kwargs):
            return inner(*args, **kwargs)
        fn.__name__ = fn.__qualname__ = name
    with _LOCK:
        _JIT_BUILDS += 1
        _JIT_BUILD_SITES[name] = _JIT_BUILD_SITES.get(name, 0) + 1
    # and in the building query's counter table (trace.coarse_counts)
    _trace.count("jit_build." + name)
    return _trace.Launcher(jax.jit(fn, **jit_kwargs), name)


def _store(rec: Dict) -> None:
    _RECORDS.append(rec)
    if len(_RECORDS) > _RECORD_CAP:
        # evict the cheapest compile: the store's job is the
        # slowest-compiles table, so the tail worth keeping is
        # the expensive one
        _RECORDS.sort(key=lambda r: -r["dur_ms"])
        del _RECORDS[_RECORD_CAP:]


def note_compile(cache: str, dur_ns: int, signature: Optional[str] = None,
                 t0_ns: Optional[int] = None) -> None:
    """Record one finished compile: histogram, bounded record store,
    process counters, the victim token's ``inline_compile_ms``, and a
    flight breadcrumb (constant name + plain ints — OBS002).

    Origin resolution order is the PR 13 bugfix: the warmup scope is
    checked BEFORE the cancellation token, so a background warmup
    compile running while tenant queries are in flight lands under
    the ``warmup`` pseudo-victim instead of charging whichever query
    context happens to be ambient on the thread."""
    global _SEQ, _TOTAL_NS, _INLINE_NS, _WARMUP_NS
    if not _ENABLED:
        return
    from ..compile import aot
    from ..service.cancellation import current_token, observe
    warmup = aot.in_warmup()
    tok = None if warmup else current_token()
    inline = tok is not None
    origin = "warmup" if warmup else ("inline" if inline else "warm")
    bucket = aot.last_demand(cache)
    COMPILE_SECONDS.labels(cache=cache).observe(dur_ns / 1e9)
    sig = "" if signature is None else str(signature)[:_SIG_MAX]
    rec = {"cache": cache, "dur_ms": round(dur_ns / 1e6, 3),
           "signature": sig, "inline": inline, "origin": origin,
           "bucket": bucket,
           "query_id": tok.query_id if inline else None,
           "t0_ns": t0_ns, "end_ns": time.perf_counter_ns()}
    with _LOCK:
        _SEQ += 1
        if warmup:
            _WARMUP_NS += dur_ns
        else:
            _TOTAL_NS += dur_ns
            if inline:
                _INLINE_NS += dur_ns
        _store(rec)
    if inline:
        observe("inline_compile_ms", dur_ns / 1e6)
    flight.record(flight.EV_COMPILE, cache, dur_ns // 1_000_000,
                  1 if inline else 0)


def note_persistent_hit(cache: str, dur_ns: int,
                        signature: Optional[str] = None,
                        t0_ns: Optional[int] = None) -> None:
    """Record a first call satisfied by the persistent executable
    cache: an earlier process compiled this (program, signature, conf
    fingerprint) and this call deserialized it.  Counted under
    ``tpu_compile_persistent_hits_total`` and the record store (so the
    report can show the load), but NOT in ``tpu_compile_seconds`` or
    the inline/total ns counters — nothing was compiled."""
    global _PERSISTENT_NS, _PERSISTENT_HITS
    if not _ENABLED:
        return
    from ..compile import aot
    from .registry import COMPILE_PERSISTENT_HITS
    COMPILE_PERSISTENT_HITS.labels(cache=cache).inc()
    sig = "" if signature is None else str(signature)[:_SIG_MAX]
    rec = {"cache": cache, "dur_ms": round(dur_ns / 1e6, 3),
           "signature": sig, "inline": False, "origin": "persistent",
           "bucket": aot.last_demand(cache), "query_id": None,
           "t0_ns": t0_ns, "end_ns": time.perf_counter_ns()}
    with _LOCK:
        _PERSISTENT_NS += dur_ns
        _PERSISTENT_HITS += 1
        _store(rec)
    flight.record(flight.EV_COMPILE, "persistent_hit",
                  dur_ns // 1_000_000, 0)


def wrap_miss(cache: str, fn: Callable, signature=None) -> Callable:
    """Wrap a compile-cache miss's freshly built callable so its first
    call (where jit traces + compiles) is timed into ``note_compile``
    — or, when the AOT manifest proves an earlier process already
    compiled it into the persistent cache, into
    ``note_persistent_hit``.  Warm calls afterwards pay one list-index
    check."""
    if not _ENABLED:
        # compile telemetry off: the cost plane still needs the
        # first-call choke point — but when it is off too, the old
        # identity-passthrough contract holds exactly
        from . import costplane as _costplane
        if _costplane._ENABLED:
            return _costplane.wrap_capture(cache, fn)
        return fn
    compiled = [False]

    def _timed(*args, **kwargs):
        if compiled[0]:
            return fn(*args, **kwargs)
        from ..compile import aot
        key = aot.first_call_key(cache, signature)
        with _trace.span("srt.jit_build", "compile", True, cache=cache,
                         site=getattr(fn, "__name__", "")) as sp:
            out = fn(*args, **kwargs)
        compiled[0] = True
        t0, dur_ns = sp.t0, sp.dur_ns
        persistent = aot.persistent_ready(key)
        if persistent:
            note_persistent_hit(cache, dur_ns, signature, t0)
        else:
            note_compile(cache, dur_ns, signature, t0)
            if key is not None:
                aot.manifest_add(key, cache, signature,
                                 aot.last_demand(cache), dur_ns / 1e6)
        try:
            # device-compute cost plane: static cost analysis of the
            # just-compiled program — one trace-only lowering pass per
            # (program, bucket), same hook for miss/warmup/persistent
            from . import costplane as _costplane
            _costplane.capture(
                cache, fn, args, kwargs,
                origin=_costplane.ORIGIN_PERSISTENT if persistent
                else _costplane.ORIGIN_WARMUP if aot.in_warmup()
                else _costplane.ORIGIN_MISS)
        except Exception:  # noqa: BLE001 — capture never fails the call
            pass
        return out

    return _timed


# ---------------------------------------------------------------------------
# accessors (cold paths: session window deltas, Service.stats())
# ---------------------------------------------------------------------------

def compile_seq() -> int:
    """Lock-free read of the compile sequence number: dispatch windows
    snapshot it to learn whether a compile landed inside them
    (dispatch_cold routing in obs/profile.py).  An int read is atomic
    under the GIL — no torn values, worst case one late tick."""
    return _SEQ


def jit_builds() -> int:
    """``jax.jit`` objects the engine has built through ``jit()`` since
    the process started (lock-free int read, like ``compile_seq``)."""
    return _JIT_BUILDS


def jit_build_sites() -> Dict[str, int]:
    """``{program name: constructions}``: which sites rebuild."""
    with _LOCK:
        return dict(_JIT_BUILD_SITES)


def total_ns() -> int:
    """Process-wide compile wall ns.  The session deltas this around
    each execution for the engine record's ``inline_compile_ms`` (the
    FLUSH_COUNT discipline: exact when queries run serially)."""
    with _LOCK:
        return _TOTAL_NS


def inline_ns() -> int:
    with _LOCK:
        return _INLINE_NS


def warmup_ns() -> int:
    """Background warmup compile ns (the pseudo-victim's bill)."""
    with _LOCK:
        return _WARMUP_NS


def persistent_hits() -> int:
    with _LOCK:
        return _PERSISTENT_HITS


def records_since(marker: int) -> List[Dict]:
    """Compiles recorded after a ``begin_query()`` marker (store index
    snapshot).  Evictions only drop pre-existing cheap entries, so a
    per-query slice right after the query is reliable."""
    with _LOCK:
        return [dict(r) for r in _RECORDS[marker:]]


def begin_query() -> int:
    with _LOCK:
        return len(_RECORDS)


def stats_section(top_n: Optional[int] = None) -> Dict:
    """The ``compile`` section of ``Service.stats().snapshot()``: the
    top-N slowest compiles plus cumulative counters."""
    n = top_n if top_n is not None else _TOP_N
    with _LOCK:
        recs = sorted(_RECORDS, key=lambda r: -r["dur_ms"])[:n]
        tot, inl = _TOTAL_NS, _INLINE_NS
        wrm, pns, phits = _WARMUP_NS, _PERSISTENT_NS, _PERSISTENT_HITS
    return {
        "total_compile_ms": round(tot / 1e6, 3),
        "inline_compile_ms": round(inl / 1e6, 3),
        "warmup_compile_ms": round(wrm / 1e6, 3),
        "persistent_hits": phits,
        "persistent_load_ms": round(pns / 1e6, 3),
        "compiles": len(recs),
        "top": [dict(r) for r in recs],
    }


def configure(conf) -> None:
    """Apply the ``spark.rapids.tpu.obs.compile.*`` conf group."""
    global _ENABLED, _TOP_N
    from ..config import OBS_COMPILE_ENABLED, OBS_COMPILE_TOP_N
    _ENABLED = bool(conf.get(OBS_COMPILE_ENABLED))
    _TOP_N = int(conf.get(OBS_COMPILE_TOP_N))


def reset() -> None:
    """Test hook: drop records and counters."""
    global _TOTAL_NS, _INLINE_NS, _WARMUP_NS, _PERSISTENT_NS
    global _PERSISTENT_HITS
    with _LOCK:
        _TOTAL_NS = 0
        _INLINE_NS = 0
        _WARMUP_NS = 0
        _PERSISTENT_NS = 0
        _PERSISTENT_HITS = 0
        del _RECORDS[:]
