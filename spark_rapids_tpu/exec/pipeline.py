"""Morsel-parallel intra-query execution: a bounded per-process worker
pool plus a prefetching partition-drain primitive.

Reference pattern: the accelerator gets much of its throughput from
keeping the device busy — multithreaded readers, async spill, the
GpuSemaphore arbitrating concurrent tasks per device (SURVEY.md §1).
Morsel-driven parallelism (Leis et al., SIGMOD 2014) is the engine-side
analogue: instead of one thread draining a query's partitions serially,
a small pool pulls N partition iterators concurrently so host-side work
(arrow staging, partition-split prep, spill/unspill, speculative
redo) overlaps in-flight device compute.

``drain_parallel(parts, sink, ...)`` is the single drain primitive the
serial loops were rewritten onto (shuffle map-side materialization and
broadcast build in exec/exchange.py, the collect loop in
api/session.py).  Contract:

- **deterministic order** — the consumer receives ``(partition_index,
  item)`` in exactly the order the serial loop would have produced:
  partition 0's items first, in pull order, then partition 1's, ...
  Since every item is computed by the same functional device program
  regardless of which thread pulled it, output is bit-identical to the
  serial drain (tested in tests/test_pipeline.py).
- **bounded buffering** — each partition prefetches at most
  ``pipelinePrefetchDepth`` items ahead of the consumer, and the drain
  as a whole parks producers past a byte budget
  (``pipelineBufferBytes``, capped at drain start to half the free
  device tier so prefetch cannot out-buffer the arena).  The head
  partition may always buffer one item when it has nothing queued —
  without that bypass a full budget would deadlock against a consumer
  blocked on the head.
- **semaphore discipline** — workers hold the DeviceSemaphore only
  around the pull + sink (the device-dispatch region), release between
  items, ``release_all()`` on exit, and attribute their blocked-wait
  time to the owning query's token (``sem_wait_ms``).  A pool worker
  never parks on the semaphore unboundedly: past ``_SEM_TRY_S`` it
  hands its partition back (``_UNSTARTED``) and moves on, so a claimed
  partition cannot wedge behind permits pinned elsewhere.
- **liveness under nesting** — pool workers themselves may hit a nested
  drain (a collect pull forces a shuffle materialization).  The
  consumer never depends on the pool: when it reaches a partition no
  worker has claimed, it produces that partition inline
  (consumer-assist), so an exhausted pool degrades to the serial drain
  instead of deadlocking.  The permit handback above keeps this true
  even when IDLE workers grab a nested drain's partitions while every
  permit is pinned by the outer drain: they time out, hand back, and
  the nested consumer (holding its permit re-entrantly) assists.
- **cancellation** — producers and the consumer run cooperative cancel
  checkpoints; a mid-drain cancel (or any producer error) fails the
  drain once, wakes everybody, and the workers unwind — semaphore
  permits released, buffered batches dropped.

Observability: every stage records allocation-free ``EV_PIPELINE``
flight events, drains export queue-depth/buffered-bytes/busy-worker
gauges + a per-batch busy histogram + an overlap-ratio gauge
(obs/registry.py), and the stall watchdog aggregates pipeline-worker
flight progress into the owning query via ``worker_idents()``.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Set

from ..obs import flight as _flight
from ..obs import trace as _trace
from ..obs.registry import (PIPELINE_BATCHES, PIPELINE_DRAINS,
                            PIPELINE_OVERLAP_RATIO,
                            PIPELINE_WORKER_BUSY_SECONDS)
from ..service.cancellation import (CancelToken, cancel_checkpoint,
                                    current_token, observe, query_context)

# drain-stage name constants for EV_PIPELINE records (interned: the
# recorder is always-on, so call sites pass these + plain ints only)
_N_DISPATCH = "dispatch"
_N_PULL = "pull"
_N_INLINE = "inline"
_N_PART_DONE = "part_done"
_N_DRAIN_END = "drain_end"
_N_HANDBACK = "sem_handback"

#: producer/consumer park-poll period; every wakeup re-runs the cancel
#: checkpoint, so cancellation latency is bounded by it
_POLL_S = 0.05

#: how long a pool worker tries for a device permit before handing its
#: partition back to the drain.  Normal permit waits are per-batch
#: (milliseconds — producers release between items); a wait this long
#: means the permits are pinned by threads that may themselves be
#: waiting on THIS drain (a nested drain under an outer pull region),
#: so the worker must yield the partition to the consumer instead of
#: parking forever
_SEM_TRY_S = 0.25

# partition drain states
_UNSTARTED, _RUNNING, _DONE = 0, 1, 2


def _auto_parallelism() -> int:
    return min(4, os.cpu_count() or 1)


# ---------------------------------------------------------------------------
# process-wide introspection (gauges, watchdog, service stats)
# ---------------------------------------------------------------------------

_INTROSPECT_LOCK = threading.Lock()
_LIVE_DRAINS: Set["_ParallelDrain"] = set()
#: pipeline-worker thread ident -> query_id currently served (watchdog
#: progress attribution: a pipelined query's heartbeat lives on these
#: threads while its service worker blocks in the drain consumer)
_ACTIVE_WORKERS: Dict[int, Optional[str]] = {}


def buffered_items() -> int:
    """Prefetched items buffered across all live drains (gauge)."""
    with _INTROSPECT_LOCK:
        drains = list(_LIVE_DRAINS)
    return sum(d._buffered for d in drains)


def buffered_bytes() -> int:
    """Bytes of prefetched items buffered across all live drains."""
    with _INTROSPECT_LOCK:
        drains = list(_LIVE_DRAINS)
    return sum(d._buffered_bytes for d in drains)


def busy_workers() -> int:
    """Pool workers currently serving a drain."""
    with _INTROSPECT_LOCK:
        return len(_ACTIVE_WORKERS)


def worker_idents(query_id: Optional[str]) -> List[int]:
    """Thread idents of pool workers currently serving ``query_id`` —
    read by the stall watchdog to fold pipeline-worker flight progress
    into the owning query's heartbeat."""
    with _INTROSPECT_LOCK:
        return [ident for ident, qid in _ACTIVE_WORKERS.items()
                if qid == query_id]


def pool_stats() -> Dict:
    """Pool + drain occupancy for ``Service.stats()``."""
    pool = PipelinePool._instance
    with _INTROSPECT_LOCK:
        live = len(_LIVE_DRAINS)
        busy = len(_ACTIVE_WORKERS)
    out = {"threads": 0, "queued": 0, "busy": busy, "live_drains": live,
           "buffered_items": buffered_items(),
           "buffered_bytes": buffered_bytes()}
    if pool is not None:
        out.update(pool.stats())
        out["busy"] = busy
    return out


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

class PipelinePool:
    """Per-process bounded worker pool serving drain requests.

    Threads are created lazily up to the largest parallelism any drain
    has requested (conf ``spark.rapids.tpu.exec.pipelineParallelism``)
    and then persist, parked on the task queue.  The park — a plain
    ``queue.get()`` — happens with **no engine lock held**; LOCK001's
    queue-receive rule allowlists this file for exactly that intentional
    idle wait (analysis/lint.py ``_LOCK001_QUEUE_GET_ALLOWLIST``).

    A task is "serve this drain": the worker claims unstarted
    partitions from the drain until none remain.  Tasks enqueued for a
    drain that already finished (the consumer drained it inline) no-op
    immediately, so stale entries cannot wedge the pool.
    """

    _instance: Optional["PipelinePool"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._lock = threading.Lock()
        self._tasks: "queue.SimpleQueue" = queue.SimpleQueue()
        self._threads: List[threading.Thread] = []
        self._seq = 0

    @classmethod
    def get(cls) -> "PipelinePool":
        inst = cls._instance
        if inst is None:
            with cls._instance_lock:
                inst = cls._instance
                if inst is None:
                    inst = cls._instance = PipelinePool()
        return inst

    def dispatch(self, fn: Callable[[], None], copies: int, size: int):
        """Enqueue ``copies`` runs of ``fn``, growing the pool to at
        most ``size`` threads (never shrinks: the largest request wins)."""
        with self._lock:
            while len(self._threads) < max(1, size):
                self._seq += 1
                t = threading.Thread(
                    target=self._worker_loop,
                    name=f"tpu-pipeline-{self._seq}", daemon=True)
                self._threads.append(t)
                t.start()
        for _ in range(copies):
            self._tasks.put(fn)

    def _worker_loop(self):
        while True:
            # the pool's idle state: parked on the task queue, holding
            # no lock (LOCK001 queue-receive allowlist, see class doc)
            fn = self._tasks.get()
            if fn is None:
                return
            try:
                fn()
            except BaseException:
                # a drain records its own failure and re-raises it on
                # the consumer thread; the pool thread must survive
                pass

    def stats(self) -> Dict:
        with self._lock:
            threads = len(self._threads)
        return {"threads": threads, "queued": self._tasks.qsize()}


# ---------------------------------------------------------------------------
# one drain
# ---------------------------------------------------------------------------

def _item_nbytes(item) -> int:
    """Best-effort size of a produced item for the byte budget.

    Sinks return containers, not just batches — the shuffle map sink
    yields ``(batch, (sorted_batch, counts))`` and pieces may arrive in
    lists — so every common container recurses; an unsized leaf counts
    as 0 (best effort, never a raise)."""
    if isinstance(item, (tuple, list)):
        return sum(_item_nbytes(x) for x in item)
    if isinstance(item, dict):
        return sum(_item_nbytes(v) for v in item.values())
    try:
        nb = getattr(item, "nbytes", None)
        if nb is None:
            return 0
        if callable(nb):
            return int(nb())
        return int(nb)
    except Exception:
        return 0


class _ParallelDrain:
    """State of one in-flight parallel drain: per-partition prefetch
    queues + one condition, claimed by pool workers lowest-index-first,
    consumed in partition order."""

    def __init__(self, parts: List, sink, depth: int, budget: int,
                 token: Optional[CancelToken], conf, label: str):
        self._parts = [iter(p) for p in parts]
        self._sink = sink
        self._depth = max(1, depth)
        self._budget = max(1, budget)
        self._token = token
        # the draining thread's query number, for the workers' spans
        # and counters (obs/trace.py)
        self._query = _trace.current_query()
        self._conf = conf
        self._label = label
        n = len(self._parts)
        self._n = n
        self._cond = threading.Condition()
        self._queues: List[deque] = [deque() for _ in range(n)]
        self._state = [_UNSTARTED] * n
        self._head = 0
        self._buffered = 0
        self._buffered_bytes = 0
        self._error: Optional[BaseException] = None
        self._closed = False
        self._busy_ns = 0
        self._t0 = time.perf_counter_ns()

    # -- producer side (pool workers + consumer-assist) --------------------

    def _stalled(self, pid: int) -> bool:
        """Backpressure predicate (under self._cond)."""
        if len(self._queues[pid]) >= self._depth:
            return True
        if self._buffered_bytes >= self._budget:
            # head-partition bypass: when the consumer's current
            # partition has nothing queued, its producer may always add
            # one more item — otherwise a full budget (held by later
            # partitions' buffers) would park the only producer the
            # consumer can make progress on
            return not (pid == self._head and not self._queues[pid])
        return False

    def _claim_next(self, skip=()) -> Optional[int]:
        with self._cond:
            if self._closed or self._error is not None:
                return None
            for pid in range(self._head, self._n):
                if self._state[pid] == _UNSTARTED and pid not in skip:
                    self._state[pid] = _RUNNING
                    return pid
        return None

    def _fail(self, exc: BaseException):
        with self._cond:
            if self._error is None:
                self._error = exc
            self._cond.notify_all()

    @staticmethod
    def _try_acquire_bounded(sem) -> bool:
        """Permit acquire for pool workers: bounded at ``_SEM_TRY_S``,
        cancel-checkpointed each poll.  False = hand the partition back."""
        deadline = time.monotonic() + _SEM_TRY_S
        while True:
            cancel_checkpoint()
            if sem.try_acquire(timeout=_POLL_S):
                return True
            if time.monotonic() >= deadline:
                return False

    def _produce_loop(self, pid: int, sem, inline: bool) -> bool:
        """Pull ``pid``'s iterator until exhausted (or one item when
        ``inline`` — the consumer produces exactly what it needs).

        Returns False when the partition was handed back instead of
        finished: a pool worker that cannot obtain a device permit
        within ``_SEM_TRY_S`` reverts ``pid`` to ``_UNSTARTED`` and
        yields it — every permit may be pinned by threads that are
        themselves waiting on this drain (nested drains), so only the
        consumer, which holds its permit re-entrantly across the nested
        pull, is guaranteed able to produce.  Handover is safe at any
        point: the iterator keeps its position in ``self._parts`` and
        exactly one owner pulls it at a time (the state machine under
        ``self._cond``)."""
        it = self._parts[pid]
        while True:
            with self._cond:
                while not self._closed and self._error is None and \
                        self._stalled(pid):
                    self._cond.wait(_POLL_S)
                    cancel_checkpoint()
                if self._closed or self._error is not None:
                    return True
            cancel_checkpoint()
            # DeviceSemaphore held only around the device-dispatch
            # region (the pull + sink), released between items so
            # prefetch never starves concurrent queries of permits.
            # The consumer (inline) may block — everyone else's
            # progress funnels through it — but pool workers must not:
            # they hand back on timeout (see docstring)
            if inline:
                sem.acquire_if_necessary()
            elif not self._try_acquire_bounded(sem):
                with self._cond:
                    if self._closed or self._error is not None:
                        return True
                    self._state[pid] = _UNSTARTED
                    self._cond.notify_all()
                _flight.record(_flight.EV_PIPELINE, _N_HANDBACK, a=pid)
                return False
            t0 = time.perf_counter_ns()
            produced = True
            try:
                try:
                    item = next(it)
                except StopIteration:
                    produced = False
                else:
                    if self._sink is not None:
                        item = self._sink(item)
            finally:
                sem.release()
            dt = time.perf_counter_ns() - t0
            if not produced:
                with self._cond:
                    self._state[pid] = _DONE
                    self._busy_ns += dt
                    self._cond.notify_all()
                _flight.record(_flight.EV_PIPELINE, _N_PART_DONE, a=pid)
                return True
            nb = _item_nbytes(item)
            PIPELINE_WORKER_BUSY_SECONDS.observe(dt / 1e9)
            _flight.record(_flight.EV_PIPELINE,
                           _N_INLINE if inline else _N_PULL, a=pid, b=nb)
            with self._cond:
                self._queues[pid].append((item, nb))
                self._buffered += 1
                self._buffered_bytes += nb
                self._busy_ns += dt
                self._cond.notify_all()
            if inline:
                return True

    def _serve(self):
        """Pool-worker entry: claim partitions until none remain."""
        ident = threading.get_ident()
        qid = self._token.query_id if self._token is not None else None
        with _INTROSPECT_LOCK:
            _ACTIVE_WORKERS[ident] = qid
        from ..config import set_active
        from ..memory.arena import DeviceManager
        sem = DeviceManager.get().semaphore
        try:
            # the caller's conf (incl. per-query service overlays) and
            # token travel to the worker: sinks read the right batch
            # sizes, checkpoints see the right cancellation state
            set_active(self._conf, thread_only=True)
            _trace.adopt_query(self._query)
            # transfer-guard parity with the collect thread: JAX's
            # guard is thread-local, so every pool worker arms its own
            # scoped disallow (analysis/residency.py)
            from ..analysis import residency as _residency
            with _residency.guard_scope(self._conf), \
                    query_context(self._token):
                try:
                    handed_back = set()
                    while True:
                        pid = self._claim_next(handed_back)
                        if pid is None:
                            break
                        if not self._produce_loop(pid, sem,
                                                  inline=False):
                            # handed back for want of a device permit:
                            # never re-claim it here (re-claiming would
                            # shut the consumer-assist window back out)
                            # — the consumer or a luckier worker takes
                            # it over
                            handed_back.add(pid)
                finally:
                    # ownership unwind + per-query wait attribution:
                    # permits this worker still holds are returned and
                    # its blocked-acquire time lands on the query token
                    sem.release_all()
                    waited = sem.pop_wait_ns()
                    if waited:
                        observe("sem_wait_ms", waited / 1e6)
        except BaseException as e:
            self._fail(e)
        finally:
            with _INTROSPECT_LOCK:
                _ACTIVE_WORKERS.pop(ident, None)

    # -- consumer side -----------------------------------------------------

    def results(self):
        from ..memory.arena import DeviceManager
        sem = DeviceManager.get().semaphore
        inline_owned: Set[int] = set()
        try:
            for pid in range(self._n):
                while True:
                    item = None
                    got = done = claim_inline = False
                    with self._cond:
                        if self._head != pid:
                            self._head = pid
                            self._cond.notify_all()
                        q = self._queues[pid]
                        if q:
                            item, nb = q.popleft()
                            self._buffered -= 1
                            self._buffered_bytes -= nb
                            got = True
                            self._cond.notify_all()
                        elif self._error is not None:
                            raise self._error
                        elif self._state[pid] == _DONE:
                            done = True
                        elif self._state[pid] == _UNSTARTED or \
                                pid in inline_owned:
                            self._state[pid] = _RUNNING
                            inline_owned.add(pid)
                            claim_inline = True
                        else:
                            self._cond.wait(_POLL_S)
                            cancel_checkpoint()
                    if got:
                        PIPELINE_BATCHES.labels(source="worker").inc()
                        yield pid, item
                    elif done:
                        break
                    elif claim_inline:
                        # consumer-assist: no worker claimed this
                        # partition (pool exhausted or a nested drain)
                        # — produce it inline so the drain always makes
                        # progress without depending on the pool
                        self._produce_loop(pid, sem, inline=True)
                        with self._cond:
                            q = self._queues[pid]
                            if q:
                                item, nb = q.popleft()
                                self._buffered -= 1
                                self._buffered_bytes -= nb
                                got = True
                        if got:
                            PIPELINE_BATCHES.labels(source="inline").inc()
                            yield pid, item
        finally:
            self._close()

    def _close(self):
        with self._cond:
            self._closed = True
            for q in self._queues:
                q.clear()
            self._buffered = 0
            self._buffered_bytes = 0
            self._cond.notify_all()
            busy_ns = self._busy_ns
        wall = time.perf_counter_ns() - self._t0
        ratio = busy_ns / wall if wall > 0 else 0.0
        PIPELINE_OVERLAP_RATIO.set(ratio)
        _flight.record(_flight.EV_PIPELINE, _N_DRAIN_END, a=self._n,
                       b=int(ratio * 1000))


# ---------------------------------------------------------------------------
# the drain primitive
# ---------------------------------------------------------------------------

def resolve_parallelism(conf=None) -> int:
    """The effective pipeline parallelism under ``conf`` (0 = auto)."""
    from ..config import (PIPELINE_ENABLED, PIPELINE_PARALLELISM,
                          get_active)
    conf = conf if conf is not None else get_active()
    if not conf.get(PIPELINE_ENABLED):
        return 1
    par = int(conf.get(PIPELINE_PARALLELISM))
    return par if par > 0 else _auto_parallelism()


def _effective_budget(conf) -> int:
    from ..config import PIPELINE_BUFFER_BYTES
    budget = int(conf.get(PIPELINE_BUFFER_BYTES))
    # spill-aware cap: buffered prefetch is not yet catalog-registered
    # (not spillable), so never plan to buffer past half the free
    # device tier — the catalog can spill registered peers to make
    # room, but headroom is the honest guard
    try:
        from ..memory.catalog import BufferCatalog
        cat = BufferCatalog.get()
        headroom = max(64 << 20,
                       (cat.device_limit - cat.device_bytes) // 2)
        budget = min(budget, headroom)
    except Exception:
        pass
    return budget


def drain_parallel(parts: Iterable, sink: Optional[Callable] = None, *,
                   parallelism: Optional[int] = None,
                   prefetch_depth: Optional[int] = None,
                   byte_budget: Optional[int] = None,
                   token: Optional[CancelToken] = None,
                   label: str = "drain"):
    """Drain ``parts`` (partition iterators), yielding
    ``(partition_index, item)`` in deterministic partition order.

    ``sink`` maps each pulled item on the producing thread (under the
    DeviceSemaphore) — put per-batch device/host staging work there so
    it overlaps across partitions.  Defaults come from the active conf;
    ``token`` defaults to the calling thread's CancelToken.  With
    parallelism 1 (or a single partition) this is exactly the serial
    loop the call site replaced — no threads, no buffering.
    """
    from ..config import PIPELINE_PREFETCH_DEPTH, get_active
    parts = [p for p in parts]
    conf = get_active()
    if token is None:
        token = current_token()
    par = parallelism if parallelism is not None \
        else resolve_parallelism(conf)
    par = min(par, len(parts))
    if par <= 1 or len(parts) <= 1:
        return _drain_serial(parts, sink)
    depth = prefetch_depth if prefetch_depth is not None \
        else int(conf.get(PIPELINE_PREFETCH_DEPTH))
    budget = byte_budget if byte_budget is not None \
        else _effective_budget(conf)
    return _drain_pipelined(parts, sink, par, depth, budget, token,
                            conf, label)


def _drain_serial(parts: List, sink):
    PIPELINE_DRAINS.labels(mode="serial").inc()
    for pid, part in enumerate(parts):
        for item in part:
            cancel_checkpoint()
            yield pid, (sink(item) if sink is not None else item)


def _drain_pipelined(parts: List, sink, par: int, depth: int,
                     budget: int, token, conf, label: str):
    drain = _ParallelDrain(parts, sink, depth, budget, token, conf,
                           label)
    PIPELINE_DRAINS.labels(mode="parallel").inc()
    _flight.record(_flight.EV_PIPELINE, _N_DISPATCH, a=len(parts),
                   b=par)
    with _INTROSPECT_LOCK:
        _LIVE_DRAINS.add(drain)
    try:
        PipelinePool.get().dispatch(drain._serve, copies=par, size=par)
        for out in drain.results():
            yield out
    finally:
        with _INTROSPECT_LOCK:
            _LIVE_DRAINS.discard(drain)
