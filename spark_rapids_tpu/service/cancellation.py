"""Cooperative per-query cancellation + deadline propagation.

Reference: Spark's task-kill contract (TaskContext.isInterrupted checked
at record boundaries) adapted to the columnar engine: a ``CancelToken``
is installed thread-locally for the duration of a query's execution and
checked at cheap checkpoints — operator boundaries (exec/base.timed),
batch hand-offs (PhysicalPlan.execute_checkpointed), shuffle-iterator
polls, and DeviceSemaphore waits.  XLA kernels themselves are never
interrupted (there is no safe mid-kernel abort); cancellation latency is
one batch/kernel, which is the same granularity the reference accepts.

The token also carries *ownership ledgers*: catalog buffer ids and
shuffle ids created while the token was current.  On cancel/failure the
service unwinds them so a killed query releases its semaphore permits,
catalog entries and map outputs (the arena live-bytes-return-to-baseline
guarantee tested in tests/test_service.py).

Stdlib-only: imported by memory/ and exec/ layers.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from .errors import QueryCancelledError


class CancelToken:
    """One query's cancellation state + resource-ownership ledger."""

    def __init__(self, query_id: Optional[str] = None,
                 deadline: Optional[float] = None):
        #: monotonic-clock deadline (time.monotonic() units), or None
        self.query_id = query_id
        self.deadline = deadline
        self.reason: Optional[str] = None
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._owned_buffers: List[str] = []
        self._owned_shuffles: List[int] = []
        #: per-query observations written by the engine while the token
        #: is current (sem_wait_ms, spill_bytes, ...)
        self.observed: Dict[str, float] = {}

    # -- cancellation ------------------------------------------------------
    def cancel(self, reason: str = "cancelled"):
        with self._lock:
            if self.reason is None:
                self.reason = reason
        self._event.set()

    @property
    def cancelled(self) -> bool:
        if self._event.is_set():
            return True
        if self.deadline is not None and time.monotonic() >= self.deadline:
            self.cancel("deadline")
            return True
        return False

    def remaining_s(self) -> Optional[float]:
        if self.deadline is None:
            return None
        return max(0.0, self.deadline - time.monotonic())

    def check(self):
        """Raise QueryCancelledError if cancelled / past deadline."""
        if self.cancelled:
            raise QueryCancelledError(self.reason or "cancelled",
                                      self.query_id)

    def wait_cancelled(self, timeout: float) -> bool:
        """Interruptible sleep (retry backoff): returns True as soon as
        the token is cancelled, False after ``timeout`` elapsed."""
        deadline = time.monotonic() + timeout
        while True:
            if self.cancelled:
                return True
            step = min(0.05, deadline - time.monotonic())
            if step <= 0:
                return False
            self._event.wait(step)

    # -- ownership ledgers -------------------------------------------------
    def own_buffer(self, buffer_id: str):
        with self._lock:
            self._owned_buffers.append(buffer_id)

    def own_shuffle(self, shuffle_id: int):
        with self._lock:
            self._owned_shuffles.append(shuffle_id)

    def pop_owned_buffers(self) -> List[str]:
        with self._lock:
            out, self._owned_buffers = self._owned_buffers, []
            return out

    def pop_owned_shuffles(self) -> List[int]:
        with self._lock:
            out, self._owned_shuffles = self._owned_shuffles, []
            return out


_TLS = threading.local()


def current_token() -> Optional[CancelToken]:
    # a dict read: ``getattr`` with a default raises and catches
    # AttributeError on a thread that never set one (every span, count
    # and launch asks)
    return _TLS.__dict__.get("token")


class query_context:
    """Install ``token`` as the thread's current query context."""

    def __init__(self, token: Optional[CancelToken]):
        self.token = token

    def __enter__(self):
        self._prev = getattr(_TLS, "token", None)
        _TLS.token = self.token
        return self.token

    def __exit__(self, *exc):
        _TLS.token = self._prev
        return False


def cancel_checkpoint():
    """Cheap cooperative checkpoint: raises QueryCancelledError when the
    current query (if any) is cancelled or past its deadline.  Safe to
    call from any engine layer; a thread with no active query context is
    a no-op."""
    tok = getattr(_TLS, "token", None)
    if tok is not None:
        tok.check()


def observe(key: str, value: float, add: bool = True):
    """Record a per-query observation (e.g. sem_wait_ms) on the current
    token, if any."""
    tok = getattr(_TLS, "token", None)
    if tok is None:
        return
    if add:
        tok.observed[key] = tok.observed.get(key, 0.0) + value
    else:
        tok.observed[key] = value
