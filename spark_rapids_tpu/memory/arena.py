"""Device manager + admission control.

Reference roles:
- GpuDeviceManager (GpuDeviceManager.scala:36): acquire 1 device per
  executor, size the memory pool from conf fractions.
- GpuSemaphore (GpuSemaphore.scala:27): counting semaphore limiting
  concurrent tasks on the device.
- RMM arena + DeviceMemoryEventHandler: allocation budget whose pressure
  triggers synchronous spill through the BufferCatalog.

TPU adaptation: XLA/PJRT owns the physical HBM allocator, so the arena
tracks logical live bytes and enforces the budget by spilling catalog
buffers before admitting new ones (``reserve``).  On real TPU backends the
HBM size is read from the device (a device that does not report it is
an error); the CPU test mesh budgets against device_peaks.CPU_TEST_MESH.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

import jax

from ..config import (TpuConf, get_active, HBM_POOL_FRACTION, HBM_RESERVE,
                      CONCURRENT_TPU_TASKS, HOST_SPILL_LIMIT, SPILL_DIR,
                      SHUFFLE_COMPRESS)
from ..device_peaks import CPU_TEST_MESH
from ..obs import flight as _flight
from ..obs import trace as _trace
from ..obs.registry import SEM_WAIT_SECONDS
from ..service.cancellation import cancel_checkpoint
from .catalog import BufferCatalog

# blocked acquires poll at this period so cooperative cancellation and
# deadlines interrupt a queued task instead of leaving it parked on the
# semaphore until a permit happens to free up
_ACQUIRE_POLL_S = 0.05


class DeviceSemaphore:
    """Counting semaphore gating concurrent tasks on the device.

    Waits are observable: time spent blocked accumulates into a
    per-thread counter (``pop_wait_ns``) that the session surfaces as
    the per-query ``sem_wait_ms`` metric, and blocked acquires honor the
    calling thread's query cancellation token (service deadlines do not
    deadlock behind a saturated device).
    """

    def __init__(self, permits: int):
        self.permits = permits
        self._sem = threading.Semaphore(permits)
        self._held = threading.local()
        self._wait = threading.local()
        # thread idents currently holding a permit — read by the stall
        # watchdog/diagnostics to tell "stalled while holding the
        # device" from "stalled in line"; updated only on the 0<->1
        # hold transitions, never on re-entrant bumps
        self._holders = set()
        self._holders_lock = threading.Lock()

    def _note_acquired(self, waited_ns: int = 0):
        ident = threading.get_ident()
        with self._holders_lock:
            self._holders.add(ident)
        _flight.record(_flight.EV_SEM_ACQUIRE, "device", a=waited_ns)

    def _note_released(self):
        ident = threading.get_ident()
        with self._holders_lock:
            self._holders.discard(ident)
        _flight.record(_flight.EV_SEM_RELEASE, "device")

    def holder_idents(self):
        """Thread idents currently holding a permit (snapshot)."""
        with self._holders_lock:
            return list(self._holders)

    def available(self) -> int:
        """Permits not currently held (approximate, for diagnostics)."""
        return self._sem._value

    def acquire_if_necessary(self, deadline: Optional[float] = None):
        """Acquire one permit for this thread (re-entrant per thread).

        ``deadline`` is an optional time.monotonic() instant; past it a
        TimeoutError is raised.  While blocked, the active query's
        CancelToken is checked every poll, so cancellation unwinds a
        queued task promptly."""
        if getattr(self._held, "count", 0) == 0:
            if self._sem.acquire(blocking=False):
                self._note_acquired()
            else:
                t0 = time.perf_counter_ns()
                acquired = False
                try:
                    while True:
                        cancel_checkpoint()
                        if deadline is not None and \
                                time.monotonic() >= deadline:
                            raise TimeoutError(
                                "DeviceSemaphore acquire deadline exceeded")
                        if self._sem.acquire(timeout=_ACQUIRE_POLL_S):
                            acquired = True
                            break
                finally:
                    waited = time.perf_counter_ns() - t0
                    self._wait.ns = getattr(self._wait, "ns", 0) + waited
                    self._observe_wait(t0, waited)
                    if acquired:
                        self._note_acquired(waited)
        self._held.count = getattr(self._held, "count", 0) + 1

    def try_acquire(self, timeout: float = 0.0,
                    deadline: Optional[float] = None) -> bool:
        """Non-raising acquire: True when a permit was obtained within
        ``timeout`` seconds (and before ``deadline``, if given)."""
        if getattr(self._held, "count", 0) > 0:
            self._held.count += 1
            return True
        limit = time.monotonic() + max(0.0, timeout)
        if deadline is not None:
            limit = min(limit, deadline)
        t0 = time.perf_counter_ns()
        acquired = False
        try:
            while True:
                step = min(_ACQUIRE_POLL_S, limit - time.monotonic())
                if self._sem.acquire(timeout=max(step, 0)):
                    self._held.count = 1
                    acquired = True
                    return True
                if time.monotonic() >= limit:
                    return False
        finally:
            waited = time.perf_counter_ns() - t0
            self._wait.ns = getattr(self._wait, "ns", 0) + waited
            self._observe_wait(t0, waited)
            if acquired:
                self._note_acquired(waited)

    @staticmethod
    def _observe_wait(t0_ns: int, waited_ns: int):
        """One blocked-acquire observation: wait histogram + the
        retroactive coarse span ``srt.sem_wait`` covering the blocked
        region.  Only blocked acquires reach here — the immediate-grant
        fast path stays observation-free."""
        SEM_WAIT_SECONDS.observe(waited_ns / 1e9)
        _trace.emit("srt.sem_wait", "memory", t0_ns, waited_ns, True)

    def release(self):
        count = getattr(self._held, "count", 0)
        if count > 0:
            self._held.count = count - 1
            if self._held.count == 0:
                self._sem.release()
                self._note_released()

    def release_all(self) -> int:
        """Drop every permit level this THREAD holds (task-completion /
        cancellation cleanup, the GpuSemaphore.releaseIfNecessary-on-
        task-end role).  Returns the held count released."""
        count = getattr(self._held, "count", 0)
        if count > 0:
            self._held.count = 0
            self._sem.release()
            self._note_released()
        return count

    def held_count(self) -> int:
        """Re-entrant hold depth of the calling thread."""
        return getattr(self._held, "count", 0)

    @contextlib.contextmanager
    def released(self):
        """Drop every permit level this THREAD holds for the duration
        of the block, restoring the same re-entrant depth on exit.

        For blocking waits that must not pin the device: a thread that
        parks on a stage barrier (shuffle map materialization, broadcast
        build) while holding a permit starves concurrent queries of
        device access — and deadlocks outright when the barrier winner
        needs pool workers that are queued behind that very permit.  A
        thread holding nothing passes through untouched."""
        held = self.release_all()
        try:
            yield
        finally:
            for _ in range(held):
                self.acquire_if_necessary()

    def pop_wait_ns(self) -> int:
        """Return and reset this thread's accumulated blocked-wait ns."""
        ns = getattr(self._wait, "ns", 0)
        self._wait.ns = 0
        return ns


class DeviceManager:
    _instance: Optional["DeviceManager"] = None

    def __init__(self, conf: Optional[TpuConf] = None):
        conf = conf or get_active()
        # a failed device query propagates: an engine that carries on
        # with no device and a guessed budget hides exactly the fault
        # a bring-up needs to see
        self.device = jax.devices()[0]
        stats = self.device.memory_stats()
        if stats and "bytes_limit" in stats:
            hbm_total = stats["bytes_limit"]
        elif self.device.platform == "cpu":
            # the CPU backend reports no memory stats: the virtual test
            # mesh budgets against a named stand-in, not a measurement
            hbm_total = CPU_TEST_MESH.hbm_bytes
        else:
            raise RuntimeError(
                f"{self.device.platform} device {self.device.device_kind!r} "
                f"reports no bytes_limit in memory_stats() ({stats!r}); "
                f"refusing to guess its HBM size")
        frac = conf.get(HBM_POOL_FRACTION)
        reserve = conf.get(HBM_RESERVE)
        device_limit = max(int(hbm_total * frac) - reserve, 1 << 30)
        self.catalog = BufferCatalog.reset(
            spill_dir=conf.get(SPILL_DIR),
            device_limit=device_limit,
            host_limit=conf.get(HOST_SPILL_LIMIT),
            compression=conf.get(SHUFFLE_COMPRESS))
        self.semaphore = DeviceSemaphore(conf.get(CONCURRENT_TPU_TASKS))
        self.hbm_total = hbm_total
        self.device_limit = device_limit

    @classmethod
    def get(cls) -> "DeviceManager":
        if cls._instance is None:
            cls._instance = DeviceManager()
        return cls._instance

    @classmethod
    def initialize(cls, conf: Optional[TpuConf] = None) -> "DeviceManager":
        cls._instance = DeviceManager(conf)
        return cls._instance

    def reserve(self, nbytes: int):
        """Admission: make room for nbytes, spilling catalog buffers if

        needed (the DeviceMemoryEventHandler.onAllocFailure contract)."""
        cat = self.catalog
        if cat.device_bytes + nbytes > cat.device_limit:
            from ..obs import memplane as _memplane
            cat.spill_device_to_fit(nbytes,
                                    reason=_memplane.REASON_BUDGET)
