"""One-flush host-transfer pool.

Every device->host pull synchronizes with the device: it waits for the
queued work its value depends on and pays a fixed per-transfer cost, so
N small pulls serialize the host against the device N times.  The
engine therefore NEVER pulls values one at a time: every host-visible
value (row counts, shuffle bin counts, output column buffers, speculative
fit flags) is *staged* here, and the first forced value flushes the whole
pool in one wait: every staged part's copy to the host starts at once
(``jax.device_get`` of them all) and the parts are joined on the host
into at most two streams (uint32 and, when doubles are present,
float64): joined on the device by ``jnp.concatenate`` they cost one
eager program for every tuple of part sizes a flush happened to hold,
compiled again and again inside a benchmark's window (ROADMAP S9; 43-59
compiles a window in ``tpch_q13q21.power``).  (The design dates from a backend whose
round trip was ~65-100 ms; the per-pull cost on the local chip is not
measured on the current machine — the flush count stays the unit the
planner predicts and the tests pin.)

Encoding notes (the chip cannot bitcast 64-bit types — the XLA x64
rewriter refuses; canon.py:55 has the same constraint):
- bool/int8/uint8        -> bytes packed 4-per-u32 word (host unpacks by view)
- 16/32-bit fixed width  -> uint32 stream (16-bit widened via astype)
- int64/uint64           -> two uint32 words by shift/mask (exact)
- float64                -> its own float64 stream, pulled directly (device
  f64 is whatever the chip holds: on TPU v5e a pair of f32s with f32
  range — DOUBLE columns therefore live as int64 bit patterns, see
  kernels/binary64.py — and 64-bit bitcasts are unsupported)
A one-time roundtrip self-check guards the encodings: a MISMATCH drops to
per-array pulls (encoding_verdict() reports it), a probe that raises
propagates.

Reference analogue: the role of cuDF's stream-ordered D2H copies batched
at batch boundaries (GpuColumnVector / ColumnarToRow).
"""
from __future__ import annotations

import threading
import weakref
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..obs import trace as _trace

# Staged items: weakrefs so abandoned handles are never transferred.
# The pool is process-wide and queries run concurrently under the query
# service, so stage/flush swaps are serialized by _POOL_LOCK (a lost
# append would leave a Staged unresolvable).
_POOL: List["weakref.ref"] = []
_POOL_LOCK = threading.Lock()


def _residency():
    # lazy: analysis/__init__ pulls exec.base, which would cycle back
    # through the columnar package at import time
    from ..analysis import residency
    return residency


class Staged:
    """Handle for one staged device array; resolves at the next flush."""

    __slots__ = ("dev", "_np_dtype", "_shape", "_val", "__weakref__")

    def __init__(self, dev):
        self.dev = dev
        self._np_dtype = np.dtype(dev.dtype)
        self._shape = tuple(dev.shape)
        self._val: Optional[np.ndarray] = None
        with _POOL_LOCK:
            _POOL.append(weakref.ref(self))

    @property
    def resolved(self) -> bool:
        return self._val is not None

    @property
    def np(self) -> np.ndarray:
        if self._val is None:
            flush()
        if self._val is None and self.dev is not None:
            # a concurrent flush captured this item but has not decoded
            # it yet: pull directly (same value; the duplicate transfer
            # only happens on this narrow race)
            with _residency().declared_transfer(site="pending_race"):
                self._val = np.asarray(self.dev)
        return self._val

    def _count(self) -> int:
        return int(np.prod(self._shape)) if self._shape else 1


def stage(dev) -> Staged:
    """Stage a device array for the next fused pull."""
    if not hasattr(dev, "dtype"):
        dev = jnp.asarray(dev)
    return Staged(dev)


# Encoders are jitted (cached per input shape): an eager jnp op pays
# per-op dispatch (and, per new shape, its own small compile) while one
# jit dispatch covers the whole encode, so per-item encode work must
# never run eagerly.

@_trace.launched()
@jax.jit
def pending_enc_bytes(x):
    """u8-ish[n] -> u32[ceil(n/4)] little-endian (host unpacks via .view)."""
    x = jnp.ravel(x)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    elif x.dtype != jnp.uint8:
        x = lax.bitcast_convert_type(x, jnp.uint8)
    n = int(x.shape[0])
    pad = (-n) % 4
    if pad:
        x = jnp.concatenate([x, jnp.zeros(pad, jnp.uint8)])
    w = x.astype(jnp.uint32).reshape(-1, 4)
    return (w[:, 0] | (w[:, 1] << 8) | (w[:, 2] << 16) | (w[:, 3] << 24))


@_trace.launched()
@jax.jit
def pending_enc_wide16(x):
    x = jnp.ravel(x)
    return (x.astype(jnp.int32).view(jnp.uint32)
            if np.dtype(x.dtype).kind == "i" else x.astype(jnp.uint32))


@_trace.launched()
@jax.jit
def pending_enc_u32(x):
    return lax.bitcast_convert_type(jnp.ravel(x), jnp.uint32)


@_trace.launched()
@jax.jit
def pending_enc_split64(x):
    # 64-bit ints: exact shift/mask split (the chip rejects 64-bit
    # bitcasts; masking the arithmetic-shifted high word is exact)
    x = jnp.ravel(x)
    mask = x.dtype.type(0xFFFFFFFF)
    lo = (x & mask).astype(jnp.uint32)
    hi = ((x >> x.dtype.type(32)) & mask).astype(jnp.uint32)
    return lo, hi


@_trace.launched()
@jax.jit
def pending_enc_f64(x):
    return jnp.ravel(x)


def _encode(x) -> Tuple[str, list]:
    """Device array -> (layout, [u32 parts] or [f64 parts])."""
    dt = np.dtype(x.dtype)
    if dt == np.bool_ or dt.itemsize == 1:
        return "u8", [pending_enc_bytes(x)]
    if dt.itemsize == 2:
        return "u32", [pending_enc_wide16(x)]
    if dt.itemsize == 4:
        return "u32", [pending_enc_u32(x)]
    if dt.kind in "iu":
        return "split64", list(pending_enc_split64(x))
    assert dt == np.float64, f"unsupported staged dtype {dt}"
    return "f64", [pending_enc_f64(x)]


def _decode(layout: str, np_dtype, shape, parts: List[np.ndarray]):
    count = int(np.prod(shape)) if shape else 1
    if layout == "u8":
        raw = np.ascontiguousarray(parts[0]).view(np.uint8)[:count]
        if np_dtype == np.bool_:
            return (raw != 0).reshape(shape)
        return raw.view(np_dtype).reshape(shape)
    if layout == "u32":
        raw = parts[0]
        if np_dtype.itemsize == 2:
            kind = "i4" if np_dtype.kind == "i" else "u4"
            return raw.view(kind).astype(np_dtype).reshape(shape)
        return np.ascontiguousarray(raw).view(np_dtype).reshape(shape)
    if layout == "split64":
        lo, hi = parts
        u = lo.astype(np.uint64) | (hi.astype(np.uint64) << 32)
        return u.view(np_dtype).reshape(shape)
    assert layout == "f64", layout
    return np.asarray(parts[0], np.float64).reshape(shape)


# None = unverified; True = fused encoding verified; False = the probe's
# round trip MISMATCHED and flushes pull per item (a correctness guard,
# visible through encoding_verdict()).  A probe that RAISES is a device
# or compile fault and propagates: swallowing it would turn one flush
# into N pulls and hide the cause.
_ENCODING_OK: Optional[bool] = None


def _check_encoding() -> bool:
    global _ENCODING_OK
    if _ENCODING_OK is None:
        probe64 = np.array([0, 1, -1, 2**63 - 1, -2**63, 123456789012345],
                           np.int64)
        probef = np.array([0.0, -0.0, 1.5, -1e30, 1e-30,
                           3.141592653589793, np.inf, np.nan], np.float64)
        ok = True
        with _residency().declared_transfer(site="pending_probe"):
            for arr in (probe64, probef, np.array([True, False]),
                        np.arange(5, dtype=np.int32)):
                dev = jnp.asarray(arr)
                # reference = what the DEVICE itself round-trips (values
                # a plain pull can't recover aren't the encoder's job to
                # recover either)
                want = np.asarray(dev)
                layout, parts = _encode(dev)
                host = [np.asarray(p) for p in parts]
                back = _decode(layout, np.dtype(arr.dtype), arr.shape,
                               host)
                same = bool(np.all((back == want) |
                                   (pd_isnan(back) & pd_isnan(want))))
                ok = ok and same
        _ENCODING_OK = ok
    return _ENCODING_OK


def encoding_verdict() -> Optional[bool]:
    """The one-time probe's verdict: None until the first multi-item
    flush ran it, True = fused transfers, False = per-item pulls."""
    return _ENCODING_OK


def pd_isnan(a: np.ndarray) -> np.ndarray:
    if a.dtype.kind == "f":
        return np.isnan(a)
    return np.zeros(a.shape, bool)


# observability: device round trips this process (each non-empty flush
# forces all queued device work — the per-query flush count is the cost
# model the planner predicts; see docs/perf.md)
FLUSH_COUNT = 0

#: stats-plane hook (obs/profile.py): called as ``observer(dur_ns,
#: n_items)`` after each non-empty flush completes.  Module attribute,
#: not a registry: this is the hottest host path in the engine and one
#: global load + None-check is all it may cost when unset.
_FLUSH_OBSERVER = None


def flush():
    """Pull every staged array in at most two fused transfers."""
    global _POOL, FLUSH_COUNT
    with _POOL_LOCK:
        pool, _POOL = _POOL, []
    items: List[Staged] = []
    for w in pool:
        it = w()
        if it is not None and it._val is None:
            items.append(it)
    if not items:
        return
    FLUSH_COUNT += 1
    # coarse span srt.flush: the host blocked on the device and the
    # copy back; the observer takes its duration from the same reads
    span = _trace.Span("srt.flush", "pool", {"items": len(items)}, True)
    try:
        with span:
            return _flush_items(items)
    finally:
        obs = _FLUSH_OBSERVER
        if obs is not None:
            try:
                obs(span.dur_ns, len(items))
            except Exception:  # noqa: BLE001 — observers never break a flush
                pass


def _flush_items(items: List[Staged]):
    # ONE declared region per flush event: the declared-transfer count
    # for this site tracks FLUSH_COUNT one-to-one, whatever the fused
    # transfer decomposes into
    with _residency().declared_transfer(site="pending_flush"):
        if len(items) == 1 or not _check_encoding():
            for it in items:
                it._val = np.asarray(it.dev)
                it.dev = None
            return
        encoded = []
        streams = {"u32": [], "f64": []}
        for it in items:
            layout, parts = _encode(it.dev)
            stream = streams["f64" if layout == "f64" else "u32"]
            idx = []
            for p in parts:
                idx.append((len(stream), int(p.shape[0])))
                stream.append(p)
            encoded.append((it, layout, idx))
        flats, offs = {}, {}
        for name, parts in streams.items():
            if parts:
                flats[name] = np.concatenate(jax.device_get(parts))
                o, lst = 0, []
                for p in parts:
                    lst.append(o)
                    o += int(p.shape[0])
                offs[name] = lst
        for it, layout, idx in encoded:
            name = "f64" if layout == "f64" else "u32"
            flat, off = flats[name], offs[name]
            parts = [flat[off[i]:off[i] + n] for i, n in idx]
            it._val = _decode(layout, it._np_dtype, it._shape, parts)
            it.dev = None
        return


def pool_size() -> int:
    with _POOL_LOCK:
        pool = list(_POOL)
    return sum(1 for w in pool if w() is not None and not w().resolved)
