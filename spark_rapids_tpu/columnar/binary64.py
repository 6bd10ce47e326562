"""Binary64Column: exact SQL DOUBLE as IEEE-754 bits in int64.

Why: the chip has no f64 ALU — XLA's emulated ``f64`` is an f32 pair
(~48-bit precision, ~1e±38 range), so a 1e300 DOUBLE cannot even
round-trip device memory.  64-bit INTEGER ops are exact on chip, so
under ``spark.rapids.tpu.sql.exactDouble.enabled`` every DOUBLE column
holds the IEEE bit pattern in int64 and arithmetic/comparison/
aggregation route through the softfloat kernels
(kernels/binary64.py).  Reference contract: bit-for-bit DOUBLE
semantics (GpuCast.scala / arithmetic.scala; the reference gets them
from cuDF's native f64).

Bits enter HOST-SIDE (numpy view — free, exact); the chip never needs
an f64<->i64 bitcast.  Ops outside the wired set raise loudly — this
mode trades breadth for exactness and is off by default.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from . import dtypes as T
from .column import Column, bucket_capacity, _pad_np


def exact_double_enabled() -> bool:
    from ..config import get_active, EXACT_DOUBLE
    try:
        return bool(get_active().get(EXACT_DOUBLE))
    except Exception:  # noqa: BLE001 - before config init
        return False


class Binary64Column(Column):
    """dtype FLOAT64; ``data`` is int64 IEEE-754 bit patterns."""

    def __init__(self, data, validity):
        super().__init__(T.FLOAT64, data, validity)

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_f64_numpy(arr: np.ndarray, validity=None,
                       capacity=None) -> "Binary64Column":
        bits = np.ascontiguousarray(arr, np.float64).view(np.int64)
        n = bits.shape[0]
        cap = capacity or bucket_capacity(n)
        if validity is None:
            validity = np.ones(n, np.bool_)
        return Binary64Column(
            jnp.asarray(_pad_np(bits, cap)),
            jnp.asarray(_pad_np(np.asarray(validity, np.bool_), cap,
                                fill=False)))

    @staticmethod
    def from_scalar_value(value, capacity: int, num_rows=None
                          ) -> "Binary64Column":
        from ..kernels import binary64 as b64
        n = capacity if num_rows is None else num_rows
        if value is None:
            return Binary64Column(jnp.zeros(capacity, jnp.int64),
                                  jnp.zeros(capacity, bool))
        bits = b64.bits_of(float(value))
        return Binary64Column(jnp.full((capacity,), bits, jnp.int64),
                              jnp.arange(capacity) < n)

    # -- host interop -------------------------------------------------------
    def to_numpy(self, num_rows: int):
        vals = np.ascontiguousarray(
            self._hnp("data")[:num_rows]).view(np.float64)
        return vals, self._hnp("validity")[:num_rows]

    # -- structural ops (must preserve the subclass) ------------------------
    def with_capacity(self, capacity: int, num_rows: int):
        if capacity == self.capacity:
            return self
        if capacity > self.capacity:
            pad = capacity - self.capacity
            data = jnp.pad(self.data, (0, pad))
            valid = jnp.pad(self.validity, (0, pad))
        else:
            data = self.data[:capacity]
            valid = self.validity[:capacity] & \
                (jnp.arange(capacity) < num_rows)
        return Binary64Column(data, valid)

    def gather(self, indices, live=None, unique=False):
        from ..obs import trace as _trace
        if _trace.eager():
            from .gather import gather_columns
            return gather_columns([self], indices, live, unique)[0]
        valid = jnp.take(self.validity, indices, axis=0, mode="clip")
        if live is not None:
            valid = valid & live
        return Binary64Column(
            jnp.take(self.data, indices, axis=0, mode="clip"), valid)

    def mask_validity(self, keep_mask):
        return Binary64Column(self.data, self.validity & keep_mask)


def require_same_kind(*cols):
    """Mixed exact-bits and emulated-f64 operands would silently compare
    bit patterns against values; refuse loudly."""
    kinds = {isinstance(c, Binary64Column) for c in cols
             if c is not None and c.dtype == T.FLOAT64}
    if len(kinds) > 1:
        raise NotImplementedError(
            "exactDouble: mixed Binary64 and emulated f64 operands — "
            "a DOUBLE entered the plan outside the exact-bits paths")
