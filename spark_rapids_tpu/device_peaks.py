"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table the engine's roofline (obs/costplane.py), the CPU
test-mesh memory budget (memory/arena.py) and chip_smoke.py read.  A
non-CPU device that is not in the table is an error, not a default: a
roofline share against another chip's ceiling is a wrong number that
looks right.  Add a row, with its source, when the engine meets a new
chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    device_kind: str
    bf16_tflops: float      # dense bf16 matmul peak, TFLOP/s per chip
    hbm_gbps: float         # HBM bandwidth, GB/s per chip
    hbm_bytes: int          # HBM capacity per chip
    source: str


class UnknownDeviceError(LookupError):
    """An accelerator whose published peaks are not in :data:`TABLE`."""


TABLE = {
    "TPU v5 lite": DevicePeaks(
        "TPU v5 lite", 197.0, 819.0, 16 << 30,
        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        '16 GB HBM2e at 819 GB/s per chip'),
}

#: what the CPU backend reports as: the virtual test mesh has no
#: published peaks, so it borrows the v5e row as a MODEL CONSTANT (so
#: verdicts and budgets are deterministic under test) — never a
#: measurement, and never used for a real accelerator
CPU_TEST_MESH = dataclasses.replace(
    TABLE["TPU v5 lite"], device_kind="cpu",
    source="CPU test mesh stand-in (the TPU v5e row as a model "
           "constant, not a measurement)")


def lookup(device) -> DevicePeaks:
    """Peaks for a ``jax.Device``; raises :class:`UnknownDeviceError`
    for an accelerator the table does not hold."""
    if device.platform == "cpu":
        return CPU_TEST_MESH
    try:
        return TABLE[device.device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device_kind={device.device_kind!r} "
            f"(platform {device.platform!r}); add a sourced row to "
            f"spark_rapids_tpu/device_peaks.py TABLE, or set both "
            f"spark.rapids.tpu.obs.cost.peakTeraflops and "
            f"spark.rapids.tpu.obs.cost.peakHbmGBps") from None
