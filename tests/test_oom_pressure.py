"""Reactive device-OOM handling: the real allocator's
RESOURCE_EXHAUSTED triggers spill-everything + retry
(DeviceMemoryEventHandler.onAllocFailure contract).  Simulated via
fault injection — a true HBM exhaustion would take the backend down
for every other test in the process."""
import numpy as np
import pytest

from spark_rapids_tpu.columnar import (ColumnarBatch, Column, Schema,
                                       Field, dtypes as T)
from spark_rapids_tpu.memory.catalog import BufferCatalog, StorageTier
from spark_rapids_tpu.memory.pressure import is_device_oom, oom_retry
from spark_rapids_tpu.memory.spillable import SpillableBatch


class FakeXlaOom(RuntimeError):
    pass

FakeXlaOom.__name__ = "XlaRuntimeError"


def _batch(n=100):
    return ColumnarBatch(
        Schema([Field("a", T.INT64)]),
        [Column.from_numpy(list(range(n)), dtype=T.INT64)], n)


def test_is_device_oom_classifier():
    assert is_device_oom(FakeXlaOom(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 bytes"))
    assert is_device_oom(MemoryError("Failed to allocate device buffer"))
    assert not is_device_oom(ValueError("RESOURCE_EXHAUSTED"))
    assert not is_device_oom(FakeXlaOom("INVALID_ARGUMENT: bad shape"))


def test_oom_retry_spills_and_retries():
    cat = BufferCatalog.reset(spill_dir="/tmp/srt_test_spill")
    sb = SpillableBatch(_batch())          # device-tier spill candidate
    calls = {"n": 0}

    def put():
        calls["n"] += 1
        if calls["n"] == 1:
            raise FakeXlaOom("RESOURCE_EXHAUSTED: Out of memory "
                             "allocating 16G")
        return "ok"

    assert oom_retry(put) == "ok"
    assert calls["n"] == 2
    # the retry spilled the device tier first
    assert cat._entries[sb.buffer_id].tier != StorageTier.DEVICE
    assert cat.oom_retries == 1
    sb.close()


def test_oom_retry_reraises_when_nothing_spillable():
    BufferCatalog.reset(spill_dir="/tmp/srt_test_spill")

    def put():
        raise FakeXlaOom("RESOURCE_EXHAUSTED: Out of memory")
    with pytest.raises(FakeXlaOom):
        oom_retry(put)


def test_oom_retry_propagates_non_oom():
    BufferCatalog.reset(spill_dir="/tmp/srt_test_spill")

    def bad():
        raise ValueError("not an oom")
    with pytest.raises(ValueError):
        oom_retry(bad)


def test_unspill_retries_after_injected_oom(monkeypatch):
    """acquire() of a spilled batch: first device put OOMs, the catalog
    spills the device tier and the retry materializes — WITHOUT the
    retry the injected error propagates and this test fails."""
    cat = BufferCatalog.reset(spill_dir="/tmp/srt_test_spill")
    victim = SpillableBatch(_batch(500))    # will be spilled by retry
    sb = SpillableBatch(_batch(50))
    cat.spill_device_to_fit(cat.device_limit)   # push both to HOST
    assert cat._entries[sb.buffer_id].tier == StorageTier.HOST
    victim.materialize()                    # victim back on DEVICE
    assert cat._entries[victim.buffer_id].tier == StorageTier.DEVICE

    real = BufferCatalog._deserialize
    calls = {"n": 0}

    def flaky(self, payload):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FakeXlaOom("RESOURCE_EXHAUSTED: Out of memory "
                             "allocating 4.00G on device ordinal 0")
        return real(self, payload)
    monkeypatch.setattr(BufferCatalog, "_deserialize", flaky)
    got = sb.materialize()
    assert got.columns[0].to_pylist(50) == list(range(50))
    assert calls["n"] == 2
    # the retry pushed the device-resident victim down a tier
    assert cat._entries[victim.buffer_id].tier != StorageTier.DEVICE
    sb.close()
    victim.close()


def test_scan_ingest_retries_after_injected_oom(monkeypatch):
    """from_arrow (the scan-side device put) retries through the same
    contract."""
    import pyarrow as pa
    from spark_rapids_tpu.columnar import arrow as A
    BufferCatalog.reset(spill_dir="/tmp/srt_test_spill")
    holder = SpillableBatch(_batch(200))
    t = pa.table({"x": list(range(64))})
    real = A.column_from_arrow
    calls = {"n": 0}

    def flaky(arr, capacity=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FakeXlaOom("RESOURCE_EXHAUSTED: Out of memory")
        return real(arr, capacity=capacity)
    monkeypatch.setattr(A, "column_from_arrow", flaky)
    b = A.from_arrow(t)
    assert b.columns[0].to_pylist(64) == list(range(64))
    assert calls["n"] == 2
    holder.close()
