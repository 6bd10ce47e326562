"""Rules the TPU v5e bring-up (PR 22) put in place: nothing on the main
path may hide the device, one owner for the compile cache, code for the
one JAX that is installed.  All of it runs on the CPU test mesh; the
chip itself is only ever proven by ``python chip_smoke.py``.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from spark_rapids_tpu import device_peaks
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.compile import aot, xla_cache
from spark_rapids_tpu.config import TpuConf

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# one owner for the compile cache
# ---------------------------------------------------------------------------

@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    aot.reset()
    aot.configure(TpuConf({}))


class TestCompileCacheOwner:
    def test_env_var_wins_after_session_and_aot_configure(
            self, monkeypatch, tmp_path, restore_cache_config):
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv(xla_cache.ENV_VAR, env_dir)
        TpuSession(TpuConf({}))
        assert jax.config.jax_compilation_cache_dir == env_dir
        # an aot.cacheDir conf places the MANIFEST, never the XLA cache
        manifest_dir = str(tmp_path / "manifest")
        aot.reset()
        aot.configure(TpuConf({
            "spark.rapids.tpu.compile.aot.cacheDir": manifest_dir}))
        assert jax.config.jax_compilation_cache_dir == env_dir
        TpuSession(TpuConf({
            "spark.rapids.tpu.compile.aot.cacheDir": manifest_dir}))
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert aot.stats_section()["cache_dir"] == manifest_dir
        assert aot.stats_section()["xla_cache_dir"] == env_dir

    def test_unset_env_is_the_fixed_checkout_path(
            self, monkeypatch, tmp_path, restore_cache_config):
        monkeypatch.delenv(xla_cache.ENV_VAR, raising=False)
        TpuSession(TpuConf({}))
        want = os.path.join(REPO_ROOT, ".jax_cache")
        assert xla_cache.CHECKOUT_CACHE_DIR == want
        assert jax.config.jax_compilation_cache_dir == want
        aot.reset()
        aot.configure(TpuConf({
            "spark.rapids.tpu.compile.aot.cacheDir": str(tmp_path)}))
        assert jax.config.jax_compilation_cache_dir == want

    def test_manifest_vouches_for_one_xla_cache_dir_only(
            self, monkeypatch, tmp_path, restore_cache_config):
        monkeypatch.setenv(xla_cache.ENV_VAR, str(tmp_path / "xla_a"))
        conf = TpuConf({
            "spark.rapids.tpu.compile.aot.cacheDir": str(tmp_path / "m"),
            "spark.rapids.tpu.compile.aot.xlaCache.enabled": False})
        aot.reset()
        aot.configure(conf)
        key = aot.first_call_key("fused_project", "sig")
        aot.manifest_add(key, "fused_project", "sig", 1024, 1.0)
        aot._load_manifest()
        assert aot.manifest_entries() == 1
        # same manifest, XLA cache moved: its entries prove nothing
        monkeypatch.setenv(xla_cache.ENV_VAR, str(tmp_path / "xla_b"))
        aot._load_manifest()
        assert aot.manifest_entries() == 0

    def test_no_other_code_path_sets_the_directory(self):
        """The acceptance grep, as a test: api/, compile/ and shims/
        build no cache path and only xla_cache.py sets the option."""
        offenders = []
        for sub in ("api", "compile", "shims"):
            base = os.path.join(REPO_ROOT, "spark_rapids_tpu", sub)
            for name in sorted(os.listdir(base)):
                if not name.endswith(".py"):
                    continue
                with open(os.path.join(base, name)) as f:
                    text = f.read()
                for needle in ("gettempdir", "getuser",
                               "SPARK_RAPIDS_TPU_XLA_CACHE"):
                    if needle in text:
                        offenders.append((sub, name, needle))
                if '"jax_compilation_cache_dir"' in text and \
                        (sub, name) != ("compile", "xla_cache.py"):
                    offenders.append((sub, name, "sets the directory"))
        assert offenders == []


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, kind, stats):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        return self._stats


class TestDeviceManager:
    def test_device_query_failure_propagates(self, monkeypatch):
        from spark_rapids_tpu.memory import arena

        def boom():
            raise RuntimeError("no backend")
        monkeypatch.setattr(arena.jax, "devices", boom)
        with pytest.raises(RuntimeError, match="no backend"):
            arena.DeviceManager(TpuConf({}))

    def test_accelerator_without_bytes_limit_is_an_error(self, monkeypatch):
        from spark_rapids_tpu.memory import arena
        monkeypatch.setattr(
            arena.jax, "devices",
            lambda: [_FakeDevice("tpu", "TPU v5 lite", None)])
        with pytest.raises(RuntimeError, match="bytes_limit"):
            arena.DeviceManager(TpuConf({}))

    def test_cpu_test_mesh_uses_the_named_stand_in(self):
        from spark_rapids_tpu.memory import arena
        dm = arena.DeviceManager(TpuConf({}))
        assert dm.device.platform == "cpu"
        assert dm.hbm_total == device_peaks.CPU_TEST_MESH.hbm_bytes

    def test_reported_bytes_limit_is_used(self, monkeypatch):
        from spark_rapids_tpu.memory import arena
        monkeypatch.setattr(
            arena.jax, "devices",
            lambda: [_FakeDevice("tpu", "TPU v5 lite",
                                 {"bytes_limit": 12 << 30})])
        assert arena.DeviceManager(TpuConf({})).hbm_total == 12 << 30


class TestNoSilentDowngrade:
    def test_native_arena_failure_raises(self, monkeypatch):
        from spark_rapids_tpu import native
        from spark_rapids_tpu.memory.catalog import BufferCatalog

        def boom():
            raise RuntimeError("g++ missing")
        monkeypatch.setattr(native, "load", boom)
        with pytest.raises(RuntimeError, match="g\\+\\+ missing"):
            BufferCatalog()
        assert BufferCatalog(use_native_arena=False).arena is None

    def test_encoding_probe_failure_propagates(self, monkeypatch):
        from spark_rapids_tpu.columnar import pending

        def boom(_x):
            raise RuntimeError("encode lowering failed")
        monkeypatch.setattr(pending, "_ENCODING_OK", None)
        monkeypatch.setattr(pending, "_encode", boom)
        with pytest.raises(RuntimeError, match="encode lowering failed"):
            pending._check_encoding()
        assert pending.encoding_verdict() is None

    def test_fused_split_failure_propagates(self, monkeypatch):
        from spark_rapids_tpu.columnar import ColumnarBatch
        from spark_rapids_tpu.columnar import dtypes as T
        from spark_rapids_tpu.expr import core as ec
        from spark_rapids_tpu.shuffle import partitioners as P

        def boom(_fn):
            def raising(*_a, **_k):
                raise RuntimeError("split program failed to compile")
            return raising
        monkeypatch.setattr(P.HashPartitioner, "_SPLIT_JIT", {})
        monkeypatch.setattr(P.jax, "jit", boom)
        batch = ColumnarBatch.from_pydict({"k": [1, 2, 3, 4]})
        part = P.HashPartitioner(
            [ec.AttributeReference("k", T.INT64)], 2)
        with pytest.raises(RuntimeError, match="failed to compile"):
            part.split_staged(batch)
        # no False sentinel pinning an eager mode for the process
        assert False not in P.HashPartitioner._SPLIT_JIT.values()


def test_host_arena_slab_outlives_its_views():
    """A session re-init drops the old catalog's arena while a pipeline
    worker may still hold a numpy view into its slab (a flaky segfault
    in the suite): the slab is freed only after the last view."""
    import gc
    import weakref
    from spark_rapids_tpu import native
    arena = native.HostArena(1 << 20)
    view = arena.view(arena.alloc(64), 64)
    alive = weakref.ref(arena)
    del arena
    gc.collect()
    assert alive() is not None
    view[:] = 7                       # still backed by live memory
    assert int(view.sum()) == 7 * 64
    del view
    gc.collect()
    assert alive() is None


class TestPartitionIds:
    def test_hash_partition_ids_match_the_jnp_chain(self):
        import jax.numpy as jnp
        from spark_rapids_tpu.kernels import basic as bk
        from spark_rapids_tpu.shuffle.partitioners import \
            partition_hash_ids
        rng = np.random.default_rng(0)
        words = tuple(jnp.asarray(rng.integers(0, 2**63, 999).astype(
            np.uint64)) for _ in range(2))
        want = (bk.hash_words(list(words)) % jnp.uint64(7)).astype(
            jnp.int32)
        got = partition_hash_ids(words, 7)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# peaks: one table keyed by device_kind
# ---------------------------------------------------------------------------

class TestDevicePeaks:
    def test_v5e_row_and_source(self):
        row = device_peaks.lookup(_FakeDevice("tpu", "TPU v5 lite", None))
        assert (row.bf16_tflops, row.hbm_gbps, row.hbm_bytes) == \
            (197.0, 819.0, 16 << 30)
        assert "Google Cloud" in row.source and "v5e" in row.source

    def test_unknown_accelerator_raises(self):
        with pytest.raises(device_peaks.UnknownDeviceError,
                           match="TPU v99"):
            device_peaks.lookup(_FakeDevice("tpu", "TPU v99", None))

    def test_costplane_takes_peaks_from_the_table_or_the_conf(self):
        from spark_rapids_tpu.obs import costplane
        try:
            costplane.configure(TpuConf({}))
            flops, byts, source = costplane.peaks()
            assert (flops, byts) == (197.0e12, 819.0e9)
            assert source == "device_table:cpu"
            costplane.configure(TpuConf({
                "spark.rapids.tpu.obs.cost.peakTeraflops": 100.0,
                "spark.rapids.tpu.obs.cost.peakHbmGBps": 500.0}))
            assert costplane.peaks() == (100.0e12, 500.0e9, "conf")
            costplane.configure(TpuConf({
                "spark.rapids.tpu.obs.cost.peakHbmGBps": 500.0}))
            assert costplane.peaks() == (197.0e12, 500.0e9,
                                         "device_table:cpu+conf")
        finally:
            costplane.configure(TpuConf({}))

    def test_costplane_unknown_accelerator_is_an_error(self, monkeypatch):
        from spark_rapids_tpu.obs import costplane
        monkeypatch.setattr(
            jax, "devices", lambda: [_FakeDevice("tpu", "TPU v99", None)])
        try:
            costplane.configure(TpuConf({}))
            with pytest.raises(device_peaks.UnknownDeviceError):
                costplane.peaks()
            # both conf keys set: the table is not consulted
            costplane.configure(TpuConf({
                "spark.rapids.tpu.obs.cost.peakTeraflops": 1.0,
                "spark.rapids.tpu.obs.cost.peakHbmGBps": 1.0}))
            assert costplane.peaks()[2] == "conf"
        finally:
            monkeypatch.undo()
            costplane.configure(TpuConf({}))

    def test_cost_block_names_its_peak_source(self):
        from spark_rapids_tpu.api import functions as F
        s = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": True}))
        df = s.create_dataframe({"k": np.arange(64) % 4,
                                 "v": np.arange(64.0)})
        df.group_by("k").agg(F.sum("v").alias("s")).collect()
        cost = s.last_query_costplane
        assert cost["peak_source"] == "device_table:cpu"
        assert (cost["peak_tflops"], cost["peak_gbps"]) == (197.0, 819.0)


# ---------------------------------------------------------------------------
# mesh: a join fed by a mesh aggregate on the same key stays on the mesh
# ---------------------------------------------------------------------------

def test_mesh_join_after_mesh_aggregate_on_the_same_key_does_not_overflow():
    if jax.device_count() < 4:
        pytest.skip("needs a multi-device mesh")
    import bench
    from spark_rapids_tpu.exec.base import (MESH_INPUT_DEVICES,
                                            MESH_OVERFLOW_FALLBACKS)
    s = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": True,
                            "spark.rapids.tpu.sql.test.enabled": True,
                            "spark.rapids.tpu.shuffle.mode": "mesh"}))
    df = bench.build_df(s, 20_000, 4)
    assert len(df.collect()) == 1000
    nodes = {n.name: n.metrics.snapshot()
             for n in df._last_physical_plan.collect_nodes()
             if n.name.startswith("TpuMesh")}
    assert set(nodes) == {"TpuMeshAggregate", "TpuMeshShuffledJoin"}
    for name, m in nodes.items():
        assert m.get(MESH_OVERFLOW_FALLBACKS, 0) == 0, (name, m)
        assert m[MESH_INPUT_DEVICES] == jax.device_count(), (name, m)


# ---------------------------------------------------------------------------
# one process per chip / the smoke refuses to pass without one
# ---------------------------------------------------------------------------

def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, **env))


def test_python_worker_import_initialises_no_backend():
    out = _run(["-c",
                "import spark_rapids_tpu.exec.python_worker, jax;"
                "from jax._src import xla_bridge;"
                "print('backends', len(xla_bridge._backends))"],
               REPO_ROOT, JAX_PLATFORMS="cpu")
    assert out.returncode == 0, out.stderr[-1000:]
    assert out.stdout.strip().endswith("backends 0")


def test_chip_smoke_refuses_cpu_and_prints_no_result():
    out = _run(["chip_smoke.py"], REPO_ROOT, JAX_PLATFORMS="cpu")
    assert out.returncode == 2
    assert out.stdout.startswith("platform=cpu device_kind=cpu")
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    # even with the rehearsal flag there is no program to drive
    out = _run(["chip_smoke.py", "--rehearse-cpu"], str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH="")
    assert out.returncode != 0
    assert "ModuleNotFoundError" in out.stderr
    assert '"ok"' not in out.stdout
