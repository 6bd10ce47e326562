"""Test harness config: run on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): unit tests run
against a local, clusterless backend; distributed logic is tested on
virtual devices (their Mockito-mock-transport pattern) rather than real
hardware.
"""
import os

# Tests exercise the 16-row capacity buckets (cheap compiles on the CPU
# backend, and capacity-edge cases stay reachable with tiny inputs); the
# TPU-production default is larger to keep the per-query program count
# down (see columnar/column.py MIN_CAPACITY).
os.environ.setdefault("SPARK_RAPIDS_TPU_MIN_CAPACITY", "16")

# Force the static plan-invariant verifier on for every plan the suite
# lowers, regardless of per-test conf: every tier-1 query plan doubles
# as a verifier regression fixture (spark.rapids.tpu.sql.planVerify).
os.environ.setdefault("SPARK_RAPIDS_TPU_FORCE_PLAN_VERIFY", "1")

# Force EXACT exchange-stats mode: every map batch sketched, no
# sampling (spark.rapids.tpu.obs.stats.sampleEvery), so stats digests
# and skew/distinct verdicts stay deterministic under test.  Sampling
# behavior itself is tested by setting the conf explicitly with an acc
# built directly (tests/test_obs_overhead.py).
os.environ.setdefault("SPARK_RAPIDS_TPU_OBS_STATS_EXACT", "1")

# Force the residency transfer guard on for every query the suite
# drains: undeclared device->host pulls raise UndeclaredTransferError
# instead of silently stalling the pipeline.  Declared sites
# (analysis/residency.py SITES) lift the guard for their scoped pull.
# Export SPARK_RAPIDS_TPU_FORCE_TRANSFER_GUARD=0 to switch off when
# bisecting (spark.rapids.tpu.analysis.residency.transferGuard).
os.environ.setdefault("SPARK_RAPIDS_TPU_FORCE_TRANSFER_GUARD", "1")

# The suite runs on the CPU backend with 8 virtual devices (set before
# JAX initializes a backend).  It proves nothing about the chip: the
# chip is reached only through `python chip_smoke.py` (README "Running").
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# No PERSISTENT compile cache under the CPU test mesh: XLA:CPU AOT
# executables re-loaded across processes trip a machine-feature
# mismatch in cpu_aot_loader (flaky SIGILL/segfault mid-suite); CPU
# compiles at the 16-row test sizes are cheap, so cache nothing.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: non-gating perf/soak checks excluded from the tier-1 "
        "run (-m 'not slow')")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# LLVM's JIT code arena fails hard (segfault on the next compile) once
# a single process accumulates enough live XLA:CPU executables; the
# engine's (op, schema, bucket) program caches pin them.  Dropping all
# compile caches every 100 tests keeps the whole suite inside the
# arena; test-size recompiles are cheap.
_TESTS_RUN = {"n": 0}


@pytest.fixture(autouse=True)
def _suite_compile_arena_bound():
    yield
    _TESTS_RUN["n"] += 1
    if _TESTS_RUN["n"] % 100 == 0:
        from spark_rapids_tpu.shims.compile_caches import \
            clear_compile_caches
        clear_compile_caches()
