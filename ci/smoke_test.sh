#!/usr/bin/env bash
# CI smoke pipeline (the jenkins/spark-tests.sh role): install the
# wheel-less package in-place, run the unit suite on the virtual
# 8-device CPU mesh, compile-check the driver entry points, and run a
# small end-to-end bench sanity pass.
#
# Host-only: every stage runs with JAX_PLATFORMS=cpu and must never be
# pointed at the chip (the chip's one entry point is chip_smoke.py).
# Compile cache: the stages use whatever compile/xla_cache.py decides —
# JAX_COMPILATION_CACHE_DIR if exported, else <checkout>/.jax_cache;
# compile_smoke.py's cold-start stage hands its children a fresh
# directory through that same variable.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== static analysis (project lint + race analysis) =="
JAX_PLATFORMS=cpu python ci/lint.py

echo "== program audit (jaxpr device-purity over every jitted program) =="
JAX_PLATFORMS=cpu python ci/audit.py
for rule in AUD001 AUD002 AUD003 AUD004; do
  # seeded negatives: the gate must FAIL on each planted defect
  if JAX_PLATFORMS=cpu python ci/audit.py --fixture "$rule" >/dev/null; then
    echo "audit fixture $rule did NOT trip the gate" >&2; exit 1
  fi
done

echo "== device residency (interprocedural host-transfer escape analysis) =="
JAX_PLATFORMS=cpu python ci/residency.py
for rule in RES001 RES002 RES003; do
  # seeded negatives: the gate must FAIL on each planted defect
  if JAX_PLATFORMS=cpu python ci/residency.py --fixture "$rule" >/dev/null; then
    echo "residency fixture $rule did NOT trip the gate" >&2; exit 1
  fi
done

echo "== plan-invariant verifier smoke (TPC-DS-style plans) =="
JAX_PLATFORMS=cpu python ci/lint.py --plan-smoke

echo "== unit suite (virtual 8-device CPU mesh) =="
python -m pytest tests/ -q

echo "== multichip dryrun =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

echo "== entry compile check =="
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import __graft_entry__
fn, args = __graft_entry__.entry()
jax.jit(fn).lower(*args).compile()
print("entry() compiles")
PY

echo "== two-process query (map in child executor, reduce in parent) =="
python ci/dist_smoke.py

echo "== concurrent query service (8 clients, bounded admission queue) =="
JAX_PLATFORMS=cpu python ci/service_smoke.py

echo "== observability (trace JSON + prometheus + report) =="
JAX_PLATFORMS=cpu python ci/obs_smoke.py

echo "== plan cache + predictive scheduler (repeat burst, breach shed) =="
JAX_PLATFORMS=cpu python ci/sched_smoke.py

echo "== morsel pipeline (parallel drains under stall watchdog) =="
JAX_PLATFORMS=cpu python ci/pipeline_smoke.py

echo "== superstage compiler (carve smoke, flush budget, determinism, cold start) =="
# stage 5 seeds and re-reads a cold XLA cache via JAX_COMPILATION_CACHE_DIR
JAX_PLATFORMS=cpu python ci/compile_smoke.py

echo "== runtime stats plane (attribution, skew stats, zero extra flushes) =="
JAX_PLATFORMS=cpu python ci/stats_smoke.py

echo "== soak plane (chaos soak, fault markers, burn monitors, flush parity) =="
JAX_PLATFORMS=cpu python ci/soak_smoke.py

echo "== api validation (docs vs live registry) =="
python -m spark_rapids_tpu.tools.api_validation

echo "== perf regression gate (newest BENCH_r* vs PERF_BASELINE) =="
JAX_PLATFORMS=cpu python ci/perf_gate.py
# seeded self-tests: a -20% throughput record must TRIP the gate...
if JAX_PLATFORMS=cpu python ci/perf_gate.py --fixture regression >/dev/null; then
  echo "perf-gate regression fixture did NOT trip the gate" >&2; exit 1
fi
# ...and a +50% record must pass AND suggest a baseline bump
JAX_PLATFORMS=cpu python ci/perf_gate.py --fixture improvement \
  | grep -q "baseline bump" \
  || { echo "perf-gate improvement fixture missing bump suggestion" >&2; exit 1; }
# ...and a record with nonzero leak drift + a crying-wolf sentinel must
# trip the soak-plane gates (exact-0 drift, fp-rate band)
if JAX_PLATFORMS=cpu python ci/perf_gate.py --fixture soak_drift >/dev/null; then
  echo "perf-gate soak_drift fixture did NOT trip the gate" >&2; exit 1
fi

echo "== bench sanity (tiny, gated on row-count-independent keys) =="
JAX_PLATFORMS=cpu python ci/perf_gate.py --run 100000

echo "CI smoke: OK"
