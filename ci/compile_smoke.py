"""CI smoke for the superstage compiler (compile/, exec/superstage.py):
run the TPC-DS quartet q3/q42/q52/q96 at tiny scale and assert

1. plan smoke — every carved plan passes the full verifier pass set
   including PV-STAGE, and the quartet's star-join plans actually carve
   (at least one TpuSuperstage with a join member);
2. flush budget — each warm carved query runs in at most 2 fused device
   round trips, and strictly fewer than its uncarved run;
3. determinism — carved results are row-identical (including order) to
   the eager superstage-off results;
4. the compile-scoped lint rules are clean on the compiler's own files
   (the layer that removes host syncs must not contain any);
5. cold start (compile/aot.py) — a fresh process against a cache dir
   seeded by an earlier process satisfies every q3 first-call from the
   persistent executable cache (zero new compiles) and its first q3
   lands within max(1.5x its own warm q3, half the unseeded child's
   first q3) — at tiny smoke scale the process-fixed IO/tracing floor
   dominates the warm run, so the second bound is the operative one.
"""
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.join(REPO_ROOT, "benchmarks"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import tpcds  # noqa: E402

from spark_rapids_tpu.analysis import lint as AL  # noqa: E402
from spark_rapids_tpu.analysis.plan_verify import verify_or_raise  # noqa: E402
from spark_rapids_tpu.api import TpuSession  # noqa: E402
from spark_rapids_tpu.columnar import pending  # noqa: E402
from spark_rapids_tpu.config import TpuConf  # noqa: E402
from spark_rapids_tpu.exec.superstage import TpuSuperstage  # noqa: E402
from spark_rapids_tpu.exec.tpu_join import TpuHashJoinBase  # noqa: E402

QUERIES = ("q3", "q42", "q52", "q96")
# Warm fused-round-trip budget per query.  q3 is the acceptance
# criterion (star-join collapses to ONE flush).  q96's second join
# BUILDS from the first join's output — a build table needs exact row
# counts, so that hand-off keeps its own resolve (docs/compile.md);
# tiny-scale data can also drop a build side under the speculative
# path's capacity gate, costing one extra exact barrier.
FLUSH_BUDGET = {"q3": 1, "q42": 2, "q52": 2, "q96": 3}


def _session(superstage: bool) -> TpuSession:
    return TpuSession(TpuConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.sql.superstage": superstage,
        "spark.rapids.tpu.sql.batchSizeRows": 1 << 22,
        "spark.rapids.tpu.sql.reader.batchSizeRows": 1 << 22,
    }))


def _stages(node):
    out = [node] if isinstance(node, TpuSuperstage) else []
    for c in node.children:
        out.extend(_stages(c))
    return out


# Child process for the cold-start stage: run q3 twice against a
# persistent cache dir, report per-run wall seconds + compile counts.
_COLD_CHILD = r"""
import json, os, sys, time
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, os.path.join(sys.argv[1], "benchmarks"))
import jax
jax.config.update("jax_platforms", "cpu")
import tpcds
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs import compile_watch

cache_dir, data_dir = sys.argv[2], sys.argv[3]
s = TpuSession(TpuConf({
    "spark.rapids.tpu.sql.enabled": True,
    "spark.rapids.tpu.sql.batchSizeRows": 1 << 22,
    "spark.rapids.tpu.sql.reader.batchSizeRows": 1 << 22,
    "spark.rapids.tpu.compile.aot.cacheDir": cache_dir,
}))
tpcds.register(s, data_dir)
sql = tpcds.QUERIES["q3"]
t0 = time.perf_counter()
first = s.sql(sql).collect()
t_first = time.perf_counter() - t0
t0 = time.perf_counter()
warm = s.sql(sql).collect()
t_warm = time.perf_counter() - t0
assert warm == first
recs = compile_watch.records_since(0)
print(json.dumps({
    "t_first_s": t_first, "t_warm_s": t_warm, "rows": len(first),
    "compiles": sum(1 for r in recs if r.get("origin") != "persistent"),
    "persistent_hits": compile_watch.persistent_hits(),
}))
"""


def _cold_child(cache_dir: str, data_dir: str) -> dict:
    # the XLA cache directory is the environment's to place
    # (compile/xla_cache.py): a deliberately cold one is handed to the
    # children through JAX_COMPILATION_CACHE_DIR; aot.cacheDir (argv[2])
    # only holds the manifest, kept in the same fresh directory
    out = subprocess.run(
        [sys.executable, "-c", _COLD_CHILD, REPO_ROOT, cache_dir,
         data_dir],
        capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir))
    assert out.returncode == 0, \
        f"cold-start child failed:\n{out.stderr[-2000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def _cold_start_stage(data_dir: str) -> None:
    """Stage 5: persistent-reuse acceptance across fresh processes."""
    cache_dir = os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "tpcds_compile_smoke",
        f"aot_cache_{os.getpid()}_{time.monotonic_ns()}")
    cold = _cold_child(cache_dir, data_dir)
    assert cold["compiles"] > 0, \
        f"seed child recorded no compiles: {cold}"
    assert os.path.exists(os.path.join(cache_dir, "aot_manifest.json")), \
        "seed child wrote no AOT manifest"
    warmed = _cold_child(cache_dir, data_dir)
    assert warmed["rows"] == cold["rows"]
    assert warmed["compiles"] == 0, \
        f"warmed-dir child still compiled: {warmed}"
    assert warmed["persistent_hits"] > 0, warmed
    # the acceptance ratio: a warmed cold process's FIRST q3 lands
    # within 1.5x warm once the query wall dominates process-fixed
    # costs (at bench scale, cold_vs_warm_ratio in BENCH_r*.json
    # tracks exactly that).  At this 0.002-scale smoke the warm run
    # is ~80ms while parquet IO + first-touch upload + jit TRACING
    # (which no executable cache can skip) cost ~1.5s per process, so
    # the tiny-scale proxy is the cold-start tax itself: the warmed
    # child must run its first q3 in at most half the seed child's —
    # the XLA-compile share is gone, proven exactly by compiles == 0
    # above
    budget = max(1.5 * warmed["t_warm_s"], 0.5 * cold["t_first_s"])
    assert warmed["t_first_s"] <= budget, \
        f"warmed cold-process q3 {warmed['t_first_s']:.3f}s exceeds " \
        f"budget {budget:.3f}s (warm {warmed['t_warm_s']:.3f}s, seed " \
        f"cold {cold['t_first_s']:.3f}s)"
    print(f"  cold-start: seed first={cold['t_first_s']:.2f}s "
          f"compiles={cold['compiles']}; warmed-dir "
          f"first={warmed['t_first_s']:.2f}s "
          f"warm={warmed['t_warm_s']:.2f}s "
          f"persistent_hits={warmed['persistent_hits']} "
          f"compiles=0")


def main():
    data_dir = os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "tpcds_compile_smoke", "sf")
    if not os.path.exists(os.path.join(data_dir, "store_sales.parquet")):
        tpcds.generate(data_dir, scale=0.002, seed=11)

    s_on = _session(True)
    s_off = _session(False)
    tpcds.register(s_on, data_dir)
    tpcds.register(s_off, data_dir)

    for q in QUERIES:
        sql = tpcds.QUERIES[q]
        # -- plan smoke: carved tree passes all five verifier passes
        phys = s_on._plan(s_on.sql(sql)._plan)
        verify_or_raise(phys)
        stages = _stages(phys)
        assert stages, f"{q}: no superstage carved"
        joins = [m for st in stages for m in st.members
                 if isinstance(m, TpuHashJoinBase)]
        assert joins, f"{q}: no join fused into any superstage"
        assert all(getattr(j, "_superstage", False) for j in joins), \
            f"{q}: carved join not armed for one-dispatch probing"

        # -- static flush prediction (PV-FLUSH): computed BEFORE any
        # execution, then asserted EXACTLY equal to the runtime
        # pending.FLUSH_COUNT delta of the warm run below
        from spark_rapids_tpu.analysis import predict_flushes
        pred_on = predict_flushes(phys, conf=s_on.conf)
        phys_off = s_off._plan(s_off.sql(sql)._plan)
        pred_off = predict_flushes(phys_off, conf=s_off.conf)

        # -- determinism + flush budget (warm: second run of each)
        rows_on = s_on.sql(sql).collect()
        f0 = pending.FLUSH_COUNT
        rows_on = s_on.sql(sql).collect()
        warm_on = pending.FLUSH_COUNT - f0

        rows_off = s_off.sql(sql).collect()
        f0 = pending.FLUSH_COUNT
        rows_off = s_off.sql(sql).collect()
        warm_off = pending.FLUSH_COUNT - f0

        assert rows_on == rows_off, f"{q}: superstage changed results"
        assert pred_on.expected(len(rows_on)) == warm_on, \
            f"{q}: PV-FLUSH predicted {pred_on.expected(len(rows_on))} " \
            f"warm flushes (superstage on), runtime took {warm_on}\n" \
            f"{pred_on.explain()}"
        assert pred_off.expected(len(rows_off)) == warm_off, \
            f"{q}: PV-FLUSH predicted " \
            f"{pred_off.expected(len(rows_off))} warm flushes " \
            f"(superstage off), runtime took {warm_off}\n" \
            f"{pred_off.explain()}"
        assert warm_on <= FLUSH_BUDGET[q], \
            f"{q}: warm carved run took {warm_on} flushes " \
            f"(budget {FLUSH_BUDGET[q]})"
        assert warm_on < warm_off, \
            f"{q}: carving did not reduce flushes " \
            f"(on={warm_on} off={warm_off})"
        # -- cross-plane doctor (obs/doctor.py): the acceptance sweep —
        # exactly one primary-bottleneck verdict per query, contribution
        # shares summing to 100, and every headroom bound equal to the
        # Amdahl bound of its timeline gap share, at zero extra flushes
        # (the warm_on delta above already ran with the doctor enabled)
        diag = s_on.last_query_diagnosis
        assert diag is not None, f"{q}: no doctor verdict"
        shares = diag.data["shares"]
        assert abs(sum(shares.values()) - 100.0) < 1e-6, \
            f"{q}: doctor shares sum to {sum(shares.values())}"
        assert diag.primary_cause in shares, q
        tl_gaps = s_on.last_query_timeline["gaps"]
        by_cause = {c["cause"]: c for c in diag.headroom}
        for cause, share in tl_gaps.items():
            if share <= 0:
                continue
            bound = by_cause[cause]["bound_x"]
            want = 1.0 / (1.0 - by_cause[cause]["share_pct"] / 100.0)
            assert abs(bound - want) < 1e-2, \
                f"{q}: {cause} headroom {bound} != Amdahl {want:.3f}"
        print(f"  {q}: rows={len(rows_on)} warm_flushes "
              f"on={warm_on} off={warm_off} "
              f"(predicted on={pred_on.expected(len(rows_on))} "
              f"off={pred_off.expected(len(rows_off))}) "
              f"stages={len(stages)} fused_joins={len(joins)} "
              f"doctor={diag.primary_cause}"
              f"@{diag.primary_share_pct:.1f}%")

    _cold_start_stage(data_dir)

    # -- compile-scoped lint clean on the compiler's own files
    findings = []
    for rel in ("spark_rapids_tpu/compile/lower.py",
                "spark_rapids_tpu/compile/carve.py",
                "spark_rapids_tpu/exec/superstage.py",
                "spark_rapids_tpu/compile/aot.py",
                "spark_rapids_tpu/service/warmup.py"):
        with open(os.path.join(REPO_ROOT, rel)) as f:
            src = f.read()
        findings += AL.lint_source(src, rel,
                                   scopes=AL._scopes_for(rel))
    assert findings == [], AL.format_findings(findings)

    print("compile smoke: OK")


if __name__ == "__main__":
    main()
