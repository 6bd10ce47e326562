"""The TPC-DS store-channel star schema of ``datagen/tpcds.py`` (the
same tables, rows, columns and streams: ``ROWS_PER_SF``, ``row_counts``
and ``generate`` are that module's) for the ROLLUP / window-function
configuration ``tpcds_sf1_olap``, behind one check: it ends a run,
cleanly and before any data is written, on an engine that cannot give
that configuration a result.

The parent of PR 34 cannot, for two reasons read on the chip and on the
host (PERF.md section 6, PR 34).  (1) Its planner loses q89's second
category / class triple: ``plan/logical_opt.py`` factored the two OR
arms' ``i_category in (...)`` and ``i_class in (...)`` out as common
because it compared them by ``repr``, which leaves the value lists out,
so every run of the cell would come out not ``correct`` (431 wrong
cells at a hundredth of the size).  (2) Its first run on an empty
compile cache compiles for 914 s (669 programs; 61 s of them the eager
window's ``jit_argsort``), 965 s for the cold pass alone and about
1,100 s for the run, at the edge of the 1,200 s a run may take; the
driver refused PR 32 once for a parent still running at that limit.
The driver's contract asks a parent that cannot run a new configuration
to fail with an exit code other than 0, soon.  A generator used without
the engine generates."""
from .tpcds import ROWS_PER_SF, TABLES, row_counts  # noqa: F401
from . import tpcds


def refuse_engine_before_pr34() -> None:
    import importlib.util
    if importlib.util.find_spec("spark_rapids_tpu") is None:
        return
    from spark_rapids_tpu.plan import logical_opt
    if importlib.util.find_spec("spark_rapids_tpu.kernels.window") is None \
            or not hasattr(logical_opt, "_same_as"):
        raise SystemExit(
            "chipbench/datagen/tpcds_olap.py: this engine runs its window "
            "operator eagerly (jnp.cumsum, jnp.argsort) and factors q89's "
            "two category / class triples into one; a run of schema "
            "tpcds_olap on it is not correct and may not end inside a "
            "run's time limit")


def generate(data_dir: str, scale: float, seed: int, tables) -> dict:
    refuse_engine_before_pr34()
    return tpcds.generate(data_dir, scale, seed, tables)
