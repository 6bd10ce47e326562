"""Negative-path tests for the speculative table-aggregation contract.

The sort-free bucket-table fast path dispatches speculatively and
verifies a device-side fit flag at the next flush barrier (the exchange,
the FINAL-aggregate merge, or — with deferred verification — the
consumer's own barrier: join phase A / session collect).  These tests
FORCE misfits at each barrier and assert the redo path reproduces the
CPU oracle exactly.

Construction: input partitions each hold a narrow key band (every
partial-aggregate batch FITS the table), but the bands are far apart, so
any post-shuffle reduce partition mixes bands and the FINAL merge core
MISFITS (key range >> tableSize) — exercising redo after a FINAL-mode
concat, through the deferred join barrier, and at root collect.
"""
import numpy as np
import pytest

from harness import assert_tpu_and_cpu_are_equal_collect

from spark_rapids_tpu.api import functions as F


BANDS = 4
KEYS_PER_BAND = 200        # < tableSize: each band alone FITS
BAND_STRIDE = 10_000_000   # band spacing: mixed bands MISFIT
TABLE_SIZE = 256
ROWS_PER_BAND = 8000       # batch capacity must reach tableSize for the
                           # table path to engage at all


def _banded_data(rows_per_band=ROWS_PER_BAND, seed=3):
    """Rows ordered band-by-band so partition i sees only band i."""
    rng = np.random.default_rng(seed)
    ks, vs = [], []
    for band in range(BANDS):
        base = band * BAND_STRIDE
        ks.append(base + rng.integers(0, KEYS_PER_BAND, rows_per_band))
        vs.append(rng.integers(-1000, 1000, rows_per_band))
    return {"k": np.concatenate(ks).astype(np.int64),
            "v": np.concatenate(vs).astype(np.float64)}


CONF = {
    # keep the table path on and small enough that mixed bands misfit
    "spark.rapids.tpu.sql.agg.tableSize": TABLE_SIZE,
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
}


def _agg_df(s):
    df = s.create_dataframe(_banded_data(), num_partitions=BANDS)
    return (df.group_by("k")
              .agg(F.sum("v").alias("sv"), F.count().alias("c"),
                   F.max("v").alias("mv")))


class TestSpeculativeMisfit:
    def test_banded_final_concat_is_exact(self):
        """Partial batches each fit their band; the FINAL merge over
        mixed post-shuffle bands runs the exact sort-merge core — the
        pipeline must reproduce the oracle with no misfit anywhere."""
        rows = assert_tpu_and_cpu_are_equal_collect(_agg_df, conf=CONF)
        assert len(rows) == BANDS * KEYS_PER_BAND

    def test_misfit_deferred_to_root_collect(self):
        """COMPLETE-mode aggregate at plan root with misfitting keys:
        the deferred fit flag resolves at session collect, whose
        resolve_speculative must swap in the exact redo."""
        def q(s):
            df = s.create_dataframe(_banded_data(), num_partitions=1)
            return (df.group_by("k")
                      .agg(F.sum("v").alias("sv"), F.count().alias("c")))
        rows = assert_tpu_and_cpu_are_equal_collect(q, conf=CONF)
        assert len(rows) == BANDS * KEYS_PER_BAND

    def test_misfit_through_deferred_join_barrier(self):
        """A COMPLETE-mode aggregate (single input partition, no
        exchange) speculates via the table path, MISFITS (key range >>
        tableSize), and defers its fit flag to the join's phase-A
        flush; the redo chain must recompute the aggregate + finalize
        exactly there, before any probe output is exposed."""
        def q(s):
            data = _banded_data()    # all bands in ONE partition: misfit
            df = s.create_dataframe(data, num_partitions=1)
            agg = (df.group_by("k")
                     .agg(F.sum("v").alias("sv"), F.count().alias("c"),
                          F.max("v").alias("mv")))
            dim_keys = np.concatenate(
                [b * BAND_STRIDE + np.arange(KEYS_PER_BAND)
                 for b in range(BANDS)]).astype(np.int64)
            dim = s.create_dataframe({
                "dk": dim_keys,
                "w": np.arange(len(dim_keys)).astype(np.float64)})
            j = agg.join(dim, agg["k"] == dim["dk"], "inner")
            return j.select(F.col("k"), F.col("sv"), F.col("c"),
                            (F.col("mv") + F.col("w")).alias("mw"))
        rows = assert_tpu_and_cpu_are_equal_collect(q, conf=CONF)
        assert len(rows) == BANDS * KEYS_PER_BAND

    def test_fitting_complete_agg_through_join(self):
        """Same shape but FITTING keys: the deferred flag verifies OK at
        the join barrier and no redo runs (the fast path stays fast and
        correct)."""
        def q(s):
            rng = np.random.default_rng(5)
            df = s.create_dataframe({
                "k": rng.integers(0, 100, 9000).astype(np.int64),
                "v": rng.standard_normal(9000)}, num_partitions=1)
            agg = df.group_by("k").agg(F.sum("v").alias("sv"))
            dim = s.create_dataframe({
                "dk": np.arange(100, dtype=np.int64),
                "w": np.arange(100).astype(np.float64)})
            j = agg.join(dim, agg["k"] == dim["dk"], "inner")
            return j.select(F.col("k"), (F.col("sv") * F.col("w"))
                            .alias("sw"))
        rows = assert_tpu_and_cpu_are_equal_collect(q, conf=CONF)
        assert len(rows) == 100

    def test_misfit_through_exchange_and_aqe(self):
        """Misfit partials crossing a shuffle with AQE enabled: the
        exchange's verify-at-flush + any AQE re-plan must still produce
        oracle rows."""
        def q(s):
            df = s.create_dataframe(_banded_data(), num_partitions=BANDS)
            agg = (df.group_by("k").agg(F.sum("v").alias("sv")))
            return agg.filter(F.col("sv") > -10_000_000)
        conf = dict(CONF)
        conf["spark.rapids.tpu.sql.adaptive.enabled"] = True
        rows = assert_tpu_and_cpu_are_equal_collect(q, conf=conf)
        assert len(rows) >= 1

    def test_all_batches_misfit_tiny_table(self):
        """tableSize so small even one band misfits: every batch redoes
        on the sort path end-to-end."""
        conf = dict(CONF)
        conf["spark.rapids.tpu.sql.agg.tableSize"] = 16
        rows = assert_tpu_and_cpu_are_equal_collect(_agg_df, conf=conf)
        assert len(rows) == BANDS * KEYS_PER_BAND


class TestCompactionMisfitUnderProject:
    """Round-5 regression (TPC-DS q97 at SF1): a COMPLETE/FINAL
    aggregate whose group count exceeds the speculative compaction cap
    must NOT hand the truncated batch to a consumer that drops the fit
    flag (a Project re-evaluates columns into fresh batches).  The
    aggregate verifies its own merge output unless the planner marked
    the consumer as a deferred-verify barrier."""

    def test_high_cardinality_agg_under_project(self):
        import numpy as np
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.api import functions as F
        rng = np.random.default_rng(9)
        n = 4000
        data = {"k": rng.integers(0, 1500, n).astype(np.int64),
                "v": rng.integers(0, 100, n).astype(np.int64)}

        def q(s):
            df = s.create_dataframe(data, num_partitions=1)
            agg = df.group_by("k").agg(F.sum("v").alias("sv"))
            # projection consumer: drops any speculative flag
            proj = agg.select((F.col("sv") * 2).alias("d"))
            return proj.agg(F.sum("d").alias("t"), F.count().alias("c"))
        assert_tpu_and_cpu_are_equal_collect(
            q, conf={"spark.rapids.tpu.sql.agg.speculativeCompactRows":
                     64})
