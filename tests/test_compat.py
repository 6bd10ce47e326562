"""Shim layer + compression codec + API-surface validation tests.

Reference patterns: ShimLoader version detection, TableCompressionCodec
round-trip, and api_validation/ (reflection audit of API parity).
"""
import numpy as np
import pytest

from spark_rapids_tpu.shims import detect_shim, get_shard_map, JaxShim
from spark_rapids_tpu.shuffle.compression import get_codec
from spark_rapids_tpu.memory.catalog import BufferCatalog, StorageTier
from spark_rapids_tpu.memory.spillable import SpillableBatch
from spark_rapids_tpu.columnar import ColumnarBatch


class TestShims:
    def test_one_provider_for_the_installed_jax(self):
        import jax
        import spark_rapids_tpu.shims as shims
        assert detect_shim() is JaxShim
        assert get_shard_map() is jax.shard_map
        # no provider for a JAX that is not installed
        providers = [n for n in vars(shims) if n.startswith("JaxShim")]
        assert providers == ["JaxShim"]

    def test_pyproject_pins_the_installed_jax(self):
        import os
        import jax
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "pyproject.toml")) as f:
            assert f'"jax=={jax.__version__}"' in f.read()

    def test_key_array(self):
        k = detect_shim().key_array(7)
        assert k is not None


class TestCompression:
    @pytest.mark.parametrize("name", ["none", "zlib"])
    def test_roundtrip(self, name):
        codec = get_codec(name)
        data = bytes(np.random.default_rng(0).integers(
            0, 255, 10000, dtype=np.uint8)) * 3
        comp = codec.compress(data)
        assert codec.decompress(comp, len(data)) == data
        if name == "zlib":
            assert len(comp) < len(data)

    def test_unknown_codec_raises(self):
        with pytest.raises(ValueError):
            get_codec("snappy9000")

    def test_compressed_disk_spill_roundtrip(self):
        cat = BufferCatalog.reset(spill_dir="/tmp/srt_test_spill",
                                  host_limit=1, compression="zlib")
        b = ColumnarBatch.from_pydict(
            {"a": list(range(200)), "s": [f"v{i % 7}" for i in range(200)]})
        expect = b.to_pydict()
        sb = SpillableBatch(b, catalog=cat)
        cat.spill_device_to_fit(cat.device_limit)
        assert cat._entries[sb.buffer_id].tier == StorageTier.DISK
        got = sb.materialize()
        assert got.to_pydict() == expect
        sb.close()


# The reference's api_validation module audits CPU-vs-GPU exec constructor
# parity via reflection; here we audit DataFrame API parity against the
# PySpark surface users migrate from.
PYSPARK_DATAFRAME_METHODS = [
    "select", "filter", "where", "withColumn", "withColumnRenamed", "drop",
    "groupBy", "agg", "join", "union", "unionAll", "distinct",
    "dropDuplicates", "sort", "orderBy", "limit", "repartition", "coalesce",
    "collect", "count", "show", "first", "head", "take", "cache", "persist",
    "toPandas", "explain", "schema", "columns", "write",
]

PYSPARK_FUNCTIONS = [
    "col", "lit", "sum", "count", "min", "max", "avg", "mean", "first",
    "last", "when", "coalesce", "isnull", "isnan", "sqrt", "exp", "log",
    "floor", "ceil", "abs", "round", "pow", "greatest", "least", "upper",
    "lower", "length", "trim", "ltrim", "rtrim", "substring", "concat",
    "md5", "year", "month", "dayofmonth", "quarter", "dayofweek", "hour",
    "minute", "second", "date_add", "date_sub", "datediff", "hash",
    "monotonically_increasing_id", "spark_partition_id", "rand",
    "row_number", "rank", "dense_rank", "lead", "lag",
]


class TestApiValidation:
    def test_dataframe_surface(self):
        from spark_rapids_tpu.api.dataframe import DataFrame
        missing = [m for m in PYSPARK_DATAFRAME_METHODS
                   if not hasattr(DataFrame, m)]
        assert not missing, f"DataFrame API gaps vs PySpark: {missing}"

    def test_functions_surface(self):
        from spark_rapids_tpu.api import functions as F
        missing = [m for m in PYSPARK_FUNCTIONS if not hasattr(F, m)]
        assert not missing, f"functions API gaps vs PySpark: {missing}"

    def test_column_surface(self):
        from spark_rapids_tpu.api.column import Col
        for m in ["alias", "cast", "isNull", "isNotNull", "isin",
                  "eqNullSafe", "like", "rlike", "startswith", "endswith",
                  "contains", "substr", "asc", "desc"]:
            assert hasattr(Col, m), f"Col missing {m}"
