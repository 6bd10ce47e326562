"""Kernel-level tests: sort/group/join cores vs numpy oracles.

Pattern parity: reference unit suites like HashAggregatesSuite/CastOpSuite
compare GPU results against CPU Spark; here the oracle is numpy.
"""
import numpy as np
import jax.numpy as jnp

from spark_rapids_tpu.columnar import Column, dtypes as T
from spark_rapids_tpu.kernels import canon, sort, aggregate, join, basic
from spark_rapids_tpu.kernels import strings as skern


def _col(vals, dtype=None):
    return Column.from_numpy(vals, dtype=dtype)


class TestCanon:
    def test_int_order(self):
        col = _col([5, -3, 0, None, 7], dtype=T.INT64)
        words = canon.column_key_words(col, 5)
        perm = np.asarray(sort.sort_permutation(words))[:5]
        got = [col.to_pylist(5)[i] for i in perm]
        assert got == [None, -3, 0, 5, 7]  # nulls first default

    def test_float_order_with_nan(self):
        col = _col(np.array([1.0, -np.inf, np.nan, -0.0, np.inf]))
        words = canon.column_key_words(col, 5)
        perm = np.asarray(sort.sort_permutation(words))[:5]
        vals = np.array([1.0, -np.inf, np.nan, -0.0, np.inf])[perm]
        assert vals[0] == -np.inf and np.isnan(vals[-1])  # NaN greatest

    def test_descending(self):
        col = _col([1, 3, 2], dtype=T.INT64)
        words = canon.column_key_words(col, 3, descending=True,
                                       nulls_last=True)
        perm = np.asarray(sort.sort_permutation(words))[:3]
        assert [[1, 3, 2][i] for i in perm] == [3, 2, 1]

    def test_string_order(self):
        vals = ["banana", "apple", None, "apricot", "b", ""]
        col = _col(vals, dtype=T.STRING)
        words = canon.column_key_words(col, 6)
        perm = np.asarray(sort.sort_permutation(words))[:6]
        got = [vals[i] for i in perm]
        assert got == [None, "", "apple", "apricot", "b", "banana"]

    def test_long_string_order(self):
        vals = ["x" * 30 + "a", "x" * 30 + "b", "x" * 9]
        col = _col(vals, dtype=T.STRING)
        words = canon.column_key_words(col, 3)
        perm = np.asarray(sort.sort_permutation(words))[:3]
        assert [vals[i] for i in perm] == ["x" * 9, "x" * 30 + "a",
                                           "x" * 30 + "b"]


class TestGroupBy:
    def test_sum_count(self):
        keys = _col([1, 2, 1, None, 2, 1], dtype=T.INT64)
        vals = _col([10.0, 20.0, 30.0, 40.0, None, 50.0], dtype=T.FLOAT64)
        words = canon.batch_key_words([keys], 6)
        plan = aggregate.groupby_plan(words)
        assert int(plan.num_groups) == 3  # null is its own group
        sums = np.asarray(aggregate.seg_sum(plan, vals.data, vals.validity))
        counts = np.asarray(aggregate.seg_count(plan, vals.validity))
        reps = np.asarray(plan.rep_indices)[:3]
        key_vals = [keys.to_pylist(6)[i] for i in reps]
        got = dict(zip(key_vals, zip(sums[:3], counts[:3])))
        assert got[None] == (40.0, 1)
        assert got[1] == (90.0, 3)
        assert got[2] == (20.0, 1)

    def test_min_max(self, rng):
        n = 500
        k = rng.integers(0, 20, n)
        v = rng.integers(-1000, 1000, n)
        keys = _col(k, dtype=T.INT64)
        vals = _col(v, dtype=T.INT64)
        words = canon.batch_key_words([keys], n)
        plan = aggregate.groupby_plan(words)
        g = int(plan.num_groups)
        mins = np.asarray(aggregate.seg_min(plan, vals.data, vals.validity))[:g]
        maxs = np.asarray(aggregate.seg_max(plan, vals.data, vals.validity))[:g]
        reps = np.asarray(plan.rep_indices)[:g]
        for i, r in enumerate(reps):
            kk = k[r]
            assert mins[i] == v[k == kk].min()
            assert maxs[i] == v[k == kk].max()

    def test_multi_key(self):
        k1 = _col([1, 1, 2, 2], dtype=T.INT64)
        k2 = _col(["a", "b", "a", "a"], dtype=T.STRING)
        words = canon.batch_key_words([k1, k2], 4)
        plan = aggregate.groupby_plan(words)
        assert int(plan.num_groups) == 3


class TestJoin:
    def test_inner_basic(self):
        bk = _col([1, 2, 2, 3], dtype=T.INT64)
        pk = _col([2, 4, 1, 2], dtype=T.INT64)
        bw = canon.batch_key_words([bk], 4)
        pw = canon.batch_key_words([pk], 4)
        bt = join.build(bw)
        jc = join.probe_counts(bt, pw, 4)
        counts = np.asarray(jc.counts)[:4]
        assert list(counts) == [2, 0, 1, 2]
        total = join.total_matches(jc.counts)
        assert total == 5
        p_idx, b_idx, live, tot = join.join_expand_matches(
            jc.lo, jc.counts, bt.perm, 8)
        pairs = sorted((int(p), int(bk.to_pylist(4)[b]))
                       for p, b, l in zip(p_idx, b_idx, live) if l)
        assert pairs == [(0, 2), (0, 2), (2, 1), (3, 2), (3, 2)]

    def test_null_keys_dont_match(self):
        bk = _col([1, None], dtype=T.INT64)
        pk = _col([None, 1], dtype=T.INT64)
        bt = join.build(canon.batch_key_words([bk], 2))
        jc = join.probe_counts(bt, canon.batch_key_words([pk], 2), 2)
        assert list(np.asarray(jc.counts)[:2]) == [0, 1]

    def test_null_safe_join(self):
        bk = _col([1, None], dtype=T.INT64)
        pk = _col([None, 1], dtype=T.INT64)
        bt = join.build(canon.batch_key_words([bk], 2))
        jc = join.probe_counts(bt, canon.batch_key_words([pk], 2), 2,
                               null_equals_null=True)
        assert list(np.asarray(jc.counts)[:2]) == [1, 1]

    def test_string_join(self):
        bk = _col(["x", "yy", "zzz"], dtype=T.STRING)
        pk = _col(["yy", "nope", "x"], dtype=T.STRING)
        # join requires identical word counts: build both against the
        # unified max width via shared canon call on equal-capacity cols
        bw = canon.batch_key_words([bk], 3)
        pw = canon.batch_key_words([pk], 3)
        assert len(bw) == len(pw)
        bt = join.build(bw)
        jc = join.probe_counts(bt, pw, 3)
        assert list(np.asarray(jc.counts)[:3]) == [1, 0, 1]

    def test_large_random_inner(self, rng):
        n, m = 300, 400
        bkv = rng.integers(0, 50, n)
        pkv = rng.integers(0, 60, m)
        bt = join.build(canon.batch_key_words([_col(bkv, dtype=T.INT64)], n))
        jc = join.probe_counts(
            bt, canon.batch_key_words([_col(pkv, dtype=T.INT64)], m), m)
        counts = np.asarray(jc.counts)[:m]
        expect = np.array([(bkv == x).sum() for x in pkv])
        assert (counts == expect).all()


class TestStrings:
    def test_upper_lower(self):
        col = _col(["Hello", "WORLD"], dtype=T.STRING)
        assert skern.upper(col).to_pylist(2) == ["HELLO", "WORLD"]
        assert skern.lower(col).to_pylist(2) == ["hello", "world"]

    def test_substring(self):
        col = _col(["hello", "ab", ""], dtype=T.STRING)
        out = skern.substring(col, 2, 3)
        assert out.to_pylist(3) == ["ell", "b", ""]

    def test_char_length_utf8(self):
        col = _col(["abc", "wörld", ""], dtype=T.STRING)
        lens = np.asarray(skern.char_length(col))[:3]
        assert list(lens) == [3, 5, 0]

    def test_contains_starts_ends(self):
        col = _col(["foobar", "barfoo", "baz"], dtype=T.STRING)
        assert list(np.asarray(skern.contains(col, b"foo"))[:3]) == [
            True, True, False]
        assert list(np.asarray(skern.starts_with(col, b"foo"))[:3]) == [
            True, False, False]
        assert list(np.asarray(skern.ends_with(col, b"foo"))[:3]) == [
            False, True, False]


class TestBasic:
    def test_compact_indices(self):
        mask = jnp.array([True, False, True, False, True, False, False, False])
        idx, cnt = basic.filter_compact_indices(mask, 5)
        assert int(cnt) == 3
        assert list(np.asarray(idx))[:3] == [0, 2, 4]

    def test_hash_partition_stable(self):
        col = _col(np.arange(100), dtype=T.INT64)
        words = canon.value_words(col, 100)
        h = basic.hash_words(words)
        parts = np.asarray(basic.hash_to_partition(h, 8))
        assert parts.min() >= 0 and parts.max() < 8
        # deterministic
        h2 = basic.hash_words(canon.value_words(col, 100))
        assert (np.asarray(h) == np.asarray(h2)).all()


class TestTableGroupby:
    """Sort-free bucket-table group-by kernels (kernels/aggregate.py
    table_bucket / table_reduce / table_compact)."""

    def test_table_bucket_single_key(self):
        import jax.numpy as jnp
        import numpy as np
        from spark_rapids_tpu.kernels import aggregate as agg_k
        k = jnp.asarray(np.array([5, 7, 5, 9, 7, 5], np.int64))
        w = (k.astype(jnp.int64).astype(jnp.uint64) ^
             jnp.uint64(1 << 63))
        valid = jnp.array([True, True, True, True, True, False])
        live = jnp.ones(6, bool)
        bucket, fit, mins, cards = agg_k.table_bucket(
            [w], [valid], live, 64)
        b = np.asarray(bucket)
        assert bool(fit)
        # same keys share buckets; invalid row gets the null digit 0
        assert b[0] == b[2] == b[5 - 5]
        assert b[1] == b[4]
        assert b[5] == 0  # null digit (valid=False, live=True)

    def test_table_bucket_overflow_sets_fit_false(self):
        import jax.numpy as jnp
        import numpy as np
        from spark_rapids_tpu.kernels import aggregate as agg_k
        k = jnp.asarray(np.array([0, 10**12], np.int64))
        w = (k.astype(jnp.uint64)) ^ jnp.uint64(1 << 63)
        valid = jnp.ones(2, bool)
        bucket, fit, _, _ = agg_k.table_bucket(
            [w], [valid], jnp.ones(2, bool), 64)
        assert not bool(fit)

    def test_table_reduce_scatter_and_compact(self):
        import jax.numpy as jnp
        import numpy as np
        from spark_rapids_tpu.kernels import aggregate as agg_k
        from spark_rapids_tpu.kernels.aggregate import table_reduce
        n, T = 4096, 64
        rng = np.random.default_rng(3)
        b = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
        v = jnp.asarray(rng.random(n).astype(np.float32))
        ones = jnp.ones(n, jnp.float32)
        sums, maxs = table_reduce(
            b, [ones, v], [jnp.where(v > 0, v, -jnp.inf)], T)
        ref_c = np.zeros(T)
        np.add.at(ref_c, np.asarray(b), 1.0)
        ref_s = np.zeros(T)
        np.add.at(ref_s, np.asarray(b), np.asarray(v, np.float64))
        ref_m = np.full(T, -np.inf)
        np.maximum.at(ref_m, np.asarray(b), np.asarray(v))
        assert np.allclose(np.asarray(sums[0]), ref_c)
        assert np.allclose(np.asarray(sums[1]), ref_s, rtol=1e-5)
        got_m = np.asarray(maxs[0])
        assert np.allclose(np.where(np.isfinite(got_m), got_m, -1),
                           np.where(np.isfinite(ref_m), ref_m, -1))
        present, order, ng = agg_k.table_compact(sums[0], T)
        assert int(ng) == 10
        assert np.array_equal(np.asarray(order)[:10], np.arange(10))

    def test_variable_float_agg_conf_off_matches_exact(self):
        import numpy as np
        from tests.harness import (assert_tpu_and_cpu_are_equal_collect)
        from spark_rapids_tpu.api import functions as F
        rng = np.random.default_rng(11)
        n = 5000
        data = {"k": rng.integers(0, 20, n).astype(np.int64),
                "x": rng.random(n)}

        def q(s):
            df = s.create_dataframe(data, num_partitions=2)
            return df.group_by("k").agg(F.sum("x").alias("sx"),
                                        F.min("x").alias("mn"))
        # exact mode: disable f32 accumulation -> bit-exact vs CPU
        assert_tpu_and_cpu_are_equal_collect(
            q, conf={"spark.rapids.tpu.sql.variableFloatAgg.enabled":
                     False})


class TestExactTableLanes:
    """Exact-float table-path lanes (fsum64/favg64/fminmax64): 8-bit
    chunk sums + two-stage u32 min/max, engaged when capacity >= table
    size.  Compared against the pyarrow oracle at tight tolerance."""

    def _q(self, data, conf=None):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.api import functions as F

        def q(s):
            df = s.create_dataframe(data, num_partitions=2)
            return df.group_by("k").agg(
                F.sum("x").alias("sx"), F.avg("x").alias("ax"),
                F.min("x").alias("mn"), F.max("x").alias("mx"),
                F.count().alias("c"))
        assert_tpu_and_cpu_are_equal_collect(q, conf=conf or {})

    def test_exact_float_agg_table_path(self):
        rng = np.random.default_rng(3)
        n = 6000  # capacity 8192 >= table 4096: table path engages
        self._q({"k": rng.integers(0, 50, n).astype(np.int64),
                 "x": rng.standard_normal(n) * 1e6})

    def test_exact_float_agg_negatives_and_zeros(self):
        rng = np.random.default_rng(4)
        n = 5000
        x = rng.standard_normal(n)
        x[::17] = 0.0
        x[1::17] = -0.0
        self._q({"k": rng.integers(0, 20, n).astype(np.int64), "x": x})

    def test_exact_float_agg_specials(self):
        rng = np.random.default_rng(5)
        n = 5000
        x = rng.standard_normal(n)
        x[100] = np.inf
        x[200] = -np.inf
        x[300] = np.nan
        k = rng.integers(0, 8, n).astype(np.int64)
        # isolate specials per group so inf/nan semantics are exercised
        k[100], k[200], k[300] = 1, 2, 3
        self._q({"k": k, "x": x})

    def test_exact_float_agg_wide_spread_falls_back(self):
        # exponent spread > 2^63: the fit flag must route the batch to
        # the sort path and results stay correct
        rng = np.random.default_rng(6)
        n = 5000
        x = np.ldexp(rng.standard_normal(n), rng.integers(-80, 80, n))
        self._q({"k": rng.integers(0, 10, n).astype(np.int64), "x": x})

    def test_exact_float_agg_tiny_magnitudes(self):
        rng = np.random.default_rng(7)
        n = 5000
        x = rng.standard_normal(n) * 1e-30
        self._q({"k": rng.integers(0, 10, n).astype(np.int64), "x": x})
