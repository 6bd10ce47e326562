"""A join's matches are expanded in one pass over the output rows
(``kernels/join.py`` ``join_expand_matches``): every probe row scatters
a +1 at its first output row and a running sum hands each output row
its probe row; its shift ``lo - excl`` comes from a second scatter (of
the shift's step) and running sum, or, with no fewer probe rows than
output rows, from a gather by the probe row; one gather (``perm``) an output
row ends it.  Each case holds the program against a plain numpy
``np.repeat`` expansion and against the program it replaced (a binary
search an output row, kept here as the oracle), on both sides of that
choice; a structural case counts the per-row gathers and the scatters
in the jaxpr; the last read the ``join.expand.*`` counters and rehearse
the step-0 benchmark."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the jaxpr walker and the counter reader of the sibling files
from test_agg_string_keys_fused import _equations
from test_str_pack_bound import _trace_clean, pack_counts  # noqa: F401
from spark_rapids_tpu.kernels import join as jkern
from spark_rapids_tpu.kernels.basic import prefix_sum


@functools.partial(jax.jit, static_argnames=("out_cap",))
def searchsorted_reference(lo, counts, perm, out_cap: int):
    """``join_expand_matches`` as it was up to PR 34: the oracle."""
    incl = prefix_sum(counts.astype(jnp.int64))
    excl = incl - counts
    total = incl[-1]
    t = jnp.arange(out_cap, dtype=jnp.int64)
    p = jnp.searchsorted(incl, t, side="right").astype(jnp.int32)
    pc = jnp.clip(p, 0, counts.shape[0] - 1)
    build_pos = jnp.take(lo, pc) + (t - jnp.take(excl, pc)).astype(jnp.int32)
    build_pos = jnp.clip(build_pos, 0, perm.shape[0] - 1)
    build_idx = jnp.take(perm, build_pos)
    live = t < total
    return pc, build_idx, live, total


def numpy_expansion(lo, counts, perm, out_cap):
    """(probe_idx, build_idx, total) of the first ``out_cap`` output
    rows: probe row ``r`` repeated ``counts[r]`` times, beside it the
    build rows ``perm[lo[r]], perm[lo[r] + 1], ...``."""
    counts = counts.astype(np.int64)
    total = int(counts.sum())
    keep = min(total, out_cap)
    probe = np.repeat(np.arange(len(counts)), counts)[:keep]
    excl = np.cumsum(counts) - counts
    pos = lo.astype(np.int64)[probe] + np.arange(keep) - excl[probe]
    return probe, perm[np.clip(pos, 0, len(perm) - 1)], total


#: pattern -> counts (int64) of ``n`` probe slots, told the output capacity
def _all_zero(rng, n, out_cap):
    return np.zeros(n, np.int64)


def _all_one(rng, n, out_cap):
    return np.ones(n, np.int64)


def _leading_zero_run(rng, n, out_cap):
    counts = rng.integers(0, 3, n)
    counts[:max(1, n // 3)] = 0
    return counts


def _trailing_zero_run(rng, n, out_cap):
    counts = rng.integers(0, 3, n)
    counts[n - max(1, n // 3):] = 0
    return counts


def _skew(rng, n, out_cap):
    counts = np.zeros(n, np.int64)
    heavy = rng.choice(n, max(1, n // 50), replace=False)
    counts[heavy] = rng.integers(1, max(2, out_cap // len(heavy)), len(heavy))
    return counts


def _fills_the_capacity(rng, n, out_cap):
    """``total == out_cap``: no dead lane."""
    counts = rng.multinomial(out_cap, np.full(n, 1.0 / n))
    return counts.astype(np.int64)


def _over_the_capacity(rng, n, out_cap):
    """``total > out_cap``: the rows past the capacity are dropped by
    the scatter and ``total`` still counts them."""
    counts = rng.multinomial(2 * out_cap + 3, np.full(n, 1.0 / n))
    return counts.astype(np.int64)


def _zero_past_num_rows(rng, n, out_cap):
    """The engine's own shape: ``counts`` zero on every dead probe
    slot, whatever ``lo`` holds there."""
    counts = rng.integers(0, 4, n)
    counts[int(rng.integers(0, n)) + 1:] = 0
    return counts


PATTERNS = {
    "all_zero": _all_zero,
    "all_one": _all_one,
    "leading_zero_run": _leading_zero_run,
    "trailing_zero_run": _trailing_zero_run,
    "skew": _skew,
    "total_equals_out_cap": _fills_the_capacity,
    "total_over_out_cap": _over_the_capacity,
    "zero_past_num_rows": _zero_past_num_rows,
}


def operands(pattern, n, out_cap, seed=0, sorted_lo=True):
    """Host (lo, counts, perm) of one launch against a build of 97 rows
    (not a power of two, so a clip that is off by one shows)."""
    rng = np.random.default_rng([seed, n, out_cap])
    build = 97
    counts = PATTERNS[pattern](rng, n, out_cap).astype(np.int32)
    # a run that reaches past the build's end reads its last row, in
    # all three expansions alike (a probe never makes one)
    lo = rng.integers(0, build, n)
    if sorted_lo:
        lo = np.sort(lo)
    perm = rng.permutation(build).astype(np.int32)
    return lo.astype(np.int32), counts, perm


def check(lo, counts, perm, out_cap):
    got = jkern.join_expand_matches(
        jnp.asarray(lo), jnp.asarray(counts), jnp.asarray(perm), out_cap)
    old = searchsorted_reference(
        jnp.asarray(lo), jnp.asarray(counts), jnp.asarray(perm), out_cap)
    probe, build_idx, live, total = (np.asarray(a) for a in got)
    want_probe, want_build, want_total = numpy_expansion(
        lo, counts, perm, out_cap)
    keep = len(want_probe)
    # total: exact, int64, also past the capacity
    assert total.dtype == np.int64 and int(total) == want_total
    assert int(old[3]) == want_total
    # live: the first min(total, out_cap) lanes
    assert live.dtype == np.bool_
    np.testing.assert_array_equal(live, np.arange(out_cap) < want_total)
    np.testing.assert_array_equal(live, np.asarray(old[2]))
    # both maps: numpy's and the old program's on every live lane
    assert probe.dtype == np.int32 and build_idx.dtype == perm.dtype
    np.testing.assert_array_equal(probe[:keep], want_probe)
    np.testing.assert_array_equal(build_idx[:keep], want_build)
    np.testing.assert_array_equal(probe[:keep], np.asarray(old[0])[:keep])
    np.testing.assert_array_equal(build_idx[:keep],
                                  np.asarray(old[1])[:keep])
    # in range on the dead lanes: callers gather before they mask
    assert probe.min(initial=0) >= 0 and probe.max(initial=0) < len(counts)
    assert set(np.unique(build_idx)) <= set(perm.tolist())


@pytest.mark.parametrize("out_cap", [64, 256])
@pytest.mark.parametrize("n", [1, 64, 300])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_same_maps_as_numpy_and_as_the_old_program(pattern, n, out_cap):
    check(*operands(pattern, n, out_cap), out_cap)


@pytest.mark.parametrize("n,out_cap", [(128, 512), (512, 128)])
@pytest.mark.parametrize("pattern", ["skew", "zero_past_num_rows",
                                     "total_over_out_cap"])
@pytest.mark.parametrize("seed", range(3))
def test_unsorted_lo(pattern, seed, n, out_cap):
    """The window's ``collect_*`` passes ``c_lo``, which follows the
    frames and not the rows: the step of the shift is signed."""
    lo, counts, perm = operands(pattern, n, out_cap, seed, sorted_lo=False)
    assert (np.diff(lo) < 0).any()
    check(lo, counts, perm, out_cap)


def test_a_total_past_int32_is_exact_and_wraps_nothing_in():
    """2^13 rows of 2^20 matches: ``excl`` passes 2^31 at row 2,049
    and 2^32 at row 4,097, where an int32 index would come round to
    lane 5.  Those rows are dropped, not wrapped into the capacity, and
    the total is exact."""
    n, out_cap = 1 << 13, 1 << 10
    counts = np.full(n, 1 << 20, np.int32)
    counts[0] = 5                          # so two rows share the capacity
    lo = np.arange(n, dtype=np.int32) % 7
    perm = np.arange(1 << 20, dtype=np.int32)[::-1].copy()
    probe, build_idx, live, total = (np.asarray(a) for a in (
        jkern.join_expand_matches(jnp.asarray(lo), jnp.asarray(counts),
                                  jnp.asarray(perm), out_cap)))
    assert int(total) == 5 + (n - 1) * (1 << 20) > 1 << 32
    assert live.all()
    want_probe = np.r_[np.zeros(5, np.int32), np.ones(out_cap - 5, np.int32)]
    np.testing.assert_array_equal(probe, want_probe)
    want_pos = np.r_[np.arange(5), 1 + np.arange(out_cap - 5)]
    np.testing.assert_array_equal(build_idx, perm[want_pos])


@pytest.mark.parametrize("n,out_cap,scatters,gathers", [
    # fewer probe rows than output rows: the +1 and the shift's step
    # scattered, ONE gather an output row (perm)
    (4096, 1 << 14, 2, ["perm"]),
    # as many or more: the +1 scattered, the shift gathered by the
    # probe row
    (4096, 4096, 1, ["shift", "perm"]),
    (1 << 14, 4096, 1, ["shift", "perm"]),
])
def test_the_indices_an_output_row_and_no_search(n, out_cap, scatters,
                                                 gathers):
    build = 1000
    jaxpr = jax.make_jaxpr(
        lambda lo, c, p: jkern.join_expand_matches(lo, c, p, out_cap))(
        jax.ShapeDtypeStruct((n,), np.int32),
        jax.ShapeDtypeStruct((n,), np.int32),
        jax.ShapeDtypeStruct((build,), np.int32)).jaxpr
    eqns = list(_equations(jaxpr))
    names = {e.primitive.name for e in eqns}
    # the search was a loop of dependent gathers an output row; a
    # cumsum compiles for 32 s at 2^20 on the chip (PR 32)
    assert not names & {"while", "scan", "sort", "cumsum", "reduce_window",
                        "reduce_window_sum"}
    gathered = [e for e in eqns if e.primitive.name == "gather"]
    assert all(e.outvars[0].aval.shape == (out_cap,) for e in gathered)
    assert [e.invars[0].aval.shape for e in gathered] == [
        {"shift": (n,), "perm": (build,)}[g] for g in gathers]
    scattered = [e for e in eqns
                 if e.primitive.name in ("scatter-add", "scatter_add")]
    # over the probe rows, in row order, into the output lanes
    assert [e.invars[2].aval.shape for e in scattered] == [(n,)] * scatters
    assert all(e.params["indices_are_sorted"] for e in scattered)
    assert all(e.invars[0].aval.shape == (out_cap,) for e in scattered)


def expand_counts():
    """The program's launch counters (its host ns left out, outside any
    operator: ``@-``) and the probe rows it scatters."""
    got = pack_counts("launch.join_expand_matches@")
    got.update(pack_counts("lanes.join_expand_matches@"))
    got.update(pack_counts("join.expand."))
    return got


def test_counters_read_launches_probe_rows_and_out_lanes():
    lo, counts, perm = (jnp.asarray(a) for a in operands("skew", 64, 256))
    assert expand_counts() == {}
    jkern.join_expand_matches(lo, counts, perm, 256)
    assert expand_counts() == {"launch.join_expand_matches@-": 1,
                               "join.expand.probe_rows": 64,
                               "lanes.join_expand_matches@-": 256}
    jkern.join_expand_matches(lo, counts, perm, out_cap=1024)
    assert expand_counts() == {"launch.join_expand_matches@-": 2,
                               "join.expand.probe_rows": 128,
                               "lanes.join_expand_matches@-": 1280}
    assert pack_counts("launch_ns.join_expand_matches@-")[
        "launch_ns.join_expand_matches@-"] > 0


def test_counters_count_nothing_under_a_jit_trace():
    host = operands("skew", 64, 256)

    @jax.jit
    def outer(lo, counts, perm):
        return jkern.join_expand_matches(lo, counts, perm, 256)

    got = outer(*(jnp.asarray(a) for a in host))
    assert expand_counts() == {}
    want_probe, want_build, want_total = numpy_expansion(*host, 256)
    assert int(got[3]) == want_total
    np.testing.assert_array_equal(np.asarray(got[0])[:len(want_probe)],
                                  want_probe)


def test_the_program_keeps_its_name():
    """``breakdown.device_ops`` and ``join_device_ms_per_query`` read
    the program by this name."""
    lowered = jkern.join_expand_matches.__wrapped__.lower(
        jax.ShapeDtypeStruct((16,), np.int32),
        jax.ShapeDtypeStruct((16,), np.int32),
        jax.ShapeDtypeStruct((8,), np.int32), out_cap=32)
    assert "@jit_join_expand_matches" in lowered.as_text()


def test_the_step_0_benchmark_rehearses_every_variant(tmp_path, capsys):
    """``benchmarks/join_expand_chip.py --rehearse-cpu`` at its smallest
    shape: every variant's maps are numpy's, no line carries a reading
    under a device's name, and off a TPU it refuses without the flag."""
    import importlib.util
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "join_expand_chip.py")
    spec = importlib.util.spec_from_file_location("join_expand_chip", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    out = tmp_path / "lines.jsonl"
    common = ["--shapes", "small_1k", "--reps", "1", "--out", str(out)]
    assert bench.main(common) == 2 and not out.exists()
    assert bench.main(common + ["--rehearse-cpu"]) == 0
    lines = [json.loads(line) for line in out.read_text().splitlines()]
    assert [line["variant"] for line in lines] == list(bench.VARIANTS)
    assert bench.VARIANTS["engine"] is jkern.join_expand_matches
    for line in lines:
        assert line["same_maps"] is True and line["device"] == "cpu"
        assert not {"median_ms", "ns_per_out_lane", "ns_per_index"} & set(line)
    assert capsys.readouterr().out.count("\n") == len(lines)
