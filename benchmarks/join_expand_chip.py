"""What expanding a join's matches costs one chip: the readings behind
``PERF.md`` section 5's join-expansion table (ISSUE 35, step 0).

``kernels/join.join_expand_matches(lo, counts, perm, out_cap)`` turns
per-probe-row ``(lo, counts)`` into the two gather maps of a join's
output: output row ``t`` of probe row ``r`` (``excl[r] <= t < incl[r]``)
has ``probe_idx = r`` and ``build_idx = perm[t + lo[r] - excl[r]]``.
Seven ways to find an output row's probe row and build position, at
the shapes the benchmark's cells launch it with (``SHAPES``):

- ``searchsorted``: the program up to PR 34, a binary search over the
  inclusive sums an output row, then three gathers (``lo``, ``excl``,
  ``perm``);
- ``incl_scatter`` (a): +1 scattered at every ``incl[r]``, the running
  sum is the output row's probe row, the same three gathers;
- ``excl_gather`` (a'): +1 scattered at every ``excl[r]``, one running
  sum, TWO int32 gathers (``shift = lo - excl`` by the probe row, then
  ``perm``);
- ``two_scatters`` (b): a +1 and the step of ``shift`` scattered at
  every ``excl[r]`` by two scatters, two running sums, ONE gather
  (``perm``);
- ``engine``: what the tree runs, (b) with fewer probe rows than output
  rows and (a') otherwise;
- ``stacked_scatter`` (b, stacked): (b) by one scatter of (+1, step)
  pairs into a ``[2, out_cap]`` buffer and one running sum along
  its rows;
- ``packed_max`` (c): every probe row writes ``r << 32 | shift + 2^31``
  at ``excl[r]`` by one scatter-max and a running MAX carries the last
  matched row's word forward (a row with no match shares a lane with
  the next matched row, whose word is larger).

One JSON line per variant, shape and ``out_cap``: the first call with
the persistent compile cache off (so it compiles), then the median of
``--reps`` warm wall-clock calls that end in ``block_until_ready``, and
whether the maps equal a numpy ``np.repeat`` reference on every live
lane and stay in range on the dead ones; refuses to run anywhere but on
a TPU unless ``--rehearse-cpu`` (which prints no reading under a
device's name)."""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import spark_rapids_tpu  # noqa: F401  (enables x64 as the engine does)
from spark_rapids_tpu.kernels import join as jkern
from spark_rapids_tpu.kernels.basic import prefix_sum

#: name -> (probe slots, output capacities, build rows, match pattern)
SHAPES = {
    # a stream batch of Q18's shuffled join against a partition's orders
    "q18_part_128k": (1 << 17, (1 << 17, 1 << 18), 1 << 19, "one_most"),
    # q67's fact batch against the filtered date_dim
    "q67_date_1m": (1 << 20, (1 << 18,), 1 << 17, "one_fifth"),
    # q67's later dimension joins: every row once
    "q67_dim_256k": (1 << 18, (1 << 18,), 1 << 15, "all_one"),
    # the store channel's fact batches
    "store_1m": (1 << 20, (1 << 20,), 1 << 21, "all_one"),
    # the chunked path: a few probe rows carry the output
    "skew_64k": (1 << 16, (1 << 20,), 1 << 16, "skew"),
    "small_1k": (1 << 10, (1 << 10,), 1 << 10, "few"),
}


@functools.partial(jax.jit, static_argnames=("out_cap",))
def searchsorted(lo, counts, perm, out_cap: int):
    """The engine's program up to PR 34."""
    incl = prefix_sum(counts.astype(jnp.int64))
    excl = incl - counts
    total = incl[-1]
    t = jnp.arange(out_cap, dtype=jnp.int64)
    p = jnp.searchsorted(incl, t, side="right").astype(jnp.int32)
    pc = jnp.clip(p, 0, counts.shape[0] - 1)
    build_pos = jnp.take(lo, pc) + (t - jnp.take(excl, pc)).astype(jnp.int32)
    build_pos = jnp.clip(build_pos, 0, perm.shape[0] - 1)
    return pc, jnp.take(perm, build_pos), t < total, total


def _sums(counts, out_cap):
    """(excl, total, each row's first lane as a droppable int32 index)."""
    incl = prefix_sum(counts.astype(jnp.int64))
    excl = incl - counts
    return excl, incl[-1], jnp.minimum(excl, out_cap).astype(jnp.int32)


def _maps(rows, build_pos, perm, total, n, out_cap):
    t = jnp.arange(out_cap, dtype=jnp.int32)
    pc = jnp.clip(rows, 0, n - 1)
    build_idx = jnp.take(perm, jnp.clip(build_pos, 0, perm.shape[0] - 1))
    return pc, build_idx, t < jnp.minimum(total, out_cap).astype(jnp.int32), \
        total


@functools.partial(jax.jit, static_argnames=("out_cap",))
def incl_scatter(lo, counts, perm, out_cap: int):
    """Candidate (a): the running sum of +1 at every ``incl[r]`` is what
    ``searchsorted(incl, t, side="right")`` returns."""
    n = counts.shape[0]
    excl, total, _ = _sums(counts, out_cap)
    ends = jnp.minimum(excl + counts, out_cap).astype(jnp.int32)
    rows = prefix_sum(jnp.zeros(out_cap, jnp.int32).at[ends].add(
        1, indices_are_sorted=True, mode="drop"))
    pc = jnp.clip(rows, 0, n - 1)
    t = jnp.arange(out_cap, dtype=jnp.int32)
    shift = lo.astype(jnp.int32) - excl.astype(jnp.int32)
    return _maps(rows, t + jnp.take(shift, pc), perm, total, n, out_cap)


def _row_numbers(first, out_cap):
    return prefix_sum(jnp.zeros(out_cap, jnp.int32).at[first].add(
        1, indices_are_sorted=True, mode="drop")) - 1


@functools.partial(jax.jit, static_argnames=("out_cap",))
def excl_gather(lo, counts, perm, out_cap: int):
    """Candidate (a'): the probe row from one scatter and one running
    sum, its shift by a gather."""
    n = counts.shape[0]
    excl, total, first = _sums(counts, out_cap)
    rows = _row_numbers(first, out_cap)
    shift = lo.astype(jnp.int32) - excl.astype(jnp.int32)
    t = jnp.arange(out_cap, dtype=jnp.int32)
    return _maps(rows, t + jnp.take(shift, jnp.clip(rows, 0, n - 1)), perm,
                 total, n, out_cap)


def _steps(lo, excl):
    shift = lo.astype(jnp.int32) - excl.astype(jnp.int32)
    return shift - jnp.concatenate([jnp.zeros(1, jnp.int32), shift[:-1]])


@functools.partial(jax.jit, static_argnames=("out_cap",))
def two_scatters(lo, counts, perm, out_cap: int):
    """Candidate (b): the shift's step scattered beside the +1, a second
    running sum, no gather by the probe row."""
    n = counts.shape[0]
    excl, total, first = _sums(counts, out_cap)
    lane_shift = prefix_sum(jnp.zeros(out_cap, jnp.int32).at[first].add(
        _steps(lo, excl), indices_are_sorted=True, mode="drop"))
    t = jnp.arange(out_cap, dtype=jnp.int32)
    return _maps(_row_numbers(first, out_cap), t + lane_shift, perm, total,
                 n, out_cap)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def stacked_scatter(lo, counts, perm, out_cap: int):
    """Candidate (b) with ONE scatter: (+1, step) pairs a probe row into
    ``[2, out_cap]`` and one running sum along the rows."""
    n = counts.shape[0]
    excl, total, first = _sums(counts, out_cap)
    pairs = jnp.stack([jnp.ones(n, jnp.int32), _steps(lo, excl)])
    x = jnp.zeros((2, out_cap), jnp.int32).at[:, first].add(
        pairs, indices_are_sorted=True, mode="drop")
    d = 1
    while d < out_cap:
        x = x + jnp.concatenate(
            [jnp.zeros((2, d), jnp.int32), x[:, :-d]], axis=1)
        d *= 2
    t = jnp.arange(out_cap, dtype=jnp.int32)
    return _maps(x[0] - 1, t + x[1], perm, total, n, out_cap)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def packed_max(lo, counts, perm, out_cap: int):
    """Candidate (c): one scatter-max of ``r << 32 | shift + 2^31`` and a
    running max; no lane sums what several rows wrote."""
    n = counts.shape[0]
    excl, total, first = _sums(counts, out_cap)
    shift = lo.astype(jnp.int32) - excl.astype(jnp.int32)
    word = (jnp.arange(n, dtype=jnp.uint64) << jnp.uint64(32)) | \
        (shift ^ jnp.int32(-1 << 31)).astype(jnp.uint32).astype(jnp.uint64)
    x = jnp.zeros(out_cap, jnp.uint64).at[first].max(
        word, indices_are_sorted=True, mode="drop")
    d = 1
    while d < out_cap:
        x = jnp.maximum(x, jnp.concatenate(
            [jnp.zeros((d,), jnp.uint64), x[:-d]]))
        d *= 2
    rows = (x >> jnp.uint64(32)).astype(jnp.int32)
    lane_shift = x.astype(jnp.uint32).astype(jnp.int32) ^ jnp.int32(-1 << 31)
    t = jnp.arange(out_cap, dtype=jnp.int32)
    return _maps(rows, t + lane_shift, perm, total, n, out_cap)


VARIANTS = {
    "searchsorted": searchsorted,
    "incl_scatter": incl_scatter,
    "excl_gather": excl_gather,
    "two_scatters": two_scatters,
    "engine": jkern.join_expand_matches,
    "stacked_scatter": stacked_scatter,
    "packed_max": packed_max,
}


def launch(slots: int, build: int, pattern: str, rng):
    """Host (lo, counts, perm) of one launch: ``counts`` by ``pattern``
    on the live probe slots (the last sixteenth is dead: zero), ``lo``
    where a probe of a sorted build would put it, ``perm`` a shuffle of
    the build's rows."""
    live = slots - slots // 16
    counts = np.zeros(slots, np.int64)
    if pattern == "one_most":        # 5-10% of the live rows unmatched
        counts[:live] = rng.random(live) >= rng.uniform(0.05, 0.10)
    elif pattern == "one_fifth":     # a filtered dimension
        counts[:live] = rng.random(live) < 0.20
    elif pattern == "all_one":
        counts[:live] = 1
    elif pattern == "skew":          # 1% of the rows, 1,000-3,000 each
        heavy = rng.choice(live, live // 100, replace=False)
        counts[heavy] = rng.integers(1000, 3001, len(heavy))
    elif pattern == "few":
        counts[:live] = rng.integers(0, 4, live)
    else:
        raise ValueError(pattern)
    lo = rng.integers(0, build - int(counts.max()) + 1, slots)
    perm = rng.permutation(build)
    return lo.astype(np.int32), counts.astype(np.int32), perm.astype(np.int32)


def reference(lo, counts, perm, out_cap: int):
    """numpy's (probe_idx, build_idx) of the live lanes, and the total."""
    counts = counts.astype(np.int64)
    total = int(counts.sum())
    keep = min(total, out_cap)
    probe = np.repeat(np.arange(len(counts)), counts)[:keep]
    excl = np.cumsum(counts) - counts
    pos = lo.astype(np.int64)[probe] + np.arange(keep) - excl[probe]
    return probe, perm[pos], total


def same_maps(got, want, slots: int, build: int, out_cap: int) -> bool:
    probe, build_idx, live, total = (np.asarray(a) for a in got)
    want_probe, want_build, want_total = want
    keep = len(want_probe)
    return bool(
        int(total) == want_total
        and (live == (np.arange(out_cap) < want_total)).all()
        and (probe[:keep] == want_probe).all()
        and (build_idx[:keep] == want_build).all()
        and probe.min() >= 0 and probe.max() < slots
        and build_idx.min() >= 0 and build_idx.max() < build)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=35)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--only", default=",".join(VARIANTS))
    ap.add_argument("--out", default="chiprun_out/join_expand.jsonl")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"refusing: platform is {dev.platform}, not tpu",
              file=sys.stderr)
        return 2
    # every first call below compiles: nothing comes from a cache
    jax.config.update("jax_enable_compilation_cache", False)
    rng = np.random.default_rng(args.seed)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for shape in args.shapes.split(","):
        slots, out_caps, build, pattern = SHAPES[shape]
        host = launch(slots, build, pattern, rng)
        operands = [jax.device_put(a) for a in host]
        for out_cap in out_caps:
            want = reference(*host, out_cap)
            for name in args.only.split(","):
                fn = VARIANTS[name]
                t0 = time.perf_counter()
                got = jax.block_until_ready(fn(*operands, out_cap))
                first = time.perf_counter() - t0
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*operands, out_cap))
                    times.append((time.perf_counter() - t0) * 1e3)
                line = {"variant": name, "shape": shape,
                        "probe_slots": slots, "out_cap": out_cap,
                        "build_rows": build, "total": want[2],
                        "same_maps": same_maps(got, want, slots, build,
                                               out_cap),
                        "first_call_s": round(first, 2),
                        "device": dev.platform,
                        "device_kind": dev.device_kind}
                if dev.platform == "tpu":
                    med = statistics.median(times)
                    line.update(median_ms=med, min_ms=min(times),
                                max_ms=max(times),
                                ns_per_out_lane=med * 1e6 / out_cap,
                                ns_per_index=med * 1e6 / (slots + out_cap))
                print(json.dumps(line), flush=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
