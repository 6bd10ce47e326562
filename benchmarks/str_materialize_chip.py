"""What materializing a gathered string column costs one chip: the
readings behind ``PERF.md`` section 5's string-materialize table (ISSUE
33, step 0).

``kernels/strings.str_materialize_bytes`` lays row ``r``'s bytes
``data[src_starts[r]:]`` at ``new_offsets[r]`` of a ``uint8[out_bytes]``
buffer.  Three ways to find a lane's source byte, at the shapes
``tpch_q3q18.power`` launches it with (a piece of Q18's join build:
131,072 or 262,144 row slots of 18-byte ``c_name`` gathered from the
300,000-row ``customer`` column into 4 Mi lanes) and beside them a big
one (2^20 rows into 2^25 lanes) and a small one (1,024 rows into 2^15):

- ``searchsorted``: the program up to PR 32, a binary search over the
  row ends a lane, then three gathers;
- ``row_number``: +1 scattered at every row end, the running sum is the
  lane's row number, two gathers a lane (the row's shift, the byte);
- ``engine``: what the tree runs, the step of ``shift = src_starts -
  new_offsets[:-1]`` scattered at every row start, the running sum is
  the lane's shift, one gather a lane.

A share of the rows is null or empty (zero bytes at a shared lane) and
the source rows repeat (a join's gather is not unique).  One JSON line
per variant and shape: the first call with the persistent compile cache
off (so it compiles), then the median of ``--reps`` warm wall-clock
calls that end in ``block_until_ready``, and whether the bytes equal a
numpy reference; refuses to run anywhere but on a TPU unless
``--rehearse-cpu`` (which prints no reading under a device's name)."""
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
from spark_rapids_tpu.kernels import strings as skern
from spark_rapids_tpu.kernels.basic import prefix_sum

#: name -> (row slots, live rows, source rows, string bytes, lanes)
SHAPES = {
    "q18_piece_128k": (1 << 17, 120_000, 300_000, 18, 1 << 22),
    "q18_piece_256k": (1 << 18, 209_683, 300_000, 18, 1 << 22),
    "sf1_piece_128k": (1 << 17, 110_000, 150_000, 18, 1 << 21),
    "big_1m": (1 << 20, 1 << 20, 1 << 20, 18, 1 << 25),
    "small_1k": (1 << 10, 1_000, 4_096, 18, 1 << 15),
}


@functools.partial(jax.jit, static_argnames=("out_bytes",))
def searchsorted(data, new_offsets, src_starts, out_bytes: int):
    """The engine's program up to PR 32."""
    j = jnp.arange(out_bytes, dtype=jnp.int32)
    row = jnp.searchsorted(new_offsets[1:], j, side="right").astype(jnp.int32)
    row = jnp.clip(row, 0, new_offsets.shape[0] - 2)
    within = j - new_offsets[row]
    src_idx = jnp.take(src_starts, row) + within
    live = j < new_offsets[-1]
    return jnp.where(live,
                     jnp.take(data, jnp.clip(src_idx, 0, data.shape[0] - 1)),
                     jnp.uint8(0))


@functools.partial(jax.jit, static_argnames=("out_bytes",))
def row_number(data, new_offsets, src_starts, out_bytes: int):
    """Candidate (a): the running sum of +1 at every row end is what
    ``searchsorted(new_offsets[1:], j, side="right")`` returns."""
    rows = new_offsets.shape[0] - 1
    ends = jnp.zeros(out_bytes, jnp.int32).at[new_offsets[1:]].add(
        1, indices_are_sorted=True, mode="drop")
    row = jnp.clip(prefix_sum(ends), 0, rows - 1)
    shift = src_starts - new_offsets[:-1]
    j = jnp.arange(out_bytes, dtype=jnp.int32)
    src_idx = j + jnp.take(shift, row)
    live = j < new_offsets[-1]
    return jnp.where(live,
                     jnp.take(data, jnp.clip(src_idx, 0, data.shape[0] - 1)),
                     jnp.uint8(0))


VARIANTS = {
    "searchsorted": searchsorted,
    "row_number": row_number,
    "engine": skern.str_materialize_bytes,
}


def piece(slots: int, live: int, src_rows: int, length: int, lanes: int, rng):
    """Host arrays of one launch: the source bytes, the output offsets,
    each row's first source byte, and the bytes numpy lays out."""
    data = rng.integers(48, 91, src_rows * length).astype(np.uint8)
    picks = rng.integers(0, src_rows, slots)
    lens = np.full(slots, length, np.int64)
    lens[rng.random(slots) < 0.03] = 0          # null and empty rows
    lens[live:] = 0                             # dead row slots
    new_offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    src_starts = (picks * length).astype(np.int32)
    total = int(new_offsets[-1])
    assert total <= lanes, (total, lanes)
    row = np.repeat(np.arange(slots), lens)
    want = np.zeros(lanes, np.uint8)
    want[:total] = data[src_starts[row] + np.arange(total)
                        - new_offsets[:-1][row]]
    return data, new_offsets, src_starts, want


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--only", default=",".join(VARIANTS))
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"refusing: platform is {dev.platform}, not tpu",
              file=sys.stderr)
        return 2
    # every first call below compiles: nothing comes from a cache
    jax.config.update("jax_enable_compilation_cache", False)
    rng = np.random.default_rng(args.seed)
    out_dir = "chiprun_out"
    os.makedirs(out_dir, exist_ok=True)
    for shape in args.shapes.split(","):
        slots, live, src_rows, length, lanes = SHAPES[shape]
        data, new_offsets, src_starts, want = piece(
            slots, live, src_rows, length, lanes, rng)
        operands = [jax.device_put(a) for a in (data, new_offsets, src_starts)]
        for name in args.only.split(","):
            fn = VARIANTS[name]
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(*operands, lanes))
            first = time.perf_counter() - t0
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*operands, lanes))
                times.append((time.perf_counter() - t0) * 1e3)
            line = {"variant": name, "shape": shape, "row_slots": slots,
                    "live_bytes": int(new_offsets[-1]), "lanes": lanes,
                    "same_bytes": bool((np.asarray(got) == want).all()),
                    "first_call_s": round(first, 2),
                    "device": dev.platform, "device_kind": dev.device_kind}
            if dev.platform == "tpu":
                med = statistics.median(times)
                line.update(median_ms=med, min_ms=min(times),
                            max_ms=max(times), ns_per_lane=med * 1e6 / lanes)
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir, "str_materialize.jsonl"),
                      "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
