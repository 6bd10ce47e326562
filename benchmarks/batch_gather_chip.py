"""What moving a batch's rows costs one chip: the readings behind
``PERF.md`` section 5's batch-gather table.

An eager gather of k columns by one index vector, two ways:

- ``per_column``: the engine's per-column gather before it, two eager
  ``jnp.take``s a column (its data, then its validity), one launch each;
- ``batch_gather``: the engine's ``columnar/gather.gather_columns``,
  ONE launch that row-gathers a ``[rows, lanes]`` uint32 matrix (data as
  32-bit lanes, every validity one bit of a shared lane).

at 2^10, 2^14, 2^17 and 2^20 rows (the output as many rows as the
input, indices a shuffle with a sixteenth out of range) over the column
mixes of ``MIXES``, 2 to 33 lanes.  Then a batch's slice (the shuffle's
partitions), three ways: ``slice_per_array`` (the engine's slice
program before it, a take of ``arange + start`` an array),
``slice_gather`` (the packed matrix's rows by one row gather) and
``slice_dynamic`` (the engine's ``slice_columns``: the matrix padded by
the slice's length, then ``lax.dynamic_slice``).

One JSON line per variant and shape: the first call with the persistent
compile cache off (so it compiles), then the median of ``--reps`` warm
wall-clock calls that end in ``block_until_ready``, the wall clock a
call of ``PIPELINED`` calls in a row that end in one (the engine's way:
it waits on no gather), whether the result
equals ``per_column``'s bit for bit, and for the packed programs the
compiler's ``memory_analysis`` (temporary and output bytes).  Refuses
to run anywhere but on a TPU unless ``--rehearse-cpu`` (which prints no
reading under a device's name)."""
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
from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import gather as cgather
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.kernels.gather import gather_rows_once

#: name -> the columns' dtypes (lanes: 64-bit types two, bool none, and
#: one lane for every 32 validities and bool columns)
MIXES = {
    "int32x1": [T.INT32],                                       # 2 lanes
    "int64x2": [T.INT64] * 2,                                   # 5
    "mixed_bool": [T.INT32, T.INT64, T.FLOAT64, T.BOOL, T.BOOL],  # 6
    "q18_join": [T.INT64] * 3 + [T.FLOAT64] * 2 + [T.DATE],     # 12
    "wide16": [T.INT64] * 8,                                    # 17
    "wide32": [T.INT64] * 16,                                   # 33
}
ROWS = (1 << 10, 1 << 14, 1 << 17, 1 << 20)
#: (input rows, slice length, start) of the slice readings
SLICES = ((1 << 20, 1 << 17, (1 << 19) + 7), (1 << 17, 1 << 14, 40_000))


def lanes_of(dtypes) -> int:
    flags = len(dtypes) + sum(dt == T.BOOL for dt in dtypes)
    return sum(2 if dt in (T.INT64, T.FLOAT64) else 1 for dt in dtypes
               if dt != T.BOOL) + -(-flags // 32)


def columns(dtypes, rows: int, rng):
    cols = []
    for dt in dtypes:
        if dt == T.BOOL:
            data = rng.random(rows) < 0.5
        elif dt == T.FLOAT64:
            data = rng.standard_normal(rows) * 1e6
        else:
            info = np.iinfo(dt.np_dtype)
            data = rng.integers(info.min, info.max, rows, endpoint=True)
        cols.append(Column(dt, jnp.asarray(data.astype(dt.np_dtype)),
                           jnp.asarray(rng.random(rows) < 0.9)))
    return cols


def per_column(cols, idx):
    return [(jnp.take(c.data, idx, axis=0, mode="clip"),
             jnp.take(c.validity, idx, axis=0, mode="clip")) for c in cols]


def batch_gather(cols, idx):
    return [(c.data, c.validity) for c in cgather.gather_columns(cols, idx)]


@functools.partial(jax.jit, static_argnames=("out_cap",))
def slice_per_array(datas, valids, start, nvalid, out_cap: int):
    idx = jnp.arange(out_cap) + start
    live = jnp.arange(out_cap) < nvalid
    return [(jnp.take(d, idx, axis=0, mode="clip"),
             jnp.take(v, idx, axis=0, mode="clip") & live)
            for d, v in zip(datas, valids)]


@functools.partial(jax.jit, static_argnames=("out_cap",))
def slice_gather(datas, valids, start, nvalid, out_cap: int):
    """The packed matrix's rows ``arange + start`` by one row gather."""
    rows = jnp.arange(out_cap)
    moved = gather_rows_once(rows + start, list(datas) + list(valids),
                             mode="clip")
    return [(moved[id(d)][1], moved[id(v)][1] & (rows < nvalid))
            for d, v in zip(datas, valids)]


#: calls in a row of the pipelined reading
PIPELINED = 32


def timed(fn, reps: int):
    """-> (result, first call s, warm calls' ms, pipelined ms a call)."""
    t0 = time.perf_counter()
    got = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    t0 = time.perf_counter()
    outs = [fn() for _ in range(PIPELINED)]
    jax.block_until_ready(outs)
    return got, first, times, (time.perf_counter() - t0) * 1e3 / PIPELINED


def same(a, b) -> bool:
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for pa, pb in zip(a, b) for x, y in zip(pa, pb))


def memory(fn, *args, **kwargs):
    ma = jax.jit(fn, static_argnames=tuple(kwargs)).lower(
        *args, **kwargs).compile().memory_analysis()
    if ma is None:
        return {}
    return {"temp_bytes": int(ma.temp_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=39)
    ap.add_argument("--mixes", default=",".join(MIXES))
    ap.add_argument("--rows", default=",".join(str(r) for r in ROWS))
    ap.add_argument("--out", default="batch_gather.jsonl")
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

    def emit(line, times, piped):
        line.update(device=dev.platform, device_kind=dev.device_kind)
        if dev.platform == "tpu":
            med = statistics.median(times)
            line.update(median_ms=med, min_ms=min(times), max_ms=max(times),
                        ns_per_row=med * 1e6 / line["rows"],
                        pipelined_ms=piped)
        print(json.dumps(line), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")

    for mix in args.mixes.split(","):
        dtypes = MIXES[mix]
        for rows in (int(r) for r in args.rows.split(",")):
            cols = columns(dtypes, rows, rng)
            idx = jnp.asarray(np.where(
                rng.random(rows) < 1 / 16, rows + 5,
                rng.permutation(rows)).astype(np.int32))
            want = None
            for name, fn in (("per_column", per_column),
                             ("batch_gather", batch_gather)):
                got, first, times, piped = timed(lambda: fn(cols, idx),
                                                 args.reps)
                want = got if want is None else want
                line = {"variant": name, "mix": mix, "rows": rows,
                        "columns": len(dtypes), "lanes": lanes_of(dtypes),
                        "launches": 2 * len(dtypes)
                        if name == "per_column" else 1,
                        "same": same(got, want),
                        "first_call_s": round(first, 3)}
                if name == "batch_gather":
                    line.update(memory(
                        cgather._batch_gather, idx, None,
                        tuple((c.data, c.validity) for c in cols), (), ()))
                emit(line, times, piped)
    q18 = MIXES["q18_join"]
    for rows, length, start in SLICES:
        cols = columns(q18, rows, rng)
        datas = tuple(c.data for c in cols)
        valids = tuple(c.validity for c in cols)
        nvalid = min(length, rows - start)
        want = None
        for name in ("slice_per_array", "slice_gather", "slice_dynamic"):
            if name == "slice_dynamic":
                def fn():
                    return [(c.data, c.validity) for c in
                            cgather.slice_columns(cols, start, length,
                                                  nvalid)]
            else:
                prog = globals()[name]

                def fn(prog=prog):
                    return prog(datas, valids, np.int32(start),
                                np.int32(nvalid), out_cap=length)
            got, first, times, piped = timed(fn, args.reps)
            # rows past nvalid may differ in data (clip against zeros):
            # compare the live rows and every validity
            got_l = [(np.asarray(d)[:nvalid], np.asarray(v))
                     for d, v in got]
            want = got_l if want is None else want
            line = {"variant": name, "mix": "q18_join", "rows": length,
                    "in_rows": rows, "start": start,
                    "lanes": lanes_of(q18), "same": same(got_l, want),
                    "first_call_s": round(first, 3)}
            if name == "slice_dynamic":
                line.update(memory(
                    cgather._batch_slice, np.int32(start), np.int32(nvalid),
                    tuple(zip(datas, valids)), (), (), out_cap=length))
            emit(line, times, piped)
    return 0


if __name__ == "__main__":
    sys.exit(main())
