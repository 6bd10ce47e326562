"""What packing a string key costs one chip: the readings behind
``PERF.md`` section 5's string-pack table (ISSUE 31, step 0).

At ``--rows`` rows (2^20 by default), over a column of strings of 1, 2,
4, 7, 8 and 16 bytes: ``kernels/strings.str_pack_words`` as every caller
without a byte bound runs it (8 gathered indices a word a row) against
the same program told the bound (``num_bytes``: the bound rounded up to
a power of two; at 8 bytes a word and over it IS the first program), and
``string_key_words`` as the aggregate calls it (the pack, the column
split and the length word).  Beside them three candidates no caller runs,
kept so the next PR can read them again: those key words as one program,
the full-width pack built from
32-bit halves, and the pack gathering aligned uint32 words (three
indices for eight bytes at any alignment) instead of bytes.  One JSON
line per variant, median of ``--reps`` wall-clock runs that end in
``block_until_ready``, the first call (compile included) beside it;
refuses to run anywhere but on a TPU unless ``--rehearse-cpu`` (which
prints no reading under a device's name)."""
from __future__ import annotations

import argparse
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
from spark_rapids_tpu.columnar.column import StringColumn
from spark_rapids_tpu.kernels import strings as skern

LENGTHS = (1, 2, 4, 7, 8, 16)


def column(rows: int, length: int, rng) -> StringColumn:
    """``rows`` strings of exactly ``length`` bytes from a four-letter
    alphabet (TPC-H's flags are such a column at length 1), built in
    bulk: no python loop over rows."""
    data = rng.integers(65, 69, rows * length).astype(np.uint8)
    offsets = (np.arange(rows + 1, dtype=np.int64) * length).astype(np.int32)
    return StringColumn(jnp.asarray(offsets), jnp.asarray(data),
                        jnp.ones(rows, jnp.bool_), max_bytes=length)


def pack_halves(offsets, data, num_words: int):
    """Candidate: today's ``[cap, 8 * num_words]`` byte gather, the words
    assembled from uint32 halves instead of a uint64 shift-and-sum."""
    cap = offsets.shape[0] - 1
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    k = jnp.arange(num_words * 8, dtype=jnp.int32)
    idx = starts[:, None] + k[None, :]
    byts = jnp.where(k[None, :] < lens[:, None],
                     jnp.take(data, jnp.clip(idx, 0, data.shape[0] - 1)),
                     jnp.uint8(0)).astype(jnp.uint32)
    shifts = jnp.uint32(8) * (jnp.uint32(3) - jnp.arange(4, dtype=jnp.uint32))
    h = jnp.sum(byts.reshape(cap, num_words, 2, 4) << shifts, axis=-1,
                dtype=jnp.uint32).astype(jnp.uint64)
    return (h[..., 0] << jnp.uint64(32)) | h[..., 1]


def pack_u32_gather(offsets, data, num_words: int):
    """Candidate: gather aligned uint32 words of the byte buffer
    (``2 * num_words + 1`` indices a row cover ``8 * num_words`` bytes at
    any alignment) and shift the string's bytes out of them."""
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    pad = (-data.shape[0]) % 4
    quads = jnp.pad(data, (0, pad)).reshape(-1, 4).astype(jnp.uint32)
    be = (quads[:, 0] << 24) | (quads[:, 1] << 16) | (quads[:, 2] << 8) \
        | quads[:, 3]
    first = starts >> 2
    sh = ((starts & 3) * 8).astype(jnp.uint32)
    got = [jnp.take(be, jnp.clip(first + i, 0, be.shape[0] - 1))
           for i in range(2 * num_words + 1)]
    words = []
    for w in range(num_words):
        halves = []
        for h in (2 * w, 2 * w + 1):
            # the 32 bits that start sh bits into got[h]
            v = jnp.where(sh == 0, got[h],
                          (got[h] << sh) | (got[h + 1] >> (32 - sh)))
            left = jnp.clip(lens - 4 * h, 0, 4).astype(jnp.uint32)
            mask = jnp.where(left == 0, jnp.uint32(0),
                             jnp.uint32(0xFFFFFFFF) << (8 * (4 - left)))
            halves.append((v & mask).astype(jnp.uint64))
        words.append((halves[0] << jnp.uint64(32)) | halves[1])
    return jnp.stack(words, axis=1)


def key_words_one_program(offsets, data, num_words: int, num_bytes):
    """Candidate: ``string_key_words`` as one program, the column split
    and the length word returned beside the pack instead of the eager
    ``words[:, i]`` and ``string_lengths`` that follow it today."""
    words = skern.str_pack_words(offsets, data, num_words, num_bytes)
    return [words[:, i] for i in range(num_words)] + [
        skern.string_lengths(offsets).astype(jnp.uint64)]


def variants(col: StringColumn, length: int):
    """name -> zero-argument callable returning device arrays."""
    num_words = skern.needed_key_words(col, col.capacity)
    bound = 1 << max(0, length - 1).bit_length()
    fused = jax.jit(key_words_one_program,
                    static_argnames=("num_words", "num_bytes"))
    halves = jax.jit(pack_halves, static_argnames=("num_words",))
    u32 = jax.jit(pack_u32_gather, static_argnames=("num_words",))
    out = {
        "pack_full": lambda: skern.str_pack_words(
            col.offsets, col.data, num_words),
        "pack_byte_bound": lambda: skern.pack_words(col, num_words, length),
        "key_words_full": lambda: skern.string_key_words(
            col, col.capacity, num_words),
        "key_words_byte_bound": lambda: skern.string_key_words(
            col, col.capacity, num_words, length),
        "candidate_key_words_one_program": lambda: fused(
            col.offsets, col.data, num_words,
            bound if bound < 8 * num_words else None),
        "candidate_full_from_halves": lambda: halves(
            col.offsets, col.data, num_words),
        "candidate_u32_word_gather": lambda: u32(
            col.offsets, col.data, num_words),
    }
    return num_words, bound, out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--lengths", default=",".join(map(str, LENGTHS)))
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"refusing: platform is {dev.platform}, not tpu",
              file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    out_dir = "chiprun_out"
    os.makedirs(out_dir, exist_ok=True)
    for length in map(int, args.lengths.split(",")):
        col = column(args.rows, length, rng)
        num_words, bound, todo = variants(col, length)
        want = None
        for name, fn in todo.items():
            # pack_full always runs: the others are compared with it
            if args.only and name != "pack_full" and \
                    name not in args.only.split(","):
                continue
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn())
            first = time.perf_counter() - t0
            words = np.stack([np.asarray(w) for w in got[:-1]], 1) \
                if "key_words" in name else np.asarray(got)
            if want is None:
                want = words
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                times.append((time.perf_counter() - t0) * 1e3)
            line = {"variant": name, "rows": args.rows,
                    "string_bytes": length, "num_words": num_words,
                    "gathered_bytes": min(bound, 8 * num_words)
                    if "byte_bound" in name or "one_program" in name
                    else 8 * num_words,
                    "same_words": bool((words == want).all()),
                    "first_call_s": round(first, 2),
                    "device": dev.platform, "device_kind": dev.device_kind}
            if dev.platform == "tpu":
                line.update(median_ms=statistics.median(times),
                            min_ms=min(times), max_ms=max(times))
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir, "str_pack.jsonl"), "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
