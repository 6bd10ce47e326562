"""How one chip moves a grouped aggregate's rows: the readings behind
``PERF.md`` section 5's gather / scatter table (ISSUE 29, step 4).

At ``--slots`` rows (2^20 by default): five float64 columns brought into
one permutation's order as (a) five takes in one program, (b) one row
gather of a ``[slots, 10]`` float32-lane matrix (and of the eleven
uint32 lanes the cores gather, beside one 1-D uint32 take), (c) payload
operands of the key sort; the key sort itself as the chained pair sorts of
``kernels/sort.py`` and as one sort over every key word; and five float64
segment sums as five scatters, one stacked scatter (plain, told its
indices are sorted, and over 2^16 segments), a segmented
``lax.associative_scan`` and a segmented shift-and-add scan.  One
JSON line per variant, median of ``--reps`` wall-clock runs that end in
``block_until_ready``; refuses to run anywhere but on a TPU unless
``--rehearse-cpu`` (which prints no reading under a device's name).
The sorts over six key words compile for 13 minutes and more each on the
chip machine (PR 29): ``--only`` names the variants to run."""
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
from jax import lax

import spark_rapids_tpu  # noqa: F401  (enables x64 as the engine does)

KEY_WORDS = 6       # Q1: two string keys, each rank + one byte word + length
COLS = 5            # Q1's distinct DOUBLE inputs


def split_lanes(v):
    """A device float64 as its two float32 components (exact on the chip,
    where float64 is such a pair)."""
    hi = v.astype(jnp.float32)
    return hi, (v - hi.astype(jnp.float64)).astype(jnp.float32)


def variants(slots: int):
    def take5(vals, perm):
        return [jnp.take(v, perm) for v in vals]

    def rows10(vals, perm):
        lanes = [x for v in vals for x in split_lanes(v)]
        got = jnp.take(jnp.stack(lanes, 1), perm, axis=0)
        return [got[:, 2 * i].astype(jnp.float64) +
                got[:, 2 * i + 1].astype(jnp.float64) for i in range(COLS)]

    def rows11(vals, bits, perm):
        # what the aggregate's cores do: ten float lanes and one lane of
        # validity bits, as uint32
        lanes = [lax.bitcast_convert_type(x, jnp.uint32)
                 for v in vals for x in split_lanes(v)] + [bits]
        got = jnp.take(jnp.stack(lanes, 1), perm, axis=0)
        f32 = [lax.bitcast_convert_type(got[:, i], jnp.float32)
               for i in range(2 * COLS)]
        return [f32[2 * i].astype(jnp.float64) +
                f32[2 * i + 1].astype(jnp.float64)
                for i in range(COLS)], got[:, 2 * COLS]

    def take1(bits, perm):
        return jnp.take(bits, perm)

    def sort_payload(nkeys, npay):
        def f(words, vals, bits):
            iota = jnp.arange(slots, dtype=jnp.int32)
            ops = tuple(words[:nkeys]) + (iota,) + tuple(vals[:npay]) + \
                ((bits,) if npay else ())
            return lax.sort(ops, num_keys=nkeys, is_stable=True)
        return f

    def chained(words):
        perm = jnp.arange(slots, dtype=jnp.int32)
        for w in reversed(words):
            _, perm = lax.sort((jnp.take(w, perm), perm), num_keys=1,
                               is_stable=True)
        return [jnp.take(w, perm) for w in words], perm

    def scatter5(vals, seg):
        return [jax.ops.segment_sum(v, seg, num_segments=slots)
                for v in vals]

    def stacked(nseg, is_sorted):
        def f(vals, seg):
            return jax.ops.segment_sum(jnp.stack(vals, 1), seg,
                                       num_segments=nseg,
                                       indices_are_sorted=is_sorted)
        return f

    def seg_scan(vals, seg, last):
        head = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])

        def comb(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, jnp.where(fb[:, None], vb, va + vb)
        _, run = lax.associative_scan(comb, (head, jnp.stack(vals, 1)))
        return jnp.take(run, last, axis=0)

    def shift_scan(vals, seg, last):
        run = jnp.stack(vals)
        stop = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])
        d = 1
        while d < slots:
            prev = jnp.concatenate(
                [jnp.zeros((COLS, d), run.dtype), run[:, :-d]], 1)
            run = jnp.where(stop[None, :], run, run + prev)
            stop = stop | jnp.concatenate([jnp.ones(d, bool), stop[:-d]])
            d *= 2
        return jnp.take(run, last, axis=1)

    return {
        "take_x5_one_program": (take5, "vals perm"),
        "row_gather_10_f32_lanes": (rows10, "vals perm"),
        "row_gather_11_u32_lanes": (rows11, "vals bits perm"),
        "take_x1_uint32": (take1, "bits perm"),
        "sort_1key_perm_only": (sort_payload(1, 0), "words vals bits"),
        "sort_1key_5_payloads": (sort_payload(1, COLS), "words vals bits"),
        "sort_6keys_perm_only": (sort_payload(KEY_WORDS, 0),
                                 "words vals bits"),
        "sort_6keys_5_payloads": (sort_payload(KEY_WORDS, COLS),
                                  "words vals bits"),
        "chained_pair_sorts_6_words": (chained, "words"),
        "scatter_x5_one_program": (scatter5, "vals seg"),
        "scatter_stacked": (stacked(slots, False), "vals seg"),
        "scatter_stacked_sorted_flag": (stacked(slots, True), "vals seg"),
        "scatter_stacked_65536_segments": (stacked(1 << 16, True),
                                           "vals seg"),
        "segmented_scan_stacked": (seg_scan, "vals seg last"),
        "shift_and_add_scan_stacked": (shift_scan, "vals seg last"),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=1 << 20)
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=29)
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"refusing: platform is {dev.platform}, not tpu",
              file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    n = args.slots
    # group ids as Q1's: a few groups, one of them small
    gid = np.minimum(rng.geometric(0.5, n) - 1, args.groups - 1) \
        if args.groups <= 64 else rng.integers(0, args.groups, n)
    words = [jnp.asarray(np.ones(n, np.uint64)),
             jnp.asarray(gid.astype(np.uint64) << np.uint64(56)),
             jnp.asarray(np.ones(n, np.uint64))] * 2
    vals = [jnp.asarray(rng.uniform(1.0, 1e5, n)) for _ in range(COLS)]
    bits = jnp.asarray(rng.integers(0, 32, n).astype(np.uint32))
    perm = jnp.asarray(np.argsort(gid, kind="stable").astype(np.int32))
    seg_np = np.sort(gid).astype(np.int32)
    seg = jnp.asarray(seg_np)
    last_np = np.zeros(1 << 16, np.int32)
    ends = np.flatnonzero(np.diff(seg_np, append=seg_np[-1] + 1))
    last_np[:len(ends)] = ends[:1 << 16]
    pool = {"words": words, "vals": vals, "bits": bits, "perm": perm,
            "seg": seg, "last": jnp.asarray(last_np)}
    out_dir = "chiprun_out"
    os.makedirs(out_dir, exist_ok=True)
    todo = variants(n)
    for name, (fn, argnames) in todo.items():
        if args.only and name not in args.only.split(","):
            continue
        call_args = [pool[a] for a in argnames.split()]
        jfn = jax.jit(fn)
        t0 = time.perf_counter()
        try:
            jax.block_until_ready(jfn(*call_args))
        except Exception as e:  # noqa: BLE001 - one refused variant, not all
            print(json.dumps({"variant": name, "refused": repr(e)[:300]}),
                  flush=True)
            continue
        first = time.perf_counter() - t0
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(*call_args))
            times.append((time.perf_counter() - t0) * 1e3)
        line = {"variant": name, "slots": n, "groups": args.groups,
                "first_call_s": round(first, 2), "device": dev.platform,
                "device_kind": dev.device_kind}
        if dev.platform == "tpu":
            line.update(median_ms=statistics.median(times),
                        min_ms=min(times), max_ms=max(times))
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "agg_moves.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
