"""Pallas TPU kernels for hot ops XLA doesn't fuse well.

Reference analogue: the hand-written CUDA kernels inside libcudf that
the plugin leans on.  The one kernel here is the bucket-table reduce of
the sort-free group-by.  (A fused hash+partition-id kernel used to live
here too: it mixed uint64 lanes, which Mosaic does not lower on TPU
v5e — the 64-bit multiply constant fails in ``ir_constant`` — so it
went; XLA fuses the plain ``jnp`` mixing chain in
shuffle/partitioners.py into one program anyway.)

Every kernel compiles through Mosaic on an accelerator and a lowering
failure raises.  Interpret mode is for the CPU test backend only, never
for "anything not called tpu", and there is no silent ``jnp`` fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def interpret_mode() -> bool:
    """Pallas interpret mode: true only on the CPU (test) backend."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Bucket-table reduce: the device core of the sort-free group-by
# (kernels/aggregate.py table_plan).  For each of k f32 rows, reduce row
# values into `table` buckets with a per-row op ('sum' | 'max').
#
# Why Pallas: XLA lowers the equivalent one-hot einsum to a convolution
# that MATERIALIZES the (n, table) one-hot in HBM.  Here the one-hot tile lives only in
# VMEM: sums ride the MXU as (rows, C) @ (C, Gt) dots, maxes are VPU
# masked reductions, and HBM traffic is just inputs x (table/Gt) passes.
# Reference analogue: the hand-rolled cuDF hash-aggregate kernels.
# ---------------------------------------------------------------------------

_TR_C = 512      # chunk columns (x8 chunk-rows = 4096 rows per step)
_TR_G = 512      # bucket chunk for the in-kernel one-hot loop
# VMEM budget: the transient one-hot tile is (_TR_G, _TR_C) f32 = 1 MB,
# reused across the loops; accumulators are (rows, table) f32 = <100 KB.


def _z(i):
    """An i32 zero derived from a program id (index maps must not return
    python-int literals: under jax_enable_x64 they trace as i64 and
    Mosaic cannot legalize the index-map function's i64 return)."""
    return i - i


def _table_reduce_kernel(nsum: int, nmax: int, gt: int):
    from jax.experimental import pallas as pl

    def kernel(bucket_ref, sums_in_ref, maxs_in_ref, sum_out_ref,
               max_out_ref):
        # All tensors stay 2-D with contractions on the lane (last) dim —
        # Mosaic cannot shape-cast across lanes, so no reshapes; the
        # bucket-chunk/sub-row loops are fori_loops so the (G_t, C)
        # transients are reused, not stacked (VMEM is 16 MB scoped).
        r = pl.program_id(0)
        rb = bucket_ref.shape[0]

        @pl.when(r == 0)
        def _init():
            sum_out_ref[...] = jnp.zeros_like(sum_out_ref)
            max_out_ref[...] = jnp.full_like(max_out_ref, -jnp.inf)

        def g_body(gi, _):
            iot = jax.lax.broadcasted_iota(
                jnp.int32, (_TR_G, _TR_C), 0) + gi * _TR_G
            sl = pl.dslice(gi * _TR_G, _TR_G)

            def r_body(rr, _):
                b = bucket_ref[pl.dslice(rr, 1), :]       # (1, C)
                oht = (b == iot)                          # (G_t, C) bool
                if nsum:
                    sv = sums_in_ref[:, rr, :]            # (nsum, C)
                    contrib = jax.lax.dot_general(
                        sv, oht.astype(jnp.float32),
                        (((1,), (1,)), ((), ())),
                        precision=jax.lax.Precision.HIGHEST)
                    sum_out_ref[:, sl] += contrib         # (nsum, G_t)
                if nmax:
                    for i in range(nmax):
                        mv = maxs_in_ref[i, pl.dslice(rr, 1), :]  # (1, C)
                        masked = jnp.where(oht, mv, -jnp.inf)
                        max_out_ref[pl.dslice(i, 1), sl] = jnp.maximum(
                            max_out_ref[pl.dslice(i, 1), sl],
                            jnp.max(masked, axis=1)[None, :])
                return 0
            return jax.lax.fori_loop(0, rb, r_body, 0)
        jax.lax.fori_loop(0, gt // _TR_G, g_body, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("table", "nsum", "nmax"))
def agg_table_reduce_tpu(bucket, sums_in, maxs_in, table: int, nsum: int,
                      nmax: int):
    # Trace with x64 OFF: every kernel type here is 32-bit, and pallas
    # fori_loop tracing under jax_enable_x64 hits an infinite promotion
    # recursion (i64 loop indices vs i32 vector math).
    with jax.enable_x64(False):
        return _table_reduce_tpu_32(bucket, sums_in, maxs_in, table,
                                    nsum, nmax)


def _table_reduce_tpu_32(bucket, sums_in, maxs_in, table: int, nsum: int,
                         nmax: int):
    from jax.experimental import pallas as pl
    n = bucket.shape[0]
    gt = (table + _TR_G) // _TR_G * _TR_G          # cover table+1 dead slot
    rows_step = 8 * _TR_C
    pad = (-n) % rows_step
    if pad:
        bucket = jnp.concatenate(
            [bucket, jnp.full(pad, table, jnp.int32)])
        zs = jnp.zeros((sums_in.shape[0], pad), jnp.float32)
        sums_in = jnp.concatenate([sums_in, zs], axis=1)
        zm = jnp.full((maxs_in.shape[0], pad), -jnp.inf, jnp.float32)
        maxs_in = jnp.concatenate([maxs_in, zm], axis=1)
    npad = bucket.shape[0]
    r_steps = npad // rows_step
    bucket2 = bucket.reshape(r_steps * 8, _TR_C)
    sums2 = sums_in.reshape(sums_in.shape[0], r_steps * 8, _TR_C)
    maxs2 = maxs_in.reshape(maxs_in.shape[0], r_steps * 8, _TR_C)
    grid = (r_steps,)
    kernel = _table_reduce_kernel(nsum, nmax, gt)
    sum_out, max_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((8, _TR_C), lambda r: (r, _z(r))),
            pl.BlockSpec((max(nsum, 1), 8, _TR_C),
                         lambda r: (_z(r), r, _z(r))),
            pl.BlockSpec((max(nmax, 1), 8, _TR_C),
                         lambda r: (_z(r), r, _z(r))),
        ],
        out_specs=[
            pl.BlockSpec((max(nsum, 1), gt), lambda r: (_z(r), _z(r))),
            pl.BlockSpec((max(nmax, 1), gt), lambda r: (_z(r), _z(r))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((max(nsum, 1), gt), jnp.float32),
            jax.ShapeDtypeStruct((max(nmax, 1), gt), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(bucket2, sums2, maxs2)
    return sum_out, max_out


def table_reduce(bucket, sum_rows, max_rows, table: int,
                 impl: str = "scatter"):
    """Reduce f32 rows into `table` buckets (+1 dead slot dropped).

    sum_rows: list of f32[n] contribution rows (dead rows must be 0).
    max_rows: list of f32[n] rows (dead rows must be -inf); min via
    caller-side negation.  Returns (sums: list of f32[table],
    maxs: list of f32[table]).

    impl='scatter' (default): one multi-column XLA scatter-add for all
    sum rows + per-row scatter-max (the multi-column scatter costs the
    same as a single-column one).  Smoke timing on one TPU v5e chip at
    n=4M, table=4096, 2 sum rows + 1 max row: scatter 56 ms, pallas
    112 ms (one warm call each, PR 22 chip probe; not a benchmark).
    impl='pallas': the hand-written one-hot MXU kernel above — compiles
    through Mosaic on v5e and is currently slower (Mosaic's scoped-VMEM
    limit forces small dot tiles whose loop overhead dominates); kept
    selectable via spark.rapids.tpu.sql.agg.tableReduceImpl for kernel
    tuning work.  Interpreted on the CPU test backend only.
    """
    nsum, nmax = len(sum_rows), len(max_rows)
    if impl == "pallas":
        sums_in = jnp.stack(sum_rows, 0) if nsum else \
            jnp.zeros((1, bucket.shape[0]), jnp.float32)
        maxs_in = jnp.stack(max_rows, 0) if nmax else \
            jnp.full((1, bucket.shape[0]), -jnp.inf, jnp.float32)
        sum_out, max_out = agg_table_reduce_tpu(
            bucket, sums_in, maxs_in, table, nsum, nmax)
        return ([sum_out[i][:table] for i in range(nsum)],
                [max_out[i][:table] for i in range(nmax)])
    sums = []
    if nsum:
        stacked = jnp.stack(sum_rows, 1)            # (n, nsum)
        out = jax.ops.segment_sum(stacked, bucket,
                                  num_segments=table + 1)
        sums = [out[:, i][:table] for i in range(nsum)]
    maxs = [jax.ops.segment_max(r, bucket, num_segments=table + 1)[:table]
            for r in max_rows]
    return sums, maxs
