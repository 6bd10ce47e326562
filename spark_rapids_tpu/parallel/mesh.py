"""Device mesh + distributed aggregation/exchange over XLA collectives.

Reference role (SURVEY.md §2.7 parallelism note): the reference's
distributed primitives are partitioned all-to-all exchange, broadcast, and
reduction-by-shuffle over UCX.  TPU-native, those map onto a
jax.sharding.Mesh with ICI collectives: all_to_all for the
hash-partitioned exchange, psum/all_gather for reductions and broadcast —
XLA inserts and schedules the collectives; there is no explicit transport
code on the hot path (the UCX client/server state machines collapse into
one `lax.all_to_all`).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import compile_watch as _compile_watch

MIX = 0x9E3779B97F4A7C15
SIGN64_BIAS = 0x8000000000000000


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = "data") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis_name,))


def _instrumented(fn, mesh: Mesh):
    """Wrap a jitted SPMD program so each dispatch window counts as
    busy time on EVERY participating device id (obs/timeline.py): an
    SPMD step runs lock-step across the mesh, so the multichip smoke
    shows per-chip occupancy instead of one blended number."""
    from ..obs import timeline as _timeline
    ids = tuple(str(d.id) for d in np.asarray(mesh.devices).ravel())
    return _timeline.device_busy_wrap(fn, ids)


def shard_rows(arrays, mesh: Mesh, axis_name: str = "data"):
    """Place [n_dev * rows, ...] arrays row-sharded across the mesh."""
    sharding = NamedSharding(mesh, P(axis_name))
    return [jax.device_put(a, sharding) for a in arrays]


def _local_sum_by_key(keys, vals, valid):
    """Sort + segmented-sum partial aggregation on one shard.

    Same design as kernels/aggregate.py, specialized to a single int64 key
    so the whole step stays inside one jit/shard_map body.
    """
    cap = keys.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    krank = jnp.where(valid, jnp.uint64(1), jnp.uint64(2))
    kwords = keys.astype(jnp.int64).view(jnp.uint64)
    kwords = jnp.where(valid, kwords, jnp.uint64(0))
    skr, skw, sv, perm = jax.lax.sort(
        (krank, kwords, vals.astype(jnp.float64), iota), num_keys=2,
        is_stable=True)
    live = skr != jnp.uint64(2)
    boundary = jnp.concatenate(
        [jnp.ones(1, bool), (skw[1:] != skw[:-1]) | (skr[1:] != skr[:-1])])
    boundary = boundary & live
    seg = jnp.maximum(jnp.cumsum(boundary.astype(jnp.int32)) - 1, 0)
    sums = jax.ops.segment_sum(jnp.where(live, sv, 0.0), seg,
                               num_segments=cap)
    skeys = jnp.take(keys, perm)
    rep_key = jax.ops.segment_max(
        jnp.where(live, skeys, jnp.int64(-2**62)), seg, num_segments=cap)
    ngroups = jnp.sum(boundary)
    gvalid = jnp.arange(cap) < ngroups
    return rep_key, sums.astype(vals.dtype), gvalid




def _route_to_owners(owner, arrays, fills, n_dev: int, axis_name: str,
                     slack: int = 1):
    """Scatter rows into contiguous per-owner regions and all_to_all them.

    ``owner``: int32 per row, n_dev == "drop this row".  ``arrays``: the
    payload columns; ``fills``: fill value per payload for empty slots.
    Returns (routed arrays..., received-validity, overflow flag) — the
    shared exchange core of every distributed primitive here (the
    GpuPartitioning + transport role).  Region capacity is
    slack * cap // n_dev; ``overflow`` reports dropped rows instead of
    hiding them.
    """
    cap = owner.shape[0]
    per = max(1, (cap * slack) // n_dev)
    order = jnp.argsort(owner, stable=True)
    sowner = jnp.take(owner, order)
    owner_c = jnp.clip(sowner, 0, n_dev - 1)
    counts = jax.ops.segment_sum(
        (sowner < n_dev).astype(jnp.int32), owner_c, num_segments=n_dev)
    excl = jnp.cumsum(counts) - counts
    within = jnp.arange(cap, dtype=jnp.int32) - jnp.take(excl, owner_c)
    slot = owner_c * per + within
    oob = jnp.int32(n_dev * per)
    put = (sowner < n_dev) & (within < per)
    overflow = jnp.any((sowner < n_dev) & (within >= per))
    idx = jnp.where(put, slot, oob)
    outs = []
    for a, fill in zip(arrays, fills):
        sa = jnp.take(a, order)
        oa = jnp.full((n_dev * per,), fill, sa.dtype).at[idx].set(
            sa, mode="drop")
        oa = jax.lax.all_to_all(oa.reshape(n_dev, per), axis_name,
                                0, 0).reshape(-1)
        outs.append(oa)
    ovalid = jnp.zeros((n_dev * per,), bool).at[idx].set(put, mode="drop")
    ovalid = jax.lax.all_to_all(ovalid.reshape(n_dev, per), axis_name,
                                0, 0).reshape(-1)
    overflow_any = jax.lax.pmax(overflow.astype(jnp.int32),
                                axis_name).astype(jnp.bool_)
    return outs, ovalid, overflow_any


def distributed_sum_by_key(mesh: Mesh, axis_name: str = "data"):
    """Build the jitted SPMD step: row-sharded (keys, vals, valid) ->

    per-key sums, keys owner-partitioned across devices.

    Three stages, the TPU realization of the reference's
    partial-agg -> hash-shuffle -> final-agg pipeline (aggregate.scala
    modes + RapidsShuffleManager):
      1. local partial aggregation (sort + segment_sum)
      2. all_to_all exchange routing each key group to hash(key) % n_dev
      3. local final merge of received partials
    """
    from ..shims import get_shard_map
    shard_map = get_shard_map()

    n_dev = mesh.devices.size

    def step(keys, vals, valid):
        rep_key, sums, gvalid = _local_sum_by_key(keys, vals, valid)
        owner = ((rep_key.view(jnp.uint64) * jnp.uint64(MIX))
                 >> jnp.uint64(33)) % jnp.uint64(n_dev)
        owner = jnp.where(gvalid, owner.astype(jnp.int32), n_dev)
        (okey, osum), oval, overflow = _route_to_owners(
            owner, [rep_key, sums], [0, 0.0], n_dev, axis_name, slack=2)
        k, v, gv = _local_sum_by_key(okey, osum, oval)
        return k, v, gv, overflow[None]

    smapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name), P(axis_name),
                   P(axis_name)))
    return _instrumented(_compile_watch.jit(smapped, "mesh_sum_by_key"), mesh)


def distributed_global_sum(mesh: Mesh, axis_name: str = "data"):
    """psum-based global reduction (the broadcast/reduce primitive)."""
    from ..shims import get_shard_map
    shard_map = get_shard_map()

    def step(vals, valid):
        local = jnp.sum(jnp.where(valid, vals, 0))
        return jax.lax.psum(local, axis_name)[None]

    return _instrumented(_compile_watch.jit(shard_map(
        step, mesh=mesh, in_specs=(P(axis_name), P(axis_name)),
        out_specs=P(axis_name)), "mesh_global_sum"), mesh)


def distributed_join_sum(mesh: Mesh, axis_name: str = "data"):
    """Hash-routed distributed equi-join reduced to per-key products.

    The SPMD form of the reference's shuffled hash join
    (GpuShuffledHashJoinBase + RapidsShuffleManager): both sides route
    their rows to hash(key) % n_dev over one ICI all_to_all, then each
    device joins its co-partitioned shards locally.  The local join here
    aggregates sum(l_val * r_val) per key (the reduction-by-shuffle-join
    shape of TPC join+agg plans) so the SPMD body keeps static shapes.

    Inputs are row-sharded (lkeys, lvals, lvalid, rkeys, rvals, rvalid);
    outputs are owner-partitioned (key, sum, valid) triples.
    """
    from ..shims import get_shard_map
    shard_map = get_shard_map()
    n_dev = mesh.devices.size

    def _route(keys, vals, valid):
        owner = ((keys.view(jnp.uint64) * jnp.uint64(MIX))
                 >> jnp.uint64(33)) % jnp.uint64(n_dev)
        owner = jnp.where(valid, owner.astype(jnp.int32), n_dev)
        (okey, oval), ovalid, overflow = _route_to_owners(
            owner, [keys, vals], [0, 0.0], n_dev, axis_name, slack=2)
        return okey, oval, ovalid, overflow

    def step(lk, lv, lm, rk, rv, rm):
        # pre-aggregate each side locally so the exchange carries one
        # partial per (device, key) — bounds the per-owner region like
        # distributed_sum_by_key (and is the partial-agg pushdown the
        # planner does before exchanges anyway)
        lkey0, lsum0, lgv0 = _local_sum_by_key(lk, lv, lm)
        rkey0, rsum0, rgv0 = _local_sum_by_key(rk, rv, rm)
        lk, lv, lm, oflow_l = _route(lkey0, lsum0, lgv0)
        rk, rv, rm, oflow_r = _route(rkey0, rsum0, rgv0)
        # local join-aggregate: per-key sums on each side, then product
        # of matching keys — sum_l(key) * sum_r(key) == sum over pairs
        # of l_val * r_val for that key
        lkey, lsum, lgv = _local_sum_by_key(lk, lv, lm)
        rkey, rsum, rgv = _local_sum_by_key(rk, rv, rm)
        cap = lkey.shape[0]
        # match l groups against r groups with a sorted binary search;
        # the search array must be monotone, so invalid slots take the
        # max word and validity rides along to reject collisions
        bias = jnp.uint64(SIGN64_BIAS)
        rw = (rkey.view(jnp.uint64) ^ bias)
        rw = jnp.where(rgv, rw, jnp.uint64(0xFFFFFFFFFFFFFFFF))
        # secondary key sorts valid entries before invalid sentinels so a
        # REAL key of INT64_MAX (word == sentinel) is found by the
        # left-search instead of an invalid slot
        inv_rank = jnp.where(rgv, jnp.uint64(0), jnp.uint64(1))
        srw, _, srv, srs = jax.lax.sort(
            (rw, inv_rank, rgv, rsum), num_keys=2, is_stable=True)
        lw = (lkey.view(jnp.uint64) ^ bias)
        pos = jnp.clip(jnp.searchsorted(srw, lw), 0, cap - 1)
        hit = (jnp.take(srw, pos) == lw) & jnp.take(srv, pos) & lgv
        prod = jnp.where(hit, lsum * jnp.take(srs, pos), 0.0)
        overflow = (oflow_l | oflow_r)[None]
        return lkey, prod, hit, overflow

    smapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(axis_name),) * 6,
        out_specs=(P(axis_name), P(axis_name), P(axis_name),
                   P(axis_name)))
    return _instrumented(_compile_watch.jit(smapped, "mesh_join_sum"), mesh)


def distributed_sort(mesh: Mesh, axis_name: str = "data",
                     slack: int = 4):
    """Global sort: range-routed all_to_all + local sort per device.

    The SPMD form of the engine's global sort (range exchange +
    per-partition sort, GpuSortExec + GpuRangePartitioning): device
    ranges come from the global min/max (pmin/pmax collectives), rows
    route to their range owner over one all_to_all, and each device
    sorts its range locally — device i then holds the i-th globally
    ordered run.  Per-region capacity is ``slack``x the even share;
    overflow (extreme skew) is reported via the returned flag rather
    than silently dropped.
    """
    from ..shims import get_shard_map
    shard_map = get_shard_map()
    n_dev = mesh.devices.size

    def step(keys, valid):
        kmax = jax.lax.pmax(
            jnp.max(jnp.where(valid, keys, jnp.int64(-2**62))), axis_name)
        kmin = jax.lax.pmin(
            jnp.min(jnp.where(valid, keys, jnp.int64(2**62))), axis_name)
        # span math in float64: int64 kmax-kmin wraps when the range
        # exceeds 2^63 (e.g. min near -2^62, max near 2^62)
        kminf = kmin.astype(jnp.float64)
        spanf = jnp.maximum(kmax.astype(jnp.float64) - kminf, 1.0)
        owner = ((keys.astype(jnp.float64) - kminf) / spanf *
                 (n_dev - 1e-9)).astype(jnp.int32)
        owner = jnp.clip(owner, 0, n_dev - 1)
        owner = jnp.where(valid, owner, n_dev)
        (okey,), ovalid, overflow_any = _route_to_owners(
            owner, [keys], [jnp.int64(2**62)], n_dev, axis_name,
            slack=slack)
        # local sort of this device's range (invalid slots sort last)
        sk = jnp.where(ovalid, okey, jnp.int64(2**62))
        sk, ovalid = jax.lax.sort((sk, ovalid), num_keys=1, is_stable=True)
        return sk, ovalid, overflow_any[None]

    smapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name), P(axis_name)))
    return _instrumented(_compile_watch.jit(smapped, "mesh_sort"), mesh)
