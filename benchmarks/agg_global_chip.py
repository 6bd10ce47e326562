"""What one batch of a global aggregate costs one chip: the readings
behind ``PERF.md`` section 5's global-aggregate table.

TPC-H Q6 (``select sum(l_extendedprice * l_discount) ... where`` a ship
date range, a discount band and a quantity bound) planned by the
engine on a tiny table, then its PARTIAL ``TpuHashAggregate`` driven
through ``_aggregate_batch`` over one batch of the benchmark cell's
shape: 2^20 slots, ``--rows`` live rows (0.91M: 4.55M rows in five
batches), the four columns Q6 reads drawn as ``chipbench/datagen/tpch.py``
draws them.  Variants:

- ``parent``: whatever the imported tree runs (``--tree`` puts another
  checkout first on ``sys.path``); on a tree without
  ``kernels/aggregate.single_group_plan`` that is the older path: the
  filter chain run eagerly (compaction indices, then a take a
  column), then ``agg_global_core`` with a sort of a constant key and
  a float64 scatter-add into one slot;
- on a tree with it, the folded global core three ways, by the function
  that sums the stacked DOUBLE lanes of the one group:
  ``scan`` (a): ``_segmented_totals``'s shift-and-add scan with its one
  segment; ``xla_reduce`` (b): ``jnp.sum``, XLA's reduce; ``halving``
  (c): the halves added pairwise, what the engine runs.

One JSON line a variant on stdout (and appended to ``--out``): the
first call with the persistent compile cache off (so it compiles), the
median of ``--reps`` warm wall-clock calls that end in
``block_until_ready``, and the revenue's relative gap to ``math.fsum``
of numpy's products over the rows the filter keeps.
Refuses to run anywhere but on a TPU unless ``--rehearse-cpu`` (which
prints no reading under a device's name)."""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time
from types import SimpleNamespace

Q6 = """select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= 8766 and l_shipdate < 9131
  and l_discount >= 0.05 and l_discount <= 0.07
  and l_quantity < 24"""


def q6_rows(n: int, seed: int):
    """Q6's four columns over ``n`` rows, as the cell's generator draws
    them, ship dates inside the year the scan's pushed filter keeps."""
    import numpy as np
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n)
    part = rng.integers(1, 1_000_001, n)
    retail_cents = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    return {"l_quantity": qty.astype(np.float64),
            "l_extendedprice": qty * retail_cents / 100.0,
            "l_discount": rng.integers(0, 11, n) / 100.0,
            "l_shipdate": rng.integers(8766, 9131, n).astype(np.int32)}


def partial_aggregate(session):
    """Q6's PARTIAL aggregate as the planner builds it, the filter chain
    folded into it."""
    from spark_rapids_tpu.exec.tpu_aggregate import TpuHashAggregate
    session.create_dataframe(q6_rows(64, 1), num_partitions=2) \
        .create_or_replace_temp_view("lineitem")
    session.sql(Q6).collect()
    node, = [n for n in session.last_physical_plan.collect_nodes()
             if isinstance(n, TpuHashAggregate) and n.pre_ops]
    return node


def summers(agg_k):
    import jax.numpy as jnp

    def scan(stack):
        n = stack.shape[1]
        one = SimpleNamespace(boundary=jnp.arange(n) == 0,
                              last_pos=jnp.full(1, n - 1, jnp.int32),
                              num_slots=1, num_groups=jnp.int32(1))
        return agg_k._segmented_totals(one, stack)
    return {"scan": scan,
            "xla_reduce": lambda stack: jnp.sum(stack, 1, keepdims=True),
            "halving": agg_k._one_group_totals}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None,
                    help="a checkout to import the engine from")
    ap.add_argument("--rows", type=int, default=910_000)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=37)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.tree or here))
    import jax
    import numpy as np
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.exec import tpu_aggregate as TA
    from spark_rapids_tpu.kernels import aggregate as agg_k
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"refusing: platform is {dev.platform}, not tpu",
              file=sys.stderr)
        return 2
    session = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": True}))
    node = partial_aggregate(session)
    # every first call below compiles: nothing comes from a cache
    jax.config.update("jax_enable_compilation_cache", False)
    rows = q6_rows(args.rows, args.seed)
    schema = node.children[0].output_schema
    batch = ColumnarBatch(
        schema, [Column.from_numpy(rows[f.name], f.dtype) for f in schema],
        args.rows)
    keep = ((rows["l_shipdate"] >= 8766) & (rows["l_shipdate"] < 9131) &
            (rows["l_discount"] >= 0.05) & (rows["l_discount"] <= 0.07) &
            (rows["l_quantity"] < 24))
    want = math.fsum((rows["l_extendedprice"] * rows["l_discount"])[keep])
    variants = {"parent": None}
    if hasattr(agg_k, "single_group_plan"):
        variants = summers(agg_k)
    for name, summer in variants.items():
        if summer is not None:
            agg_k._one_group_totals = summer
        TA.TpuHashAggregate._CORE_CACHE.clear()
        node._ws_memo.clear()

        def call():
            out = node._aggregate_batch(batch, TA.PARTIAL)
            return jax.block_until_ready([c.data for c in out.columns])
        t0 = time.perf_counter()
        got = call()
        first = time.perf_counter() - t0
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            call()
            times.append((time.perf_counter() - t0) * 1e3)
        revenue = float(np.asarray(got[0])[0])
        line = {"variant": name, "tree": args.tree or ".",
                "slots": batch.capacity, "rows": args.rows,
                "kept": int(keep.sum()), "revenue": revenue,
                "rel_gap": abs(revenue - want) / abs(want),
                "first_call_s": round(first, 2),
                "device": dev.platform, "device_kind": dev.device_kind}
        if dev.platform == "tpu":
            line.update(median_ms=statistics.median(times),
                        min_ms=min(times), max_ms=max(times))
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
