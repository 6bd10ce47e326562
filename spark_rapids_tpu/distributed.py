"""Two-process query execution: map stage in a child executor process,
reduce stage in the parent, over the TCP shuffle wire.

Reference role: the executor-process split the reference inherits from
Spark — RapidsShuffleInternalManagerBase's write/read sides live in
DIFFERENT executor JVMs and meet through the MapOutputTracker + UCX
transport (RapidsShuffleInternalManagerBase.scala:66, UCX.scala:74).
Here the child process re-plans the same SQL (deterministic planning, the
closure-shipping role), runs every map stage of the exchange into its
ShuffleExecutorContext, and serves fetches; the parent plans the same
query, skips the local map stage, and reduces through the transport.

Failure handling (the lineage-recompute role): a dead executor surfaces
``ShuffleFetchFailedError`` from the reduce-side iterator; the runner
recovers by re-planning and re-running the map stage locally — Spark's
stage-retry semantics with the driver as the only surviving executor.

One process per chip: an accelerator belongs to the process that first
touched JAX, so a child that asked for it would fail or hang behind its
parent.  The child executor therefore runs on the CPU backend unless
``SPARK_RAPIDS_TPU_DIST_PLATFORM`` names another platform — on a
one-chip host CPU is the only thing that can work — and it prints the
platform it actually got rather than leaving it implicit.
"""
from __future__ import annotations

import multiprocessing as mp
import os
from typing import Dict, List, Optional, Tuple

QUERY_SHUFFLE_ID = 7001          # preassigned: both processes must agree


def _find_exchanges(phys):
    """All TpuShuffleExchange nodes in a physical tree (planning is
    deterministic, so parent and child find them in the same order)."""
    from .exec.exchange import TpuShuffleExchange
    out = []

    def walk(p):
        if isinstance(p, TpuShuffleExchange):
            out.append(p)
        for c in getattr(p, "children", []):
            walk(c)
    walk(phys)
    return out


def _make_session(tables: Dict[str, str], conf_overrides=None):
    from .api import TpuSession
    from .config import TpuConf
    conf = {"spark.rapids.tpu.sql.enabled": True,
            # deterministic planning between processes: AQE re-plans
            # from partition stats that differ per process
            "spark.rapids.tpu.sql.adaptive.enabled": False}
    conf.update(conf_overrides or {})
    s = TpuSession(TpuConf(conf))
    for name, path in tables.items():
        s.read.parquet(path).create_or_replace_temp_view(name)
    return s


def _child_executor_main(sql: str, tables: Dict[str, str], q_out, q_in):
    """Child process: plan the query, run the map stage of its (single)
    exchange into a served ShuffleExecutorContext, then serve fetches
    until the parent says stop."""
    try:
        import jax
        jax.config.update(
            "jax_platforms",
            os.environ.get("SPARK_RAPIDS_TPU_DIST_PLATFORM", "cpu"))
        platform = jax.devices()[0].platform
        print(f"[exec-child pid={os.getpid()}] platform={platform}",
              flush=True)
        from .shuffle.manager import MapOutputTracker, \
            ShuffleExecutorContext
        from .shuffle.tcp import TcpTransport
        s = _make_session(tables)
        phys = s._plan(s.sql(sql)._plan)
        exchanges = _find_exchanges(phys)
        assert len(exchanges) == 1, \
            f"two-process runner supports one exchange, got " \
            f"{len(exchanges)}"
        transport = TcpTransport("exec-child")
        tracker = MapOutputTracker()
        ctx = ShuffleExecutorContext("exec-child", transport, tracker)
        ex = exchanges[0]
        ex.attach_distributed(ctx, QUERY_SHUFFLE_ID, run_map=True)
        ex.ensure_materialized()
        map_ids = tracker.map_ids(QUERY_SHUFFLE_ID)
        q_out.put(("ready", transport.address, map_ids))
        q_in.get(timeout=300)
    except Exception as e:  # noqa: BLE001 - reported to the parent
        q_out.put(("error", f"{type(e).__name__}: {e}", []))
    finally:
        try:
            transport.close()
        except Exception:  # noqa: BLE001
            pass


class TwoProcessQueryRunner:
    """Drive one SQL query with its map stage in a child OS process."""

    def __init__(self, sql: str, tables: Dict[str, str]):
        self.sql = sql
        self.tables = tables
        self._child = None
        self._q_in = None

    def _spawn_child(self):
        ctx_mp = mp.get_context("spawn")
        q_out = ctx_mp.Queue()
        self._q_in = ctx_mp.Queue()
        self._child = ctx_mp.Process(
            target=_child_executor_main,
            args=(self.sql, self.tables, q_out, self._q_in),
            daemon=True)
        self._child.start()
        import queue as _queue
        import time as _time
        deadline = _time.monotonic() + 300
        while True:
            try:
                msg, addr, map_ids = q_out.get(timeout=2)
                break
            except _queue.Empty:
                if not self._child.is_alive():
                    raise RuntimeError(
                        "child executor died before reporting ready "
                        f"(exitcode={self._child.exitcode})") from None
                if _time.monotonic() > deadline:
                    raise RuntimeError(
                        "child executor timed out") from None
        if msg != "ready":
            raise RuntimeError(f"child executor failed: {addr}")
        return addr, map_ids

    def run(self, kill_child_before_reduce: bool = False):
        """Returns (rows, recovered): ``recovered`` is True when the
        reduce hit ShuffleFetchFailedError (dead executor) and the map
        stage re-ran locally (the stage-retry role)."""
        from .shuffle.iterator import ShuffleFetchFailedError
        from .shuffle.manager import MapOutputTracker, \
            ShuffleExecutorContext
        from .shuffle.tcp import TcpTransport
        child_addr, child_map_ids = self._spawn_child()

        s = _make_session(self.tables)
        phys = s._plan(s.sql(self.sql)._plan)
        exchanges = _find_exchanges(phys)
        assert len(exchanges) == 1
        transport = TcpTransport("exec-parent")
        transport.add_peer("exec-child", tuple(child_addr))
        tracker = MapOutputTracker()
        ctx = ShuffleExecutorContext("exec-parent", transport, tracker)
        for mid in child_map_ids:
            tracker.register_map_output(QUERY_SHUFFLE_ID, mid,
                                        "exec-child")
        exchanges[0].attach_distributed(ctx, QUERY_SHUFFLE_ID,
                                        run_map=False)
        if kill_child_before_reduce:
            self._child.terminate()
            self._child.join(timeout=10)
        recovered = False
        try:
            out = s.execute_physical(phys)
        except ShuffleFetchFailedError:
            # stage retry: the executor died; re-plan and re-run the
            # whole map stage locally (lineage recompute)
            recovered = True
            s2 = _make_session(self.tables)
            out = s2.sql(self.sql).to_arrow()
        finally:
            transport.close()
            self.stop()
        return out, recovered

    def stop(self):
        if self._q_in is not None:
            try:
                self._q_in.put("stop")
            except Exception:  # noqa: BLE001
                pass
        if self._child is not None:
            self._child.join(timeout=10)
            if self._child.is_alive():
                self._child.terminate()
            self._child = None


def run_two_process_query(sql: str, tables: Dict[str, str],
                          kill_child_before_reduce: bool = False):
    return TwoProcessQueryRunner(sql, tables).run(
        kill_child_before_reduce=kill_child_before_reduce)
