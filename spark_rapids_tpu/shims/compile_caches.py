"""Release every compiled-executable cache the engine holds.

The engine memoizes jitted programs at several layers (fused
expression cores, staged whole-stage programs, join probe/expand
kernels, aggregate cores, window programs, mesh SPMD programs) keyed on
(op, schema, capacity bucket).  Long many-query processes on the
XLA:CPU backend accumulate thousands of live executables; past a
threshold LLVM's JIT code memory fails hard (segfault on the next
compile).  ``clear_compile_caches()`` drops every engine-held
executable reference and JAX's own caches so the arena can be
reclaimed; subsequent queries simply recompile.

(The TPU compiler is not subject to the local LLVM arena, but clearing
is equally safe there.)
"""
from __future__ import annotations


def clear_compile_caches() -> None:
    from ..exec import fused, staged, tpu_aggregate, tpu_join, tpu_window
    from ..exec import tpu_mesh_aggregate, tpu_mesh_join, tpu_mesh_sort

    fused._JIT_CACHE.clear()
    staged.TpuStagedCompute._JIT_CACHE.clear()
    tpu_aggregate.TpuHashAggregate._CORE_CACHE.clear()
    tpu_join.TpuHashJoinBase._PROBE_JIT.clear()
    tpu_join.TpuHashJoinBase._EXPAND_JIT.clear()
    tpu_join.TpuHashJoinBase._DIRECT_JIT.clear()
    tpu_window.TpuWindow._PROGRAMS.clear()
    tpu_mesh_aggregate.TpuMeshAggregate._PROGRAM_CACHE.clear()
    tpu_mesh_join.TpuMeshShuffledJoin._PROGRAM_CACHE.clear()
    tpu_mesh_sort.TpuMeshSort._PROGRAM_CACHE.clear()

    import jax
    jax.clear_caches()
