"""spark_rapids_tpu — TPU-native columnar SQL execution framework.

A ground-up re-design of the RAPIDS Accelerator for Apache Spark
(reference: /root/reference, studied in SURVEY.md) for TPU hardware:
JAX/XLA programs in place of cuDF, an HBM arena + spill catalog in
place of RMM, and ICI/DCN collectives (jax.sharding over a Mesh) in place
of UCX shuffle.

Layering (bottom → top), mirroring SURVEY.md §1:
  columnar/   device batch substrate (GpuColumnVector role)
  kernels/    relational compute kernels (cuDF/libcudf role)
  expr/       expression library (GpuExpression role)
  exec/       physical operators (GpuExec role)
  plan/       planner: wrap/tag/convert + TypeSig (GpuOverrides role)
  memory/     arena, spill tiers, semaphore (RMM/RapidsBufferCatalog role)
  shuffle/    partitioners + shuffle manager + transports (UCX role)
  io/         scans and writers (GpuParquetScan role)
  udf/        Python bytecode -> expression compiler (udf-compiler role)
  parallel/   device mesh, collectives, distributed exchange
  api/        user-facing session/DataFrame API (the Spark surface)
"""
import jax

# SQL semantics default to 64-bit longs/doubles (Spark's bigint/double);
# enable x64 before any array is created.
jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"
