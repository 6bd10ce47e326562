"""Superstage compiler: one device dispatch per exchange-delimited
pipeline stage, with device-resident handoff between member operators.

Planner post-pass (runs after analysis/plan_verify.py):

- :mod:`.lower` classifies each operator's dispatch strategy
  (PROGRAM / CHAIN / BARRIER / BOUNDARY);
- :mod:`.carve` splits the plan into maximal exchange-delimited member
  regions, arms the members' sync-free paths, and wraps each region in
  an :class:`~..exec.superstage.TpuSuperstage`;
- the PV-STAGE verifier pass (analysis/plan_verify.py) re-checks the
  carved tree.

Conf: ``spark.rapids.tpu.sql.superstage`` (off switch),
``...superstage.minOps``.
"""
from .carve import carve_plan
from .lower import (BARRIER, BOUNDARY, CHAIN, PROGRAM, barrier_count,
                    classify, is_member, lower_region)

__all__ = [
    "carve_plan", "classify", "is_member", "lower_region",
    "barrier_count", "PROGRAM", "CHAIN", "BARRIER", "BOUNDARY",
]
