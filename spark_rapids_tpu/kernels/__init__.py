"""Relational compute kernels — the cuDF/libcudf role (SURVEY.md §2.10.1),

implemented as JAX/XLA computations. Modules: canon (sortable key words),
sort, aggregate (sort + segmented reduce; bucket table), join (sorted binary-search probe), strings, basic
(compaction, hashing)."""
from . import basic, canon, sort, aggregate, join, strings  # noqa: F401
