"""JAX touchpoint shim — the ShimLoader role.

Reference: ShimLoader.scala:26 + shims/ (12 modules): every touchpoint
with version-unstable Spark internals goes through a SparkShims trait
selected at runtime.  The TPU build's unstable dependency surface is the
**JAX API** (modules move between jax.experimental and core across
releases), so version-sensitive JAX access goes through one shim class.

There is exactly one provider, for the one JAX this repo is installed
with (pyproject.toml pins it); an API the next JAX moves is repaired
here or at its call site, not probed for at run time.  The override
conf (spark.rapids.tpu.shims-provider-override) mirrors
spark.rapids.shims-provider-override.
"""
from __future__ import annotations

import importlib
from typing import Optional, Type

import jax


class JaxShim:
    """Every version-sensitive JAX API the engine uses, for jax 0.9."""

    @staticmethod
    def shard_map():
        return jax.shard_map

    @staticmethod
    def key_array(seed: int):
        import jax.random as jr
        return jr.key(seed)


_active: Optional[Type[JaxShim]] = None


def detect_shim() -> Type[JaxShim]:
    """ShimLoader.detectShimProvider role: the override conf, else the
    provider for the installed JAX."""
    global _active
    if _active is not None:
        return _active
    from ..config import get_active, SHIM_PROVIDER_OVERRIDE
    override = get_active().get(SHIM_PROVIDER_OVERRIDE)
    if override:
        mod, _, cls = override.rpartition(".")
        _active = getattr(importlib.import_module(mod), cls)
    else:
        _active = JaxShim
    return _active


def get_shard_map():
    return detect_shim().shard_map()
