"""The one owner of JAX's persistent compilation cache directory.

JAX reads ``jax_compilation_cache_dir`` once, at the first compile that
consults the cache; a directory that moves afterwards is ignored, and
the directory is part of nothing else's key — so a cache that is
re-pointed per user, per process or per conf never hits.  Every engine
entry point (``TpuSession``, ``compile/aot.configure``) therefore goes
through :func:`enable`, and no other code path sets the directory:

- ``JAX_COMPILATION_CACHE_DIR`` set: the program uses it untouched (JAX
  itself already loaded it into ``jax.config``).
- unset: a fixed, git-ignored directory inside the checkout
  (``<repo>/.jax_cache``).  Never ``tempfile``, a user name, a pid or a
  clock: the path must be the same in the next process.

``aot.cacheDir`` keeps owning the AOT *manifest* only.  A run that
wants a deliberately cold cache (ci/compile_smoke.py) sets the
environment variable for its children.

The CPU test mesh switches the cache off altogether
(``jax_enable_compilation_cache=False`` in tests/conftest.py and
``__graft_entry__.dryrun_multichip``): XLA:CPU AOT results re-loaded on
a machine with different CPU features can SIGILL.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fallback when ENV_VAR is unset: fixed, inside the checkout,
#: listed in .gitignore and .chiprunignore
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: compiles quicker than this are cheaper to redo than to store (keeps
#: the directory small); ``persist_everything`` drops it
_MIN_COMPILE_SECS = 0.5


def cache_dir() -> str:
    """The directory the persistent cache lives in for this process."""
    return os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR


def enable(persist_everything: bool = False) -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`
    (idempotent) and return the directory.

    ``persist_everything`` drops the min-compile-time / min-entry-size
    thresholds so every engine program is written — what the AOT
    manifest's "an earlier run compiled this, so this first call is a
    cache load" claim needs (compile/aot.py)."""
    import jax
    d = cache_dir()
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    if persist_everything:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    elif jax.config.jax_persistent_cache_min_compile_time_secs \
            > _MIN_COMPILE_SECS:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          _MIN_COMPILE_SECS)
    return d
