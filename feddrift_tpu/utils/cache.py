"""Persistent XLA compile cache, shared by every entry point.

A machine that starts each call with no compiled code pays every program's
compile again; the conv round programs alone are minutes. The cache
directory is part of the cache key, so it must not move between the
processes that are meant to share it:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no directory (whoever launched the process placed the cache);
- otherwise: the fixed ``<checkout>/.jax_cache``.

Keyed by platform + HLO, so CPU and TPU executables coexist in one
directory. This is the only place in the repo that sets a cache directory.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Raises if the cache cannot be configured: a silently cold cache is a
    multi-minute tax on every process of a call.
    """
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", d)
    # every program, however quick to compile: a second process of the
    # same call then compiles nothing, and "no new cache entry" is an
    # exact check instead of one that depends on compile-time jitter
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d
