"""Persistent XLA compilation cache setup.

The crypto kernels are large graphs (batched 381-bit limb arithmetic,
Miller-loop scans); first compilation is expensive.  Pointing JAX at an
on-disk cache makes every later process start (tests, bench, the
crypto-plane worker) reuse the compiled executables.

The directory is ``JAX_COMPILATION_CACHE_DIR`` where the environment
sets it (JAX reads that variable itself; nothing here overrides it) and
the fixed ``<checkout>/.jax_cache`` otherwise — the path is part of the
cache key, so a directory that moves never hits.
"""

from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
