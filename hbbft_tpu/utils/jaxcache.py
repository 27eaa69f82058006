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
from typing import Callable

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


#: JAX's own events -> the counters :func:`count_setup_seconds` adds them
#: into.  ``compile_s`` is the backend step as a whole, so on a cache hit
#: it holds the read that ``cache_read_s`` times alone.
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "crypto.jax.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "crypto.jax.lower_s",
    "/jax/core/compile/backend_compile_duration": "crypto.jax.compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "crypto.jax.cache_read_s",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "crypto.jax.cache_hits",
    "/jax/compilation_cache/cache_misses": "crypto.jax.cache_misses",
}


def count_setup_seconds(metrics) -> Callable[[], None]:
    """Sum where a process's set-up goes, by JAX's own monitoring events,
    into ``metrics`` counters: seconds of tracing, lowering, backend
    compile and persistent-cache reads (``crypto.jax.*_s``, every program
    of the process together) and the programs the persistent cache held
    and did not hold (``crypto.jax.cache_hits``, ``crypto.jax.cache_misses``:
    a miss compiled, and was written to the cache only if it took
    ``jax_persistent_cache_min_compile_time_secs`` or more).
    Returns the function that stops the counting."""
    import jax.monitoring

    def duration(event: str, seconds: float, **_) -> None:
        name = _DURATIONS.get(event)
        if name is not None:
            metrics.count(name, seconds)

    def event(name: str, **_) -> None:
        name = _EVENTS.get(name)
        if name is not None:
            metrics.count(name)

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(event)

    def stop() -> None:
        jax.monitoring.unregister_event_duration_listener(duration)
        jax.monitoring.unregister_event_listener(event)

    return stop
