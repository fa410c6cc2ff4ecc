"""JAX's persistent compilation cache, placed by the entry point.

The library sets no cache directory. Entry points (`chip_smoke.py`,
`bench.py`, the examples) call `enable_compile_cache` once, before their
first compile: JAX's own `JAX_COMPILATION_CACHE_DIR` wins when set, and the
fixed `<checkout>/.jax_cache` is used otherwise. The path is part of what a
later process must name to hit, so it is never derived from a temp dir, a pid
or the clock.
"""

from __future__ import annotations

import os


def enable_compile_cache(default_dir: str) -> str:
    """Point JAX's persistent cache at `JAX_COMPILATION_CACHE_DIR`, or at
    `default_dir` when that is unset, and cache every program regardless of
    compile time or size. Returns the directory in use."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_dir
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # JAX decides on first compile whether the cache is on and keeps that
    # decision; resetting lets a process that already compiled pick it up.
    compilation_cache.reset_cache()
    return path
