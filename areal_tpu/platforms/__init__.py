"""Where the process keeps JAX's persistent compilation cache.

The device itself needs no abstraction here: JAX picks the platform
(`JAX_PLATFORMS=cpu` in the environment is how a CPU run is asked for), and
the collectives come from sharding annotations.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache, from this package's own location. The path is part
# of the cache key, so it must not move between runs.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> None:
    """Give JAX a persistent compilation cache, unless the environment
    already did: where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it
    itself and this sets nothing. Engines call it before their first
    compile; calling it again is harmless."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    os.makedirs(_DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
