"""JAX persistent compilation cache for the repo's entry points.

:func:`enable` is called by ``chip_smoke.py``, ``benchmarks/run.py`` and the
examples before their first compile — never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it. Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(gitignored): the path is part of what a cache hit depends on, so it is never
derived from a temp dir, a pid or the clock.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
