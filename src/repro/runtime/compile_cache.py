"""JAX's persistent compilation cache for the repo's entry points.

Called from a ``main()`` (``launch/serve.py``, ``chip_smoke.py``), never
at import time.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX,
which reads it itself.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(gitignored): a fixed path, since the path is part of the cache's key.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
