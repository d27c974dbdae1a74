"""JAX's persistent compilation cache for the entry points.

`JAX_COMPILATION_CACHE_DIR`, when set, is read by JAX itself and no code
here names another directory. When it is unset, an entry point calls
`enable_compile_cache()` at program start (never on import) and the
cache lives at a fixed path inside the checkout, `<repo>/.jax_cache`
(gitignored): the path is part of the cache key, so it never depends on
a temporary name, a pid or the time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
