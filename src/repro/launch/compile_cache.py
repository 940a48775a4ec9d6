"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (`chip_smoke.py`, `examples/`, `benchmarks/`) call
`enable_compile_cache()` once, from their `main`, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this sets
nothing. Otherwise the cache goes to ``<checkout>/.jax_cache``: the path is
part of each entry's key, so it is fixed, never derived from a temporary
name, a pid or the time.
"""

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
