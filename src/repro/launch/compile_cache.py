"""Where the entry points keep JAX's persistent compilation cache.

Called by ``chip_smoke.py`` and ``launch/serve.py`` before their first
compile — never at import. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing here overrides it. Otherwise the cache lives
at ``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed path,
because the path is part of the cache key and a directory that moves
between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
