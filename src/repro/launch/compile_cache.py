"""JAX's persistent compilation cache for the entry points that compile
the model (``launch/serve.py``, ``launch/server.py --backend engine``,
``chip_smoke.py``). Call ``enable_compile_cache`` from ``main``, never
at import."""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/src/repro/launch/compile_cache.py -> <repo>/.jax_cache
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins where it is set. Otherwise the
    cache lives in ``.jax_cache`` at the root of the checkout (listed in
    ``.gitignore``): a fixed path, so that every later run from this
    checkout finds the programs earlier runs compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
