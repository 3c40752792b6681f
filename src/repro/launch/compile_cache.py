"""Where JAX's persistent compilation cache lives for this repository's
entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``launch/serve.py``).

With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself and
nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``
(git-ignored): a fixed path, because the directory is part of what a later
process must find again — a temporary, pid- or time-derived directory would
never hit.  The test suite does not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
