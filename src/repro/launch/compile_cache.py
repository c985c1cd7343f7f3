"""Where JAX keeps compiled programs between processes.

Entry points that compile a whole model (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/run.py``) call ``use_compile_cache``
once before compiling. Importing the library never touches the setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root (src/repro/launch/ -> three levels up); a fixed path,
# because the cache directory is part of every entry's key
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left as it is; otherwise the cache lives in ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
