"""Where JAX keeps its persistent compilation cache.

A cold process on the chip pays for every compile of the fused step; the
persistent cache lets the next process in the same checkout skip them.
The cache key includes the directory, so it must not move between runs:
an operator's ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself),
otherwise the cache sits at a fixed ``.jax_cache/`` in the checkout root.

Called first by the entry points (``python -m sitewhere_tpu``,
``chip_smoke.py``), never at import, so importing the package or running
the tests writes no cache.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its place and return
    the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
