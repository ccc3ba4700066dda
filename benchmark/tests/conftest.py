"""The benchmark's tests run on the CPU at a tiny size:

    python -m pytest benchmark/tests -q
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
