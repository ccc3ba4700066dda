"""Test harness: the tests run on the CPU, on a virtual 8-device mesh.

SURVEY.md §4 consequence: unlike the reference (no multi-node harness, live
brokers required), every test here is deterministic and in-proc — sharding is
exercised on `--xla_force_host_platform_device_count=8` CPU devices standing
in for a v5e-8 slice. The chip itself is exercised by `python chip_smoke.py`
through the chip tool; tests/test_tpu_compile.py compiles for a described
v5e chip without one. Env vars must be set before any backend starts.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take a chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# JAX reads JAX_PLATFORMS when it is imported; the config update also
# covers anything that imported jax before this file (no backend has
# started yet either way)
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tmp_data_dir(tmp_path):
    return str(tmp_path / "swtpu-data")
