"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses here what the chip would refuse — a kernel over
its fast-memory budget, a tiling it cannot lower — at no chip time. The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this
file (on-chip-measurement guide, section 2). A compile that passes is
not a chip run.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep it out of the way around these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                if not hasattr(x, "dtype") else x.dtype,
                                sharding=sharding)


@pytest.mark.parametrize("n_zones", [256, 4096])
def test_pallas_geofence_compiles_for_v5e(one_chip, n_zones):
    """B=8,192 points against Z zones of 32 vertices: the served default
    (256) and a zone table past what the untiled kernel fit in VMEM."""
    from sitewhere_tpu.ops.pallas_geofence import points_in_zones_pallas

    pts = jax.ShapeDtypeStruct((8192,), jnp.float32, sharding=one_chip)
    verts = jax.ShapeDtypeStruct((n_zones, 32, 2), jnp.float32,
                                 sharding=one_chip)
    compiled = points_in_zones_pallas.lower(pts, pts, verts).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_step_compiles_for_v5e(one_chip):
    """The fused single-chip step at `__graft_entry__.entry()` shapes."""
    from __graft_entry__ import entry

    fn, args = entry()
    specs = jax.tree_util.tree_map(lambda x: _spec(x, one_chip), args)
    compiled = jax.jit(fn).lower(*specs).compile()
    stats = compiled.memory_analysis()
    assert stats is not None and stats.argument_size_in_bytes > 0
