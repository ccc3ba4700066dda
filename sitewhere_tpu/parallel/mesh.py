"""Mesh construction: one `shard` axis over all available devices.

The hot path is embarrassingly parallel over devices (each shard owns a
disjoint slice of the device population), so a 1-D mesh suffices; tenants ride
the same axis (a tenant's devices spread over all shards, stats psum'd).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

SHARD_AXIS = "shard"


def make_mesh(n_shards: Optional[int] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over the first `n_shards` of `devices` (default: every
    device of the default backend). Too few devices is an error — the
    mesh never moves to another platform behind the caller's back. Tests
    run on virtual CPU devices by asking for them
    (`JAX_PLATFORMS=cpu` plus
    `XLA_FLAGS=--xla_force_host_platform_device_count=N`)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_shards is not None:
        if n_shards > len(devs):
            raise ValueError(
                f"requested {n_shards} shards, have {len(devs)} "
                f"{devs[0].platform} devices")
        devs = devs[:n_shards]
    return Mesh(np.asarray(devs), (SHARD_AXIS,))


def shard_axis_size(mesh: Mesh) -> int:
    return mesh.shape[SHARD_AXIS]
