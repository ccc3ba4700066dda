"""Pallas TPU kernel for point-in-polygon geofence containment.

The hottest op of the pipeline step (see ops/geofence.py — the TPU-native
replacement for the reference's per-event JTS containment at
ZoneTestRuleProcessor.java:47-52) as a hand-written VPU kernel: the batch of
points is tiled along sublanes, the zone axis rides the 128-wide lanes, and
the edge loop runs entirely in VMEM, producing the [B, Z] parity matrix in a
single pass with no [B, Z, V] intermediate in HBM. The grid tiles both
axes — blocks of points by blocks of zones, with the edge tables blocked
on the same lane axis — so fast-memory use per step is fixed by the tile
sizes, not by the zone count: any `pipeline.max_zones` compiles.

The XLA `lax.scan` implementation in ops/geofence.py stays as the reference
semantics (and the CPU / non-TPU path); this kernel is bit-identical on the
same inputs and is selected by the engines when their devices are TPUs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128      # TPU lane width: zone axis padding quantum
_BLOCK_B = 512    # points per grid step (multiple of 8 sublanes; measured
                  # best at Z>=256 on v5e vs 256/1024)
_BLOCK_Z = 512    # zones per grid step (multiple of the lane width): the
                  # [512, 512] int32 out tile and the step's temporaries
                  # stay a few MiB of VMEM at any zone count


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pip_kernel(py_ref, px_ref, y1_ref, x1_ref, y2_ref, x2_ref, out_ref,
                *, n_edges: int):
    """Crossing-number parity for one block of points against one block
    of zones.

    py/px: [Bb, 1] point coordinates (lat=y, lon=x).
    y1/x1/y2/x2: [V, Bz] edge endpoint tables (zones along lanes).
    out: [Bb, Bz] int32 containment parity (0/1).
    """
    py = py_ref[:]                                   # [Bb, 1]
    px = px_ref[:]

    # Parity is carried and stored as int32 (Mosaic cannot carry i1
    # vectors through scf loops); callers compare != 0.
    def edge_step(v, parity):
        y1 = y1_ref[pl.ds(v, 1), :]                  # [1, Bz]
        x1 = x1_ref[pl.ds(v, 1), :]
        y2 = y2_ref[pl.ds(v, 1), :]
        x2 = x2_ref[pl.ds(v, 1), :]
        straddles = (y1 > py) != (y2 > py)           # [Bb, Zp]
        dy = y2 - y1
        safe_dy = jnp.where(dy == 0.0, 1.0, dy)
        x_at_y = x1 + (x2 - x1) * (py - y1) / safe_dy
        crosses = straddles & (px < x_at_y)
        return parity ^ crosses.astype(jnp.int32)

    parity0 = jnp.zeros(out_ref.shape, jnp.int32)
    out_ref[:] = jax.lax.fori_loop(0, n_edges, edge_step, parity0)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_z", "interpret"))
def points_in_zones_pallas(lat: jnp.ndarray, lon: jnp.ndarray,
                           vertices: jnp.ndarray, *, block_b: int = _BLOCK_B,
                           block_z: int = _BLOCK_Z,
                           interpret: bool = False) -> jnp.ndarray:
    """Even-odd containment of points [B] in polygons [Z, V, 2] -> bool [B, Z].

    Semantically identical to ops.geofence.points_in_zones (XLA scan); padded
    zones/edges are degenerate (zero-length) so they never toggle parity.
    """
    B = lat.shape[0]
    Z, V = vertices.shape[0], vertices.shape[1]
    Bp = _round_up(max(B, 1), block_b)
    # fewest zone tiles of at most block_z lanes, padded evenly
    z_lanes = _round_up(max(Z, 1), _LANES)
    n_tiles = -(-z_lanes // block_z)
    block_z = _round_up(-(-z_lanes // n_tiles), _LANES)
    Zp = block_z * n_tiles

    starts = vertices                                 # [Z, V, 2]
    ends = jnp.roll(vertices, shift=-1, axis=1)
    # [V, Zp] edge tables; pad zones with zero-length edges (inert).
    def table(a):
        t = a.T.astype(jnp.float32)                   # [V, Z]
        return jnp.pad(t, ((0, 0), (0, Zp - Z)))

    y1, x1 = table(starts[:, :, 0]), table(starts[:, :, 1])
    y2, x2 = table(ends[:, :, 0]), table(ends[:, :, 1])

    py = jnp.pad(lat.astype(jnp.float32), (0, Bp - B)).reshape(Bp, 1)
    px = jnp.pad(lon.astype(jnp.float32), (0, Bp - B)).reshape(Bp, 1)

    grid = (Bp // block_b, Zp // block_z)
    point_spec = pl.BlockSpec((block_b, 1), lambda i, j: (i, 0),
                              memory_space=pltpu.VMEM)
    edge_spec = pl.BlockSpec((V, block_z), lambda i, j: (0, j),
                             memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_pip_kernel, n_edges=V),
        grid=grid,
        in_specs=[point_spec, point_spec,
                  edge_spec, edge_spec, edge_spec, edge_spec],
        out_specs=pl.BlockSpec((block_b, block_z), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Bp, Zp), jnp.int32),
        cost_estimate=pl.CostEstimate(
            flops=8 * Bp * Zp * V,
            bytes_accessed=4 * (2 * Bp + 4 * V * Zp + Bp * Zp),
            transcendentals=0),
        interpret=interpret,
    )(py, px, y1, x1, y2, x2)
    return out[:B, :Z] != 0
