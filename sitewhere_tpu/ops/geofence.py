"""Vectorized geofencing: point-in-polygon over all zones at once.

The TPU replacement for the reference's per-event JTS containment test
(ZoneTestRuleProcessor.java:47-52: cached JTS polygon per zone,
poly.contains(point) per location event): all B location events are tested
against all Z zone polygons simultaneously with the crossing-number
(even-odd) algorithm, scanning the padded vertex dimension with `lax.scan`
so the [B,Z] working set stays small (never materializing [B,Z,V]).

Zones are padded to V vertices by repeating the last vertex
(registry/tensors.py): degenerate zero-length edges satisfy y1==y2 and never
toggle crossing parity, so padding is semantically inert.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from sitewhere_tpu.model.event import DeviceEventType
from sitewhere_tpu.ops.pack import EventBatch


@struct.dataclass
class ZoneTable:
    """Zone geometry + scoping, shapes [Z] / [Z,V,2]."""

    vertices: np.ndarray   # f32 [Z,V,2] (lat, lon)
    nvert: np.ndarray      # int32 [Z]
    tenant_idx: np.ndarray  # int32 [Z]
    active: np.ndarray     # bool [Z]

    @property
    def num_zones(self) -> int:
        return self.nvert.shape[0]


class GeofenceCondition:
    INSIDE = 0   # fire when the point IS in the zone
    OUTSIDE = 1  # fire when the point is NOT in the zone
    ENTER = 2    # fire when the point is in the zone and the device's
                 # previous location was not (previous_locations)
    BY_NAME = {"inside": INSIDE, "outside": OUTSIDE, "enter": ENTER}


@struct.dataclass
class GeofenceRuleTable:
    """Rules binding zones to alert outcomes, shapes [G].

    Mirrors ZoneTestRuleProcessor configuration: zone token + containment
    condition + alert type/level/message to fire.
    """

    active: np.ndarray       # bool
    zone_row: np.ndarray     # int32 row into ZoneTable
    condition: np.ndarray    # int32 GeofenceCondition
    alert_level: np.ndarray  # int32
    alert_type_idx: np.ndarray  # int32


def empty_geofence_table(max_rules: int) -> GeofenceRuleTable:
    zi = np.zeros(max_rules, np.int32)
    return GeofenceRuleTable(active=np.zeros(max_rules, bool), zone_row=zi,
                             condition=zi.copy(), alert_level=zi.copy(),
                             alert_type_idx=zi.copy())


def points_in_zones(lat: jnp.ndarray, lon: jnp.ndarray,
                    vertices: jnp.ndarray) -> jnp.ndarray:
    """Even-odd containment: points [B] against polygons [Z,V,2] -> bool [B,Z].

    Scans edges (v, v+1 mod V) accumulating crossing parity of a rightward ray
    from each point. Working set per step: [B,Z] booleans.
    """
    V = vertices.shape[1]
    # Edge endpoints per step: start = vertices[:, v], end = vertices[:, (v+1)%V]
    starts = vertices                                   # [Z,V,2]
    ends = jnp.roll(vertices, shift=-1, axis=1)         # [Z,V,2]
    px = lon[:, None]  # [B,1] x = longitude
    py = lat[:, None]  # [B,1] y = latitude

    def edge_step(parity, edge):
        (y1, x1, y2, x2) = edge                         # each [Z]
        y1b, y2b = y1[None, :], y2[None, :]             # [1,Z]
        x1b, x2b = x1[None, :], x2[None, :]
        straddles = (y1b > py) != (y2b > py)            # [B,Z]
        dy = y2b - y1b
        safe_dy = jnp.where(dy == 0, 1.0, dy)
        x_at_y = x1b + (x2b - x1b) * (py - y1b) / safe_dy
        crosses = straddles & (px < x_at_y)
        return parity ^ crosses, None

    edges = (starts[:, :, 0].T, starts[:, :, 1].T,      # [V,Z] each
             ends[:, :, 0].T, ends[:, :, 1].T)
    # Derive the initial parity from the points so it inherits their
    # varying-manual-axes under shard_map (a plain jnp.zeros would be
    # unvarying and fail lax.scan's carry type check).
    parity0 = jnp.broadcast_to((lat > jnp.inf)[:, None],
                               (lat.shape[0], vertices.shape[0]))
    parity, _ = jax.lax.scan(edge_step, parity0, edges)
    return parity


def previous_locations(dev: jnp.ndarray, ts: jnp.ndarray,
                       is_loc: jnp.ndarray, lat: jnp.ndarray,
                       lon: jnp.ndarray, last_location: jnp.ndarray,
                       last_location_ts: jnp.ndarray):
    """Each row's previous location of its device, for the `enter`
    condition: (lat [B], lon [B], has [B]).

    For a location row e of device d, the previous location is that of
    d's latest location row in the same batch that orders before e by
    (date, batch position); failing that, the state's last location of d
    where `last_location_ts` is set; failing both, there is none (`has`
    False). Only `is_loc` rows (valid location events) count as
    predecessors. No history is kept beyond the state's one location, so
    a report dated before the state's last location still compares with
    that state.
    """
    B = dev.shape[0]
    D = last_location.shape[0]
    pos = jnp.arange(B, dtype=jnp.int32)
    key = jnp.where(is_loc, dev, D)
    # primary device, then date, then batch position
    order = jnp.lexsort((pos, ts, key))
    skey = key[order]
    same = jnp.concatenate([jnp.zeros((1,), bool), skey[1:] == skey[:-1]])
    before = jnp.concatenate([order[:1], order[:-1]])
    in_batch = jnp.zeros((B,), bool).at[order].set(same) & is_loc
    prev_row = jnp.zeros((B,), order.dtype).at[order].set(before)
    held = last_location_ts[dev] != jnp.iinfo(jnp.int32).min
    state_loc = last_location[dev]
    plat = jnp.where(in_batch, lat[prev_row], state_loc[:, 0])
    plon = jnp.where(in_batch, lon[prev_row], state_loc[:, 1])
    return plat, plon, in_batch | held


def resolve_geofence_impl(impl: str, platform: str) -> str:
    """Resolve an `auto` containment implementation choice for a platform.

    `pallas` (the hand-written VPU kernel in ops/pallas_geofence.py) on real
    TPUs; the XLA scan everywhere else (CPU shard meshes, interpret-less
    debugging). Explicit choices pass through.
    """
    if impl == "auto":
        return "pallas" if platform == "tpu" else "xla"
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(
            f"geofence impl {impl!r}: expected one of "
            f"'auto', 'xla', 'pallas', 'pallas_interpret'")
    return impl


def _containment(lat: jnp.ndarray, lon: jnp.ndarray, vertices: jnp.ndarray,
                 impl: str) -> jnp.ndarray:
    # Below one lane-width of zones the pallas kernel pads Z to 128 and wastes
    # most of the VPU; the XLA scan measures faster there (v5e), so "pallas"
    # only engages at Z >= 128 (explicit "pallas_interpret" always runs the
    # kernel — that mode exists for CPU correctness tests).
    if impl == "xla" or (impl == "pallas" and vertices.shape[0] < 128):
        return points_in_zones(lat, lon, vertices)
    from sitewhere_tpu.ops.pallas_geofence import points_in_zones_pallas
    return points_in_zones_pallas(lat, lon, vertices,
                                  interpret=(impl == "pallas_interpret"))


def eval_geofence_rules(batch: EventBatch, zones: ZoneTable,
                        rules: GeofenceRuleTable,
                        impl: str = "xla",
                        prev=None) -> Dict[str, jnp.ndarray]:
    """Evaluate geofence rules against the location events of a batch.

    `prev` is previous_locations' (lat, lon, has) for the `enter`
    condition, or None where no `enter` rule is installed: the previous
    points are then neither tested nor traced. With `prev` the current
    and previous points are tested in one containment call over 2B
    points, with the same kernel and arithmetic.

    Returns per-event outputs (shape [B]):
      fired:       bool, any geofence rule fired
      fired_count: int32
      first_rule:  int32 lowest-index fired rule (-1 if none)
      alert_level: int32 max alert level among fired rules
    and the raw containment matrix `inside` [B,Z] (device-state / analytics
    reuse it without recomputing).
    """
    is_location = batch.event_type == DeviceEventType.LOCATION
    event_ok = batch.valid & is_location                        # [B]

    if prev is None:
        inside = _containment(batch.lat, batch.lon, zones.vertices,
                              impl)                              # [B,Z]
    else:
        B = batch.lat.shape[0]
        both = _containment(jnp.concatenate([batch.lat, prev[0]]),
                            jnp.concatenate([batch.lon, prev[1]]),
                            zones.vertices, impl)                # [2B,Z]
        inside, prev_inside = both[:B], both[B:]
    zone_ok = (zones.active[None, :]
               & ((zones.tenant_idx[None, :] == 0)
                  | (zones.tenant_idx[None, :] == batch.tenant_idx[:, None])))
    inside_scoped = inside & zone_ok

    # Gather per-rule containment: [B,G]
    rule_inside = inside_scoped[:, rules.zone_row]
    rule_zone_ok = zone_ok[:, rules.zone_row]
    cond_met = jnp.where(rules.condition[None, :] == GeofenceCondition.INSIDE,
                         rule_inside, rule_zone_ok & ~rule_inside)
    if prev is not None:
        # the device came from outside the zone, or from nowhere
        was_inside = prev[2][:, None] & (prev_inside & zone_ok)[
            :, rules.zone_row]
        cond_met = jnp.where(
            rules.condition[None, :] == GeofenceCondition.ENTER,
            rule_inside & ~was_inside, cond_met)
    fired_matrix = (rules.active[None, :] & event_ok[:, None] & cond_met)

    fired_count = jnp.sum(fired_matrix, axis=1, dtype=jnp.int32)
    fired = fired_count > 0
    G = rules.zone_row.shape[0]
    rule_ids = jnp.arange(G, dtype=jnp.int32)[None, :]
    first_rule = jnp.min(jnp.where(fired_matrix, rule_ids, G), axis=1)
    first_rule = jnp.where(fired, first_rule, -1).astype(jnp.int32)
    alert_level = jnp.max(
        jnp.where(fired_matrix, rules.alert_level[None, :], -1), axis=1
    ).astype(jnp.int32)
    return {
        "fired": fired,
        "fired_count": fired_count,
        "first_rule": first_rule,
        "alert_level": alert_level,
        "inside": inside_scoped,
    }
