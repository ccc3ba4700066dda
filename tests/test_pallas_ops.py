"""Pallas kernel vs XLA-reference parity (interpret mode on CPU devices).

Mirrors the blueprint's kernel-test strategy (SURVEY.md §4): deterministic
unit tests of hand-written kernels against the pure-XLA/NumPy reference
semantics, runnable without TPU hardware.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.ops.geofence import (
    points_in_zones, resolve_geofence_impl)
from sitewhere_tpu.ops.pallas_geofence import points_in_zones_pallas


def _random_world(seed, B=97, Z=5, V=7):
    rng = np.random.default_rng(seed)
    # Random convex-ish polygons: center + sorted angular offsets
    centers = rng.uniform(-50, 50, (Z, 2))
    verts = np.zeros((Z, V, 2), np.float32)
    for z in range(Z):
        nv = int(rng.integers(3, V + 1))
        ang = np.sort(rng.uniform(0, 2 * np.pi, nv))
        r = rng.uniform(2, 12, nv)
        pts = centers[z] + np.stack([r * np.sin(ang), r * np.cos(ang)], 1)
        verts[z, :nv] = pts
        verts[z, nv:] = pts[-1]  # pad by repeating last vertex (inert edges)
    lat = rng.uniform(-70, 70, B).astype(np.float32)
    lon = rng.uniform(-70, 70, B).astype(np.float32)
    return lat, lon, verts


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_containment_matches_xla(seed):
    lat, lon, verts = _random_world(seed)
    ref = np.asarray(points_in_zones(jnp.asarray(lat), jnp.asarray(lon),
                                     jnp.asarray(verts)))
    got = np.asarray(points_in_zones_pallas(
        jnp.asarray(lat), jnp.asarray(lon), jnp.asarray(verts),
        interpret=True))
    assert got.shape == ref.shape
    np.testing.assert_array_equal(got, ref)


def test_pallas_containment_odd_shapes():
    # B not a multiple of the block, Z not a multiple of lanes, single zone
    lat, lon, verts = _random_world(7, B=3, Z=1, V=4)
    ref = np.asarray(points_in_zones(jnp.asarray(lat), jnp.asarray(lon),
                                     jnp.asarray(verts)))
    got = np.asarray(points_in_zones_pallas(
        jnp.asarray(lat), jnp.asarray(lon), jnp.asarray(verts),
        interpret=True))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("Z,block_z", [(2100, 512), (600, 256)])
def test_pallas_containment_tiles_the_zone_axis(Z, block_z):
    """Z beyond one zone tile (and beyond the 2,048 zones the untiled
    kernel's whole-table VMEM layout could hold): several zone tiles,
    each with its own block of the edge tables, bit-identical to XLA."""
    lat, lon, verts = _random_world(11, B=600, Z=Z, V=6)
    ref = np.asarray(points_in_zones(jnp.asarray(lat), jnp.asarray(lon),
                                     jnp.asarray(verts)))
    got = np.asarray(points_in_zones_pallas(
        jnp.asarray(lat), jnp.asarray(lon), jnp.asarray(verts),
        block_z=block_z, interpret=True))
    assert got.shape == (600, Z) and ref.any()
    np.testing.assert_array_equal(got, ref)


def test_resolve_geofence_impl():
    assert resolve_geofence_impl("auto", "tpu") == "pallas"
    assert resolve_geofence_impl("auto", "cpu") == "xla"
    assert resolve_geofence_impl("xla", "tpu") == "xla"
    assert resolve_geofence_impl("pallas_interpret", "cpu") == "pallas_interpret"


def test_engine_uses_interpret_impl_end_to_end():
    """Full fused step with the pallas (interpret) containment kernel."""
    from sitewhere_tpu.model import (
        AlertLevel, Area, Device, DeviceAssignment, DeviceType, Zone)
    from sitewhere_tpu.model.common import Location
    from sitewhere_tpu.model.event import DeviceEventType
    from sitewhere_tpu.pipeline.engine import GeofenceRule, PipelineEngine
    from sitewhere_tpu.registry import DeviceManagement, RegistryTensors

    dm = DeviceManagement()
    dtype = dm.create_device_type(DeviceType(token="sensor"))
    area = dm.create_area(Area(token="a"))
    dm.create_zone(Zone(token="z", area_id=area.id, bounds=[
        Location(0.0, 0.0), Location(0.0, 10.0), Location(10.0, 10.0),
        Location(10.0, 0.0)]))
    tensors = RegistryTensors(max_devices=64, max_zones=4,
                              max_zone_vertices=8)
    tensors.attach(dm, "t1")
    d = dm.create_device(Device(token="dev-0", device_type_id=dtype.id))
    dm.create_device_assignment(DeviceAssignment(
        token="as-0", device_id=d.id, area_id=area.id))

    eng = PipelineEngine(tensors, batch_size=16,
                         geofence_impl="pallas_interpret")
    assert eng.geofence_impl == "pallas_interpret"
    eng.add_geofence_rule(GeofenceRule(token="fence", zone_token="z",
                                       condition="inside",
                                       alert_level=AlertLevel.WARNING))
    eng.start()
    idx = eng.packer.devices.lookup("dev-0")
    now = eng.packer.epoch_base_ms
    batch = eng.packer.pack_columns(
        np.array([idx, idx], np.int32),
        np.array([int(DeviceEventType.LOCATION)] * 2, np.int32),
        np.array([now, now + 1], np.int64),
        lat=np.array([5.0, 55.0], np.float32),
        lon=np.array([5.0, 55.0], np.float32))
    out = eng.submit(batch)
    fired = np.asarray(out.geofence_fired)
    assert fired[0] and not fired[1]
