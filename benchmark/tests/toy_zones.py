"""A toy zoned deployment for the tests, never a cell: `devices` vehicles
sending one position report per record, one zone (`zone`, a polygon of
(latitude, longitude) vertices) and one geofence rule that raises an
alert for every report inside it. `test_deployments.py` copies this file
into a deployments directory of its own, beside a configuration, a mix
and tiny sizes, to show that a zoned deployment is files added and
nothing edited.

Traffic: each record one location of a device drawn uniformly over the
fleet, at a point drawn uniformly from the mix's `box` of (latitude,
longitude) corners.

The reference: every report folds into its device's count, the last
report of each device wins by event date, every report is persisted as
it was sent, and every report inside the zone, by the even-odd rule,
raises one alert, dated as the report, on its device.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from benchmark.reference import rows, unmatched
from benchmark.world import World, attach_devices

ZONE_TYPE = "toy.inside"
LIMITS = {
    "lost_events": 0,
    "uncommitted_records": 0,
    "dead_lettered": 0,
    "count_mismatch_devices": 0,
    "last_location_mismatches": 0,
    "persisted_row_mismatches": 0,
    "geofence_alert_mismatches": 0,
    "lane_drops": 0,
}
CONTROL_FAILS = ("last_location_mismatches",)
FAULT_FIELD = "latitude"


def n_devices(cfg: Dict) -> int:
    return int(cfg["devices"])


def rule_dicts(cfg: Dict) -> List[Dict]:
    return [{"type": "geofence", "token": "geo-0", "zone_token": "zone-0",
             "condition": "inside", "alert_type": ZONE_TYPE,
             "alert_level": "WARNING"}]


def provision(instance, cfg: Dict) -> None:
    """The device type, one area with the zone, and the devices with
    their assignments, through the control-plane API."""
    from sitewhere_tpu.model import (
        Area, Device, DeviceAssignment, DeviceType, Zone)
    from sitewhere_tpu.model.common import Location

    reg = instance.get_tenant_engine(cfg["tenant"]).registry
    dtype = reg.create_device_type(DeviceType(token="toy-vehicle"))
    area = reg.create_area(Area(token="area-0"))
    reg.create_zone(Zone(token="zone-0", area_id=area.id, bounds=[
        Location(lat, lon) for lat, lon in cfg["zone"]]))
    for i in range(n_devices(cfg)):
        device = reg.create_device(Device(token=f"veh-{i}",
                                          device_type_id=dtype.id))
        reg.create_device_assignment(DeviceAssignment(
            token=f"as-{i}", device_id=device.id, area_id=area.id))


def attach(instance, cfg: Dict) -> World:
    return attach_devices(instance, cfg,
                          [f"veh-{i}" for i in range(n_devices(cfg))])


def describe(cfg: Dict) -> str:
    return f"{cfg['name']} devices={n_devices(cfg)} zones=1"


def events_per_record(mix: Dict) -> int:
    return 1


@dataclass
class Traffic:
    """One location per record, in publish order."""

    per_record: int
    record_dev: np.ndarray   # [N] device number
    ts: np.ndarray           # [N] int64 ms, unique, increasing
    lat: np.ndarray          # [N] float32
    lon: np.ndarray          # [N] float32

    @property
    def n(self) -> int:
        return int(self.record_dev.shape[0])

    def prefix(self, n_records: int) -> "Traffic":
        return Traffic(1, self.record_dev[:n_records], self.ts[:n_records],
                       self.lat[:n_records], self.lon[:n_records])


def make_traffic(world: World, mix: Dict, seed: int, n_records: int,
                 base_ms: int) -> Traffic:
    rng = np.random.default_rng([seed, 3])
    (lat0, lon0), (lat1, lon1) = mix["box"]
    return Traffic(1, rng.integers(0, world.n, n_records),
                   base_ms + np.arange(n_records, dtype=np.int64),
                   rng.uniform(lat0, lat1, n_records).astype(np.float32),
                   rng.uniform(lon0, lon1, n_records).astype(np.float32))


def encode_records(world: World, traffic: Traffic,
                   mix: Dict) -> List[Tuple[bytes, bytes]]:
    import msgpack

    records = []
    for d, ts, lat, lon in zip(traffic.record_dev.tolist(),
                               traffic.ts.tolist(), traffic.lat.tolist(),
                               traffic.lon.tolist()):
        token = world.tokens[d]
        request = {"device_token": token, "measurements": [], "alerts": [],
                   "locations": [{"event_type": 1, "latitude": lat,
                                  "longitude": lon, "elevation": 0.0,
                                  "event_date": ts}]}
        envelope = {"sourceId": "toy", "deviceToken": token,
                    "kind": "DeviceEventBatch", "request": request,
                    "metadata": {}}
        records.append((token.encode(),
                        msgpack.packb(envelope, use_bin_type=True)))
    return records


def inside(lat: np.ndarray, lon: np.ndarray, polygon) -> np.ndarray:
    """The even-odd rule in float32: a point is inside when a ray from it
    toward rising longitude crosses an odd number of the polygon's
    edges."""
    odd = np.zeros(lat.shape, bool)
    vertices = np.asarray(polygon, np.float32)
    for (y1, x1), (y2, x2) in zip(vertices, np.roll(vertices, -1, 0)):
        if y1 == y2:
            continue
        crossing = x1 + (x2 - x1) * (lat - y1) / (y2 - y1)
        odd ^= ((y1 > lat) != (y2 > lat)) & (lon < crossing)
    return odd


@dataclass
class Outcome:
    events: int
    event_count: np.ndarray     # [n_devices]
    last: np.ndarray            # [n_devices, 2] float32 (lat, lon)
    last_ts: np.ndarray         # [n_devices] int64 (-1: none)
    rows: np.ndarray            # persisted (date, device, lat, lon bits)
    alerts: np.ndarray          # geofence alerts (date, device)
    uncommitted_records: int = 0
    dead_lettered: int = 0
    lane_drops: int = 0


def expected(world: World, traffic: Traffic,
             coord_dtype=np.float32) -> Outcome:
    """The reference; `coord_dtype` below float32 makes the control."""
    lat = traffic.lat.astype(coord_dtype).astype(np.float32)
    lon = traffic.lon.astype(coord_dtype).astype(np.float32)
    dev = traffic.record_dev
    last = np.zeros((world.n, 2), np.float32)
    last_ts = np.full(world.n, -1, np.int64)
    # dates rise with the row: a device's last row is its last report
    _, from_end = np.unique(dev[::-1], return_index=True)
    final = traffic.n - 1 - from_end
    last[dev[final]] = np.stack([lat[final], lon[final]], 1)
    last_ts[dev[final]] = traffic.ts[final]
    hit = inside(lat, lon, world.cfg["zone"])
    return Outcome(
        events=traffic.n,
        event_count=np.bincount(dev, minlength=world.n),
        last=last, last_ts=last_ts,
        rows=rows([traffic.ts, dev], [lat, lon]),
        alerts=np.stack([traffic.ts[hit], dev[hit].astype(np.int64)], 1))


def observe(instance, world: World, topic, group) -> Outcome:
    from sitewhere_tpu.model.event import DeviceEventType
    from sitewhere_tpu.persist.eventlog import EventFilter

    tenant = world.cfg["tenant"]
    engine = instance.pipeline_engine
    state = engine.canonical_state()
    idx = world.device_idx
    log = instance.event_log

    def device_of(tokens) -> np.ndarray:
        return np.array([world.token_index.get(t, -1) for t in tokens],
                        np.int64)

    cols = log.query_columns(
        tenant, EventFilter(event_type=DeviceEventType.LOCATION),
        ["event_date", "device_token", "latitude", "longitude"])
    alerts = log.query_columns(
        tenant, EventFilter(event_type=DeviceEventType.ALERT),
        ["event_date", "device_token", "alert_type"])
    mine = alerts["alert_type"] == ZONE_TYPE
    dlq = instance.bus.topic(topic.name + ".dead-letter")
    return Outcome(
        events=int(sum(engine.stats()["tenant_event_count"])),
        event_count=np.asarray(state.event_count)[idx].astype(np.int64),
        last=np.asarray(state.last_location)[idx][:, :2],
        last_ts=(np.asarray(state.last_location_ts)[idx].astype(np.int64)
                 + engine.packer.epoch_base_ms),
        rows=rows([cols["event_date"], device_of(cols["device_token"])],
                  [cols["latitude"], cols["longitude"]]),
        alerts=np.stack([alerts["event_date"][mine].astype(np.int64),
                         device_of(alerts["device_token"][mine])], 1),
        uncommitted_records=int(sum(topic.end_offsets())
                                - sum(group.committed)),
        dead_lettered=int(sum(dlq.end_offsets())),
        lane_drops=int(engine.alerts_dropped))


def control(world: World, traffic: Traffic, topic, group) -> Outcome:
    """The reference with coordinates in bfloat16."""
    import ml_dtypes

    out = expected(world, traffic, coord_dtype=ml_dtypes.bfloat16)
    out.uncommitted_records = int(sum(topic.end_offsets())
                                  - sum(group.committed))
    return out


def compare(observed: Outcome, want: Outcome) -> List[Tuple[str, int, int]]:
    seen = want.last_ts >= 0
    bad = ~((observed.last == want.last).all(1)
            & (observed.last_ts == want.last_ts))
    numbers = {
        "lost_events": abs(want.events - observed.events),
        "uncommitted_records": observed.uncommitted_records,
        "dead_lettered": observed.dead_lettered,
        "count_mismatch_devices": int(
            (observed.event_count != want.event_count).sum()),
        "last_location_mismatches": int((bad & seen).sum()),
        "persisted_row_mismatches": unmatched(observed.rows, want.rows),
        "geofence_alert_mismatches": unmatched(observed.alerts, want.alerts),
        "lane_drops": observed.lane_drops,
    }
    return [(name, value, LIMITS[name]) for name, value in numbers.items()]
