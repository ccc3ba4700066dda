"""Linear Road's deployment (`configs/linear_road.json`; Arasu et al., VLDB
2004): vehicles on L expressways send a position report every 30 s, and
each report that crosses into a new segment gets a toll notification.

The world: one device per vehicle, one area per expressway, and one zone
per (expressway, direction, segment), an axis-aligned latitude/longitude
rectangle over the five lanes of that mile; every zone carries one
geofence rule with the `enter` condition, whose alert names the segment.
The segment is never sent: the platform recovers it from the
coordinates.

Traffic: rounds of `report_interval_ms` simulated time; in each round
every vehicle reports once, in one fixed seeded vehicle order, so a
vehicle's reports are exactly one interval apart. A record is a
msgpack `DeviceEventBatch` of one location and one `speed` measurement
with the same date. Speeds are whole mph around a cruise speed drawn
per vehicle; a vehicle moves speed x 44 ft between reports (30 s), and a
trip wraps at mile 100 to mile 0 of its direction.

The reference, over exactly the records published: per-vehicle event
counts, the last location and last speed with their dates, every
location and speed as persisted, and one enter alert (date, vehicle,
zone) per report whose zone by the even-odd rule differs from that of
the vehicle's previous report, or that is the vehicle's first.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from benchmark.reference import rows, unmatched
from benchmark.world import World, attach_devices

FEET_PER_MILE = 5280
SPEED = "speed"

# Every number compared is a count with the exact limit 0.
LIMITS = {
    "lost_events": 0,
    "uncommitted_records": 0,
    "dead_lettered": 0,
    "count_mismatch_devices": 0,
    "last_location_mismatches": 0,
    "last_value_mismatches": 0,
    "persisted_row_mismatches": 0,
    "enter_alert_mismatches": 0,
    "lane_drops": 0,
}
# what the control (coordinates in bfloat16) pushes over their limits
CONTROL_FAILS = ("last_location_mismatches", "enter_alert_mismatches")
# adding 1.0 degree moves a report north of every expressway
FAULT_FIELD = "latitude"


# ------------------------------------------------------------ geometry --

def n_zones(cfg: Dict) -> int:
    return int(cfg["expressways"]) * int(cfg["directions"]) \
        * int(cfg["segments"])


def zone_number(cfg: Dict, x, d, s):
    """Zone of (expressway, direction, segment): x major, then d, then s."""
    return (x * int(cfg["directions"]) + d) * int(cfg["segments"]) + s


def band_lat(cfg: Dict, x, d):
    """Southern edge (float64 degrees) of expressway x's direction d."""
    g = cfg["geometry"]
    return (g["lat0"] + x * g["expressway_spacing_deg"]
            + d * g["direction_offset_deg"])


def mile_lon(cfg: Dict, miles):
    """Longitude (float64) of a whole mile mark; exact in float32."""
    g = cfg["geometry"]
    return g["lon0"] + np.asarray(miles, np.float64) * g["mile_deg"]


def zones(cfg: Dict) -> List[Tuple[str, List[Tuple[float, float]], int]]:
    """(token, rectangle of (lat, lon) vertices, expressway) of every
    zone, in zone-number order."""
    lanes_deg = int(cfg["lanes"]) * cfg["geometry"]["lane_deg"]
    out = []
    for x in range(int(cfg["expressways"])):
        for d in range(int(cfg["directions"])):
            south = float(band_lat(cfg, x, d))
            north = south + lanes_deg
            for s in range(int(cfg["segments"])):
                west = float(mile_lon(cfg, s))
                east = float(mile_lon(cfg, s + 1))
                out.append((f"seg-{x}-{d}-{s}",
                            [(south, west), (north, west), (north, east),
                             (south, east)], x))
    return out


def zone_message(cfg: Dict, z: int) -> str:
    segments, directions = int(cfg["segments"]), int(cfg["directions"])
    x, rest = divmod(z, directions * segments)
    d, s = divmod(rest, segments)
    return f"expressway {x} direction {d} segment {s}"


def zone_of(cfg: Dict, lat: np.ndarray, lon: np.ndarray) -> np.ndarray:
    """The zone holding each float32 point by the even-odd rule, -1 for
    none. For a rectangle with edges along the axes the rule reduces,
    exactly in float32, to half-open bounds: a vertical edge's crossing
    point is its own longitude, and the two edges that straddle a
    latitude in [south, north) toggle parity at west and at east, so
    the point is inside when west <= lon < east. The zones tile disjoint
    bands and miles, so a sorted search over the float32 edges finds
    the one zone."""
    lat = np.asarray(lat, np.float32)
    lon = np.asarray(lon, np.float32)
    x, d = np.divmod(np.arange(int(cfg["expressways"])
                               * int(cfg["directions"])),
                     int(cfg["directions"]))
    lanes_deg = int(cfg["lanes"]) * cfg["geometry"]["lane_deg"]
    south = np.float32(band_lat(cfg, x, d))
    north = np.float32(band_lat(cfg, x, d) + lanes_deg)
    edges = np.float32(mile_lon(cfg, np.arange(int(cfg["segments"]) + 1)))
    band = np.searchsorted(south, lat, side="right") - 1
    seg = np.searchsorted(edges, lon, side="right") - 1
    b, s = np.clip(band, 0, None), np.clip(seg, 0, edges.size - 2)
    ok = (band >= 0) & (lat < north[b]) & (seg >= 0) \
        & (seg < edges.size - 1)
    return np.where(ok, b * int(cfg["segments"]) + s, -1)


def point(cfg: Dict, x, d, lane, position_ft) -> Tuple[np.ndarray,
                                                        np.ndarray]:
    """float32 (lat, lon) of a report in `lane` of expressway x's
    direction d at `position_ft` feet from the west end. The longitude
    rounds toward the west, so it never crosses a mile mark: the zone
    holding the point is segment floor(position / 5280)."""
    g = cfg["geometry"]
    lat = np.float32(band_lat(cfg, x, d)
                     + (np.asarray(lane) + 0.5) * g["lane_deg"])
    # pos * mile is exact, and the division by 5280 is exact on a mark
    exact = g["lon0"] + (np.asarray(position_ft, np.float64)
                         * g["mile_deg"]) / FEET_PER_MILE
    lon = exact.astype(np.float32)
    up = lon.astype(np.float64) > exact
    lon[up] = np.nextafter(lon[up], np.float32(-np.inf))
    return lat, lon


# --------------------------------------------------------------- world --

def n_devices(cfg: Dict) -> int:
    return int(cfg["vehicles"])


def rule_dicts(cfg: Dict) -> List[Dict]:
    """One `enter` geofence rule per zone, its alert naming the segment."""
    return [{"type": "geofence", "token": f"toll-{z}", "zone_token": token,
             "condition": "enter", "alert_type": cfg["alert_type"],
             "alert_level": "INFO", "alert_message": zone_message(cfg, z)}
            for z, (token, _, _) in enumerate(zones(cfg))]


def provision(instance, cfg: Dict) -> None:
    """The vehicle type, one area per expressway with its zones, and the
    vehicles with their assignments, through the control-plane API."""
    from sitewhere_tpu.model import (
        Area, Device, DeviceAssignment, DeviceType, Zone)
    from sitewhere_tpu.model.common import Location

    reg = instance.get_tenant_engine(cfg["tenant"]).registry
    dtype = reg.create_device_type(DeviceType(token="lr-vehicle",
                                              name="vehicle"))
    areas = [reg.create_area(Area(token=f"expressway-{x}",
                                  name=f"expressway {x}"))
             for x in range(int(cfg["expressways"]))]
    for token, rect, x in zones(cfg):
        reg.create_zone(Zone(token=token, area_id=areas[x].id, bounds=[
            Location(lat, lon) for lat, lon in rect]))
    for i in range(n_devices(cfg)):
        device = reg.create_device(Device(token=f"veh-{i}",
                                          device_type_id=dtype.id))
        reg.create_device_assignment(DeviceAssignment(
            token=f"as-{i}", device_id=device.id,
            area_id=areas[i % len(areas)].id))


def attach(instance, cfg: Dict) -> World:
    return attach_devices(instance, cfg,
                          [f"veh-{i}" for i in range(n_devices(cfg))])


def describe(cfg: Dict) -> str:
    return (f"{cfg['name']} vehicles={n_devices(cfg)} "
            f"zones={n_zones(cfg)} enter_rules={n_zones(cfg)}")


# ------------------------------------------------------------- traffic --

def events_per_record(mix: Dict) -> int:
    return 2


@dataclass
class Traffic:
    """One report per record, in publish order."""

    per_record: int
    record_dev: np.ndarray   # [R] vehicle number
    ts: np.ndarray           # [R] int64 ms
    lat: np.ndarray          # [R] float32
    lon: np.ndarray          # [R] float32
    speed: np.ndarray        # [R] float32, whole mph
    position: np.ndarray     # [R] int64 feet from the west end

    @property
    def n(self) -> int:
        return int(self.record_dev.shape[0])

    def prefix(self, n_records: int) -> "Traffic":
        cut = slice(0, n_records)
        return Traffic(self.per_record, self.record_dev[cut], self.ts[cut],
                       self.lat[cut], self.lon[cut], self.speed[cut],
                       self.position[cut])


def make_traffic(world: World, mix: Dict, seed: int, n_records: int,
                 base_ms: int) -> Traffic:
    """Rounds of one report per vehicle, the vehicles in one seeded order
    each round, dated evenly across the round's interval."""
    cfg = world.cfg
    rng = np.random.default_rng([seed, 27])
    n_veh = world.n
    interval = int(cfg["report_interval_ms"])
    road_ft = int(cfg["segments"]) * FEET_PER_MILE
    order = rng.permutation(n_veh)
    x = rng.integers(0, int(cfg["expressways"]), n_veh)
    d = rng.integers(0, int(cfg["directions"]), n_veh)
    lo, hi = cfg["cruise_mph"]
    cruise = rng.uniform(lo, hi, n_veh)
    start = rng.integers(0, road_ft, n_veh)
    rounds = max(1, -(-n_records // n_veh))
    speed = np.rint(np.clip(
        cruise + rng.normal(0.0, cfg["speed_noise_mph"], (rounds, n_veh)),
        0.0, 100.0)).astype(np.int64)
    lane = rng.choice(np.asarray(cfg["travel_lanes"]), (rounds, n_veh))
    # a report's speed covers the 30 s before it: 44 ft per mph
    moved = 44 * (np.cumsum(speed, axis=0) - speed[0])
    trip = (start + moved) % road_ft
    pos = np.where(d == 0, trip, road_ft - 1 - trip)
    i = np.arange(n_records, dtype=np.int64)
    r, k = np.divmod(i, n_veh)
    v = order[k]
    lat, lon = point(cfg, x[v], d[v], lane[r, v], pos[r, v])
    return Traffic(2, v, base_ms + r * interval + k * interval // n_veh,
                   lat, lon, speed[r, v].astype(np.float32), pos[r, v])


def encode_records(world: World, traffic: Traffic,
                   mix: Dict) -> List[Tuple[bytes, bytes]]:
    """Every record as (key, msgpack DeviceEventBatch envelope)."""
    import msgpack

    records = []
    for d, ts, lat, lon, speed in zip(
            traffic.record_dev.tolist(), traffic.ts.tolist(),
            traffic.lat.tolist(), traffic.lon.tolist(),
            traffic.speed.tolist()):
        token = world.tokens[d]
        request = {"device_token": token, "alerts": [],
                   "locations": [{"event_type": 1, "latitude": lat,
                                  "longitude": lon, "elevation": 0.0,
                                  "event_date": ts}],
                   "measurements": [{"event_type": 0, "name": SPEED,
                                     "value": speed, "event_date": ts}]}
        envelope = {"sourceId": "linear-road", "deviceToken": token,
                    "kind": "DeviceEventBatch", "request": request,
                    "metadata": {}}
        records.append((token.encode(),
                        msgpack.packb(envelope, use_bin_type=True)))
    return records


# ----------------------------------------------------------- reference --

@dataclass
class Outcome:
    """What the served path did, or what it should have done."""

    events: int
    event_count: np.ndarray     # [n_devices]
    last_location: np.ndarray   # [n_devices, 2] float32 (lat, lon)
    last_location_ts: np.ndarray  # [n_devices] int64 (-1: none)
    last_speed: np.ndarray      # [n_devices] float32
    last_speed_ts: np.ndarray   # [n_devices] int64 (-1: none)
    location_rows: np.ndarray   # persisted (date, vehicle, lat, lon bits)
    speed_rows: np.ndarray      # persisted (date, vehicle, speed bits)
    alerts: np.ndarray          # enter alerts (date, vehicle, zone)
    uncommitted_records: int = 0
    dead_lettered: int = 0
    lane_drops: int = 0


def expected(world: World, traffic: Traffic,
             coord_dtype=np.float32) -> Outcome:
    """The reference; `coord_dtype` below float32 makes the control."""
    cfg = world.cfg
    lat = traffic.lat.astype(coord_dtype).astype(np.float32)
    lon = traffic.lon.astype(coord_dtype).astype(np.float32)
    dev = traffic.record_dev
    n = world.n
    # a vehicle's reports are published in date order: its last row is
    # its last report, and the row before is its previous one
    _, from_end = np.unique(dev[::-1], return_index=True)
    last = traffic.n - 1 - from_end
    last_location = np.zeros((n, 2), np.float32)
    last_ts = np.full(n, -1, np.int64)
    last_location[dev[last]] = np.stack([lat[last], lon[last]], 1)
    last_ts[dev[last]] = traffic.ts[last]
    last_speed = np.full(n, np.nan, np.float32)
    last_speed[dev[last]] = traffic.speed[last]
    zone = zone_of(cfg, lat, lon)
    by_vehicle = np.lexsort((np.arange(traffic.n), traffic.ts, dev))
    z_sorted = zone[by_vehicle]
    first = np.r_[True, dev[by_vehicle][1:] != dev[by_vehicle][:-1]]
    changed = np.r_[True, z_sorted[1:] != z_sorted[:-1]]
    enter = np.zeros(traffic.n, bool)
    enter[by_vehicle] = (z_sorted >= 0) & (first | changed)
    return Outcome(
        events=2 * traffic.n,
        event_count=2 * np.bincount(dev, minlength=n),
        last_location=last_location, last_location_ts=last_ts,
        last_speed=last_speed, last_speed_ts=last_ts.copy(),
        location_rows=rows([traffic.ts, dev], [lat, lon]),
        speed_rows=rows([traffic.ts, dev], [traffic.speed]),
        alerts=np.stack([traffic.ts[enter], dev[enter].astype(np.int64),
                         zone[enter]], 1))


def observe(instance, world: World, topic, group) -> Outcome:
    """The same quantities read back from the program after the drain:
    device state from the engine, rows and alerts from the event log."""
    from sitewhere_tpu.model.event import DeviceEventType
    from sitewhere_tpu.persist.eventlog import EventFilter

    cfg = world.cfg
    tenant = cfg["tenant"]
    engine = instance.pipeline_engine
    state = engine.canonical_state()
    idx = world.device_idx
    base = engine.packer.epoch_base_ms
    slot = engine.packer.measurements.lookup(SPEED)
    log = instance.event_log

    def device_of(tokens) -> np.ndarray:
        return np.array([world.token_index.get(t, -1) for t in tokens],
                        np.int64)

    locs = log.query_columns(
        tenant, EventFilter(event_type=DeviceEventType.LOCATION),
        ["event_date", "device_token", "latitude", "longitude"])
    mms = log.query_columns(
        tenant, EventFilter(event_type=DeviceEventType.MEASUREMENT),
        ["event_date", "device_token", "mm_name", "value"])
    speed = mms["mm_name"] == SPEED
    alerts = log.query_columns(
        tenant, EventFilter(event_type=DeviceEventType.ALERT),
        ["event_date", "device_token", "alert_type", "alert_message"])
    mine = alerts["alert_type"] == cfg["alert_type"]
    zone_by_message = {zone_message(cfg, z): z for z in range(n_zones(cfg))}
    zone = np.array([zone_by_message.get(m, -1)
                     for m in alerts["alert_message"][mine]], np.int64)
    dlq = instance.bus.topic(topic.name + ".dead-letter")
    if slot > 0:
        last_speed = np.asarray(state.last_measurement)[idx][:, slot]
        last_speed_ts = (np.asarray(state.last_measurement_ts)[idx][:, slot]
                         .astype(np.int64) + base)
    else:
        last_speed = np.full(world.n, np.nan, np.float32)
        last_speed_ts = np.full(world.n, -1, np.int64)
    return Outcome(
        events=int(sum(engine.stats()["tenant_event_count"])),
        event_count=np.asarray(state.event_count)[idx].astype(np.int64),
        last_location=np.asarray(state.last_location)[idx][:, :2],
        last_location_ts=(np.asarray(state.last_location_ts)[idx]
                          .astype(np.int64) + base),
        last_speed=last_speed, last_speed_ts=last_speed_ts,
        location_rows=rows([locs["event_date"],
                            device_of(locs["device_token"])],
                           [locs["latitude"], locs["longitude"]]),
        speed_rows=rows([mms["event_date"][speed],
                         device_of(mms["device_token"][speed])],
                        [mms["value"][speed]]),
        alerts=np.stack([alerts["event_date"][mine].astype(np.int64),
                         device_of(alerts["device_token"][mine]), zone], 1),
        uncommitted_records=int(sum(topic.end_offsets())
                                - sum(group.committed)),
        dead_lettered=int(sum(dlq.end_offsets())),
        lane_drops=int(engine.alerts_dropped))


def control(world: World, traffic: Traffic, topic, group) -> Outcome:
    """The control: the reference in the program's place, with the
    coordinates carried in bfloat16, the precision below the float32
    that the configuration states."""
    import ml_dtypes

    out = expected(world, traffic, coord_dtype=ml_dtypes.bfloat16)
    out.uncommitted_records = int(sum(topic.end_offsets())
                                  - sum(group.committed))
    return out


def compare(observed: Outcome, want: Outcome) -> List[Tuple[str, int, int]]:
    """Each number compared, with its limit."""
    seen = want.last_location_ts >= 0
    loc_bad = ~((observed.last_location == want.last_location).all(1)
                & (observed.last_location_ts == want.last_location_ts))
    speed_bad = ~((observed.last_speed == want.last_speed)
                  & (observed.last_speed_ts == want.last_speed_ts))
    numbers = {
        "lost_events": abs(want.events - observed.events),
        "uncommitted_records": observed.uncommitted_records,
        "dead_lettered": observed.dead_lettered,
        "count_mismatch_devices": int(
            (observed.event_count != want.event_count).sum()),
        "last_location_mismatches": int((loc_bad & seen).sum()),
        "last_value_mismatches": int((speed_bad & seen).sum()),
        "persisted_row_mismatches": (
            unmatched(observed.location_rows, want.location_rows)
            + unmatched(observed.speed_rows, want.speed_rows)),
        "enter_alert_mismatches": unmatched(observed.alerts, want.alerts),
        "lane_drops": observed.lane_drops,
    }
    return [(name, value, LIMITS[name]) for name, value in numbers.items()]
