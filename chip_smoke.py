#!/usr/bin/env python3
"""Chip smoke: the served event path, end to end, on a TPU at the
deployed fleet size.

    python chip_smoke.py               one chip: the served single-chip path
    python chip_smoke.py --chips 4     four chips: the sharded path only

One process, the normal entry points: the config and instance of
``python -m sitewhere_tpu serve`` (``__main__._build_config`` /
``_build_instance``) with a persist data_dir under a scratch directory, a
``RestServer`` on port 0, and traffic published onto the tenant's
decoded-events topic (bus -> inbound consumer -> fused step -> lanes ->
persist). The effects are checked against a plain NumPy reference built
from the same seeded events, then a few answers over real HTTP with a
minted JWT. Any failed check exits non-zero; the last line of a passing
run is the contract line ``{"ok": true, "device": {...}}``.

``main`` insists on a TPU and never falls back to the CPU. The phase
functions (world, traffic, reference, checks, HTTP) take their sizes as
arguments so ``tests/test_chip_smoke.py`` runs them at a tiny size on
the CPU.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from sitewhere_tpu.runtime.compile_cache import configure_compile_cache

TENANT = "default"
MEASUREMENTS = ("m0", "m1", "m2", "m3")
DEVICE_ALERT_TYPES = ("dev.overheat", "dev.tamper", "dev.battery",
                      "dev.door")
# one record = one device's DeviceEventBatch of 6 measurements, 3
# locations and 1 alert: the 60/30/10 mix in every record, so every
# consumer batch carries locations (elevation set -> one wire variant)
RECORD_MIX = (6, 3, 1)
EVENTS_PER_RECORD = sum(RECORD_MIX)
THRESHOLD_TYPE = "smoke.threshold"
GEOFENCE_TYPE = "smoke.geofence"
PROGRAM_TYPE = "smoke.program"
MODEL_TYPE = "smoke.model"
POLICY_COMMAND = "smoke-cool"
# location region (lat, lon) the fleet reports from; a small share of
# reports comes from far away so the "outside" geofence rules fire
REGION = ((10.0, 60.0), (0.0, 50.0))
FAR_SHARE = 0.004
# points closer than this (degrees) to a rule-zone edge crossing are
# redrawn: containment then never hinges on the last bit of a division
EDGE_CLEARANCE = 1e-3


@dataclass(frozen=True)
class Size:
    """What a run registers and offers. The engine's widths (device
    slots, batch, zone table) come from the served config."""

    devices: int
    zones: int
    events: int
    timeout_s: float


# the deployed fleet: 100,000 registered devices in the 131,072 slots of
# the default config, its full 256 x 32 zone table, and >= 32 full
# batches of the default 8,192 (26,215 records of 10 events)
FULL = Size(devices=100_000, zones=256, events=262_150, timeout_s=900.0)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- boot --

def boot(data_dir: str, overrides: Optional[Dict] = None):
    """The served instance, as ``serve`` builds it, plus a REST gateway on
    port 0. `overrides` are config keys (tests shrink the widths)."""
    from sitewhere_tpu.__main__ import _build_config, _build_instance
    from sitewhere_tpu.web.server import RestServer

    cfg = _build_config(None)
    cfg.set("persist.data_dir", data_dir)
    for key, value in (overrides or {}).items():
        cfg.set(key, value)
    instance = _build_instance(cfg)
    instance.start()
    rest = RestServer(instance, host="127.0.0.1", port=0,
                      token_expiration_minutes=int(
                          cfg.get("api.jwt_expiration_min")))
    rest.start()
    return cfg, instance, rest


# --------------------------------------------------------------- world --

@dataclass
class World:
    n_devices: int
    tokens: List[str]
    assignments: List[str]
    zones: np.ndarray             # [Z, V, 2] float32 (lat, lon)
    inside_zones: List[int]       # zone rows of the "inside" rules
    outside_zones: List[int]      # zone rows of the "outside" rules
    device_idx: np.ndarray        # [n_devices] engine index per device
    commands: Optional[object] = None  # the in-process command destination


def _star(rng, center, radius, n_verts) -> np.ndarray:
    """A non-convex star polygon (lat, lon) around `center`."""
    ang = np.sort(rng.uniform(0, 2 * np.pi, n_verts))
    rad = radius * rng.uniform(0.45, 1.0, n_verts)
    return np.stack([center[0] + rad * np.sin(ang),
                     center[1] + rad * np.cos(ang)], 1).astype(np.float32)


def _square(lo_lat, hi_lat, lo_lon, hi_lon, n_verts) -> np.ndarray:
    """A rectangle traced with `n_verts` vertices (n_verts % 4 == 0)."""
    k = n_verts // 4
    t = np.arange(k, dtype=np.float64) / k
    lat = np.concatenate([lo_lat + (hi_lat - lo_lat) * t,
                          np.full(k, hi_lat),
                          hi_lat - (hi_lat - lo_lat) * t,
                          np.full(k, lo_lat)])
    lon = np.concatenate([np.full(k, lo_lon),
                          lo_lon + (hi_lon - lo_lon) * t,
                          np.full(k, hi_lon),
                          hi_lon - (hi_lon - lo_lon) * t])
    return np.stack([lat, lon], 1).astype(np.float32)


def make_zones(seed: int, n_zones: int, n_verts: int):
    """(zones [Z, V, 2], inside rule rows, outside rule rows). Twelve
    small rule zones inside the region, four rectangles around it (their
    "outside" rules fire on far-away reports), the rest filler."""
    rng = np.random.default_rng([seed, 1])
    (lat0, lat1), (lon0, lon1) = REGION
    zones = []
    for _ in range(12):
        zones.append(_star(rng, (rng.uniform(lat0 + 2, lat1 - 2),
                                 rng.uniform(lon0 + 2, lon1 - 2)),
                           0.6, n_verts))
    for i in range(4):
        pad = 0.5 + i
        zones.append(_square(lat0 - pad, lat1 + pad, lon0 - pad, lon1 + pad,
                             n_verts))
    while len(zones) < n_zones:
        zones.append(_star(rng, (rng.uniform(lat0, lat1),
                                 rng.uniform(lon0, lon1)),
                           rng.uniform(0.5, 4.0), n_verts))
    zones = np.stack(zones[:n_zones])
    rule_rows = list(range(min(16, n_zones)))
    return zones, rule_rows[:12], rule_rows[12:16]


# four rules per measurement name; together they fire on ~0.5% of the
# readings, so the fired rows of a full step stay well inside the
# 128-row alert lane (ops/compact.py)
_THRESHOLD_FAMILY = ((">", 99.6, "WARNING"), (">", 99.8, "ERROR"),
                     (">=", 99.9, "CRITICAL"), ("<", 0.1, "ERROR"))


# a composite program over two measurement names (one leg debounced)
PROGRAM_SPEC = {
    "token": "smoke-program", "alert_type": PROGRAM_TYPE,
    "alert_level": "WARNING",
    "when": {"any": [
        {"pred": "value", "measurement": "m0", "op": ">", "value": 99.5},
        {"debounce": {"pred": "value", "measurement": "m1", "op": ">",
                      "value": 99.5}, "count": 2}]}}
# sigmoid(20 * tanh((m2 - 99.7) / 0.01) - 10) > 0.5, i.e. m2 > ~99.7055
MODEL_SPEC = {
    "token": "smoke-model", "kind": "mlp", "threshold": 0.5,
    "alert_type": MODEL_TYPE, "alert_level": "ERROR",
    "features": [{"feature": "value", "measurement": "m2",
                  "mean": 99.7, "std": 0.01}],
    "layers": [{"weights": [[1.0]], "bias": [0.0]}],
    "output": {"weights": [20.0], "bias": -10.0}}
POLICY_SPEC = {
    "token": "smoke-policy", "source": "threshold", "match_slot": -1,
    "min_level": "ERROR", "debounce_ms": 10_000,
    "command": POLICY_COMMAND, "params": [1, 2]}


def build_world(instance, seed: int, n_devices: int, n_zones: int) -> World:
    """Register the fleet, its zones and every in-step stage through the
    control-plane API: tenant registry (devices, assignments, zones, the
    policy's command), the rule surface REST drives, and the instance's
    durable rule-program / anomaly-model / actuation-policy installs."""
    from sitewhere_tpu.model import (
        Area, Device, DeviceAssignment, DeviceType, Zone)
    from sitewhere_tpu.model.common import Location
    from sitewhere_tpu.commands.destinations import (
        CommandDestination, InProcDeliveryProvider)
    from sitewhere_tpu.model.device import DeviceCommand

    engine = instance.pipeline_engine
    tenant = instance.get_tenant_engine(TENANT)
    reg = tenant.registry
    # policy commands go to a co-located device simulator
    commands = InProcDeliveryProvider()
    tenant.command_delivery.add_destination(
        CommandDestination("smoke-devices", commands))
    n_verts = engine.registry.max_zone_vertices
    dtype = reg.create_device_type(DeviceType(token="smoke-sensor",
                                              name="smoke sensor"))
    reg.create_device_command(DeviceCommand(
        token=POLICY_COMMAND, device_type_id=dtype.id, name="cool"))
    area = reg.create_area(Area(token="smoke-area", name="smoke area"))
    zones, inside, outside = make_zones(seed, n_zones, n_verts)
    for z in range(n_zones):
        reg.create_zone(Zone(
            token=f"zone-{z}", area_id=area.id,
            bounds=[Location(float(a), float(b)) for a, b in zones[z]]))
    tokens = [f"dev-{i}" for i in range(n_devices)]
    assignments = [f"as-{i}" for i in range(n_devices)]
    for token, as_token in zip(tokens, assignments):
        device = reg.create_device(Device(token=token,
                                          device_type_id=dtype.id))
        reg.create_device_assignment(DeviceAssignment(
            token=as_token, device_id=device.id, area_id=area.id))

    world = World(n_devices=n_devices, tokens=tokens,
                  assignments=assignments, zones=zones, inside_zones=inside,
                  outside_zones=outside, commands=commands,
                  device_idx=np.array([engine.registry.devices.lookup(t)
                                       for t in tokens], np.int64))
    for rule in threshold_rules():
        engine.create_rule("threshold", rule)
    for rule in geofence_rules(world):
        engine.create_rule("geofence", rule)
    instance.install_rule_program(TENANT, PROGRAM_SPEC)
    instance.install_anomaly_model(TENANT, MODEL_SPEC)
    instance.install_actuation_policy(TENANT, POLICY_SPEC)
    return world


def threshold_rules():
    from sitewhere_tpu.model.event import AlertLevel
    from sitewhere_tpu.pipeline.engine import ThresholdRule

    return [ThresholdRule(token=f"thr-{mm}-{j}", measurement_name=mm,
                          operator=op, threshold=value,
                          alert_type=THRESHOLD_TYPE,
                          alert_level=AlertLevel[level])
            for mm in MEASUREMENTS
            for j, (op, value, level) in enumerate(_THRESHOLD_FAMILY)]


def geofence_rules(world: World):
    from sitewhere_tpu.model.event import AlertLevel
    from sitewhere_tpu.pipeline.engine import GeofenceRule

    return [GeofenceRule(token=f"geo-{z}", zone_token=f"zone-{z}",
                         condition="inside" if z in world.inside_zones
                         else "outside",
                         alert_type=GEOFENCE_TYPE,
                         alert_level=AlertLevel.ERROR)
            for z in world.inside_zones + world.outside_zones]


def install_stages(engine, world: World) -> None:
    """The same rules and in-step stages on a bare engine (the sharded
    route-parity pair), straight through the engine API."""
    for rule in threshold_rules():
        engine.add_threshold_rule(rule)
    for rule in geofence_rules(world):
        engine.add_geofence_rule(rule)
    engine.upsert_rule_program(dict(PROGRAM_SPEC, tenant_token=TENANT))
    engine.upsert_anomaly_model(dict(MODEL_SPEC, tenant_token=TENANT))
    engine.upsert_actuation_policy(dict(POLICY_SPEC, tenant_token=TENANT))


# ------------------------------------------------------------- traffic --

@dataclass
class Traffic:
    """Columnar events in publish order; record r holds events
    [r * EVENTS_PER_RECORD, (r + 1) * EVENTS_PER_RECORD)."""

    record_dev: np.ndarray   # [R] device number
    dev: np.ndarray          # [N] device number
    kind: np.ndarray         # [N] 0 measurement, 1 location, 2 alert
    ts: np.ndarray           # [N] int64 absolute ms, unique, increasing
    mm: np.ndarray           # [N] measurement name index (-1 otherwise)
    value: np.ndarray        # [N] float32
    lat: np.ndarray          # [N] float32
    lon: np.ndarray
    elevation: np.ndarray
    alert: np.ndarray        # [N] device alert type index (-1 otherwise)
    level: np.ndarray        # [N] device alert level

    @property
    def n(self) -> int:
        return int(self.dev.shape[0])


def _crossings(px, py, zones) -> np.ndarray:
    """Even-odd containment [N, Z] in float32, the kernel's arithmetic
    (ops/pallas_geofence.py), plus the least |px - x_at_y| over the
    straddled edges [N] (float64) for the clearance test."""
    y1 = zones[:, :, 0][None]                       # [1, Z, V]
    x1 = zones[:, :, 1][None]
    y2 = np.roll(zones, -1, axis=1)[:, :, 0][None]
    x2 = np.roll(zones, -1, axis=1)[:, :, 1][None]
    pyb, pxb = py[:, None, None], px[:, None, None]
    straddles = (y1 > pyb) != (y2 > pyb)
    dy = y2 - y1
    safe = np.where(dy == 0.0, np.float32(1.0), dy)
    with np.errstate(invalid="ignore", divide="ignore"):
        x_at = x1 + (x2 - x1) * (pyb - y1) / safe
    crosses = straddles & (pxb < x_at)
    inside = np.bitwise_xor.reduce(crosses, axis=2)
    gap = np.where(straddles, np.abs(pxb.astype(np.float64) - x_at), np.inf)
    return inside, gap.min(axis=(1, 2))


def make_traffic(world: World, seed: int, n_events: int,
                 base_ms: int) -> Traffic:
    """Seeded records of one device each; every event has its own
    millisecond so "last" is unambiguous everywhere."""
    rng = np.random.default_rng([seed, 2])
    n_rec = math.ceil(n_events / EVENTS_PER_RECORD)
    n = n_rec * EVENTS_PER_RECORD
    record_dev = rng.integers(0, world.n_devices, n_rec)
    dev = np.repeat(record_dev, EVENTS_PER_RECORD)
    kind = np.tile(np.repeat(np.arange(3), RECORD_MIX), n_rec)
    ts = base_ms + np.arange(n, dtype=np.int64)
    is_mm, is_loc, is_alert = kind == 0, kind == 1, kind == 2
    mm = np.where(is_mm, rng.integers(0, len(MEASUREMENTS), n), -1)
    value = np.where(is_mm, rng.uniform(0.0, 100.0, n), 0.0).astype(
        np.float32)
    (lat0, lat1), (lon0, lon1) = REGION
    lat = rng.uniform(lat0, lat1, n).astype(np.float32)
    lon = rng.uniform(lon0, lon1, n).astype(np.float32)
    far = is_loc & (rng.random(n) < FAR_SHARE)
    lat[far] = rng.uniform(-80.0, -70.0, int(far.sum())).astype(np.float32)
    rule_zones = world.zones[world.inside_zones + world.outside_zones]
    loc_rows = np.nonzero(is_loc)[0]
    for _ in range(64):
        bad = []
        for lo in range(0, loc_rows.size, 4096):
            rows = loc_rows[lo:lo + 4096]
            _, gap = _crossings(lon[rows], lat[rows], rule_zones)
            bad.append(rows[gap < EDGE_CLEARANCE])
        loc_rows = np.concatenate(bad)
        if loc_rows.size == 0:
            break
        lat[loc_rows] = rng.uniform(lat0, lat1, loc_rows.size)
        lon[loc_rows] = rng.uniform(lon0, lon1, loc_rows.size)
    else:
        raise RuntimeError("could not clear location edges")
    lat = np.where(is_loc, lat, 0.0).astype(np.float32)
    lon = np.where(is_loc, lon, 0.0).astype(np.float32)
    elevation = np.where(is_loc, rng.uniform(1.0, 500.0, n),
                         0.0).astype(np.float32)
    alert = np.where(is_alert, rng.integers(0, len(DEVICE_ALERT_TYPES), n),
                     -1)
    level = np.where(is_alert, rng.integers(0, 4, n), 0)
    return Traffic(record_dev=record_dev, dev=dev, kind=kind, ts=ts, mm=mm,
                   value=value, lat=lat, lon=lon, elevation=elevation,
                   alert=alert, level=level)


def publish_traffic(instance, world: World, traffic: Traffic,
                    chunk: int = 4096) -> None:
    """Every record onto the tenant's decoded-events topic, keyed by the
    device token: the hot-path feed of the inbound consumer."""
    import msgpack

    topic = instance.naming.event_source_decoded_events(TENANT)
    kinds = traffic.kind.tolist()
    mms, values = traffic.mm.tolist(), traffic.value.tolist()
    lats, lons = traffic.lat.tolist(), traffic.lon.tolist()
    elevs, tss = traffic.elevation.tolist(), traffic.ts.tolist()
    alerts, levels = traffic.alert.tolist(), traffic.level.tolist()
    records = []
    for r, d in enumerate(traffic.record_dev.tolist()):
        token = world.tokens[d]
        request = {"device_token": token, "measurements": [],
                   "locations": [], "alerts": []}
        for i in range(r * EVENTS_PER_RECORD, (r + 1) * EVENTS_PER_RECORD):
            if kinds[i] == 0:
                request["measurements"].append({
                    "event_type": 0, "name": MEASUREMENTS[mms[i]],
                    "value": values[i], "event_date": tss[i]})
            elif kinds[i] == 1:
                request["locations"].append({
                    "event_type": 1, "latitude": lats[i],
                    "longitude": lons[i], "elevation": elevs[i],
                    "event_date": tss[i]})
            else:
                request["alerts"].append({
                    "event_type": 2, "type": DEVICE_ALERT_TYPES[alerts[i]],
                    "level": levels[i], "message": "device alert",
                    "event_date": tss[i]})
        records.append((token.encode(), msgpack.packb({
            "sourceId": "chip-smoke", "deviceToken": token,
            "kind": "DeviceEventBatch", "request": request,
            "metadata": {}}, use_bin_type=True)))
        if len(records) == chunk:
            instance.bus.publish_batch(topic, records)
            records = []
    if records:
        instance.bus.publish_batch(topic, records)


def traffic_batches(packer, world: World, traffic: Traffic, per_batch: int,
                    limit: Optional[int] = None):
    """The traffic as packed EventBatches of `per_batch` events (direct
    submits: the route-parity drill and the step timing)."""
    slots = np.array([packer.measurements.intern(m) for m in MEASUREMENTS])
    atypes = np.array([packer.alert_types.intern(t)
                       for t in DEVICE_ALERT_TYPES])
    n = traffic.n if limit is None else min(limit, traffic.n)
    for lo in range(0, n, per_batch):
        s = slice(lo, min(lo + per_batch, n))
        mm, alert = traffic.mm[s], traffic.alert[s]
        yield packer.pack_columns(
            world.device_idx[traffic.dev[s]].astype(np.int32),
            traffic.kind[s].astype(np.int32), traffic.ts[s],
            mm_idx=np.where(mm >= 0, slots[np.maximum(mm, 0)], 0),
            value=traffic.value[s], lat=traffic.lat[s], lon=traffic.lon[s],
            elevation=traffic.elevation[s],
            alert_type_idx=np.where(alert >= 0,
                                    atypes[np.maximum(alert, 0)], 0),
            alert_level=traffic.level[s])


def processed_events(engine) -> int:
    return int(sum(engine.stats()["tenant_event_count"]))


def wait_consumed(instance, offered: int, timeout_s: float) -> float:
    """Block until the fused step has folded every offered event and the
    inbound consumer has committed past every record; returns seconds."""
    engine = instance.pipeline_engine
    t0 = time.perf_counter()
    deadline = t0 + timeout_s
    while time.perf_counter() < deadline:
        if (processed_events(engine) >= offered
                and instance._ingest_backlog() == 0):
            return time.perf_counter() - t0
        time.sleep(0.25)
    raise TimeoutError(
        f"served path folded {processed_events(engine)} of {offered} "
        f"events in {timeout_s:.0f} s (backlog "
        f"{instance._ingest_backlog()} records)")


# ----------------------------------------------------------- reference --

@dataclass
class Reference:
    """What the served path must have produced, from NumPy alone."""

    event_count: np.ndarray      # [n_devices]
    last_value: np.ndarray       # [n_devices, len(MEASUREMENTS)] (NaN: none)
    last_ts: np.ndarray          # [n_devices, len(MEASUREMENTS)] (-1: none)
    threshold_fired: np.ndarray  # [N] bool
    geofence_fired: np.ndarray   # [N] bool
    device_alerts: np.ndarray    # [n_devices] device-sent alert events


_OPS = {">": np.greater, ">=": np.greater_equal, "<": np.less}


def build_reference(world: World, traffic: Traffic) -> Reference:
    n_dev, k = world.n_devices, len(MEASUREMENTS)
    event_count = np.bincount(traffic.dev, minlength=n_dev)
    last_value = np.full((n_dev, k), np.nan, np.float32)
    last_ts = np.full((n_dev, k), -1, np.int64)
    rows = np.nonzero(traffic.kind == 0)[0]   # ts increase with the row
    last_value[traffic.dev[rows], traffic.mm[rows]] = traffic.value[rows]
    last_ts[traffic.dev[rows], traffic.mm[rows]] = traffic.ts[rows]
    thr = np.zeros(traffic.n, bool)
    for mm in range(k):
        for op, value, _ in _THRESHOLD_FAMILY:
            thr |= ((traffic.kind == 0) & (traffic.mm == mm)
                    & _OPS[op](traffic.value, np.float32(value)))
    geo = np.zeros(traffic.n, bool)
    loc = np.nonzero(traffic.kind == 1)[0]
    n_in = len(world.inside_zones)
    rule_zones = world.zones[world.inside_zones + world.outside_zones]
    for lo in range(0, loc.size, 4096):
        rows = loc[lo:lo + 4096]
        inside, _ = _crossings(traffic.lon[rows], traffic.lat[rows],
                               rule_zones)
        geo[rows] = (inside[:, :n_in].any(1) | (~inside[:, n_in:]).any(1))
    device_alerts = np.bincount(traffic.dev[traffic.kind == 2],
                                minlength=n_dev)
    return Reference(event_count=event_count, last_value=last_value,
                     last_ts=last_ts, threshold_fired=thr,
                     geofence_fired=geo, device_alerts=device_alerts)


# -------------------------------------------------------------- checks --

class Checks:
    """Named pass/fail lines; `failed` decides the exit code."""

    def __init__(self) -> None:
        self.failed: List[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        log(f"check {name}: {'ok' if ok else 'FAIL'}"
            + (f" ({detail})" if detail else ""))
        if not ok:
            self.failed.append(name)
        return bool(ok)


def alert_columns(instance) -> Dict[str, np.ndarray]:
    from sitewhere_tpu.model.event import DeviceEventType
    from sitewhere_tpu.persist.eventlog import EventFilter

    return instance.event_log.query_columns(
        TENANT, EventFilter(event_type=DeviceEventType.ALERT),
        ["device_token", "alert_type"])


def check_effects(instance, served: Served, checks: Checks,
                  geofence_impl: str) -> None:
    """Device state, alert counts per family, in-step stage fires,
    delivery and health, against the reference."""
    world, traffic, ref = served.world, served.traffic, served.ref
    engine = instance.pipeline_engine
    processed = processed_events(engine)
    checks("processed", processed == traffic.n,
           f"processed={processed} offered={traffic.n}")

    state = engine.canonical_state()
    idx = world.device_idx
    got_count = np.asarray(state.event_count)[idx]
    checks("event_counts", np.array_equal(got_count, ref.event_count),
           f"{world.n_devices} devices, "
           f"{int((got_count != ref.event_count).sum())} differ")
    slots = [engine.packer.measurements.lookup(m) for m in MEASUREMENTS]
    lm = np.asarray(state.last_measurement)[idx][:, slots]
    lmts = (np.asarray(state.last_measurement_ts)[idx][:, slots]
            .astype(np.int64) + engine.packer.epoch_base_ms)
    seen = ref.last_ts >= 0
    value_ok = np.array_equal(lm[seen], ref.last_value[seen])
    ts_ok = np.array_equal(lmts[seen], ref.last_ts[seen])
    checks("last_value_per_slot", value_ok and ts_ok,
           f"{int(seen.sum())} (device, slot) pairs over "
           f"{world.n_devices} devices, slots {slots}")

    cols = alert_columns(instance)
    types = cols["alert_type"]
    n_thr = int((types == THRESHOLD_TYPE).sum())
    n_geo = int((types == GEOFENCE_TYPE).sum())
    n_dev_alerts = int(np.isin(types, DEVICE_ALERT_TYPES).sum())
    checks("threshold_alerts", n_thr == int(ref.threshold_fired.sum()),
           f"persisted={n_thr} reference={int(ref.threshold_fired.sum())}")
    checks("geofence_alerts", n_geo == int(ref.geofence_fired.sum()),
           f"persisted={n_geo} reference={int(ref.geofence_fired.sum())}")
    checks("device_alerts", n_dev_alerts == int(ref.device_alerts.sum()),
           f"persisted={n_dev_alerts} "
           f"reference={int(ref.device_alerts.sum())}")
    checks("alert_lane_drops", engine.alerts_dropped == 0,
           f"alerts_dropped={engine.alerts_dropped}")

    prog = engine.rule_program_counters()["smoke-program"]["fires"]
    n_prog = int((types == PROGRAM_TYPE).sum())
    checks("rule_program_fires", prog >= 1 and n_prog == prog,
           f"lane-delivered={n_prog} fires={prog}")
    model = engine.anomaly_model_counters()["smoke-model"]["fires"]
    n_model = int((types == MODEL_TYPE).sum())
    checks("anomaly_model_fires", model >= 1 and n_model == model,
           f"lane-delivered={n_model} fires={model}")
    policy = engine.actuation_policy_counters()["smoke-policy"]["fires"]
    fanout = instance.command_fanout.stats()
    received = len(world.commands.delivered)
    checks("actuation_policy_fires",
           policy >= 1 and engine.commands_fired == policy
           and fanout["delivered"] == policy and received == policy
           and fanout["parked"] == 0 and engine.commands_dropped == 0,
           f"lane-delivered={engine.commands_fired} fires={policy} "
           f"delivered={fanout['delivered']} received={received} "
           f"parked={fanout['parked']}")

    dlq = instance.bus.topic(
        instance.naming.event_source_decoded_events(TENANT)
        + ".dead-letter")
    inbound = instance.get_tenant_engine(TENANT).inbound
    parked = int(inbound.dead_letter_counter.value)
    dead = sum(dlq.end_offsets())
    checks("dead_lettered", parked == 0 and dead == 0,
           f"parked batches={parked} dead-letter records={dead}")
    retries = int(engine._retry_counter.value) - served.retries_before
    checks("step_retries", retries == 0, f"step_retries={retries}")
    checks("health", engine.health.state == "healthy",
           f"health={engine.health.state}")
    checks("geofence_impl", engine.geofence_impl == geofence_impl,
           f"geofence_impl={engine.geofence_impl}")
    missing = engine.presence_sweep()
    checks("presence_sweep", missing == [],
           f"newly missing={len(missing)}")


def check_compiled_step(engine, checks: Checks) -> None:
    """The served step program carries the Pallas kernel as a TPU custom
    call (compiled again from the same arguments; the persistent cache
    answers)."""
    from sitewhere_tpu.ops.pack import batch_to_blob

    blob = batch_to_blob(warmup_batch(engine))
    with engine._state_lock:
        compiled = engine._step_blob.lower(
            engine._ensure_params(), engine._state, engine._rule_state,
            engine._model_state, engine._actuation_state, blob).compile()
    text = compiled.as_text()
    checks("tpu_custom_call", "tpu_custom_call" in text,
           f"{text.count('tpu_custom_call')} occurrences in the compiled "
           f"step")


def warmup_batch(engine):
    """One event from an unknown device (index 0): the step compiles and
    runs on the served wire variant (elevation set -> 5 rows) but folds
    nothing, because index 0 is never registered."""
    from sitewhere_tpu.model.event import DeviceEventType

    return engine.packer.pack_columns(
        np.zeros(1, np.int32),
        np.full(1, int(DeviceEventType.LOCATION), np.int32),
        np.full(1, engine.packer.epoch_base_ms, np.int64),
        lat=np.ones(1, np.float32), lon=np.ones(1, np.float32),
        elevation=np.ones(1, np.float32))


def timed_step_seconds(engine, batch) -> float:
    import jax

    t0 = time.perf_counter()
    _, outputs = engine.submit_routed(batch)
    jax.block_until_ready(outputs.processed)
    return time.perf_counter() - t0


# ---------------------------------------------------------------- http --

def check_http(rest, world: World, traffic: Traffic, ref: Reference,
               checks: Checks, sample: np.ndarray) -> None:
    """A few answers over real HTTP with a minted JWT: device state,
    the alert listing, and one windowed analytics query."""
    from sitewhere_tpu.client.rest import SiteWhereClient

    client = SiteWhereClient(rest.base_url)
    client.authenticate("admin", "password")
    bad = []
    for d in sample.tolist():
        doc = client.get(f"/api/devicestates/{world.tokens[d]}")
        got = {name: np.float32(v[1])
               for name, v in (doc.get("lastMeasurements")
                               or doc.get("last_measurements") or {}).items()}
        want = {MEASUREMENTS[k]: ref.last_value[d, k]
                for k in range(len(MEASUREMENTS)) if ref.last_ts[d, k] >= 0}
        if got != want:
            bad.append(world.tokens[d])
    checks("http_device_state", not bad,
           f"{len(sample)} devices" + (f", differ: {bad}" if bad else ""))

    fired = ref.threshold_fired | ref.geofence_fired
    bad = []
    for d in sample.tolist():
        doc = client.get(f"/api/assignments/{world.assignments[d]}/alerts",
                         pageSize=10_000)
        types = [r.get("type") for r in doc["results"]]
        want = (int(ref.device_alerts[d]),
                int((ref.threshold_fired & (traffic.dev == d)).sum()),
                int((ref.geofence_fired & (traffic.dev == d)).sum()))
        got = (sum(t in DEVICE_ALERT_TYPES for t in types),
               types.count(THRESHOLD_TYPE), types.count(GEOFENCE_TYPE))
        if got != want:
            bad.append((world.tokens[d], got, want))
    checks("http_alert_listing", not bad,
           f"{len(sample)} assignments, {int(fired.sum())} rule alerts "
           "fleet-wide" + (f", differ: {bad}" if bad else ""))

    window_ms = 60_000
    start = int(traffic.ts[0] // window_ms * window_ms)
    end = int(traffic.ts[-1])
    doc = client.get("/api/analytics/windows", window_ms=window_ms,
                     start_ms=start, end_ms=end, mm="m0", keys=64)
    rows = (traffic.kind == 0) & (traffic.mm == 0)
    keys_ref = np.unique(traffic.dev[rows])
    ok = doc["num_keys"] == keys_ref.size and len(doc["keys"]) > 0
    by_token = {t: i for i, t in enumerate(world.tokens)}
    for key in doc["keys"]:
        d = by_token.get(key["token"])
        sel = rows & (traffic.dev == (-1 if d is None else d))
        bucket = (traffic.ts[sel] - start) // window_ms
        want = np.bincount(bucket, minlength=doc["n_windows"])
        want_sum = np.bincount(bucket, weights=traffic.value[sel],
                               minlength=doc["n_windows"])
        got_sum = np.array([s or 0.0 for s in key["sum"]])
        ok &= (np.array_equal(np.asarray(key["count"]), want)
               and np.allclose(got_sum, want_sum, rtol=1e-5, atol=1e-3))
    checks("http_analytics_windows", bool(ok),
           f"num_keys={doc['num_keys']} reference={keys_ref.size}, "
           f"{len(doc['keys'])} keys x {doc['n_windows']} windows compared,"
           f" route={doc['serving']['route']}")


# ------------------------------------------------------------- phases --

@dataclass
class Served:
    """What one served run leaves for the checks."""

    world: World
    traffic: Traffic
    ref: Reference
    retries_before: int


def serve_traffic(instance, size: Size, seed: int,
                  geofence_impl: str) -> Served:
    """World, warm-up compile, seeded traffic through the decoded-events
    topic until the fused step has folded all of it."""
    engine = instance.pipeline_engine
    if engine.geofence_impl != geofence_impl:
        # tests run the kernel in interpret mode on the CPU
        engine.geofence_impl = geofence_impl
        engine._build_step_blob()
    t0 = time.perf_counter()
    world = build_world(instance, seed, size.devices, size.zones)
    log(f"world: tenant={TENANT} registered_devices={size.devices} "
        f"device_slots={engine.registry.devices.capacity} "
        f"zones={size.zones}x{engine.registry.max_zone_vertices} "
        f"threshold_rules={len(threshold_rules())} geofence_rules="
        f"{len(world.inside_zones) + len(world.outside_zones)} "
        f"rule_programs=1 anomaly_models=1 actuation_policies=1 "
        f"batch={engine.packer.batch_size} "
        f"build_s={time.perf_counter() - t0:.3f}")
    retries_before = int(engine._retry_counter.value)
    compile_s = timed_step_seconds(engine, warmup_batch(engine))
    log(f"compile: first fused step (warm-up, one unregistered event, "
        f"block_until_ready) compile_s={compile_s:.3f}")
    traffic = make_traffic(world, seed, size.events,
                           engine.packer.epoch_base_ms + 1000)
    ref = build_reference(world, traffic)
    t0 = time.perf_counter()
    publish_traffic(instance, world, traffic)
    publish_s = time.perf_counter() - t0
    consume_s = wait_consumed(instance, traffic.n, size.timeout_s)
    log(f"served: offered={traffic.n} events in "
        f"{traffic.record_dev.size} records "
        f"(mix {'/'.join(str(10 * m) for m in RECORD_MIX)} "
        f"measurement/location/alert over {len(MEASUREMENTS)} "
        f"measurements) publish_s={publish_s:.3f} "
        f"consume_s={consume_s:.3f} steps={engine.batches_processed}")
    return Served(world, traffic, ref, retries_before)


def run_single(size: Size, seed: int, data_dir: str,
               overrides: Optional[Dict] = None,
               geofence_impl: str = "pallas") -> Checks:
    """The served single-chip path: reference checks, the compiled
    kernel (on the chip), HTTP answers, then step timing."""
    checks = Checks()
    _, instance, rest = boot(data_dir, overrides)
    try:
        engine = instance.pipeline_engine
        served = serve_traffic(instance, size, seed, geofence_impl)
        world, traffic, ref = served.world, served.traffic, served.ref
        check_effects(instance, served, checks, geofence_impl)
        if geofence_impl == "pallas":
            check_compiled_step(engine, checks)
        rng = np.random.default_rng([seed, 3])
        sample = rng.choice(np.unique(traffic.dev), 8, replace=False)
        check_http(rest, world, traffic, ref, checks, sample)

        # steady step seconds AFTER the checks (these steps fold the
        # traffic again): full batches on the served wire variant
        steps = [timed_step_seconds(engine, b) for b in traffic_batches(
            engine.packer, world, traffic, engine.packer.batch_size,
            limit=8 * engine.packer.batch_size)]
        log(f"timing: steady_step_s={float(np.median(steps)):.6f} "
            f"(median of {len(steps)} direct fused steps of "
            f"{engine.packer.batch_size} events, each ended with "
            f"block_until_ready; not a benchmark)")
    finally:
        rest.stop()
        instance.stop()
    return checks


def run_sharded(size: Size, seed: int, data_dir: Optional[str],
                overrides: Optional[Dict] = None, n_shards: int = 4,
                geofence_impl: str = "pallas") -> Checks:
    """``serve --shards 4``: the served path over a 4-device mesh with
    device routing left at auto, against the NumPy reference; then the
    route-parity drill (a device-routed and a host-routed engine on the
    same devices, fed identical batches, bit-identical lanes and state);
    then the window-sharded replay with both combines. `data_dir` None
    keeps the registry and logs in memory: on the chip host a durable
    registry commit costs ~2 ms, and 200,000 of them would spend most of
    a four-chip call on sqlite instead of on the mesh."""
    checks = Checks()
    _, instance, rest = boot(data_dir, dict(overrides or {},
                                            **{"mesh.shards": n_shards}))
    try:
        engine = instance.pipeline_engine
        mesh_devices = set(engine.mesh.devices.flat)
        checks("mesh", len(mesh_devices) == n_shards
               and engine.device_routing,
               f"{n_shards} shards on {sorted(d.id for d in mesh_devices)}"
               f" device_routing={engine.device_routing}")
        served = serve_traffic(instance, size, seed, geofence_impl)
        stats = engine.stats()
        log(f"routes: device_route_steps={stats['device_route_steps']} "
            f"device_route_fallbacks={stats['device_route_fallbacks']}")
        check_effects(instance, served, checks, geofence_impl)
        checks("served_route", stats["device_route_dropped"] == 0
               and stats["pending_overflow"] == 0,
               f"device_route_dropped={stats['device_route_dropped']} "
               f"pending_overflow={stats['pending_overflow']}")
        placed = set(engine.state.event_count.sharding.device_set)
        checks("state_placement", placed == mesh_devices,
               f"event_count on {len(placed)} distinct devices")
        check_route_parity(instance, served.world, served.traffic, checks,
                           geofence_impl)
        check_sharded_replay(engine.mesh, served.traffic, checks)
    finally:
        rest.stop()
        instance.stop()
    return checks


def check_route_parity(instance, world: World, traffic: Traffic,
                       checks: Checks, geofence_impl: str) -> None:
    """Device routing against its host-routed oracle on the same mesh:
    the same batches (half the global batch, so no shard overflows on
    the host path) must give bit-identical alert lanes, alerts and
    canonical state."""
    import jax

    from sitewhere_tpu.parallel import ShardedPipelineEngine

    served = instance.pipeline_engine
    pair = []
    for routing in (True, False):
        engine = ShardedPipelineEngine(
            instance.registry_tensors, mesh=served.mesh,
            per_shard_batch=served.batch_size,
            measurement_slots=served.measurement_slots,
            max_tenants=served.max_tenants, device_routing=routing,
            geofence_impl=geofence_impl,
            name=f"smoke-route-{'device' if routing else 'host'}")
        engine.packer.epoch_base_ms = served.packer.epoch_base_ms
        install_stages(engine, world)
        engine.start()
        pair.append(engine)
    dev_engine, host_engine = pair
    per_batch = served.packer.batch_size // 2
    lanes_ok = alerts_ok = True
    steps = 0
    for b_dev, b_host in zip(
            traffic_batches(dev_engine.packer, world, traffic, per_batch),
            traffic_batches(host_engine.packer, world, traffic, per_batch)):
        r_dev, o_dev = dev_engine.submit_routed(b_dev)
        r_host, o_host = host_engine.submit_routed(b_host)
        lanes_ok &= np.array_equal(
            np.asarray(jax.device_get(o_dev.alert_lanes)),
            np.asarray(jax.device_get(o_host.alert_lanes)))
        a_dev = dev_engine.materialize_alerts(r_dev, o_dev)
        a_host = host_engine.materialize_alerts(r_host, o_host)
        alerts_ok &= ([(a.device_id, a.type, int(a.level)) for a in a_dev]
                      == [(a.device_id, a.type, int(a.level))
                          for a in a_host])
        steps += 1
    checks("route_parity_lanes", lanes_ok and alerts_ok,
           f"{steps} steps of {per_batch} events")
    state_ok = True
    for name in ("canonical_state", "canonical_rule_state",
                 "canonical_model_state", "canonical_actuation_state"):
        got = getattr(dev_engine, name)()
        want = getattr(host_engine, name)()
        state_ok &= all(np.array_equal(np.asarray(a), np.asarray(b))
                        for a, b in zip(jax.tree_util.tree_leaves(got),
                                        jax.tree_util.tree_leaves(want)))
    checks("route_parity_state", state_ok,
           "device, rule-program, model and actuation state")
    s = dev_engine.stats()
    checks("route_parity_counters", s["device_route_steps"] >= 1
           and s["device_route_dropped"] == 0,
           f"device_route_steps={s['device_route_steps']} "
           f"fallbacks={s['device_route_fallbacks']} "
           f"dropped={s['device_route_dropped']} lane_capacity="
           f"{dev_engine.route_lane_capacity}")
    for engine in pair:
        engine.stop()


def check_sharded_replay(mesh, traffic: Traffic, checks: Checks) -> None:
    """Window-sharded replay of the m0 readings over the mesh, psum and
    ring combines, against NumPy."""
    from sitewhere_tpu.parallel.distributed import sharded_windowed_stats

    rows = (traffic.kind == 0) & (traffic.mm == 0)
    uniq, keys = np.unique(traffic.dev[rows], return_inverse=True)
    window_ms = 60_000
    ts_rel = traffic.ts[rows] - traffic.ts[0]
    n_windows = int(ts_rel.max() // window_ms) + 1
    num_keys = 1 << int(uniq.size - 1).bit_length()
    w_pad = 1 << int(n_windows - 1).bit_length()
    want = np.zeros((num_keys, w_pad), np.int64)
    np.add.at(want, (keys, ts_rel // window_ms), 1)
    for combine in ("psum", "ring"):
        stats = sharded_windowed_stats(
            keys, ts_rel, traffic.value[rows], np.ones(keys.size, bool),
            window_ms=window_ms, num_keys=num_keys, n_windows=w_pad,
            mesh=mesh, combine=combine)
        got = np.asarray(stats.count)
        checks(f"sharded_replay_{combine}", np.array_equal(got, want),
               f"{int(rows.sum())} rows, {uniq.size} keys x {n_windows} "
               f"windows")


def device_report(chips: int) -> Dict:
    """Print the device, versions, native runtime and compile cache;
    exit non-zero at once unless `chips` TPU devices are visible."""
    import importlib.metadata as md

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    log(f"device: platform={platform} kind={kind} count={len(devices)}")
    if platform != "tpu" or len(devices) < chips:
        print(f"chip_smoke: needs {chips} TPU device(s), found "
              f"{len(devices)} x {platform}; no CPU fallback",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    versions = []
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            versions.append(f"{dist}={md.version(dist)}")
        except md.PackageNotFoundError:
            versions.append(f"{dist}=not installed")
    log("versions: " + " ".join(versions))
    from sitewhere_tpu import native

    log(f"native host runtime: {native.load_report()}")
    return {"platform": platform, "kind": kind, "count": len(devices)}


def main(argv=None) -> int:
    cache_dir = configure_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: the sharded path only (serve --shards 4)")
    args = parser.parse_args(argv)
    device = device_report(args.chips)
    log(f"compile cache: {cache_dir}")
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as scratch:
        if args.chips == 4:
            checks = run_sharded(FULL, args.seed, None)
        else:
            checks = run_single(FULL, args.seed, scratch)
    if checks.failed:
        print(f"chip_smoke: failed checks: {', '.join(checks.failed)}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
