"""TPCx-IoT's deployment: power-substation gateways ingesting ~1 KB
sensor readings (`configs/tpcx_iot.json`).

The world is a device type, `areas` substations and `devices_per_area`
sensors in each, with their assignments; the fused rules are the
configuration's threshold rules.

Traffic: records of one device each, uniform over the fleet, holding
`readings_per_record` readings of uniform measurement names and values.
Records are msgpack `DeviceEventBatch` envelopes. Every seed draws the same
number of records of the same sizes; only devices, names and values move.

The reference: every reading folds into its device's count, the last
reading of each (device, measurement) wins by event date, every reading
is persisted as it was sent, and every reading that breaks a threshold
rule of its name raises one threshold alert, dated as the reading, on its
device.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from benchmark.reference import rows, unmatched
from benchmark.world import World, attach_devices

THRESHOLD_TYPE = "bench.threshold"
_OPS = {">": np.greater, ">=": np.greater_equal, "<": np.less,
        "<=": np.less_equal}

# Every number compared is a count, and every limit is exact (PERF.md,
# "How correct is decided", gives the readings behind each).
LIMITS = {
    "lost_events": 0,
    "uncommitted_records": 0,
    "dead_lettered": 0,
    "count_mismatch_devices": 0,
    "last_value_mismatches": 0,
    "persisted_row_mismatches": 0,
    "threshold_alert_mismatches": 0,
    "lane_drops": 0,
}
# the numbers the control (readings in bfloat16) pushes over their limits
CONTROL_FAILS = ("last_value_mismatches",)
# the event field that the tests' "value altered" fault changes
FAULT_FIELD = "value"


# --------------------------------------------------------------- world --

def n_devices(cfg: Dict) -> int:
    return int(cfg["areas"]) * int(cfg["devices_per_area"])


def rule_dicts(cfg: Dict) -> List[Dict]:
    """The fused rules as `serve` config entries (the `rules` list that
    `_apply_rule_config` installs at every boot)."""
    return [{"type": "threshold", "token": f"thr-{i}",
             "measurement_name": mm, "operator": op, "threshold": value,
             "alert_type": THRESHOLD_TYPE, "alert_level": level}
            for i, (mm, op, value, level) in enumerate(cfg["threshold_rules"])]


def provision(instance, cfg: Dict) -> None:
    """Register the world through the control-plane API: the device type,
    the areas and the devices with their assignments."""
    from sitewhere_tpu.model import Area, Device, DeviceAssignment, DeviceType

    reg = instance.get_tenant_engine(cfg["tenant"]).registry
    dtype = reg.create_device_type(DeviceType(token="bench-sensor",
                                              name="bench sensor"))
    areas = [reg.create_area(Area(token=f"area-{a}", name=f"area {a}"))
             for a in range(int(cfg["areas"]))]
    per_area = int(cfg["devices_per_area"])
    for i in range(n_devices(cfg)):
        device = reg.create_device(Device(token=f"dev-{i}",
                                          device_type_id=dtype.id))
        reg.create_device_assignment(DeviceAssignment(
            token=f"as-{i}", device_id=device.id,
            area_id=areas[i // per_area].id))


def attach(instance, cfg: Dict) -> World:
    return attach_devices(instance, cfg,
                          [f"dev-{i}" for i in range(n_devices(cfg))])


def describe(cfg: Dict) -> str:
    return (f"{cfg['name']} devices={n_devices(cfg)} "
            f"threshold_rules={len(cfg['threshold_rules'])}")


# ------------------------------------------------------------- traffic --

def events_per_record(mix: Dict) -> int:
    return int(mix["readings_per_record"])


@dataclass
class Traffic:
    """Columnar readings in publish order; record r holds readings
    [r * E, (r + 1) * E) of device `record_dev[r]`."""

    per_record: int
    record_dev: np.ndarray   # [R] device number
    dev: np.ndarray          # [N] device number
    ts: np.ndarray           # [N] int64 ms, unique, increasing
    mm: np.ndarray           # [N] measurement name number
    value: np.ndarray        # [N] float32

    @property
    def n(self) -> int:
        return int(self.dev.shape[0])

    def prefix(self, n_records: int) -> "Traffic":
        """The first `n_records` records."""
        e = n_records * self.per_record
        return Traffic(self.per_record, self.record_dev[:n_records],
                       self.dev[:e], self.ts[:e], self.mm[:e],
                       self.value[:e])


def make_traffic(world: World, mix: Dict, seed: int, n_records: int,
                 base_ms: int) -> Traffic:
    """Seeded records of one device each, uniform over the fleet; every
    reading has its own millisecond so "last" is unambiguous everywhere."""
    rng = np.random.default_rng([seed, 2])
    per = events_per_record(mix)
    n = n_records * per
    record_dev = rng.integers(0, world.n, n_records)
    lo, hi = mix["value_range"]
    return Traffic(
        per, record_dev, np.repeat(record_dev, per),
        base_ms + np.arange(n, dtype=np.int64),
        rng.integers(0, len(world.cfg["measurement_names"]), n),
        rng.uniform(lo, hi, n).astype(np.float32))


def encode_records(world: World, traffic: Traffic, mix: Dict,
                   source: str = "bench") -> List[Tuple[bytes, bytes]]:
    """Every record as (key, msgpack DeviceEventBatch envelope), padded
    with a metadata payload to `record_bytes` where the mix asks for it."""
    import msgpack

    names = world.cfg["measurement_names"]
    target = int(mix.get("record_bytes", 0))
    mms, values = traffic.mm.tolist(), traffic.value.tolist()
    tss = traffic.ts.tolist()
    tokens = world.tokens
    per = traffic.per_record
    pad = ""
    records = []
    for r, d in enumerate(traffic.record_dev.tolist()):
        token = tokens[d]
        request = {"device_token": token, "measurements": [],
                   "locations": [], "alerts": []}
        for i in range(r * per, (r + 1) * per):
            event = {"event_type": 0, "name": names[mms[i]],
                     "value": values[i], "event_date": tss[i]}
            if target:
                event["metadata"] = {"sensor_key": token, "payload": pad}
            request["measurements"].append(event)
        envelope = {"sourceId": source, "deviceToken": token,
                    "kind": "DeviceEventBatch", "request": request,
                    "metadata": {}}
        value = msgpack.packb(envelope, use_bin_type=True)
        if target and r == 0:
            # size the padding once, from the first record
            pad = "x" * max(0, target - len(value))
            request["measurements"][0]["metadata"]["payload"] = pad
            value = msgpack.packb(envelope, use_bin_type=True)
        records.append((token.encode(), value))
    return records


# ----------------------------------------------------------- reference --

@dataclass
class Outcome:
    """What the served path did, or what it should have done."""

    events: int                 # readings folded into device state
    event_count: np.ndarray     # [n_devices]
    last_value: np.ndarray      # [n_devices, K] float32 (NaN: none)
    last_ts: np.ndarray         # [n_devices, K] int64 (-1: none)
    rows: np.ndarray            # persisted readings (date, device, name, bits)
    alerts: np.ndarray          # threshold alerts (date, device)
    uncommitted_records: int = 0
    dead_lettered: int = 0
    lane_drops: int = 0


def expected(world: World, traffic: Traffic,
             value_dtype=np.float32) -> Outcome:
    """The reference. `value_dtype` below float32 makes the control."""
    cfg = world.cfg
    names = cfg["measurement_names"]
    n_dev, k = world.n, len(names)
    value = traffic.value.astype(value_dtype).astype(np.float32)
    last_value = np.full((n_dev, k), np.nan, np.float32)
    last_ts = np.full((n_dev, k), -1, np.int64)
    # dates rise with the row: the last row of each pair is its last
    pair = traffic.dev.astype(np.int64) * k + traffic.mm
    _, from_end = np.unique(pair[::-1], return_index=True)
    last = traffic.n - 1 - from_end
    last_value[traffic.dev[last], traffic.mm[last]] = value[last]
    last_ts[traffic.dev[last], traffic.mm[last]] = traffic.ts[last]
    # one alert per reading, however many of its name's rules it breaks
    broken = np.zeros(traffic.n, bool)
    for mm_name, op, threshold, _level in cfg["threshold_rules"]:
        broken |= ((traffic.mm == names.index(mm_name))
                   & _OPS[op](value, np.float32(threshold)))
    return Outcome(
        events=traffic.n,
        event_count=np.bincount(traffic.dev, minlength=n_dev),
        last_value=last_value, last_ts=last_ts,
        rows=rows([traffic.ts, traffic.dev, traffic.mm], [value]),
        alerts=np.stack([traffic.ts[broken],
                         traffic.dev[broken].astype(np.int64)], 1))


def observe(instance, world: World, topic, group) -> Outcome:
    """The same quantities read back from the program after the drain:
    device state from the engine, rows and alerts from the event log."""
    from sitewhere_tpu.model.event import DeviceEventType
    from sitewhere_tpu.persist.eventlog import EventFilter

    cfg = world.cfg
    tenant = cfg["tenant"]
    engine = instance.pipeline_engine
    names = cfg["measurement_names"]
    state = engine.canonical_state()
    idx = world.device_idx
    slots = [engine.packer.measurements.lookup(m) for m in names]
    event_count = np.asarray(state.event_count)[idx].astype(np.int64)
    last_value = np.asarray(state.last_measurement)[idx][:, slots]
    last_ts = (np.asarray(state.last_measurement_ts)[idx][:, slots]
               .astype(np.int64) + engine.packer.epoch_base_ms)
    log = instance.event_log

    def device_of(tokens) -> np.ndarray:
        return np.array([world.token_index.get(t, -1) for t in tokens],
                        np.int64)

    cols = log.query_columns(
        tenant, EventFilter(event_type=DeviceEventType.MEASUREMENT),
        ["event_date", "device_token", "mm_name", "value"])
    name_of = {m: i for i, m in enumerate(names)}
    mm = np.array([name_of.get(m, -1) for m in cols["mm_name"]], np.int64)
    alerts = log.query_columns(
        tenant, EventFilter(event_type=DeviceEventType.ALERT),
        ["event_date", "device_token", "alert_type"])
    mine = alerts["alert_type"] == THRESHOLD_TYPE
    dlq = instance.bus.topic(topic.name + ".dead-letter")
    return Outcome(
        events=int(sum(engine.stats()["tenant_event_count"])),
        event_count=event_count, last_value=last_value, last_ts=last_ts,
        rows=rows([cols["event_date"], device_of(cols["device_token"]), mm],
                  [cols["value"]]),
        alerts=np.stack([alerts["event_date"][mine].astype(np.int64),
                         device_of(alerts["device_token"][mine])], 1),
        uncommitted_records=int(sum(topic.end_offsets())
                                - sum(group.committed)),
        dead_lettered=int(sum(dlq.end_offsets())),
        lane_drops=int(engine.alerts_dropped))


def control(world: World, traffic: Traffic, topic, group) -> Outcome:
    """The control: the reference in the program's place, with readings
    carried in bfloat16, the precision below the float32 that the
    configuration states."""
    import ml_dtypes

    out = expected(world, traffic, value_dtype=ml_dtypes.bfloat16)
    out.uncommitted_records = int(sum(topic.end_offsets())
                                  - sum(group.committed))
    return out


def compare(observed: Outcome, want: Outcome) -> List[Tuple[str, int, int]]:
    """Each number compared, with its limit."""
    seen = want.last_ts >= 0
    value_bad = ~((observed.last_value == want.last_value)
                  & (observed.last_ts == want.last_ts))
    numbers = {
        "lost_events": abs(want.events - observed.events),
        "uncommitted_records": observed.uncommitted_records,
        "dead_lettered": observed.dead_lettered,
        "count_mismatch_devices": int(
            (observed.event_count != want.event_count).sum()),
        "last_value_mismatches": int((value_bad & seen).sum()),
        "persisted_row_mismatches": unmatched(observed.rows, want.rows),
        "threshold_alert_mismatches": unmatched(observed.alerts,
                                                want.alerts),
        "lane_drops": observed.lane_drops,
    }
    return [(name, value, LIMITS[name]) for name, value in numbers.items()]
