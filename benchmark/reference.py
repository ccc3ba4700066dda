"""The plain reference and the comparison that decides `correct`.

`expected` is a straightforward NumPy statement of the served path's
semantics over exactly the records that were published (and, after the
drain, committed): every reading folds into its device's count, the last
reading of each (device, measurement) wins by event date, every reading
is persisted as it was sent, and every reading that breaks a threshold
rule of its name raises one threshold alert, dated as the reading, on its
device. It imports nothing of the program.

`observe` reads the same quantities back from the program after the
window. `compare` gives each number compared with its limit; a run is
correct when none exceeds its limit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from benchmark.traffic import Traffic
from benchmark.world import THRESHOLD_TYPE, World

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


def _rows(date, dev, mm, value) -> np.ndarray:
    """Readings as one int64 row each, comparable as wholes; a value
    compares by its float32 bits."""
    bits = np.asarray(value, np.float32).view(np.int32).astype(np.int64)
    return np.stack([np.asarray(date, np.int64), np.asarray(dev, np.int64),
                     np.asarray(mm, np.int64), bits], 1)


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
        rows=_rows(traffic.ts, traffic.dev, traffic.mm, value),
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
        rows=_rows(cols["event_date"], device_of(cols["device_token"]), mm,
                   cols["value"]),
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


def unmatched(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of either side with no equal row on the other, each row
    counted as often as it occurs."""
    both = np.concatenate([got, want])
    if both.shape[0] == 0:
        return 0
    side = np.r_[np.ones(got.shape[0]), -np.ones(want.shape[0])]
    _, inverse = np.unique(both, axis=0, return_inverse=True)
    return int(np.abs(np.bincount(inverse.ravel(), weights=side)).sum())


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
