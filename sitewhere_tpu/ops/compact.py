"""On-device alert-lane compaction: prefix-sum pack of fired rows.

The latency tier's floor is set by D2H round trips, not compute: every
separate fetch is its own host<->device round trip, and the pre-lane
materializer shipped six per-row arrays (two phases on big batches) to
find the handful of rows that actually fired. The tf.data / pipelined-
execution principle (arXiv:2101.12127, arXiv:1908.09291) — move the data
reduction to where the data lives — applied to the *output* side of the
fused step: a prefix-sum over the fired mask packs fired rows into
fixed-capacity lanes INSIDE the jit, so alert materialization ships one
fixed-shape, lane-capacity-sized int32 array per step regardless of
batch size.

Lane layout ([ALERT_LANE_ROWS, K] int32; slot i = i-th fired row in
batch-row order, so materialization order matches a mask scan exactly):

  row 0 (idx):   batch-row index of the fired row; -1 in unused slots
  row 1 (rules): threshold first_rule in bits 0-15, geofence first_rule
                 in bits 16-31 (int16 two's complement; -1 = none)
  row 2 (meta):  threshold alert_level bits 0-3 | anomaly-model slot
                 low nibble bits 4-7 | geofence alert_level bits 8-11 |
                 anomaly-model slot high nibble bits 12-15 |
                 threshold_fired bit 16 | geofence_fired bit 17 |
                 program_fired bit 18 | program slot id bits 19-26 |
                 program alert_level bits 27-30 | model_fired bit 31
                 (the sign bit: a negative meta word IS a model fire).
                 Levels/ids are only meaningful under their fired bit.
                 AlertLevel tops out at 3 (model/event.py), so the
                 built-in level fields always fit a nibble — the upper
                 nibbles of the old 8-bit level fields are the spare
                 bits the anomaly-model slot id rides. Rule-program and
                 anomaly-model fires both ride spare meta bits so the
                 lane layout and the perf gate's bytes budget are
                 unchanged; the model's alert LEVEL is resolved host
                 side from its slot's spec (no bits needed)
  row 3 (counts): [0] = fired rows this step (INCLUDING rows beyond
                 capacity), [1] = alerts dropped by lane overflow (each
                 fired rule family on a row beyond capacity counts one),
                 [2] = total alerts fired (mirrors ProcessOutputs.alerts),
                 [3] = rows the on-device shard route had to drop
                 (ops/route.py ROUTE_DROPPED_SLOT; zero on host-routed
                 steps and whenever the host lane-fit guard ran)

Overflow contract: rows beyond the K capacity are counted on device
(counts[1]) and surface on the engine's `alerts_dropped` — an alert
storm degrades to bounded delivery with loud accounting, never silent
loss of the count. Capacity is a compile-time constant (one cached jit
program per capacity, like every other static shape here).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np

ALERT_LANE_ROWS = 4
# bytes each lane slot costs on the wire (ALERT_LANE_ROWS int32 rows) —
# the perf gate's fetch-size budget is capacity * this
ALERT_LANE_BYTES_PER_SLOT = ALERT_LANE_ROWS * 4
DEFAULT_ALERT_LANE_CAPACITY = 128
# counts ride slots 0..2 of the counts row
MIN_ALERT_LANE_CAPACITY = 4

_THR_FIRED_BIT = 16
_GEO_FIRED_BIT = 17
# rule-program fire fields ride the SPARE meta bits (18-30) so the lane
# layout — and the perf gate's bytes-per-slot budget — stays unchanged:
# bit 18 = program fired, bits 19-26 = program slot id (table bucket is
# capped at 256 programs), bits 27-30 = program alert level (<= 15)
_PROG_FIRED_BIT = 18
_PROG_RULE_SHIFT = 19
_PROG_LEVEL_SHIFT = 27
# anomaly-model fires (ops/anomaly.py): fired rides the sign bit, the
# 8-bit model slot id (table bucket capped at 64, so 8 bits is roomy)
# splits across the two nibbles the 4-bit level fields never used
_MODEL_FIRED_BIT = 31
_MODEL_SLOT_LO_SHIFT = 4
_MODEL_SLOT_HI_SHIFT = 12


def compact_alert_lanes(thr: Dict, geo: Dict, capacity: int,
                        prog: Dict = None, model: Dict = None):
    """Pack the step's fired rows into alert lanes (jax, call under jit).

    `thr`/`geo` are the eval_threshold_rules / eval_geofence_rules output
    dicts (fired/first_rule/alert_level, all [B]); `prog` is the optional
    rule-program row dict of the same shape (ops/stateful.py fires mapped
    to attach rows); `model` is the optional anomaly-model row dict
    (ops/anomaly.py: fired/first_model, also attach-row mapped). Returns
    the [ALERT_LANE_ROWS, capacity] int32 lane array described above.
    Works per shard under shard_map (row indices are shard-local).
    """
    import jax.numpy as jnp

    if capacity < MIN_ALERT_LANE_CAPACITY:
        raise ValueError(
            f"alert lane capacity {capacity} < {MIN_ALERT_LANE_CAPACITY}")
    B = thr["fired"].shape[0]
    if prog is None:
        zero = jnp.zeros((B,), jnp.int32)
        prog = {"fired": jnp.zeros((B,), bool), "first_rule": zero,
                "alert_level": zero}
    if model is None:
        model = {"fired": jnp.zeros((B,), bool),
                 "first_model": jnp.full((B,), -1, jnp.int32)}
    fired = (thr["fired"] | geo["fired"] | prog["fired"]
             | model["fired"])                                # bool [B]
    fired_i = fired.astype(jnp.int32)
    rank = jnp.cumsum(fired_i) - 1                            # 0-based
    keep = fired & (rank < capacity)
    # out-of-capacity rows scatter to index `capacity` -> dropped by the
    # OOB mode; kept ranks are unique by construction
    slot = jnp.where(keep, rank, capacity)
    idx_lane = jnp.full((capacity,), -1, jnp.int32).at[slot].set(
        jnp.arange(B, dtype=jnp.int32), mode="drop")
    rules = ((thr["first_rule"] & 0xFFFF)
             | ((geo["first_rule"] & 0xFFFF) << 16))
    rules_lane = jnp.zeros((capacity,), jnp.int32).at[slot].set(
        rules, mode="drop")
    prog_fired_i = prog["fired"].astype(jnp.int32)
    model_slot = jnp.where(model["fired"], model["first_model"] & 0xFF, 0)
    meta = ((thr["alert_level"] & 0xF)
            | ((model_slot & 0xF) << _MODEL_SLOT_LO_SHIFT)
            | ((geo["alert_level"] & 0xF) << 8)
            | (((model_slot >> 4) & 0xF) << _MODEL_SLOT_HI_SHIFT)
            | (thr["fired"].astype(jnp.int32) << _THR_FIRED_BIT)
            | (geo["fired"].astype(jnp.int32) << _GEO_FIRED_BIT)
            | (prog_fired_i << _PROG_FIRED_BIT)
            | (jnp.where(prog["fired"], prog["first_rule"] & 0xFF, 0)
               << _PROG_RULE_SHIFT)
            | (jnp.where(prog["fired"], prog["alert_level"] & 0xF, 0)
               << _PROG_LEVEL_SHIFT))
    # bit 31 via the sign: `x << 31` on a positive int is undefined
    # territory in some numpy paths, so set the sign bit with where
    meta = jnp.where(model["fired"], meta | jnp.int32(-(2 ** 31)), meta)
    meta_lane = jnp.zeros((capacity,), jnp.int32).at[slot].set(
        meta, mode="drop")
    alerts_of = (thr["fired"].astype(jnp.int32)
                 + geo["fired"].astype(jnp.int32)
                 + prog_fired_i
                 + model["fired"].astype(jnp.int32))          # 0..4 per row
    total_alerts = jnp.sum(alerts_of)
    kept_alerts = jnp.sum(jnp.where(keep, alerts_of, 0))
    counts_lane = (jnp.zeros((capacity,), jnp.int32)
                   .at[0].set(jnp.sum(fired_i))
                   .at[1].set(total_alerts - kept_alerts)
                   .at[2].set(total_alerts))
    return jnp.stack([idx_lane, rules_lane, meta_lane, counts_lane])


@dataclass
class DecodedAlertLanes:
    """Host-side view of one lane array's used slots (all arrays [n])."""

    rows: np.ndarray        # int32 batch-row indices, ascending
    thr_fired: np.ndarray   # bool
    geo_fired: np.ndarray   # bool
    thr_rule: np.ndarray    # int32 (sign-extended; -1 = none)
    geo_rule: np.ndarray    # int32
    thr_level: np.ndarray   # int32 (meaningful only where thr_fired)
    geo_level: np.ndarray   # int32
    fired_rows: int         # total fired rows incl. overflow
    dropped_alerts: int     # alerts lost to lane overflow
    total_alerts: int
    prog_fired: np.ndarray = None  # bool (rule-program composite fires)
    prog_rule: np.ndarray = None   # int32 program slot (-1 = none)
    prog_level: np.ndarray = None  # int32 (meaningful under prog_fired)
    route_dropped: int = 0         # rows dropped by the on-device route
    model_fired: np.ndarray = None  # bool (anomaly-model fires)
    model_slot: np.ndarray = None   # int32 model slot (-1 = none)

    def __post_init__(self):
        n = self.rows.shape[0]
        if self.prog_fired is None:
            self.prog_fired = np.zeros(n, bool)
            self.prog_rule = np.full(n, -1, np.int32)
            self.prog_level = np.zeros(n, np.int32)
        if self.model_fired is None:
            self.model_fired = np.zeros(n, bool)
            self.model_slot = np.full(n, -1, np.int32)

    @property
    def n(self) -> int:
        return int(self.rows.shape[0])

    def head(self, n: int) -> "DecodedAlertLanes":
        """First `n` slots (max_alerts bounding; counts untouched)."""
        return DecodedAlertLanes(
            rows=self.rows[:n], thr_fired=self.thr_fired[:n],
            geo_fired=self.geo_fired[:n], thr_rule=self.thr_rule[:n],
            geo_rule=self.geo_rule[:n], thr_level=self.thr_level[:n],
            geo_level=self.geo_level[:n], fired_rows=self.fired_rows,
            dropped_alerts=self.dropped_alerts,
            total_alerts=self.total_alerts,
            prog_fired=self.prog_fired[:n], prog_rule=self.prog_rule[:n],
            prog_level=self.prog_level[:n],
            route_dropped=self.route_dropped,
            model_fired=self.model_fired[:n],
            model_slot=self.model_slot[:n])


def decode_alert_lanes(lanes: np.ndarray) -> DecodedAlertLanes:
    """Inverse of compact_alert_lanes on the fetched host copy (numpy)."""
    lanes = np.asarray(lanes)
    capacity = lanes.shape[-1]
    counts = lanes[3]
    fired_rows = int(counts[0])
    n = min(fired_rows, capacity)
    rules = lanes[1, :n]
    meta = lanes[2, :n]
    prog_fired = ((meta >> _PROG_FIRED_BIT) & 1).astype(bool)
    model_fired = meta < 0                     # sign bit IS the fire bit
    return DecodedAlertLanes(
        rows=lanes[0, :n],
        thr_fired=((meta >> _THR_FIRED_BIT) & 1).astype(bool),
        geo_fired=((meta >> _GEO_FIRED_BIT) & 1).astype(bool),
        # int32 arithmetic shifts sign-extend the int16 halves exactly
        thr_rule=(rules << 16) >> 16,
        geo_rule=rules >> 16,
        thr_level=meta & 0xF,
        geo_level=(meta >> 8) & 0xF,
        fired_rows=fired_rows,
        dropped_alerts=int(counts[1]),
        total_alerts=int(counts[2]),
        prog_fired=prog_fired,
        prog_rule=np.where(prog_fired,
                           (meta >> _PROG_RULE_SHIFT) & 0xFF,
                           -1).astype(np.int32),
        prog_level=((meta >> _PROG_LEVEL_SHIFT) & 0xF).astype(np.int32),
        route_dropped=int(counts[3]),
        model_fired=model_fired,
        model_slot=np.where(
            model_fired,
            ((meta >> _MODEL_SLOT_LO_SHIFT) & 0xF)
            | (((meta >> _MODEL_SLOT_HI_SHIFT) & 0xF) << 4),
            -1).astype(np.int32))
