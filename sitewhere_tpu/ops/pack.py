"""Event packing: API events -> fixed-width SoA tensors.

The wire/API view of events is the dataclass family in model/event.py; the
device view is `EventBatch`: one fixed-width column per field, shape [B], with
a validity mask for padding. Variable-rate ingest never changes shapes — the
host packs whatever arrived into the next fixed-size batch and pads
(SURVEY.md §7 hard part (a): bucketed shapes + padding masks, no recompiles).

Timestamps are int32 milliseconds relative to a host-held `epoch_base_ms` so
they fit TPU-friendly 32-bit lanes; the host rebases periodically (int32 ms
covers ±24 days per base).

Columns are a strict superset of what each event type needs; unused columns
for a given event type are zero. This wastes HBM bytes but keeps a single
batch schema for the whole pipeline — the same trade the reference's
GDeviceEventPayload protobuf union makes, resolved SoA instead of AoS.

Reference: model fields from IDeviceMeasurement/IDeviceLocation/IDeviceAlert
(sitewhere-core-api spi/device/event/); packing replaces the per-event protobuf
decode at InboundPayloadProcessingLogic.java:141.
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple

import numpy as np
from flax import struct

from sitewhere_tpu.model.event import (
    DeviceAlert, DeviceEvent, DeviceEventType, DeviceLocation, DeviceMeasurement,
)
from sitewhere_tpu.registry.interning import TokenInterner


@struct.dataclass
class EventBatch:
    """SoA columns, all shape [B]. A jax pytree (works under jit/shard_map)."""

    device_idx: np.ndarray   # int32, interned device token (0 = unknown)
    tenant_idx: np.ndarray   # int32, interned tenant (filled by validation)
    event_type: np.ndarray   # int32, DeviceEventType value
    ts: np.ndarray           # int32, ms since epoch_base
    mm_idx: np.ndarray       # int32, interned measurement name
    value: np.ndarray        # float32, measurement value
    lat: np.ndarray          # float32
    lon: np.ndarray          # float32
    elevation: np.ndarray    # float32
    alert_type_idx: np.ndarray  # int32, interned alert type code
    alert_level: np.ndarray  # int32, AlertLevel value
    valid: np.ndarray        # bool, False for padding rows

    @property
    def batch_size(self) -> int:
        return self.device_idx.shape[0]


def empty_batch(batch_size: int) -> EventBatch:
    zi = np.zeros(batch_size, np.int32)
    zf = np.zeros(batch_size, np.float32)
    return EventBatch(
        device_idx=zi, tenant_idx=zi.copy(), event_type=zi.copy(), ts=zi.copy(),
        mm_idx=zi.copy(), value=zf, lat=zf.copy(), lon=zf.copy(),
        elevation=zf.copy(), alert_type_idx=zi.copy(), alert_level=zi.copy(),
        valid=np.zeros(batch_size, bool))


# Wire-blob layout v2: the host->device staging format is ONE contiguous
# int32 array of WIRE_ROWS rows per batch ([5, B]; [S, 5, B] routed).
# Host->device bandwidth is the pipeline's hard ceiling (the host link —
# SURVEY.md north star analysis), so the wire format is minimized:
# 20 B/event instead of the 48 B of one row per EventBatch column. The two
# payload rows are unions discriminated by event_type — a measurement's
# (value, mm_idx), a location's (lat, lon) and an alert's alert_type_idx
# are mutually exclusive, so they share rows with no precision loss.
# tenant_idx never crosses (validation re-derives it from the registry
# mirror on device, pipeline/step.py stage 1).
#   row 0: device_idx (bits 0-21) | event_type (22-24) |
#          alert_level (25-27) | valid (28)
#   row 1: ts (int32 ms, relative)
#   row 2: payload A — value f32 bits (measurement) | lat f32 bits (location)
#   row 3: payload B — mm_idx (measurement) | lon f32 bits (location) |
#          alert_type_idx (alert)
#   row 4: elevation f32 bits (carried for every type; zero unless set)
#
# COMPACT variant (v3): when no row of a batch carries a nonzero
# elevation — the common case for measurement/alert traffic and 2-D
# location fixes — row 4 is omitted entirely: 16 B/event instead of 20.
# The unpackers derive the variant from the blob's row dimension
# (elevation reads as 0 for 4-row blobs); jit compiles one program per
# shape, both cached. On a transfer-bound link (step_breakdown shows H2D
# dominating the step) this is a direct ~20% throughput lift.
#
# PACKED variant (v4): measurement/alert-only batches whose timestamps
# span < 65.536 s (any real-time ingest window) drop to THREE rows —
# 12 B/event. ts travels as a 16-bit delta against a per-batch base;
# mm_idx/alert_type_idx (12 bits) shares row 1 with the delta; the f32
# payload keeps full precision in row 2. The 32-bit ts base rides the
# 3 spare bits of row 0 across lanes 0..10 (3 bits/lane, two's
# complement), so no side-channel scalar transfer and no extra bytes.
# Location events still need lat+lon at full precision -> those batches
# stay on the 4/5-row layouts; the unpackers keep dispatching on the
# row dimension (one cached jit program per variant).
WIRE_ROWS = 5
WIRE_ROWS_COMPACT = 4
WIRE_ROWS_PACKED = 3
_TS_DELTA_BITS = 16
_TS_DELTA_MASK = (1 << _TS_DELTA_BITS) - 1
_PKIDX_SHIFT = 16
_BASE_SHIFT = 29     # row-0 bits 29..31 carry the ts base, lanes 0..10
_BASE_LANES = 11
WIRE_DEV_BITS = 22
WIRE_DEV_MAX = 1 << WIRE_DEV_BITS   # 4.19M interned devices per wire batch
_ET_SHIFT = 22
_LEVEL_SHIFT = 25
_VALID_SHIFT = 28
_META_MAX_IDX = 1 << 12  # mm_idx / alert_type_idx interner width (unchanged)

_ET_MEASUREMENT = int(DeviceEventType.MEASUREMENT)
_ET_LOCATION = int(DeviceEventType.LOCATION)
_ET_ALERT = int(DeviceEventType.ALERT)


def wire_variant_for(batch: EventBatch) -> Tuple[int, int]:
    """(wire_rows, ts_base) for a flat batch. The checks are full-column
    numpy reductions (~0.1 ms at bench scale) buying 25-40% off a
    transfer-bound step: packed 3-row when the batch has no elevation, no
    location events, and a ts span under 2^16 ms; compact 4-row when only
    the elevation is absent; full 5-row otherwise. ts_base is meaningful
    for the packed variant only."""
    if np.any(np.asarray(batch.elevation)):
        return WIRE_ROWS, 0
    valid = np.asarray(batch.valid)
    if valid.shape[-1] >= _BASE_LANES \
            and not np.any(np.asarray(batch.event_type) == _ET_LOCATION):
        ts = np.asarray(batch.ts)
        lo = int(ts.min(where=valid, initial=2 ** 31 - 1))
        hi = int(ts.max(where=valid, initial=-(2 ** 31)))
        if hi < lo:  # no valid rows
            return WIRE_ROWS_PACKED, 0
        if hi - lo <= _TS_DELTA_MASK:
            return WIRE_ROWS_PACKED, lo
    return WIRE_ROWS_COMPACT, 0


def wire_rows_for(batch: EventBatch) -> int:
    """Wire variant row count only (callers that cannot use the packed
    layout's ts base, e.g. the multi-host fixed-rows pin)."""
    return wire_variant_for(batch)[0]


def _embed_ts_base(row0: np.ndarray, ts_base: int) -> None:
    """Scatter the 32-bit ts base over row 0's spare bits, 3 per lane
    (lane 10 carries the top 2). row0 may be [B] or [S, B] (routed: the
    same base lands in every shard's lanes). Bit work happens on a
    uint32 view so bit 31 never trips int32 overflow handling."""
    lanes = row0[..., :_BASE_LANES].view(np.uint32)
    base = np.uint32(int(ts_base) & 0xFFFFFFFF)
    for lane in range(_BASE_LANES):
        lanes[..., lane] |= ((base >> np.uint32(3 * lane)) & np.uint32(7)) \
            << np.uint32(_BASE_SHIFT)


def _extract_ts_base_np(row0: np.ndarray) -> np.ndarray:
    """Inverse of _embed_ts_base; returns an int32 of row0's leading
    shape (scalar for flat blobs, [S] for routed)."""
    base = np.zeros(row0.shape[:-1], np.uint32)
    for lane in range(_BASE_LANES):
        base |= ((row0[..., lane].astype(np.uint32) >> _BASE_SHIFT) & 7) \
            << np.uint32(3 * lane)
    return base.astype(np.int32)


def batch_to_blob(batch: EventBatch,
                  out: Optional[np.ndarray] = None,
                  wire_rows: Optional[int] = None) -> np.ndarray:
    """Pack an EventBatch into the compact wire blob (host side, numpy).

    A single transfer instead of 12 (every device_put pays its own
    round trip), at 20 B/event instead of 48. Payload
    fields are preserved per event type (see layout comment); a
    well-formed batch — anything the packer/decoders produce — round-trips
    exactly.

    `out` (flat batches only) is an optional preallocated [WIRE_ROWS, B]
    int32 buffer — engines pass a rotating staging buffer so the hot path
    does not pay a fresh 2.6 MB mmap-backed allocation (page faults) per
    step. Every element is overwritten; no pre-zeroing needed. When the
    batch carries no elevation, only the first WIRE_ROWS_COMPACT rows are
    written and that contiguous view is returned (16 B/event on the
    wire).
    """
    lead = batch.device_idx.shape[:-1]   # () flat, (S,) routed
    B = batch.device_idx.shape[-1]
    # routed blobs always carry the full layout; flat batches pick the
    # smallest variant the content allows — unless the caller pins one
    # (`wire_rows` >= 4 forces a classic layout: the multi-host lockstep
    # pin must not take the packed path, whose 3-row layout is not a
    # prefix of the 4/5-row one). Pinning the PACKED layout is only legal
    # when the content is eligible — the ts base cannot be zero-guessed.
    if wire_rows == WIRE_ROWS_PACKED:
        rows, ts_base = wire_variant_for(batch)
        if rows != WIRE_ROWS_PACKED:
            raise ValueError(
                "batch is not packed-eligible (carries locations, "
                "elevation, or a ts span over 2^16 ms); pack with a "
                "classic layout")
    elif wire_rows is not None:
        rows, ts_base = wire_rows, 0
    elif lead:
        rows, ts_base = WIRE_ROWS, 0
    else:
        rows, ts_base = wire_variant_for(batch)
    if not lead:
        from sitewhere_tpu import native

        if native.available():
            if out is None or out.shape[-1] != B or out.shape[0] < rows:
                out = np.empty((rows, B), np.int32)
            view = out[:rows]
            if native.pack_blob(batch, view, ts_base=ts_base):
                return view
            # fall through: the numpy range check below raises the
            # (single, shared) diagnostic for the out-of-range device_idx
    dev = np.asarray(batch.device_idx, np.int32)
    if dev.size and (int(dev.max()) >= WIRE_DEV_MAX or int(dev.min()) < 0):
        raise ValueError(
            f"device_idx out of wire-blob device field range "
            f"[0, {WIRE_DEV_MAX}): min {int(dev.min())}, "
            f"max {int(dev.max())}")
    et = np.asarray(batch.event_type, np.int32) & 7
    is_loc = et == _ET_LOCATION
    is_alert = et == _ET_ALERT
    if out is not None and out.shape[-1] == B \
            and out.shape[:-2] == lead and out.shape[-2] >= rows:
        blob = out[..., :rows, :]
    else:
        blob = np.empty(lead + (rows, B), np.int32)
    valid = np.asarray(batch.valid)
    blob[..., 0, :] = (
        dev
        | (et << _ET_SHIFT)
        | (np.asarray(batch.alert_level, np.int32) & 7) << _LEVEL_SHIFT
        | valid.astype(np.int32) << _VALID_SHIFT)
    # mm_idx/alert_type_idx keep the v1 12-bit wire mask: a negative or
    # oversized index (reachable via un-validated pack_columns input) must
    # not reach the device-side `idx < M` guards as a negative — a negative
    # index would wrap Python-style in the keyed scatter and corrupt a
    # NEIGHBORING device's state slot.
    idx_mask = _META_MAX_IDX - 1
    if rows == WIRE_ROWS_PACKED:
        delta = np.where(valid,
                         np.asarray(batch.ts, np.int32) - np.int32(ts_base),
                         0) & _TS_DELTA_MASK
        idx = np.where(is_alert,
                       np.asarray(batch.alert_type_idx, np.int32),
                       np.asarray(batch.mm_idx, np.int32)) & idx_mask
        blob[..., 1, :] = delta | (idx << _PKIDX_SHIFT)
        blob[..., 2, :] = np.asarray(batch.value, np.float32).view(np.int32)
        _embed_ts_base(blob[..., 0, :], ts_base)
        return blob
    blob[..., 1, :] = batch.ts
    blob[..., 2, :] = np.where(
        is_loc, np.asarray(batch.lat, np.float32).view(np.int32),
        np.asarray(batch.value, np.float32).view(np.int32))
    blob[..., 3, :] = np.where(
        is_loc, np.asarray(batch.lon, np.float32).view(np.int32),
        np.where(is_alert,
                 np.asarray(batch.alert_type_idx, np.int32) & idx_mask,
                 np.asarray(batch.mm_idx, np.int32) & idx_mask))
    if rows >= WIRE_ROWS:
        blob[..., 4, :] = np.asarray(batch.elevation,
                                     np.float32).view(np.int32)
    return blob


def blob_to_batch_np(blob: np.ndarray) -> EventBatch:
    """Host-side inverse of batch_to_blob (native one-pass when available,
    numpy views/bit ops otherwise). Used to materialize a routed blob back
    into columns for alert materialization without keeping a second routed
    copy around."""
    blob = np.asarray(blob, np.int32)
    from sitewhere_tpu import native

    if native.available():
        shape = blob.shape[:-2] + blob.shape[-1:]   # [n] flat, [S, B] routed
        cols = {name: np.empty(shape, np.int32) for name in
                ("device_idx", "event_type", "ts", "mm_idx",
                 "alert_type_idx", "alert_level")}
        cols.update({name: np.empty(shape, np.float32) for name in
                     ("value", "lat", "lon", "elevation")})
        cols["valid"] = np.empty(shape, np.uint8)
        if blob.ndim == 2:
            native.unpack_blob(blob, cols)
        else:
            flat = blob.reshape((-1,) + blob.shape[-2:])
            for s in range(flat.shape[0]):
                native.unpack_blob(
                    flat[s], {k: v.reshape(-1, shape[-1])[s]
                              for k, v in cols.items()})
        return EventBatch(
            device_idx=cols["device_idx"],
            tenant_idx=np.zeros(shape, np.int32),
            event_type=cols["event_type"], ts=cols["ts"],
            mm_idx=cols["mm_idx"], value=cols["value"], lat=cols["lat"],
            lon=cols["lon"], elevation=cols["elevation"],
            alert_type_idx=cols["alert_type_idx"],
            alert_level=cols["alert_level"],
            valid=cols["valid"].view(bool))  # 0/1 uint8 -> bool, no copy
    if blob.shape[-2] == WIRE_ROWS_PACKED:
        return _packed_blob_to_batch_np(blob)
    r0 = blob[..., 0, :]
    et = (r0 >> _ET_SHIFT) & 7
    is_meas = et == _ET_MEASUREMENT
    is_loc = et == _ET_LOCATION
    pa = blob[..., 2, :]
    pb = blob[..., 3, :]
    zf = np.float32(0)
    if blob.shape[-2] >= WIRE_ROWS:
        elevation = np.ascontiguousarray(blob[..., 4, :]).view(np.float32)
    else:  # compact variant: elevation row omitted, reads as 0
        elevation = np.zeros(r0.shape, np.float32)
    return EventBatch(
        device_idx=r0 & (WIRE_DEV_MAX - 1),
        tenant_idx=np.zeros_like(r0),
        event_type=et,
        ts=blob[..., 1, :],
        mm_idx=np.where(is_meas, pb, 0).astype(np.int32),
        value=np.where(is_meas, pa.view(np.float32), zf),
        lat=np.where(is_loc, pa.view(np.float32), zf),
        lon=np.where(is_loc, pb.view(np.float32), zf),
        elevation=elevation,
        alert_type_idx=np.where(et == _ET_ALERT, pb, 0).astype(np.int32),
        alert_level=(r0 >> _LEVEL_SHIFT) & 7,
        valid=(r0 & (1 << _VALID_SHIFT)) != 0)


def _packed_blob_to_batch_np(blob: np.ndarray) -> EventBatch:
    """Host-side decode of the 3-row packed variant (numpy)."""
    r0 = blob[..., 0, :]
    r1 = blob[..., 1, :]
    et = (r0 >> _ET_SHIFT) & 7
    is_meas = et == _ET_MEASUREMENT
    base = _extract_ts_base_np(r0)
    ts = (np.expand_dims(base, -1)
          + (r1 & _TS_DELTA_MASK)).astype(np.int32)
    idx = (r1 >> _PKIDX_SHIFT) & (_META_MAX_IDX - 1)
    value_bits = np.ascontiguousarray(blob[..., 2, :]).view(np.float32)
    zf32 = np.zeros(r0.shape, np.float32)
    return EventBatch(
        device_idx=r0 & (WIRE_DEV_MAX - 1),
        tenant_idx=np.zeros_like(r0),
        event_type=et, ts=ts,
        mm_idx=np.where(is_meas, idx, 0).astype(np.int32),
        value=np.where(is_meas, value_bits, np.float32(0)),
        lat=zf32, lon=zf32.copy(), elevation=zf32.copy(),
        alert_type_idx=np.where(et == _ET_ALERT, idx, 0).astype(np.int32),
        alert_level=(r0 >> _LEVEL_SHIFT) & 7,
        valid=(r0 & (1 << _VALID_SHIFT)) != 0)


def blob_to_batch(blob) -> EventBatch:
    """Inverse of batch_to_blob on-device (jax ops; call under jit — XLA
    fuses the unpack + selects into the step's first consumers). Variant
    dispatch is on the (static) row dimension: one cached program per
    wire layout."""
    import jax
    import jax.numpy as jnp

    if blob.shape[-2] == WIRE_ROWS_PACKED:
        r0 = blob[..., 0, :]
        r1 = blob[..., 1, :]
        et = (r0 >> _ET_SHIFT) & 7
        is_meas = et == _ET_MEASUREMENT
        spare = (r0[..., :_BASE_LANES] >> _BASE_SHIFT) & 7
        base = spare[..., 0]
        for lane in range(1, _BASE_LANES):
            # int32 shifts wrap mod 2^32: lane 10's bits land on 30/31,
            # reconstructing the base's two's complement exactly
            base = base | (spare[..., lane] << (3 * lane))
        ts = jnp.expand_dims(base, -1) + (r1 & _TS_DELTA_MASK)
        idx = (r1 >> _PKIDX_SHIFT) & (_META_MAX_IDX - 1)
        value = jax.lax.bitcast_convert_type(blob[..., 2, :], jnp.float32)
        zf32 = jnp.zeros(r0.shape, jnp.float32)
        return EventBatch(
            device_idx=r0 & (WIRE_DEV_MAX - 1),
            tenant_idx=jnp.zeros_like(r0),
            event_type=et, ts=ts,
            mm_idx=jnp.where(is_meas, idx, 0),
            value=jnp.where(is_meas, value, jnp.float32(0)),
            lat=zf32, lon=zf32, elevation=zf32,
            alert_type_idx=jnp.where(et == _ET_ALERT, idx, 0),
            alert_level=(r0 >> _LEVEL_SHIFT) & 7,
            valid=(r0 & (1 << _VALID_SHIFT)) != 0)
    r0 = blob[..., 0, :]
    et = (r0 >> _ET_SHIFT) & 7
    is_meas = et == _ET_MEASUREMENT
    is_loc = et == _ET_LOCATION
    pa = blob[..., 2, :]
    pb = blob[..., 3, :]
    fa = jax.lax.bitcast_convert_type(pa, jnp.float32)
    fb = jax.lax.bitcast_convert_type(pb, jnp.float32)
    zf = jnp.float32(0)
    if blob.shape[-2] >= WIRE_ROWS:  # static shape: resolved at trace time
        elevation = jax.lax.bitcast_convert_type(blob[..., 4, :],
                                                 jnp.float32)
    else:  # compact variant: elevation row omitted, reads as 0
        elevation = jnp.zeros(r0.shape, jnp.float32)
    return EventBatch(
        device_idx=r0 & (WIRE_DEV_MAX - 1),
        tenant_idx=jnp.zeros_like(r0),
        event_type=et,
        ts=blob[..., 1, :],
        mm_idx=jnp.where(is_meas, pb, 0),
        value=jnp.where(is_meas, fa, zf),
        lat=jnp.where(is_loc, fa, zf),
        lon=jnp.where(is_loc, fb, zf),
        elevation=elevation,
        alert_type_idx=jnp.where(et == _ET_ALERT, pb, 0),
        alert_level=(r0 >> _LEVEL_SHIFT) & 7,
        valid=(r0 & (1 << _VALID_SHIFT)) != 0)


class EventPacker:
    """Host-side packer: Python event objects / raw column arrays -> EventBatch.

    Owns the measurement-name and alert-type interners; device tokens are
    interned against the shared registry interner so packed indices line up
    with the registry lookup tensors.
    """

    def __init__(self, batch_size: int, device_interner: TokenInterner,
                 max_measurement_names: int = 1024, max_alert_types: int = 1024,
                 epoch_base_ms: Optional[int] = None):
        if max_measurement_names > _META_MAX_IDX or \
                max_alert_types > _META_MAX_IDX:
            raise ValueError(
                f"measurement/alert-type interner capacity is limited to "
                f"{_META_MAX_IDX} by the wire-blob meta field width")
        self.batch_size = batch_size
        self.devices = device_interner
        self.measurements = TokenInterner(max_measurement_names, "measurements")
        self.alert_types = TokenInterner(max_alert_types, "alert_types")
        self.epoch_base_ms = (epoch_base_ms if epoch_base_ms is not None
                              else int(time.time() * 1000))

    # int32 range minus a margin for the -2^31 "never" sentinel in state tensors
    _REL_MIN = -(2 ** 31) + 2
    _REL_MAX = 2 ** 31 - 1

    def rel_ts(self, ts_ms: int) -> int:
        # Events dated before epoch_base are legitimate (delayed delivery,
        # replay): rebased ts may be negative. Clamp to int32 range.
        rel = int(ts_ms - self.epoch_base_ms)
        return max(self._REL_MIN, min(self._REL_MAX, rel))

    def abs_ts(self, rel: int) -> int:
        return self.epoch_base_ms + int(rel)

    def pack_events(self, events: Sequence[DeviceEvent],
                    device_tokens: Sequence[str]) -> List[EventBatch]:
        """Pack API-level events (paired with their device tokens) into one or
        more fixed-size batches."""
        batches: List[EventBatch] = []
        for start in range(0, max(len(events), 1), self.batch_size):
            chunk = events[start:start + self.batch_size]
            tokens = device_tokens[start:start + self.batch_size]
            if not chunk:
                break
            batches.append(self._pack_chunk(chunk, tokens))
        return batches

    def _pack_chunk(self, events: Sequence[DeviceEvent],
                    tokens: Sequence[str]) -> EventBatch:
        B = self.batch_size
        batch = empty_batch(B)
        n = len(events)
        device_idx = np.zeros(B, np.int32)
        event_type = np.zeros(B, np.int32)
        ts = np.zeros(B, np.int32)
        mm_idx = np.zeros(B, np.int32)
        value = np.zeros(B, np.float32)
        lat = np.zeros(B, np.float32)
        lon = np.zeros(B, np.float32)
        elevation = np.zeros(B, np.float32)
        alert_type_idx = np.zeros(B, np.int32)
        alert_level = np.zeros(B, np.int32)
        valid = np.zeros(B, bool)
        for i, (event, token) in enumerate(zip(events, tokens)):
            device_idx[i] = self.devices.lookup(token)
            event_type[i] = int(event.event_type)
            ts[i] = self.rel_ts(event.event_date)
            valid[i] = True
            if isinstance(event, DeviceMeasurement):
                mm_idx[i] = self.measurements.intern(event.name)
                value[i] = event.value
            elif isinstance(event, DeviceLocation):
                lat[i] = event.latitude
                lon[i] = event.longitude
                elevation[i] = event.elevation
            elif isinstance(event, DeviceAlert):
                alert_type_idx[i] = self.alert_types.intern(event.type)
                alert_level[i] = int(event.level)
        return EventBatch(
            device_idx=device_idx, tenant_idx=batch.tenant_idx,
            event_type=event_type, ts=ts, mm_idx=mm_idx, value=value,
            lat=lat, lon=lon, elevation=elevation,
            alert_type_idx=alert_type_idx, alert_level=alert_level, valid=valid)

    def pack_columns(self, device_idx: np.ndarray, event_type: np.ndarray,
                     ts_ms_abs: np.ndarray, *, mm_idx: Optional[np.ndarray] = None,
                     value: Optional[np.ndarray] = None,
                     lat: Optional[np.ndarray] = None,
                     lon: Optional[np.ndarray] = None,
                     elevation: Optional[np.ndarray] = None,
                     alert_type_idx: Optional[np.ndarray] = None,
                     alert_level: Optional[np.ndarray] = None) -> EventBatch:
        """Zero-copy-ish fast path for bulk synthetic/replayed columns; pads or
        rejects to exactly one batch."""
        n = len(device_idx)
        if n > self.batch_size:
            raise ValueError(f"{n} events > batch size {self.batch_size}")
        B = self.batch_size

        def col(arr: Optional[np.ndarray], dtype) -> np.ndarray:
            out = np.zeros(B, dtype)
            if arr is not None:
                out[:n] = arr
            return out

        ts_rel = np.clip(np.asarray(ts_ms_abs, np.int64) - self.epoch_base_ms,
                         self._REL_MIN, self._REL_MAX).astype(np.int32)
        valid = np.zeros(B, bool)
        valid[:n] = True
        return EventBatch(
            device_idx=col(device_idx, np.int32),
            tenant_idx=np.zeros(B, np.int32),
            event_type=col(event_type, np.int32),
            ts=col(ts_rel, np.int32),
            mm_idx=col(mm_idx, np.int32), value=col(value, np.float32),
            lat=col(lat, np.float32), lon=col(lon, np.float32),
            elevation=col(elevation, np.float32),
            alert_type_idx=col(alert_type_idx, np.int32),
            alert_level=col(alert_level, np.int32), valid=valid)
