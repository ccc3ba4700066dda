"""Device-compacted alert lanes (ops/compact.py + the lane materializer).

Differential contract: lane materialization must produce the EXACT same
DeviceAlert list — order included — as the pre-lane mask-scan reference
(pipeline/engine.py materialize_alerts_maskscan), across no-fire /
some-fire / alert-storm (> capacity fired rows, with `alerts_dropped`
incremented by the on-device overflow count), on both the single-chip
and sharded engines. Plus: the fetch budget (one lane-sized D2H fetch
per materialize) and the interner token-array cache the vectorized
token resolution rides on.
"""

import numpy as np
import pytest

from sitewhere_tpu.model import (
    AlertLevel, Device, DeviceAssignment, DeviceLocation, DeviceMeasurement,
    DeviceType,
)
from sitewhere_tpu.ops.actuate import COMMAND_LANE_ROWS
from sitewhere_tpu.ops.compact import (
    ALERT_LANE_ROWS, compact_alert_lanes, decode_alert_lanes,
)
from sitewhere_tpu.pipeline.engine import (
    GeofenceRule, PipelineEngine, ThresholdRule, materialize_alerts_maskscan,
)
from sitewhere_tpu.registry import DeviceManagement, RegistryTensors


def _world(n_devices=16):
    from sitewhere_tpu.model import Area, Zone
    from sitewhere_tpu.model.common import Location

    dm = DeviceManagement()
    dtype = dm.create_device_type(DeviceType(token="t"))
    area = dm.create_area(Area(token="area"))
    dm.create_zone(Zone(token="safe", area_id=area.id, bounds=[
        Location(0, 0), Location(0, 10), Location(10, 10), Location(10, 0)]))
    tensors = RegistryTensors(max_devices=64, max_zones=8,
                              max_zone_vertices=8)
    for i in range(n_devices):
        device = dm.create_device(Device(token=f"d{i}",
                                         device_type_id=dtype.id))
        dm.create_device_assignment(DeviceAssignment(
            token=f"a{i}", device_id=device.id, area_id=area.id))
    tensors.attach(dm, "tenant")
    return dm, tensors


def _add_rules(engine):
    engine.add_threshold_rule(ThresholdRule(
        token="hot", measurement_name="m", operator=">", threshold=100.0,
        alert_level=AlertLevel.CRITICAL, alert_message="too hot"))
    engine.add_geofence_rule(GeofenceRule(
        token="out", zone_token="safe", condition="outside",
        alert_level=AlertLevel.ERROR))


def _mixed_events(n, fire_every=2):
    """Measurements (every `fire_every`-th crosses the threshold)
    interleaved with locations (odd ones outside the zone -> geofence)."""
    events, tokens = [], []
    for i in range(n):
        if i % 3 == 2:
            # outside the zone for i % 2 == 1
            lat = 50.0 if (i // 3) % 2 else 5.0
            events.append(DeviceLocation(latitude=lat, longitude=5.0,
                                         event_date=1000 + i))
        else:
            value = 200.0 + i if i % fire_every == 0 else 10.0
            events.append(DeviceMeasurement(name="m", value=value,
                                            event_date=1000 + i))
        tokens.append(f"d{i % 16}")
    return events, tokens


def _key(alert):
    """Semantic identity (auto-generated event ids differ by object)."""
    return (alert.device_id, alert.source, alert.level, alert.type,
            alert.message, alert.event_date)


_ENGINE_SEQ = iter(range(10_000))


def _unique_name() -> str:
    """Per-test engine name: the GLOBAL_METRICS registry scopes by engine
    name, so a default-named engine here would pollute the alert-drop
    counters other test files assert on."""
    return f"lanes-test-{next(_ENGINE_SEQ)}"


def _ref_filtered_to_rows(engine_out_flat, ref_alerts, kept_rows):
    """The mask-scan reference's alerts restricted to `kept_rows`, order
    preserved — the spec for what a capacity-truncated lane returns."""
    thr_f = np.asarray(engine_out_flat.threshold_fired).reshape(-1)
    geo_f = np.asarray(engine_out_flat.geofence_fired).reshape(-1)
    fired = np.nonzero(thr_f | geo_f)[0]
    counts = thr_f[fired].astype(int) + geo_f[fired].astype(int)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    kept = set(int(r) for r in kept_rows)
    out = []
    for i, row in enumerate(fired):
        if int(row) in kept:
            out.extend(ref_alerts[offsets[i]:offsets[i + 1]])
    return out


class TestCompactOp:
    """Unit-level pack/decode round trip of the lane layout."""

    def _dicts(self, thr_fired, geo_fired, thr_rule=None, geo_rule=None):
        import jax.numpy as jnp

        B = len(thr_fired)
        thr_fired = np.asarray(thr_fired, bool)
        geo_fired = np.asarray(geo_fired, bool)
        thr = {"fired": jnp.asarray(thr_fired),
               "first_rule": jnp.asarray(
                   np.where(thr_fired, thr_rule if thr_rule is not None
                            else np.arange(B), -1).astype(np.int32)),
               "alert_level": jnp.asarray(
                   np.where(thr_fired, 3, -1).astype(np.int32))}
        geo = {"fired": jnp.asarray(geo_fired),
               "first_rule": jnp.asarray(
                   np.where(geo_fired, geo_rule if geo_rule is not None
                            else np.arange(B) + 7, -1).astype(np.int32)),
               "alert_level": jnp.asarray(
                   np.where(geo_fired, 2, -1).astype(np.int32))}
        return thr, geo

    def test_no_fire(self):
        import jax

        thr, geo = self._dicts([False] * 8, [False] * 8)
        lanes = np.asarray(jax.jit(
            compact_alert_lanes, static_argnums=2)(thr, geo, 4))
        assert lanes.shape == (ALERT_LANE_ROWS, 4)
        dec = decode_alert_lanes(lanes)
        assert dec.n == 0 and dec.fired_rows == 0
        assert dec.dropped_alerts == 0 and dec.total_alerts == 0

    def test_some_fire_preserves_row_order_and_fields(self):
        import jax

        thr_fired = [False, True, False, True, False, False, True, False]
        geo_fired = [False, True, True, False, False, False, False, False]
        thr, geo = self._dicts(thr_fired, geo_fired)
        lanes = np.asarray(jax.jit(
            compact_alert_lanes, static_argnums=2)(thr, geo, 8))
        dec = decode_alert_lanes(lanes)
        assert dec.rows.tolist() == [1, 2, 3, 6]
        assert dec.thr_fired.tolist() == [True, False, True, True]
        assert dec.geo_fired.tolist() == [True, True, False, False]
        # rule ids round-trip through the int16 halves, -1 included
        assert dec.thr_rule.tolist() == [1, -1, 3, 6]
        assert dec.geo_rule.tolist() == [8, 9, -1, -1]
        assert dec.fired_rows == 4 and dec.dropped_alerts == 0
        assert dec.total_alerts == 5

    def test_overflow_counts_dropped_alerts_on_device(self):
        import jax

        # 6 fired rows, capacity 4: rows 4 and 5 overflow; row 4 fires
        # BOTH families -> 3 dropped alerts total
        thr_fired = [True, True, True, True, True, True, False, False]
        geo_fired = [False, False, False, False, True, False, False, False]
        thr, geo = self._dicts(thr_fired, geo_fired)
        lanes = np.asarray(jax.jit(
            compact_alert_lanes, static_argnums=2)(thr, geo, 4))
        dec = decode_alert_lanes(lanes)
        assert dec.rows.tolist() == [0, 1, 2, 3]
        assert dec.fired_rows == 6
        assert dec.total_alerts == 7
        assert dec.dropped_alerts == 3


class TestDifferentialSingleChip:
    def _engine(self, capacity=None, command_capacity=None):
        _, tensors = _world()
        engine = PipelineEngine(tensors, batch_size=64, measurement_slots=8,
                                max_tenants=4, max_threshold_rules=16,
                                max_geofence_rules=16,
                                alert_lane_capacity=capacity,
                                command_lane_capacity=command_capacity,
                                name=_unique_name())
        engine.start()
        _add_rules(engine)
        return engine

    def _submit(self, engine, events, tokens):
        batch = engine.packer.pack_events(events, tokens)[0]
        return batch, engine.submit(batch)

    def test_no_fire(self):
        engine = self._engine()
        events = [DeviceMeasurement(name="m", value=1.0, event_date=1000)
                  for _ in range(8)]
        batch, out = self._submit(engine, events, [f"d{i}" for i in range(8)])
        assert materialize_alerts_maskscan(engine, batch, out) == []
        assert engine.materialize_alerts(batch, out) == []
        assert engine.alerts_dropped == 0

    def test_some_fire_exact_list_parity(self):
        engine = self._engine()
        events, tokens = _mixed_events(30)
        batch, out = self._submit(engine, events, tokens)
        ref = materialize_alerts_maskscan(engine, batch, out)
        got = engine.materialize_alerts(batch, out)
        assert len(ref) > 0
        assert [_key(a) for a in got] == [_key(a) for a in ref]
        assert engine.alerts_dropped == 0

    def test_storm_overflow_truncates_with_accounting(self):
        engine = self._engine(capacity=8)
        # every measurement fires; > capacity fired rows
        events, tokens = _mixed_events(48, fire_every=1)
        batch, out = self._submit(engine, events, tokens)
        ref = materialize_alerts_maskscan(engine, batch, out)
        got = engine.materialize_alerts(batch, out)
        dec = decode_alert_lanes(np.asarray(out.alert_lanes))
        assert dec.fired_rows > 8  # the storm actually overflowed
        expected = _ref_filtered_to_rows(out, ref, dec.rows)
        assert [_key(a) for a in got] == [_key(a) for a in expected]
        # on-device overflow count == exactly the alerts the lane lost
        assert engine.alerts_dropped == len(ref) - len(got)
        assert engine.alerts_dropped == dec.dropped_alerts > 0
        assert (engine._metrics.counter("alerts.dropped").value
                == dec.dropped_alerts)

    def test_raised_capacity_carries_a_fire_rate_above_the_default(self):
        """A deployment that fires on a large share of its rows (a
        toll notification per segment crossing) raises the capacity:
        every fired row then rides the lanes, equal to the mask scan,
        and the lane's fill is recorded against the capacity."""
        from sitewhere_tpu.ops.compact import DEFAULT_ALERT_LANE_CAPACITY

        _, tensors = _world()
        engine = PipelineEngine(tensors, batch_size=512,
                                measurement_slots=8, max_tenants=4,
                                max_threshold_rules=16,
                                max_geofence_rules=16,
                                alert_lane_capacity=512,
                                name=_unique_name())
        engine.start()
        _add_rules(engine)
        events, tokens = _mixed_events(400, fire_every=1)
        batch, out = self._submit(engine, events, tokens)
        dec = decode_alert_lanes(np.asarray(out.alert_lanes))
        assert dec.fired_rows > DEFAULT_ALERT_LANE_CAPACITY
        ref = materialize_alerts_maskscan(engine, batch, out)
        got = engine.materialize_alerts(batch, out)
        assert [_key(a) for a in got] == [_key(a) for a in ref]
        assert engine.alerts_dropped == 0
        assert engine.alert_lane_fill_max == dec.fired_rows
        rec = engine.flight.export(last_n=1)["records"][-1]
        assert rec["lane"] == {"fired_rows": dec.fired_rows,
                               "capacity": 512}
        geo = sum(a.type == "zone.violation" for a in got)
        assert rec["geofence_fires"] == {"inside": 0, "outside": geo,
                                         "enter": 0}
        assert engine._metrics.counter(
            "geofence.fires_outside").value == geo > 0

    def test_parity_under_pallas_interpret_geofence(self):
        """Lane compaction composes with every containment kernel the
        step can select — the interpret-mode pallas variant included."""
        _, tensors = _world()
        engine = PipelineEngine(tensors, batch_size=64, measurement_slots=8,
                                max_tenants=4, max_threshold_rules=16,
                                max_geofence_rules=16,
                                geofence_impl="pallas_interpret",
                                name=_unique_name())
        engine.start()
        _add_rules(engine)
        events, tokens = _mixed_events(30)
        batch, out = self._submit(engine, events, tokens)
        ref = materialize_alerts_maskscan(engine, batch, out)
        got = engine.materialize_alerts(batch, out)
        assert len(ref) > 0
        assert [_key(a) for a in got] == [_key(a) for a in ref]

    def test_max_alerts_bound_still_counts(self):
        engine = self._engine()
        events, tokens = _mixed_events(30, fire_every=1)
        batch, out = self._submit(engine, events, tokens)
        ref = materialize_alerts_maskscan(engine, batch, out)
        got = engine.materialize_alerts(batch, out, max_alerts=3)
        expected = _ref_filtered_to_rows(
            engine_out_flat=out, ref_alerts=ref,
            kept_rows=decode_alert_lanes(
                np.asarray(out.alert_lanes)).rows[:3])
        assert [_key(a) for a in got] == [_key(a) for a in expected]
        assert engine.alerts_dropped > 0

    def test_single_fixed_fetch_per_materialize(self):
        # capacities sized for the batch the way a deployment sizes them
        # (the default 128 over the latency tier's 4096 batch is the
        # same 1:32 ratio; a toy 64-row batch pins both lanes to 8 so
        # the bytes claim is tested at deployment proportions)
        engine = self._engine(capacity=8, command_capacity=8)
        events, tokens = _mixed_events(30)
        batch, out = self._submit(engine, events, tokens)
        f0, b0 = engine.d2h_fetches, engine.d2h_bytes
        engine.materialize_alerts(batch, out)
        lane_bytes = engine.d2h_bytes - b0
        # two fixed-shape fetches per offer: alert lane + command lane,
        # one batched device_get
        assert engine.d2h_fetches - f0 == 2
        assert lane_bytes == (
            ALERT_LANE_ROWS * engine.alert_lane_capacity * 4
            + COMMAND_LANE_ROWS * engine.command_lane_capacity * 4)
        # >= 3x fewer bytes than the pre-lane six-array fetch (the
        # deterministic half of the materialize win; the wall-clock
        # speedup is pinned by bench.py on the real link)
        maskscan_bytes = sum(
            np.asarray(getattr(out, name)).nbytes
            for name in ("threshold_fired", "geofence_fired",
                         "threshold_alert_level", "geofence_alert_level",
                         "threshold_first_rule", "geofence_first_rule"))
        assert maskscan_bytes >= 3 * lane_bytes


class TestDifferentialSharded:
    def _engine(self, capacity=None, per_shard=16, shards=4):
        from sitewhere_tpu.parallel import ShardedPipelineEngine, make_mesh

        _, tensors = _world()
        engine = ShardedPipelineEngine(
            tensors, mesh=make_mesh(shards), per_shard_batch=per_shard,
            measurement_slots=8, max_tenants=4, max_threshold_rules=16,
            max_geofence_rules=16, alert_lane_capacity=capacity,
            name=_unique_name())
        engine.start()
        _add_rules(engine)
        return engine

    def _flatten(self, engine, routed, out):
        """The pre-lane flatten: [S, B] -> flat rows with GLOBAL device
        indices, per-row outputs flattened alongside — the mask-scan
        oracle's input for the sharded engine."""
        import jax

        batch = routed.batch if hasattr(routed, "batch") else routed
        S, B = np.asarray(batch.valid).shape
        shard_of_row = np.repeat(np.arange(S, dtype=np.int32), B)

        def flat(a):
            a = np.asarray(a)
            return a.reshape((S * B,) + a.shape[2:])

        flat_batch = jax.tree_util.tree_map(flat, batch)
        flat_batch = flat_batch.replace(
            device_idx=flat_batch.device_idx * engine.n_shards
            + shard_of_row)
        per_row = ("valid", "unregistered", "threshold_fired",
                   "threshold_first_rule", "threshold_alert_level",
                   "geofence_fired", "geofence_first_rule",
                   "geofence_alert_level", "program_fired",
                   "program_first_rule", "program_alert_level",
                   "model_fired", "model_first", "model_level",
                   "model_score")
        flat_out = out.replace(
            **{name: flat(np.asarray(getattr(out, name)))
               for name in per_row})
        return flat_batch, flat_out

    def test_no_fire(self):
        engine = self._engine()
        events = [DeviceMeasurement(name="m", value=1.0, event_date=1000)
                  for _ in range(8)]
        batch = engine.packer.pack_events(
            events, [f"d{i}" for i in range(8)])[0]
        routed, out = engine.submit(batch)
        assert engine.materialize_alerts(routed, out) == []
        assert engine.alerts_dropped == 0

    def test_some_fire_exact_list_parity(self):
        engine = self._engine()
        events, tokens = _mixed_events(30)
        batch = engine.packer.pack_events(events, tokens)[0]
        routed, out = engine.submit(batch)
        flat_batch, flat_out = self._flatten(engine, routed, out)
        ref = materialize_alerts_maskscan(engine, flat_batch, flat_out)
        got = engine.materialize_alerts(routed, out)
        assert len(ref) > 0
        assert [_key(a) for a in got] == [_key(a) for a in ref]
        assert engine.alerts_dropped == 0

    def test_storm_overflow_per_shard_capacity(self):
        engine = self._engine(capacity=4)
        events, tokens = _mixed_events(48, fire_every=1)
        batch = engine.packer.pack_events(events, tokens)[0]
        routed, out = engine.submit(batch)
        flat_batch, flat_out = self._flatten(engine, routed, out)
        ref = materialize_alerts_maskscan(engine, flat_batch, flat_out)
        got = engine.materialize_alerts(routed, out)
        # kept rows: each shard keeps its first `capacity` fired rows
        lanes = np.asarray(out.alert_lanes)
        S, B = np.asarray(routed.valid).shape
        kept, dropped_dev = [], 0
        for s in range(S):
            dec = decode_alert_lanes(lanes[s])
            kept.extend(s * B + dec.rows)
            dropped_dev += dec.dropped_alerts
        assert dropped_dev > 0  # the storm overflowed at least one shard
        expected = _ref_filtered_to_rows(flat_out, ref, kept)
        assert [_key(a) for a in got] == [_key(a) for a in expected]
        assert engine.alerts_dropped == len(ref) - len(got) == dropped_dev

    def test_single_fixed_fetch_per_materialize(self):
        engine = self._engine()
        events, tokens = _mixed_events(30)
        batch = engine.packer.pack_events(events, tokens)[0]
        routed, out = engine.submit(batch)
        f0, b0 = engine.d2h_fetches, engine.d2h_bytes
        engine.materialize_alerts(routed, out)
        # alert lane + command lane, both sharded, one batched device_get
        assert engine.d2h_fetches - f0 == 2
        assert (engine.d2h_bytes - b0
                == engine.n_shards * ALERT_LANE_ROWS
                * engine.alert_lane_capacity * 4
                + engine.n_shards * COMMAND_LANE_ROWS
                * engine.command_lane_capacity * 4)


class TestTokenArray:
    def test_cached_until_version_moves(self):
        from sitewhere_tpu.registry.interning import TokenInterner

        interner = TokenInterner(16, "t")
        a = interner.intern("alpha")
        arr = interner.token_array()
        assert arr[a] == "alpha" and arr[0] == ""
        assert interner.token_array() is arr  # cached, same object
        b = interner.intern("beta")
        arr2 = interner.token_array()
        assert arr2 is not arr and arr2[b] == "beta"

    def test_restore_invalidates_and_gaps_read_empty(self):
        from sitewhere_tpu.registry.interning import TokenInterner

        interner = TokenInterner(16, "t")
        interner.intern("alpha")
        interner.token_array()
        interner.restore([None, "x", None, "y"])
        arr = interner.token_array()
        assert arr[1] == "x" and arr[2] == "" and arr[3] == "y"
        # unassigned tail slots read "" (safe to fancy-index anywhere)
        assert arr[15] == ""

    def test_congruent_interner_gap_slots(self):
        from sitewhere_tpu.registry.interning import TokenInterner

        interner = TokenInterner(16, "t", shard_classes=4)
        tokens = [f"tok-{i}" for i in range(6)]
        idx = [interner.intern(t) for t in tokens]
        arr = interner.token_array()
        for token, i in zip(tokens, idx):
            assert arr[i] == token
        unused = set(range(16)) - set(idx)
        assert all(arr[i] == "" for i in unused)
