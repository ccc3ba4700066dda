"""The `enter` geofence condition against a NumPy oracle.

A rule with condition `enter` on zone z fires for a valid location event e
of device d when e is inside z (tenant-scoped) and d's previous location
was not: the latest valid location row of d earlier in the same batch by
(date, batch position), else the state's last location, else none (which
counts as "not inside"). The oracle below states that row by row, with the
even-odd rule in float32, and walks the device state across batches the way
the step's last-value fold does. Both containment paths are checked: the
XLA scan and the Pallas kernel in interpret mode. `inside` and `outside`
results do not change when an `enter` rule joins them, and without an
`enter` rule the step is traced without the previous-location stage.
"""


import numpy as np
import pytest

from sitewhere_tpu.model import (
    Area, Device, DeviceAssignment, DeviceLocation, DeviceMeasurement,
    DeviceType, Zone,
)
from sitewhere_tpu.model.common import Location
from sitewhere_tpu.pipeline.engine import GeofenceRule, PipelineEngine
from sitewhere_tpu.registry import DeviceManagement, RegistryTensors

# (token, tenant, polygon of (lat, lon)); the triangle's slanted edge
# exercises the general crossing formula
ZONES = [
    ("north", "acme", [(0.0, 0.0), (0.0, 4.0), (4.0, 4.0), (4.0, 0.0)]),
    ("east", "acme", [(0.0, 4.0), (0.0, 8.0), (4.0, 8.0), (4.0, 4.0)]),
    ("wedge", "acme", [(5.0, 0.0), (9.0, 0.0), (5.0, 6.0)]),
    ("beta-yard", "beta", [(0.0, 0.0), (0.0, 8.0), (9.0, 8.0), (9.0, 0.0)]),
]
ACME_DEVICES = [f"a-{i}" for i in range(6)]
BETA_DEVICES = [f"b-{i}" for i in range(3)]


def inside(lat, lon, polygon) -> np.ndarray:
    """Even-odd containment in float32, edge by edge as the kernels do."""
    lat = np.asarray(lat, np.float32)
    lon = np.asarray(lon, np.float32)
    odd = np.zeros(lat.shape, bool)
    vertices = np.asarray(polygon, np.float32)
    for (y1, x1), (y2, x2) in zip(vertices, np.roll(vertices, -1, 0)):
        if y1 == y2:
            continue
        crossing = x1 + (x2 - x1) * (lat - y1) / (y2 - y1)
        odd ^= ((y1 > lat) != (y2 > lat)) & (lon < crossing)
    return odd


def oracle(rows, rules, state):
    """First fired geofence rule per row (-1: none) of one batch, and the
    device state after it. `rows` are (device, tenant, date, lat, lon) of
    valid location events in batch order; `rules` (zone, condition) by
    rule id; `state` maps device -> (date, lat, lon) and is updated as
    the step's last-value fold does (the batch's latest row by (date,
    position), where not older than the state)."""
    zones = {token: (tenant, poly) for token, tenant, poly in ZONES}
    first = []
    for i, (dev, tenant, date, lat, lon) in enumerate(rows):
        prior = [(d2, j) for j, (dv, _, d2, _, _) in enumerate(rows)
                 if dv == dev and (d2, j) < (date, i)]
        if prior:
            _, j = max(prior)
            prev = (rows[j][3], rows[j][4])
        else:
            prev = state.get(dev, (None, None, None))[1:]
            prev = None if prev[0] is None else prev
        fired = -1
        for r, (zone, condition) in enumerate(rules):
            ztenant, poly = zones[zone]
            in_scope = ztenant == tenant
            now_in = in_scope and bool(inside([lat], [lon], poly)[0])
            if condition == "inside":
                met = now_in
            elif condition == "outside":
                met = in_scope and not now_in
            else:
                was_in = prev is not None and in_scope and bool(
                    inside([prev[0]], [prev[1]], poly)[0])
                met = now_in and not was_in
            if met:
                fired = r
                break
        first.append(fired)
    for dev in {r[0] for r in rows}:
        date, i = max((r[2], i) for i, r in enumerate(rows) if r[0] == dev)
        if dev not in state or date >= state[dev][0]:
            state[dev] = (date, rows[i][3], rows[i][4])
    return first


def _world(impl):
    tensors = RegistryTensors(max_devices=64, max_zones=8,
                              max_zone_vertices=8)
    for tenant, devices in (("acme", ACME_DEVICES), ("beta", BETA_DEVICES)):
        dm = DeviceManagement()
        dtype = dm.create_device_type(DeviceType(token=f"car-{tenant}"))
        area = dm.create_area(Area(token=f"road-{tenant}"))
        for token, ztenant, poly in ZONES:
            if ztenant == tenant:
                dm.create_zone(Zone(token=token, area_id=area.id, bounds=[
                    Location(lat, lon) for lat, lon in poly]))
        tensors.attach(dm, tenant)
        for token in devices:
            device = dm.create_device(Device(token=token,
                                             device_type_id=dtype.id))
            dm.create_device_assignment(DeviceAssignment(
                token=f"as-{token}", device_id=device.id, area_id=area.id))
    engine = PipelineEngine(tensors, batch_size=32, measurement_slots=4,
                            max_tenants=4, max_geofence_rules=8,
                            geofence_impl=impl,
                            name=f"geofence-enter-{impl}")
    engine.start()
    return engine


RULES = [("north", "enter"), ("east", "enter"), ("wedge", "enter"),
         ("beta-yard", "enter")]


def _install(engine, rules):
    for r, (zone, condition) in enumerate(rules):
        engine.add_geofence_rule(GeofenceRule(
            token=f"r{r}", zone_token=zone, condition=condition,
            alert_type=f"zone.{condition}", alert_message=f"r{r}"))


def _batches(seed, base):
    """Seeded batches of location events (some measurements between),
    points on a half-unit lattice so none lies on an edge, with repeated
    and out-of-order dates per device."""
    rng = np.random.default_rng(seed)
    tenant_of = {d: "acme" for d in ACME_DEVICES}
    tenant_of.update({d: "beta" for d in BETA_DEVICES})
    devices = ACME_DEVICES + BETA_DEVICES
    out = []
    for b in range(4):
        events, tokens, rows = [], [], []
        for _ in range(24):
            dev = devices[rng.integers(len(devices))]
            date = base + 1000 * b + int(rng.integers(0, 6))
            if rng.random() < 0.15:
                events.append(DeviceMeasurement(name="speed", value=1.0,
                                                event_date=date))
                tokens.append(dev)
                continue
            lat = float(rng.integers(-1, 10)) + 0.5
            lon = float(rng.integers(-1, 9)) + 0.5
            events.append(DeviceLocation(latitude=lat, longitude=lon,
                                         event_date=date))
            tokens.append(dev)
            rows.append((dev, tenant_of[dev], date, np.float32(lat),
                         np.float32(lon)))
        out.append((events, tokens, rows))
    return out


def _first_rules(engine, events, tokens):
    batches = engine.packer.pack_events(events, tokens)
    assert len(batches) == 1
    out = engine.submit(batches[0])
    is_loc = [isinstance(e, DeviceLocation) for e in events]
    first = np.asarray(out.geofence_first_rule)[:len(events)]
    return [int(f) for f, loc in zip(first, is_loc) if loc], \
        batches[0], out


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_enter_matches_oracle(impl):
    engine = _world(impl)
    _install(engine, RULES)
    state = {}
    fired = 0
    for events, tokens, rows in _batches(7, engine.packer.epoch_base_ms):
        got, _, _ = _first_rules(engine, events, tokens)
        want = oracle(rows, RULES, state)
        assert got == want
        fired += sum(f >= 0 for f in want)
    assert fired > 0


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_enter_cases(impl):
    """The named cases one by one: a device's first report, the
    predecessor earlier in the batch, equal dates broken by batch
    position, the predecessor from state, and another tenant's zone."""
    engine = _world(impl)
    _install(engine, RULES)
    t = engine.packer.epoch_base_ms

    def loc(lat, lon, date):
        return DeviceLocation(latitude=lat, longitude=lon, event_date=date)

    # a-0: first report inside north fires; a-1: outside then inside
    # (later date, earlier position) fires only on the later date;
    # a-2: two reports inside east at one date: the first by position
    # fires; b-0 inside acme's north coordinates (beta's yard) fires
    # on beta's rule only
    events = [loc(1.5, 1.5, t + 1),
              loc(1.5, 1.5, t + 5), loc(6.5, 6.5, t + 2),
              loc(1.5, 5.5, t + 3), loc(2.5, 5.5, t + 3),
              loc(1.5, 1.5, t + 1)]
    tokens = ["a-0", "a-1", "a-1", "a-2", "a-2", "b-0"]
    got, batch, out = _first_rules(engine, events, tokens)
    assert got == [0, 0, -1, 1, -1, 3]
    alerts = engine.materialize_alerts(batch, out)
    assert sorted((a.device_id, a.message) for a in alerts) == [
        ("a-0", "r0"), ("a-1", "r0"), ("a-2", "r1"), ("b-0", "r3")]
    # next batch: predecessors from state; a-0 stays in north (no fire),
    # a-1 moves to east (fires), a-2 leaves to nowhere and comes back
    events = [loc(2.5, 2.5, t + 10), loc(2.5, 6.5, t + 10),
              loc(-0.5, -0.5, t + 10), loc(2.5, 6.5, t + 11)]
    got, _, _ = _first_rules(engine, events, ["a-0", "a-1", "a-2", "a-2"])
    assert got == [-1, 1, -1, 1]


def _alert_rows(engine, batches, rules):
    _install(engine, rules)
    firsts = []
    for events, tokens, _ in batches:
        got, _, _ = _first_rules(engine, events, tokens)
        firsts.append(got)
    return firsts


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_inside_outside_unchanged_by_an_enter_rule(impl):
    plain = [("north", "inside"), ("east", "outside"), ("wedge", "inside")]
    base = _world(impl)
    batches = _batches(11, base.packer.epoch_base_ms)
    before = _alert_rows(base, batches, plain)
    assert base._step_built_config[-1] is False
    mixed = _world(impl)
    mixed.packer.epoch_base_ms = base.packer.epoch_base_ms
    after = _alert_rows(mixed, batches, plain + [("north", "enter")])
    assert mixed._step_built_config[-1] is True
    state = {}
    for b, (_, _, rows) in enumerate(batches):
        want = oracle(rows, plain, dict(state))
        assert before[b] == want
        # where an inside/outside rule fired it is still the first rule;
        # elsewhere only the enter rule (id 3) may fire
        assert [f if f < 3 else -1 for f in after[b]] == want
        oracle(rows, plain, state)


def test_enter_stage_is_traced_only_with_an_enter_rule():
    engine = _world("xla")
    _install(engine, [("north", "inside")])
    from sitewhere_tpu.ops.pack import batch_to_blob, empty_batch

    engine._ensure_params()
    args = (engine._params, engine._state, engine._rule_state,
            engine._model_state, engine._actuation_state,
            batch_to_blob(empty_batch(32)))

    def sorts():
        text = engine._step_blob.lower(*args).as_text()
        return text.count("sort")

    without = sorts()
    engine.add_geofence_rule(GeofenceRule(token="in", zone_token="east",
                                          condition="enter"))
    engine._ensure_params()
    assert engine._step_built_config[-1] is True
    assert sorts() > without
    engine.remove_rule("in")
    engine._ensure_params()
    assert engine._step_built_config[-1] is False
    assert sorts() == without


def test_enter_on_a_mesh_matches_one_chip():
    """The sharded engine routes each device's rows to its shard, so the
    previous location is found there: its alerts equal the single-chip
    engine's."""
    from sitewhere_tpu.parallel import ShardedPipelineEngine, make_mesh

    single = _world("xla")
    tensors = single.registry
    sharded = ShardedPipelineEngine(
        tensors, mesh=make_mesh(2), per_shard_batch=32,
        measurement_slots=4, max_tenants=4, max_geofence_rules=8,
        geofence_impl="xla", name="geofence-enter-sharded")
    sharded.packer.epoch_base_ms = single.packer.epoch_base_ms
    sharded.start()
    for engine in (single, sharded):
        _install(engine, RULES)
    for events, tokens, _ in _batches(3, single.packer.epoch_base_ms):
        seen = []
        for engine in (single, sharded):
            alerts = []
            for batch in engine.packer.pack_events(events, tokens):
                routed, out = engine.submit_routed(batch)
                alerts += engine.materialize_alerts(routed, out)
            seen.append(sorted((a.device_id, a.event_date, a.message)
                               for a in alerts))
        assert seen[0] == seen[1]


def test_rule_from_dict_accepts_enter():
    from sitewhere_tpu.errors import SiteWhereError
    from sitewhere_tpu.pipeline.engine import rule_from_dict

    kind, rule = rule_from_dict({"type": "geofence", "token": "g",
                                 "zone_token": "z", "condition": "enter"})
    assert kind == "geofence" and rule.condition == "enter"
    with pytest.raises(SiteWhereError, match="inside|outside|enter"):
        rule_from_dict({"type": "geofence", "token": "g", "zone_token": "z",
                        "condition": "exit"})
