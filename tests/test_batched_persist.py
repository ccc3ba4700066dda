"""Cycle-batched store (persist/event_management.py store_device_events,
pipeline/inbound.py process).

Contracts: one inbound batch of N records is one event-log append and
one bulk publish per touched partition of `inbound-persisted-events`;
rows, event ids and `id_seq` come out in record order; each device's
order on the topic is the per-record path's; a record whose device is
unknown or unassigned fails alone; replay-suppressed records are neither
stored nor fanned out; `persist.store_events` observes the events each
call stores. And the log's buffer holds one chunk per batched append,
whose merge equals the per-row appends'.
"""

import msgpack
import numpy as np
import pytest

from sitewhere_tpu.errors import SiteWhereError
from sitewhere_tpu.model import (
    Device, DeviceAssignment, DeviceMeasurement, DeviceType)
from sitewhere_tpu.model.event import (
    DeviceAlert, DeviceCommandResponse, DeviceEventBatch, DeviceEventType,
    DeviceLocation)
from sitewhere_tpu.persist.event_management import (
    DeviceEventManagement, EventPersistenceTriggers)
from sitewhere_tpu.persist.eventlog import _COLUMNS, ColumnarEventLog
from sitewhere_tpu.pipeline.inbound import InboundProcessingService
from sitewhere_tpu.registry import DeviceManagement
from sitewhere_tpu.runtime.bus import EventBus, TopicNaming, _Partition
from sitewhere_tpu.runtime.metrics import GLOBAL_METRICS
from sitewhere_tpu.runtime.recovery import GLOBAL_REPLAY_BARRIER

N_DEVICES = 6


def _registry(n=N_DEVICES):
    dm = DeviceManagement()
    dtype = dm.create_device_type(DeviceType(token="t"))
    for i in range(n):
        device = dm.create_device(Device(token=f"d{i}",
                                         device_type_id=dtype.id))
        dm.create_device_assignment(
            DeviceAssignment(token=f"a{i}", device_id=device.id))
    return dm


def _payload(token, value, date):
    return msgpack.packb({
        "sourceId": "t", "deviceToken": token, "kind": "DeviceEventBatch",
        "request": {"device_token": token, "measurements": [
            DeviceMeasurement(name="m", value=value,
                              event_date=date).to_dict()],
            "locations": [], "alerts": []},
        "metadata": {}}, use_bin_type=True)


def _publish(bus, naming, tokens):
    """One record per token, its reading's value and date the token's
    index; returns them as one poll hands them to the consumer."""
    topic = naming.event_source_decoded_events("default")
    for i, token in enumerate(tokens):
        bus.publish(topic, token.encode(), _payload(token, float(i),
                                                    1_000 + i))
    records = bus.consumer(topic, "reader").poll(10_000)
    assert len(records) == len(tokens)
    return records


class _Packer:
    def __init__(self):
        self.stepped = []

    def pack_events(self, events, tokens):
        self.stepped.extend(zip(tokens, events))
        return [("batch", len(events))]


class _Engine:
    """Stands in for the engine: records what it is handed to step."""

    def __init__(self):
        self.packer = _Packer()

    def submit_routed(self, batch):
        return batch, None

    def materialize_alerts(self, batch, outputs):
        return []

    def drain_parked(self):
        return []


class _World:
    def __init__(self, tmp_path, name="log", partitions=4):
        self.bus = EventBus(partitions=partitions)
        self.naming = TopicNaming()
        self.registry = _registry()
        self.log = ColumnarEventLog(str(tmp_path / name))
        self.events = DeviceEventManagement(self.log, self.registry)
        EventPersistenceTriggers(self.bus, self.naming).attach(self.events)
        self.engine = _Engine()
        self.svc = InboundProcessingService(
            self.bus, self.registry, events=self.events, engine=self.engine,
            naming=self.naming)
        self.events.start()

    def close(self):
        self.events.stop()
        self.log.stop()

    def rows(self):
        """The tenant's buffered rows, merged."""
        return self.log.tenant("default")._buffer.peek().cols

    def persisted(self):
        """Per partition of inbound-persisted-events: (key, name, value,
        date) of each record in offset order."""
        topic = self.bus.topic(self.naming.inbound_persisted_events(
            "default"))
        out = []
        for part in topic.partitions:
            recs = []
            for _, key, value, _ in part.read(0, 10_000):
                ev = msgpack.unpackb(value, raw=False)
                recs.append((key, ev.get("name"), ev.get("value"),
                              ev["event_date"]))
            out.append(recs)
        return out


@pytest.fixture
def world(tmp_path):
    w = _World(tmp_path)
    yield w
    w.close()


def _tokens(records):
    return [r.key.decode() for r in records]


def _values(records):
    return [msgpack.unpackb(r.value)["request"]["measurements"][0]["value"]
            for r in records]


# a mix with repeats, so each device has several records in the batch
TOKENS = [f"d{i % N_DEVICES}" for i in (0, 1, 2, 0, 3, 1, 4, 5, 0, 2, 5, 1)]


class TestInboundBatch:
    def test_one_append_and_one_publish_per_partition(self, world,
                                                      monkeypatch):
        records = _publish(world.bus, world.naming, TOKENS)
        appends, bulk, single = [], [], []
        real_append = world.log.append_events
        monkeypatch.setattr(world.log, "append_events",
                            lambda t, evs, i=None: (appends.append(len(evs)),
                                                    real_append(t, evs, i)))
        real_many, real_one = _Partition.append_many, _Partition.append
        monkeypatch.setattr(_Partition, "append_many",
                            lambda p, recs: (bulk.append((p, len(recs))),
                                             real_many(p, recs))[1])
        monkeypatch.setattr(_Partition, "append",
                            lambda p, k, v: (single.append(p),
                                             real_one(p, k, v))[1])
        world.svc.process(records)
        assert appends == [len(TOKENS)]
        topic = world.bus.topic(world.naming.inbound_persisted_events(
            "default"))
        touched = {topic.partition_for(t.encode()) for t in TOKENS}
        parts = [p for p, _ in bulk]
        assert len(parts) == len(set(parts)) == len(touched)
        assert {topic.partitions.index(p) for p in parts} == touched
        assert sum(n for _, n in bulk) == len(TOKENS)
        assert single == []
        assert len(world.engine.packer.stepped) == len(TOKENS)

    def test_rows_ids_and_seq_in_record_order(self, world):
        records = _publish(world.bus, world.naming, TOKENS)
        world.svc.process(records)
        cols = world.rows()
        stepped = [ev for _, ev in world.engine.packer.stepped]
        assert list(cols["device_token"]) == _tokens(records)
        assert list(cols["value"]) == _values(records)
        assert list(cols["id"]) == [ev.id for ev in stepped]
        seq = np.asarray(cols["id_seq"])
        assert (np.diff(seq) == 1).all()
        assert [t for t, _ in world.engine.packer.stepped] == \
            _tokens(records)

    def test_per_device_order_matches_the_per_record_path(self, tmp_path):
        batched, one_by_one = _World(tmp_path, "a"), _World(tmp_path, "b")
        try:
            batched.svc.process(_publish(batched.bus, batched.naming,
                                         TOKENS))
            for record in _publish(one_by_one.bus, one_by_one.naming,
                                   TOKENS):
                one_by_one.svc.process([record])
            assert batched.persisted() == one_by_one.persisted()
            assert sum(map(len, batched.persisted())) == len(TOKENS)
        finally:
            batched.close()
            one_by_one.close()

    def test_a_command_response_stores_in_the_same_call(self, world):
        topic = world.naming.event_source_decoded_events("default")
        response = DeviceCommandResponse(originating_event_id="inv-1",
                                         response="ok", event_date=2)
        world.bus.publish(topic, b"d0", _payload("d0", 1.0, 1))
        world.bus.publish(topic, b"d0", msgpack.packb({
            "sourceId": "t", "deviceToken": "d0",
            "kind": "DeviceCommandResponse", "request": response.to_dict(),
            "metadata": {}}, use_bin_type=True))
        world.bus.publish(topic, b"d0", _payload("d0", 3.0, 3))
        world.svc.process(world.bus.consumer(topic, "reader").poll(100))
        cols = world.rows()
        assert list(cols["event_type"]) == [
            DeviceEventType.MEASUREMENT, DeviceEventType.COMMAND_RESPONSE,
            DeviceEventType.MEASUREMENT]
        assert list(cols["assignment_token"]) == ["a0"] * 3
        assert [d for *_, d in world.persisted()[
            world.bus.topic(topic).partition_for(b"d0")]] == [1, 2, 3]

    @pytest.mark.parametrize("fault", ["unknown", "unassigned"])
    def test_a_failing_device_fails_alone(self, world, monkeypatch, fault):
        # the device passes validate, then loses its device or assignment
        # before persist resolves it
        tokens = ["d0", "d1", "d2", "d3"]
        records = _publish(world.bus, world.naming, tokens)
        monkeypatch.setattr(world.svc, "_validate", lambda token, rec: True)
        get_device = world.registry.get_device_by_token
        get_assignment = world.registry.get_active_assignment
        lost = world.registry.get_device_by_token("d1").id
        if fault == "unknown":
            monkeypatch.setattr(
                world.registry, "get_device_by_token",
                lambda t: None if t == "d1" else get_device(t))
        else:
            monkeypatch.setattr(
                world.registry, "get_active_assignment",
                lambda i: None if i == lost else get_assignment(i))
        failed = world.svc.failed_counter.value
        world.svc.process(records)
        assert world.svc.failed_counter.value == failed + 1
        kept = [t for t in _tokens(records) if t != "d1"]
        assert list(world.rows()["device_token"]) == kept
        assert sorted(k.decode() for recs in world.persisted()
                      for k, *_ in recs) == sorted(kept)
        assert [t for t, _ in world.engine.packer.stepped] == kept

    def test_replay_suppressed_records_are_neither_stored_nor_fanned_out(
            self, world):
        records = _publish(world.bus, world.naming, TOKENS)
        GLOBAL_REPLAY_BARRIER.arm({"default": 5})
        try:
            world.svc.process(records)
        finally:
            GLOBAL_REPLAY_BARRIER.disarm()
        assert list(world.rows()["device_token"]) == _tokens(records)[5:]
        assert sum(map(len, world.persisted())) == len(TOKENS) - 5
        # every record still rebuilds state, in record order
        assert [t for t, _ in world.engine.packer.stepped] == \
            _tokens(records)

    def test_store_events_observes_the_batch(self, world):
        hist = GLOBAL_METRICS.histogram("persist.store_events")

        def totals():
            snap = hist.snapshot().get((), {"sum_s": 0.0, "count": 0})
            return snap["sum_s"], snap["count"]

        before = totals()
        world.svc.process(_publish(world.bus, world.naming, TOKENS))
        after = totals()
        assert after[1] - before[1] == 1
        assert after[0] - before[0] == len(TOKENS)


class TestStoreDeviceEvents:
    def test_mixed_items_fail_alone(self, world):
        def reading(v):
            return [DeviceMeasurement(name="m", value=v, event_date=1)]

        out = world.events.store_device_events(
            [("d0", reading(1.0)), ("ghost", reading(2.0)),
             ("d1", reading(3.0) + [DeviceLocation(latitude=1.0)])])
        assert isinstance(out[1], SiteWhereError)
        assert [ev.device_assignment_id for ev in out[0] + out[2]] == [
            "a0", "a1", "a1"]
        assert list(world.rows()["device_token"]) == ["d0", "d1", "d1"]
        assert sum(map(len, world.persisted())) == 3

    def test_a_malformed_event_fails_only_its_item(self, world):
        bad = DeviceMeasurement(name="m", value="not a number",
                                event_date=1)
        out = world.events.store_device_events(
            [("d0", [DeviceMeasurement(name="m", value=1.0, event_date=1)]),
             ("d1", [bad]),
             ("d2", [DeviceMeasurement(name="m", value=3.0, event_date=1)])])
        assert isinstance(out[1], ValueError)
        assert list(world.rows()["device_token"]) == ["d0", "d2"]
        assert sorted(k for recs in world.persisted()
                      for k, *_ in recs) == [b"d0", b"d2"]

    def test_rest_batch_is_a_one_item_store(self, world):
        batch = DeviceEventBatch(device_token="d3", measurements=[
            DeviceMeasurement(name="m", value=1.0)], alerts=[
            DeviceAlert(type="hot")])
        stored = world.events.add_device_event_batch("d3", batch)
        assert [type(ev) for ev in stored] == [DeviceMeasurement,
                                               DeviceAlert]
        with pytest.raises(SiteWhereError):
            world.events.add_device_event_batch("ghost", DeviceEventBatch(
                device_token="ghost", measurements=[
                    DeviceMeasurement(name="m", value=1.0)]))
        assert list(world.rows()["device_token"]) == ["d3", "d3"]


class TestSealOfCycleAppends:
    K, N = 5, 7

    def _events(self, k, n):
        return [DeviceMeasurement(id=f"e{k}-{i}", name=f"m{i % 3}",
                                  value=float(k * n + i),
                                  event_date=10 * k + i, device_id=f"d{i}",
                                  received_date=1)
                for i in range(n)]

    def test_one_chunk_per_cycle_merges_as_the_rows_do(self, tmp_path):
        log = ColumnarEventLog(str(tmp_path / "log"))
        try:
            for k in range(self.K):
                log.append_events("cycles", self._events(k, self.N))
            for k in range(self.K):
                for ev in self._events(k, self.N):
                    log.append_events("rows", [ev])
            cycles = log.tenant("cycles")._buffer
            rows = log.tenant("rows")._buffer
            assert len(cycles.chunks) == self.K
            assert len(rows.chunks) == self.K * self.N
            merged, per_row = cycles.drain().cols, rows.drain().cols
        finally:
            log.stop()
        for name in _COLUMNS:
            a, b = np.asarray(merged[name]), np.asarray(per_row[name])
            if name == "id_seq":   # one process-wide counter: offsets
                a, b = a - a[0], b - b[0]
            assert len(a) == self.K * self.N
            assert list(a) == list(b), name
