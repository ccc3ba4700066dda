"""Consumer-cycle spans and counters (runtime/flight.py CycleRecorder,
runtime/bus.py ConsumerHost, the inbound and persist stages).

Contracts: a cycle is recorded per poll that returned records, with its
poll / handler / commit stages; the inbound handler accumulates one mark
per record into its decode / validate / persist.context stages, and one
per batch into persist and its append and fan-out; the lossless
`bus.consumer_*` histograms move by exactly what the ring's records sum
to; persist called without a cycle records nothing; and while a
profiler trace runs, the leaf stages (never the parents) write host
spans into it.
"""

import time

import jax
import msgpack
import pytest

from benchmark import tracereduce
from sitewhere_tpu.model import (
    Device, DeviceAssignment, DeviceMeasurement, DeviceType)
from sitewhere_tpu.errors import SiteWhereError
from sitewhere_tpu.model.event import DeviceEventBatch
from sitewhere_tpu.persist.event_management import (
    DeviceEventManagement, EventPersistenceTriggers)
from sitewhere_tpu.persist.eventlog import ColumnarEventLog
from sitewhere_tpu.pipeline.inbound import InboundProcessingService
from sitewhere_tpu.registry import DeviceManagement
from sitewhere_tpu.runtime.bus import ConsumerHost, EventBus, TopicNaming
from sitewhere_tpu.runtime.flight import (
    CYCLE_STAGES, GLOBAL_CYCLES, GLOBAL_FLIGHT, STAGES, CycleRecord,
    CycleRecorder, FlightRecorder, StepRecord, trace_enabled)
from sitewhere_tpu.runtime.metrics import GLOBAL_METRICS

class _Since:
    """A label's cycle records and stage counters from now on. Labels are
    fixed names, as in the program: a fresh label per run would grow the
    histogram families towards their cardinality cap."""

    def __init__(self, label):
        self.label = label
        self.seen = {r["seq"] for r in _cycles(label)}
        self.sums = _stage_sums(label)

    def cycles(self):
        return [r for r in _cycles(self.label) if r["seq"] not in self.seen]

    def stage_sums(self):
        return {stage: secs - self.sums.get(stage, 0.0)
                for stage, secs in _stage_sums(self.label).items()}


def _wait(predicate, timeout_s=20.0):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, "condition not reached"
        time.sleep(0.01)


def _cycles(label):
    return GLOBAL_CYCLES.export(last_n=GLOBAL_CYCLES.capacity,
                                consumer=label)["records"]


def _stage_sums(label):
    snap = GLOBAL_METRICS.histogram("bus.consumer_stage_seconds").snapshot()
    return {dict(k)["stage"]: v["sum_s"] for k, v in snap.items()
            if dict(k)["consumer"] == label}


# -- the recorder ------------------------------------------------------------

class TestRecorder:
    def test_custom_vocabulary(self):
        fr = FlightRecorder(capacity=4, stages=("fetch", "apply"))
        rec = fr.begin_step("x")
        rec.mark("fetch", 1.0, 1.25)
        rec.mark("apply", 1.25, 2.0)
        out = fr.export()
        assert out["stages"] == ["fetch", "apply"]
        [r] = out["records"]
        assert r["stages"]["fetch"]["ms"] == pytest.approx(250.0)
        assert r["critical_stage"] == "apply"
        assert out["rollups"]["h2d_overlap_fraction"] == 0.0
        with pytest.raises(KeyError):
            rec.mark("pack", 0.0, 1.0)

    def test_accumulating_stage_keeps_sum_count_and_first_begin(self):
        rec = CycleRecord()
        rec.reset(seq=0, gen=0, engine="c")
        for _ in range(3):
            rec.open("decode")
            time.sleep(0.002)
            rec.close("decode")
        rec.mark("poll", rec.begin[3] - 1.0, rec.begin[3] - 0.5)
        out = rec.export()
        decode = out["stages"]["decode"]
        assert decode["n"] == 3
        assert decode["ms"] == pytest.approx(rec.acc[3] * 1e3, rel=1e-6)
        # the sum of three ~2 ms marks, not the first-begin-to-last-end span
        assert decode["ms"] < (rec.end[3] - rec.begin[3]) * 1e3
        assert decode["ms"] >= 6.0
        assert "n" not in out["stages"]["poll"]
        assert out["consumer"] == "c" and out["steps"] == []

    def test_default_engine_export_is_unchanged(self):
        fr = FlightRecorder(capacity=4)
        rec = fr.begin_step("eng")
        rec.mark("pack", 0.0, 0.001)
        rec.mark("dispatch", 0.001, 0.004)
        rec.events = 7
        out = fr.export()
        assert out["stages"] == list(STAGES)
        [r] = out["records"]
        assert r == {
            "seq": 0, "engine": "eng", "events": 7,
            "stages": {"pack": {"begin_s": 0.0, "ms": 1.0},
                       "dispatch": {"begin_s": 0.001, "ms": 3.0}},
            "sum_ms": 4.0, "span_ms": 4.0, "critical_stage": "dispatch"}
        assert isinstance(GLOBAL_FLIGHT._slots[0], StepRecord)
        assert not any(isinstance(s, CycleRecord)
                       for s in GLOBAL_FLIGHT._slots)

    def test_rings_per_consumer_keep_a_slow_consumer(self):
        rec = CycleRecorder(capacity=2)
        slow = rec.begin_cycle("slow", False)
        slow.mark("poll", 0.0, 1.0)
        for _ in range(5):
            rec.begin_cycle("fast", False).mark("poll", 2.0, 3.0)
        out = rec.export(last_n=10)
        assert [r["consumer"] for r in out["records"]] == [
            "slow", "fast", "fast"]
        assert [r["seq"] for r in out["records"]] == [0, 4, 5]
        assert out["rollups"]["by_consumer"]["fast"]["cycles"] == 2
        assert rec.export(consumer="slow")["count"] == 1


# -- consumer cycles at the bus boundary ------------------------------------

class TestConsumerHost:
    def test_one_cycle_per_non_empty_poll(self):
        bus = EventBus(partitions=1)
        since = _Since("test-cycles")
        batches = []
        host = ConsumerHost(bus, "t.cycles", "g", batches.append,
                            poll_timeout_s=0.02, label="test-cycles")
        host.start()
        try:
            time.sleep(0.2)                   # empty polls: no record
            assert since.cycles() == []
            for i in range(3):
                bus.publish("t.cycles", b"k", b"v%d" % i)
                _wait(lambda: len(batches) == i + 1)
            _wait(lambda: len(since.cycles()) == 3)
        finally:
            host.stop()
        recs = since.cycles()
        assert sum(r["records"] for r in recs) == 3
        for r in recs:
            assert set(r["stages"]) == {"poll", "handler", "commit"}
            assert r["span_ms"] >= r["stages"]["handler"]["ms"]
            assert "error" not in r and r["cpu_ms"] >= 0.0

    def test_raising_handler_records_error(self):
        bus = EventBus(partitions=1)
        since = _Since("test-raising")

        def handler(batch):
            raise ValueError("poison")

        host = ConsumerHost(bus, "t.raise", "g", handler,
                            poll_timeout_s=0.02, max_retries=1,
                            max_backoff_s=0.01, label="test-raising")
        host.start()
        try:
            bus.publish("t.raise", b"k", b"v")
            _wait(lambda: host.dead_lettered == 1)
        finally:
            host.stop()
        recs = since.cycles()
        assert len(recs) == 2               # the try and its one retry
        assert all(r["error"] == "ValueError" for r in recs)
        assert all("commit" not in r["stages"] for r in recs)

    @pytest.mark.parametrize("traced", [False, True])
    def test_poll_sequence_is_the_same_traced_or_not(self, traced,
                                                     monkeypatch):
        # a fetch of what is there, then the long-poll wait only on an
        # empty topic: the traced runs poll as the untraced ones do
        from sitewhere_tpu.runtime import bus as bus_mod

        monkeypatch.setattr(bus_mod, "trace_enabled", lambda: traced)
        bus = EventBus(partitions=1)
        calls = []
        made = bus.consumer

        def consumer(topic_name, group_id):
            group = made(topic_name, group_id)
            poll = group.poll
            depth = [0]

            def recording(max_records, *args, **kwargs):
                if not depth[0]:           # not poll's own re-poll
                    calls.append(kwargs)
                depth[0] += 1
                try:
                    return poll(max_records, *args, **kwargs)
                finally:
                    depth[0] -= 1

            group.poll = recording
            return group

        monkeypatch.setattr(bus, "consumer", consumer)
        batches = []
        host = ConsumerHost(bus, "t.polls", "g", batches.append,
                            poll_timeout_s=0.02, label="test-polls")
        host.start()
        try:
            _wait(lambda: len(calls) >= 2)
            bus.publish("t.polls", b"k", b"v")
            _wait(lambda: len(batches) == 1)
        finally:
            host.stop()
        assert calls[:2] == [{"until": None}, {"timeout_s": 0.02}]
        assert all(kw in ({"until": None}, {"timeout_s": 0.02})
                   for kw in calls)

    def test_histograms_move_by_the_ring_sums(self):
        bus = EventBus(partitions=2)
        label = "test-counted"
        since = _Since(label)
        key = (("consumer", label),)
        before = {family: GLOBAL_METRICS.histogram(family).snapshot().get(
            key, {"sum_s": 0.0, "count": 0}) for family in (
            "bus.consumer_cycle_records", "bus.consumer_cpu_seconds")}
        host = ConsumerHost(bus, "t.count", "g", lambda b: None,
                            poll_timeout_s=0.02, label=label)
        host.start()
        try:
            for i in range(40):
                bus.publish("t.count", b"k%d" % i, b"v")
            _wait(lambda: sum(r["records"] for r in since.cycles()) == 40)
        finally:
            host.stop()
        recs = since.cycles()
        sums = since.stage_sums()
        for stage in ("poll", "handler", "commit"):
            ring_s = sum(r["stages"][stage]["ms"] for r in recs) / 1e3
            assert sums[stage] == pytest.approx(ring_s, rel=1e-4,
                                                abs=1e-6)
        after = {family: GLOBAL_METRICS.histogram(family).snapshot()[key]
                 for family in before}
        records = after["bus.consumer_cycle_records"]
        assert records["sum_s"] - before[
            "bus.consumer_cycle_records"]["sum_s"] == 40
        assert records["count"] - before[
            "bus.consumer_cycle_records"]["count"] == len(recs)
        cpu_s = (after["bus.consumer_cpu_seconds"]["sum_s"]
                 - before["bus.consumer_cpu_seconds"]["sum_s"])
        assert cpu_s == pytest.approx(
            sum(r["cpu_ms"] for r in recs) / 1e3, rel=1e-4, abs=1e-6)


# -- the inbound and persist stages -----------------------------------------

def _registry(n):
    dm = DeviceManagement()
    dtype = dm.create_device_type(DeviceType(token="t"))
    for i in range(n):
        device = dm.create_device(Device(token=f"d{i}",
                                         device_type_id=dtype.id))
        dm.create_device_assignment(
            DeviceAssignment(token=f"a{i}", device_id=device.id))
    return dm


def _records(bus, naming, n, unknown=0):
    topic = naming.event_source_decoded_events("default")
    tokens = [f"d{i}" for i in range(n)] + [f"ghost{i}"
                                             for i in range(unknown)]
    for i, token in enumerate(tokens):
        bus.publish(topic, token.encode(), msgpack.packb({
            "sourceId": "t", "deviceToken": token,
            "kind": "DeviceEventBatch",
            "request": {"device_token": token, "measurements": [
                DeviceMeasurement(name="m", value=float(i)).to_dict()],
                "locations": [], "alerts": []},
            "metadata": {}}, use_bin_type=True))
    return bus.consumer(topic, "reader").poll(1000)


class _Packer:
    def pack_events(self, events, tokens):
        return [("batch", len(events))]


class _Engine:
    """Stands in for the engine: one step per pack, no alerts."""

    def __init__(self):
        self.packer = _Packer()
        self.flight = FlightRecorder(capacity=4)
        self._flight_last = None

    def submit_routed(self, batch):
        self._flight_last = self.flight.begin_step("stub")
        return batch, None

    def materialize_alerts(self, batch, outputs):
        return []

    def drain_parked(self):
        return []


@pytest.fixture
def inbound(tmp_path):
    bus = EventBus(partitions=2)
    naming = TopicNaming()
    registry = _registry(8)
    log = ColumnarEventLog(str(tmp_path / "log"))
    events = DeviceEventManagement(log, registry)
    EventPersistenceTriggers(bus, naming).attach(events)
    engine = _Engine()
    svc = InboundProcessingService(bus, registry, events=events,
                                   engine=engine, naming=naming)
    events.start()
    yield svc, bus, naming, events, engine
    events.stop()
    log.stop()


class TestInboundStages:
    def test_each_record_marks_decode_validate_persist(self, inbound):
        svc, bus, naming, _events, engine = inbound
        records = _records(bus, naming, 8, unknown=2)
        cycle = GLOBAL_CYCLES.begin_cycle("test-inbound", False)
        t0 = time.perf_counter()
        svc.process(records, cycle=cycle)
        wall_s = time.perf_counter() - t0
        out = cycle.export()["stages"]
        assert out["decode"]["n"] == 10
        assert out["validate"]["n"] == 10
        # one persist call per cycle; a context per valid record (the
        # ghosts fail validate)
        assert out["persist"]["n"] == 1
        assert out["persist.context"]["n"] == 8
        assert out["persist.append"]["n"] == 1
        assert out["persist.fanout"]["n"] == 1
        children = sum(out[c]["ms"] for c in (
            "persist.context", "persist.append", "persist.fanout"))
        assert children <= out["persist"]["ms"] + 1e-6
        assert out["pack_events"]["n"] == 1 and out["step"]["n"] == 1
        assert out["materialize"]["n"] == 1
        assert out["alert_persist"]["n"] == 2     # alerts + drain_parked
        assert cycle.events == 8
        assert cycle.steps == [engine._flight_last.seq]
        leaves = sum(out[s]["ms"] for s in out
                     if s not in ("handler", "persist"))
        assert leaves / 1e3 <= wall_s

    def test_persist_without_a_cycle_records_nothing(self, inbound):
        svc, bus, naming, events, _engine = inbound
        def persisting():
            return [r["seq"] for r in GLOBAL_CYCLES.export(
                last_n=10 ** 6)["records"] if "persist.append" in r["stages"]]

        before = persisting()
        batch = DeviceEventBatch(device_token="d1")
        batch.measurements.append(DeviceMeasurement(name="m", value=1.0))
        stored = events.add_device_event_batch("d1", batch)
        assert len(stored) == 1 and stored[0].device_assignment_id == "a1"
        svc.process(_records(bus, naming, 2))
        assert persisting() == before
        # the store with a cycle marks the persist stages into it
        cycle = GLOBAL_CYCLES.begin_cycle("test-inbound", False)
        events.store_device_events(
            [("d2", [DeviceMeasurement(name="m", value=2.0)])], cycle=cycle)
        assert {s: cycle.export()["stages"][s]["n"] for s in (
            "persist.context", "persist.append", "persist.fanout")} == {
            "persist.context": 1, "persist.append": 1, "persist.fanout": 1}

    def test_persist_stages_close_when_persist_raises(self, inbound):
        # a device with no active assignment: its context raises after
        # the stage opened; the stage still closes, span and all, and the
        # item fails with the error
        _svc, _bus, _naming, events, _engine = inbound
        registry = events.registry
        lone = registry.create_device(Device(
            token="lone", device_type_id=registry.get_device_by_token(
                "d0").device_type_id))
        assert registry.get_active_assignment(lone.id) is None
        cycle = GLOBAL_CYCLES.begin_cycle("test-inbound", True)
        batch = DeviceEventBatch(device_token="lone", measurements=[
            DeviceMeasurement(name="m", value=1.0)])
        [failed] = events.store_device_events(
            [("lone", batch.all_events())], cycle=cycle)
        assert isinstance(failed, SiteWhereError)
        assert cycle.export()["stages"]["persist.context"]["n"] == 1
        assert all(span is None for span in cycle._open)

    def test_served_inbound_group_records_its_cycles(self, inbound):
        svc, bus, naming, _events, _engine = inbound
        seen = {r["seq"] for r in _cycles("inbound-processing")}

        def new():
            return [r for r in _cycles("inbound-processing")
                    if r["seq"] not in seen]

        svc.start()
        try:
            _records(bus, naming, 5)
            _wait(lambda: sum(r["records"] for r in new()) >= 5)
        finally:
            svc.stop()
        rec = new()[-1]
        assert {"poll", "handler", "commit", "decode", "validate",
                "persist", "persist.append"} <= set(rec["stages"])
        assert set(rec["stages"]) <= set(CYCLE_STAGES)


# -- profiler spans ----------------------------------------------------------

def test_engine_host_stages_write_step_spans(tmp_path):
    fr = FlightRecorder(capacity=4)
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=tracereduce.trace_options())
    try:
        rec = fr.begin_step("e")
        for stage in ("guard", "pack", "dispatch", "materialize"):
            rec.begin_stage(stage)
            rec.end_stage(stage)
    finally:
        jax.profiler.stop_trace()
    names = {name for name, _, _ in tracereduce.events(
        tracereduce.load(str(tmp_path / "trace")),
        lambda n: n == "/host:CPU", lambda n: True)}
    assert {"step.pack", "step.dispatch", "step.materialize"} <= names
    assert "step.guard" not in names       # not an annotated stage
    assert rec.stage_s("dispatch") >= 0.0 and rec.export()["stages"]


def test_leaf_stages_write_host_spans_into_a_cpu_trace(inbound, tmp_path):
    svc, bus, naming, _events, _engine = inbound
    records = _records(bus, naming, 4)
    label = "test-traced"
    since = _Since(label)
    host = ConsumerHost(bus, "t.traced", "g", lambda b: None,
                        poll_timeout_s=0.02, label=label)
    assert not trace_enabled()
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=tracereduce.trace_options())
    try:
        assert trace_enabled()
        cycle = GLOBAL_CYCLES.begin_cycle("test-inbound", trace_enabled())
        svc.process(records, cycle=cycle)
        host.start()
        bus.publish("t.traced", b"k", b"v")
        _wait(lambda: len(since.cycles()) == 1)
        host.stop()
    finally:
        jax.profiler.stop_trace()
    host_events = tracereduce.events(tracereduce.load(str(tmp_path / "trace")),
                                     lambda n: n == "/host:CPU",
                                     lambda n: True)
    names = {name for name, _, _ in host_events}
    for leaf in ("inbound.decode", "inbound.validate", "persist.context",
                 "persist.append", "persist.fanout", "inbound.step",
                 f"consumer.{label}.poll", f"consumer.{label}.handler",
                 f"consumer.{label}.commit"):
        assert leaf in names, leaf
    # one span per record, one per persist call
    for leaf in ("inbound.decode", "persist.context"):
        assert sum(1 for e in host_events if e[0] == leaf) >= 4
    for leaf in ("persist.append", "persist.fanout"):
        assert sum(1 for e in host_events if e[0] == leaf) == 1
    # parents open no span
    assert not names & {"inbound.persist", "inbound.handler", "persist",
                        "handler", "consumer.inbound-processing.handler"}
