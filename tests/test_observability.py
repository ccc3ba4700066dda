"""End-to-end event-age telemetry (runtime/eventage.py) and the
observability plane around it.

Differential contract: the AgeSidecar/AgeSummary fold — count, sum,
min/max, and the fixed log2 bucket counts — must match a NumPy oracle
that mirrors `bucket_index` exactly, and the sidecar must survive the
real handoffs on BOTH engine kinds (single-chip submit -> materialize,
sharded prepare -> dispatch -> materialize, and the pipelined feeder's
cross-thread heap hop). Around it: busnet traceparent stitching, the
tracer's dead-thread sweep, the histogram cardinality guard, and the
HBM residency ledger.
"""

import math
import threading
import time

import numpy as np
import pytest

from sitewhere_tpu.model import (
    Device, DeviceAssignment, DeviceMeasurement, DeviceType)
from sitewhere_tpu.pipeline.engine import PipelineEngine, ThresholdRule
from sitewhere_tpu.registry import DeviceManagement, RegistryTensors
from sitewhere_tpu.runtime.eventage import (
    AGE_BUCKET_EDGES_S, AGE_BUCKET_FLOOR_S, AGE_MAX_ENTRIES, N_AGE_BUCKETS,
    AgeSidecar, AgeSummary, age_histogram, bucket_index, observe_summary)
from sitewhere_tpu.runtime.flight import FlightRecorder
from sitewhere_tpu.runtime.metrics import Histogram, MetricsRegistry
from sitewhere_tpu.runtime.tracing import GLOBAL_TRACER, Tracer


def _world(n_devices=16, capacity=64):
    dm = DeviceManagement()
    dtype = dm.create_device_type(DeviceType(token="t"))
    tensors = RegistryTensors(capacity, 4, 4)
    for i in range(n_devices):
        device = dm.create_device(Device(token=f"d{i}",
                                         device_type_id=dtype.id))
        dm.create_device_assignment(
            DeviceAssignment(token=f"a{i}", device_id=device.id))
    tensors.attach(dm, "tenant")
    return dm, tensors


def _batch(engine, k=0, n_devices=16):
    events = [DeviceMeasurement(name="m", value=float(k * 100 + i),
                                event_date=1000 + k * 50 + i)
              for i in range(n_devices)]
    return engine.packer.pack_events(
        events, [f"d{i}" for i in range(n_devices)])[0]


def _oracle_buckets(ages_s, weights):
    """NumPy mirror of eventage.bucket_index — keep in lockstep."""
    ages = np.maximum(np.asarray(ages_s, dtype=np.float64), 0.0)
    idx = np.zeros(len(ages), dtype=np.int64)
    over = ages > AGE_BUCKET_FLOOR_S
    idx[over] = np.minimum(
        np.floor(np.log2(ages[over] / AGE_BUCKET_FLOOR_S)).astype(np.int64)
        + 1,
        N_AGE_BUCKETS - 1)
    return np.bincount(idx, weights=np.asarray(weights, dtype=np.int64),
                       minlength=N_AGE_BUCKETS).astype(np.int64)


class TestAgeOracle:
    def test_bucket_index_spot_values(self):
        assert bucket_index(0.0) == 0
        assert bucket_index(AGE_BUCKET_FLOOR_S) == 0      # floor inclusive
        assert bucket_index(1.5e-4) == 1                  # (1x, 2x] floor
        assert bucket_index(3.0e-4) == 2                  # (2x, 4x] floor
        assert bucket_index(1e9) == N_AGE_BUCKETS - 1     # clamps open-ended
        assert len(AGE_BUCKET_EDGES_S) == N_AGE_BUCKETS - 1

    def test_summary_matches_numpy_oracle(self):
        rng = np.random.default_rng(7)
        now = 1000.0
        # ages spanning the whole dynamic range: sub-floor, the log2
        # ladder (0.1 ms .. ~30 s), and beyond the last finite edge —
        # strictly off bucket boundaries so fp noise can't flip a bucket
        ages = np.concatenate([
            rng.uniform(0.0, AGE_BUCKET_FLOOR_S * 0.9, 8),
            10.0 ** rng.uniform(-3.9, 1.4, 48),
            np.array([45.0, 0.0, AGE_BUCKET_FLOOR_S * 0.5]),
        ])
        ns = rng.integers(1, 50, size=len(ages))
        assert len(ages) <= AGE_MAX_ENTRIES  # stay under the spill path
        stamps = now - ages
        ages = now - stamps  # the fp round trip the sidecar actually sees
        sidecar = AgeSidecar()
        for stamp, n in zip(stamps, ns):
            sidecar.add(float(stamp), int(n))
        assert sidecar.count == int(ns.sum())
        summary = sidecar.close(now)

        assert summary.count == int(ns.sum())
        assert summary.sum_s == pytest.approx(float((ages * ns).sum()),
                                              rel=1e-9, abs=1e-9)
        assert summary.min_s == pytest.approx(float(ages.min()), abs=1e-9)
        assert summary.max_s == pytest.approx(float(ages.max()), abs=1e-9)
        assert summary.buckets == _oracle_buckets(ages, ns).tolist()
        # derived quantiles: bucketed upper bounds, ordered, inside range
        out = summary.export()
        assert out["p50_ms"] <= out["p99_ms"] <= out["max_ms"] + 1e-6

    def test_merge_matches_oracle(self):
        rng = np.random.default_rng(11)
        ages = 10.0 ** rng.uniform(-4.2, 1.2, 40)
        ns = rng.integers(1, 9, size=40)
        a, b = AgeSummary(), AgeSummary()
        for i, (age, n) in enumerate(zip(ages, ns)):
            (a if i % 2 else b).fold(float(age), int(n))
        a.merge(b)
        assert a.count == int(ns.sum())
        assert a.buckets == _oracle_buckets(ages, ns).tolist()
        assert a.sum_s == pytest.approx(float((ages * ns).sum()), rel=1e-9)

    def test_overflow_merge_is_count_and_sum_exact(self):
        """Past AGE_MAX_ENTRIES the newest entries merge by weighted
        mean: count and sum stay exact however many deliveries fold in."""
        now = 50.0
        rng = np.random.default_rng(3)
        ages = rng.uniform(0.001, 0.5, 300)
        ns = rng.integers(1, 20, size=300)
        stamps = now - ages
        ages = now - stamps
        sidecar = AgeSidecar()
        for stamp, n in zip(stamps, ns):
            sidecar.add(float(stamp), int(n))
        assert len(sidecar.entries) <= AGE_MAX_ENTRIES
        summary = sidecar.close(now)
        assert summary.count == int(ns.sum())
        assert summary.sum_s == pytest.approx(float((ages * ns).sum()),
                                              rel=1e-6)
        # merged stamps stay inside [min, max] of their constituents
        assert summary.min_s >= float(ages.min()) - 1e-9
        assert summary.max_s <= float(ages.max()) + 1e-9

    def test_close_is_pure_and_reclosable(self):
        """Materialize, alert, and persist edges each close the SAME
        sidecar at their own instant — close must not consume entries."""
        sidecar = AgeSidecar()
        sidecar.add(10.0, 4)
        first = sidecar.close(10.5)
        second = sidecar.close(11.5)
        assert len(sidecar.entries) == 1
        assert first.count == second.count == 4
        assert second.sum_s > first.sum_s

    def test_observe_summary_feeds_histogram_buckets_exactly(self):
        reg = MetricsRegistry()
        hist = age_histogram(reg)
        summary = AgeSummary()
        summary.fold(0.003, 5)     # ~3 ms
        summary.fold(0.2, 2)       # 200 ms
        observe_summary(hist, summary, engine="e", edge="materialize")
        key = tuple(sorted({"engine": "e", "edge": "materialize"}.items()))
        snap = hist.snapshot()[key]
        assert snap["count"] == 7
        assert snap["sum_s"] == pytest.approx(0.003 * 5 + 0.2 * 2)
        # cumulative bucket counts cross 5 at the 3 ms edge, 7 at the top
        assert snap["buckets"][-1] == 7
        edge_3ms = next(i for i, e in enumerate(AGE_BUCKET_EDGES_S)
                        if e >= 0.003)
        assert snap["buckets"][edge_3ms] == 5


class TestAgeSingleChip:
    def test_submit_to_materialize_closes_age(self):
        _, tensors = _world()
        engine = PipelineEngine(tensors, batch_size=32, name="age-single")
        engine.flight = FlightRecorder(capacity=16)   # isolate from suite
        engine._age_hist = age_histogram(MetricsRegistry())
        engine.start()
        engine.add_threshold_rule(ThresholdRule(
            token="r", measurement_name="m", operator=">", threshold=1.0))
        try:
            batch = _batch(engine)
            age = AgeSidecar()
            age.add(time.perf_counter() - 0.005, 16)  # ingested 5 ms ago
            fetches_before = engine.d2h_fetches
            routed, out = engine.submit_routed(batch, age=age)
            engine.materialize_alerts(routed, out)
            # two lane fetches per offer (alert + command lanes, one
            # batched device_get) — telemetry must not add D2H syncs
            assert engine.d2h_fetches == fetches_before + 2
            rec = engine._flight_last
            assert hasattr(rec.age, "buckets")        # closed AgeSummary
            assert rec.age.count == 16
            assert rec.age.min_s >= 0.005 - 1e-4
            key = tuple(sorted(
                {"engine": "age-single", "edge": "materialize"}.items()))
            snap = engine._age_hist.snapshot()[key]
            assert snap["count"] == 16
            assert snap["sum_s"] >= 16 * 0.004
            # the closed summary rides the flight export + rollups
            export = engine.flight.export(last_n=8)
            assert export["records"][-1]["age"]["count"] == 16
            roll_age = export["rollups"]["event_age"]
            assert roll_age["count"] == 16
            assert roll_age["p50_ms"] <= roll_age["p99_ms"]
        finally:
            engine.stop()

    def test_submit_without_age_records_nothing(self):
        _, tensors = _world()
        engine = PipelineEngine(tensors, batch_size=32, name="age-none")
        engine.flight = FlightRecorder(capacity=16)
        engine._age_hist = age_histogram(MetricsRegistry())
        engine.start()
        try:
            routed, out = engine.submit_routed(_batch(engine))
            engine.materialize_alerts(routed, out)
            assert engine._flight_last.age is None
            assert engine._age_hist.snapshot() == {}
            assert "event_age" not in engine.flight.export()["rollups"]
        finally:
            engine.stop()


class TestAgeSharded:
    def test_prepare_to_materialize_closes_age(self):
        from sitewhere_tpu.parallel import ShardedPipelineEngine, make_mesh

        _, tensors = _world(n_devices=48, capacity=256)
        eng = ShardedPipelineEngine(
            tensors, mesh=make_mesh(4), per_shard_batch=16,
            measurement_slots=4, max_tenants=4, max_threshold_rules=8,
            max_geofence_rules=8, name="age-sharded")
        eng.flight = FlightRecorder(capacity=16)
        eng._age_hist = age_histogram(MetricsRegistry())
        eng.packer.measurements.intern("m")
        eng.start()
        try:
            batch = _batch(eng, n_devices=48)
            age = AgeSidecar()
            age.add(time.perf_counter() - 0.007, 48)
            routed, out = eng.submit_routed(batch, age=age)
            eng.materialize_alerts(routed, out)
            rec = eng._flight_last
            assert hasattr(rec.age, "buckets")
            assert rec.age.count == 48
            key = tuple(sorted(
                {"engine": "age-sharded", "edge": "materialize"}.items()))
            snap = eng._age_hist.snapshot()[key]
            assert snap["count"] == 48
            assert eng.flight.export()["rollups"]["event_age"]["count"] == 48
        finally:
            eng.stop()


class TestAgeFeederHandoff:
    def test_sidecar_crosses_feeder_threads(self):
        """The sidecar attached at submit() on the caller thread must ride
        the feeder's heap handoff to the stager/step threads and close at
        materialize — the same cross-thread stitch the flight record does."""
        from sitewhere_tpu.pipeline.feed import PipelinedSubmitter

        _, tensors = _world()
        engine = PipelineEngine(tensors, batch_size=32, name="age-feed")
        engine.flight = FlightRecorder(capacity=16)
        engine._age_hist = age_histogram(MetricsRegistry())
        engine.start()
        sub = PipelinedSubmitter(engine, depth=2, stagers=2)
        try:
            batch = _batch(engine)
            age = AgeSidecar()
            age.add(time.perf_counter() - 0.003, 16)
            fut = sub.submit(batch, age=age)
            out = fut.result(timeout=30)
            rec = engine._flight_last
            assert rec.age is age                     # open: crossed threads
            engine.materialize_alerts(batch, out)
            assert hasattr(rec.age, "buckets")        # closed at materialize
            assert rec.age.count == 16
            key = tuple(sorted(
                {"engine": "age-feed", "edge": "materialize"}.items()))
            assert engine._age_hist.snapshot()[key]["count"] == 16
        finally:
            sub.close()
            engine.stop()


class TestIngestServiceEdges:
    def test_persist_and_materialize_edges_both_close(self):
        """BulkWireIngestService stamps one sidecar per batch; the engine
        closes the materialize edge and the service re-closes the SAME
        sidecar at the persist edge (pure close)."""
        from sitewhere_tpu.persist.eventlog import ColumnarEventLog
        from sitewhere_tpu.runtime.bus import EventBus, TopicNaming
        from sitewhere_tpu.sources.fastlane import BulkWireIngestService
        from sitewhere_tpu.transport.wire import (
            MessageType, WireCodec, encode_frame)

        dm, tensors = _world(n_devices=5)
        engine = PipelineEngine(tensors, batch_size=16, name="age-ingest")
        engine.packer.measurements.intern("m1")
        engine.flight = FlightRecorder(capacity=16)
        engine.add_threshold_rule(ThresholdRule(
            token="hot", measurement_name="m1", operator=">",
            threshold=1.0))
        engine.start()

        class _Events:  # minimal alert sink
            def __init__(self):
                self.alerts = []

            def add_alerts(self, token, alert):
                self.alerts.append((token, alert))

        events = _Events()
        svc = BulkWireIngestService(
            engine, eventlog=ColumnarEventLog(), events=events, bus=EventBus(),
            tenant="tenant", naming=TopicNaming(), registry=dm,
            metrics=MetricsRegistry(), trace_sample_n=1)
        engine._age_hist = svc._age_hist  # one registry for all edges
        svc.start()
        try:
            finished_before = GLOBAL_TRACER.finished_count
            now = engine.packer.epoch_base_ms
            payload = b"".join(
                encode_frame(MessageType.MEASUREMENT,
                             WireCodec.encode_measurement(
                                 f"d{i}", now, "m1", 7.0))
                for i in range(3))
            svc.on_encoded_event_received(
                payload,
                metadata={"received_at": time.perf_counter() - 0.004})
            snap = svc._age_hist.snapshot()
            mat = snap[tuple(sorted(
                {"engine": "age-ingest", "edge": "materialize"}.items()))]
            per = snap[tuple(sorted(
                {"engine": "age-ingest", "edge": "persist"}.items()))]
            alert = snap[tuple(sorted(
                {"engine": "age-ingest", "edge": "alert"}.items()))]
            assert mat["count"] == 3 and per["count"] == 3
            assert alert["count"] == 3 and len(events.alerts) == 3
            # edges re-close the same sidecar later in time: ages only
            # grow, so each later edge reads at least as old
            assert alert["sum_s"] >= mat["sum_s"]
            assert mat["sum_s"] >= 3 * 0.003
            # trace_sample_n=1: the delivery ran inside a journey span
            assert GLOBAL_TRACER.finished_count > finished_before
            journeys = [s for s in GLOBAL_TRACER.finished(limit=50)
                        if s["operation"] == "ingest.journey"]
            assert journeys and journeys[-1]["tags"]["tenant"] == "tenant"
        finally:
            svc.stop()
            engine.stop()


class TestBusnetTracePropagation:
    @pytest.fixture
    def server(self, tmp_path):
        from sitewhere_tpu.runtime.bus import EventBus
        from sitewhere_tpu.runtime.busnet import BusServer

        bus = EventBus(partitions=2, data_dir=str(tmp_path / "bus"))
        srv = BusServer(bus)
        srv.start()
        yield bus, srv
        srv.stop()
        bus.close()

    def test_journey_span_stitches_across_the_wire(self, server):
        """A sampled ingest journey's traceparent rides the busnet RPC
        envelope: the server opens a `busnet.<op>` span parented on the
        caller's active span — same trace id, correct parent id."""
        from sitewhere_tpu.runtime.busnet import BusClient

        _bus, srv = server
        client = BusClient("127.0.0.1", srv.port)
        try:
            with GLOBAL_TRACER.span("ingest.journey") as journey:
                client.publish("tr.events", b"k", b"v")
            deadline = time.time() + 5
            while time.time() < deadline:
                spans = GLOBAL_TRACER.finished(limit=200)
                stitched = [
                    s for s in spans
                    if s["operation"] == "busnet.publish"
                    and s["traceId"].endswith(journey.trace_id)]
                if stitched:
                    break
                time.sleep(0.02)
            assert stitched, "no server span joined the journey trace"
            assert stitched[-1]["parentId"].endswith(journey.span_id)
        finally:
            client.close()

    def test_unsampled_rpc_mints_no_server_span(self, server):
        """The steady state (no active span on the calling thread) sends
        no traceparent, so the server must not mint spans for it."""
        from sitewhere_tpu.runtime.busnet import BusClient

        _bus, srv = server
        assert GLOBAL_TRACER.active() is None
        client = BusClient("127.0.0.1", srv.port)
        try:
            marker = GLOBAL_TRACER.finished_count
            client.publish("tr2.events", b"k", b"v")
            time.sleep(0.1)
            new = GLOBAL_TRACER.finished(
                limit=GLOBAL_TRACER.finished_count - marker or 1) \
                if GLOBAL_TRACER.finished_count > marker else []
            assert not [s for s in new
                        if s["operation"].startswith("busnet.")]
        finally:
            client.close()

    def test_telemetry_op_round_trip(self, server):
        """BusServer.telemetry_provider answers the `telemetry` op; an
        unwired server rejects it without dying."""
        from sitewhere_tpu.runtime.busnet import BusClient, BusNetError

        _bus, srv = server
        client = BusClient("127.0.0.1", srv.port, retries=0)
        try:
            with pytest.raises(BusNetError):
                client.telemetry()
            srv.telemetry_provider = lambda: {
                "process_id": "7", "metrics": {"counters": {}}}
            out = client.telemetry()
            assert out["process_id"] == "7"
            assert client.ping()  # connection survived the rejected op
        finally:
            client.close()


class TestTracerHygiene:
    def test_dead_thread_stacks_are_swept(self):
        tracer = Tracer(capacity=64)

        def work():
            with tracer.span("feeder-op"):
                pass

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # the dead idents' stack entries exist until a sweep runs
        assert tracer.stats()["finished"] == 4
        live = {t.ident for t in threading.enumerate()}
        assert not set(tracer._stacks) - live, (
            "stats() left dead-thread stacks behind")

    def test_sweep_keeps_live_threads(self):
        tracer = Tracer(capacity=64)
        release = threading.Event()
        opened = threading.Event()

        def work():
            with tracer.span("long-op"):
                opened.set()
                release.wait(timeout=10)

        t = threading.Thread(target=work)
        t.start()
        try:
            assert opened.wait(timeout=10)
            stats = tracer.stats()
            assert stats["thread_stacks"] >= 1
            assert t.ident in tracer._stacks  # live stack survived sweep
        finally:
            release.set()
            t.join()


class TestCardinalityGuard:
    def test_overflow_child_caps_label_cardinality(self):
        from sitewhere_tpu.runtime.metrics import (
            GLOBAL_METRICS, MAX_LABEL_CHILDREN)

        reg = MetricsRegistry()
        hist = reg.histogram("guard.h", buckets=(1.0, 2.0))
        overflow_before = GLOBAL_METRICS.counter(
            "metrics.label_overflow").value
        for i in range(MAX_LABEL_CHILDREN + 10):
            hist.observe(0.5, tenant=f"t{i}")
        snap = hist.snapshot()
        overflow_key = (("tenant", "_overflow"),)
        assert overflow_key in snap
        assert snap[overflow_key]["count"] == 10
        assert len(snap) == MAX_LABEL_CHILDREN + 1
        assert GLOBAL_METRICS.counter(
            "metrics.label_overflow").value == overflow_before + 10

    def test_a_kept_overflow_child_counts_every_spill(self):
        # a hot path keeps the child it looked up once; past the cap that
        # is the _overflow child, and each observation into it still
        # counts as a spill
        from sitewhere_tpu.runtime.metrics import GLOBAL_METRICS

        hist = Histogram(buckets=(1.0,), max_children=2)
        hist.observe(0.5, consumer="a")
        hist.observe(0.5, consumer="b")
        overflow = GLOBAL_METRICS.counter("metrics.label_overflow")
        before = overflow.value
        kept = hist.child(consumer="c")
        assert overflow.value == before          # a lookup is no spill
        for _ in range(3):
            hist.observe_child(kept, 0.5)
        hist.observe_buckets([1], 0.5, 1, consumer="d")
        assert overflow.value == before + 4
        assert hist.snapshot()[(("consumer", "_overflow"),)]["count"] == 4

    def test_existing_children_keep_working_after_cap(self):
        from sitewhere_tpu.runtime.metrics import MAX_LABEL_CHILDREN

        reg = MetricsRegistry()
        hist = reg.histogram("guard.h2", buckets=(1.0,))
        for i in range(MAX_LABEL_CHILDREN):
            hist.observe(0.5, tenant=f"t{i}")
        hist.observe(0.5, tenant="t0")  # pre-existing child: not spilled
        snap = hist.snapshot()
        assert snap[(("tenant", "t0"),)]["count"] == 2
        assert (("tenant", "_overflow"),) not in snap


class TestHbmLedger:
    def test_ledger_accounts_every_resident_table(self):
        from sitewhere_tpu.runtime import hbmledger

        _, tensors = _world()
        engine = PipelineEngine(tensors, batch_size=32, name="hbm-test")
        engine.start()
        engine.add_threshold_rule(ThresholdRule(
            token="r", measurement_name="m", operator=">", threshold=1.0))
        try:
            engine.submit(_batch(engine))  # params + state materialized
            tables = hbmledger.table_bytes(engine)
            for name in ("device_state", "rule_state", "model_state",
                         "rule_tables", "model_weights", "registry_params",
                         "alert_lanes", "route_lanes", "staging_buffers"):
                assert name in tables and tables[name] >= 0, name
            assert tables["device_state"] > 0
            assert tables["rule_tables"] > 0
            assert tables["alert_lanes"] > 0
            led = hbmledger.ledger(engine)
            assert led["total_bytes"] == sum(led["tables"].values())
        finally:
            engine.stop()

    def test_export_gauges_shape_and_prometheus_render(self):
        from sitewhere_tpu.runtime import hbmledger

        _, tensors = _world()
        engine = PipelineEngine(tensors, batch_size=32, name="hbm-prom")
        engine.start()
        try:
            engine.submit(_batch(engine))
            gauges = hbmledger.export_gauges(engine)
            assert 'hbm.table_bytes{table="device_state"}' in gauges
            assert gauges["hbm.total_bytes"] == sum(
                v for k, v in gauges.items() if k != "hbm.total_bytes")
            text = MetricsRegistry().prometheus_text(extra_gauges=gauges)
            lines = text.splitlines()
            samples = [l for l in lines
                       if l.startswith("swtpu_hbm_table_bytes{")]
            assert any('table="device_state"' in l for l in samples)
            # one TYPE line for the whole labeled family
            assert sum(1 for l in lines
                       if l == "# TYPE swtpu_hbm_table_bytes gauge") == 1
        finally:
            engine.stop()


class TestClusterTelemetryMerge:
    def test_peer_label_injection(self):
        from sitewhere_tpu.parallel.cluster import _inject_peer_label

        assert _inject_peer_label('swtpu_x{a="b"} 1.0', "2") == (
            'swtpu_x{a="b",peer="2"} 1.0')
        assert _inject_peer_label("swtpu_y 3", "2") == 'swtpu_y{peer="2"} 3'

    def test_instance_snapshot_shape(self):
        """The per-process snapshot a peer hands back over busnet: the
        instance-level gauges (incl. the HBM ledger) plus flight rollups."""
        from sitewhere_tpu.instance import SiteWhereInstance

        instance = SiteWhereInstance(
            instance_id="telem-unit", enable_pipeline=True,
            max_devices=64, batch_size=16, measurement_slots=4)
        instance.start()
        try:
            gauges = instance.extra_gauges()
            assert "pipeline.batches_processed" in gauges
            assert any(k.startswith("hbm.table_bytes{") for k in gauges)
            assert "hbm.total_bytes" in gauges
            text = instance.prometheus_text()
            assert "swtpu_hbm_total_bytes" in text
            topo = instance.topology()
            assert topo["hbm"]["total_bytes"] == sum(
                topo["hbm"]["tables"].values())
        finally:
            instance.stop()
