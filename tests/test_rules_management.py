"""Operator-facing management of the fused pipeline rules: REST CRUD,
boot-time config application, /admin surface, and cluster replication.

Reference: the reference configures ZoneTestRuleProcessor via per-tenant
spring config with live restart (service-rule-processing
processors/geospatial/ZoneTestRuleProcessor.java:33, wired by
spring/RuleProcessingParser.java); here the same rules are first-class
REST resources on the fused engine (pipeline/engine.py), applied from
config at boot (__main__._apply_rule_config) and gossiped across cluster
hosts (parallel/cluster.py RegistryGossip.register_rules_engine).
"""

import time

import msgpack
import pytest


@pytest.fixture(scope="module")
def rig():
    from sitewhere_tpu.client.rest import SiteWhereClient
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.web.server import RestServer

    instance = SiteWhereInstance(
        instance_id="rulestest", enable_pipeline=True,
        max_devices=256, batch_size=32, measurement_slots=4)
    instance.start()
    rest = RestServer(instance, port=0)
    rest.start()
    client = SiteWhereClient(rest.base_url)
    client.authenticate("admin", "password")
    yield instance, rest, client
    rest.stop()
    instance.stop()


class TestRuleRest:
    def test_crud_round_trip(self, rig):
        _instance, _rest, client = rig
        created = client.post("/api/rules", {
            "type": "threshold", "token": "crud-hot",
            "measurement_name": "temp", "operator": ">", "threshold": 75.0,
            "alert_type": "engine.overheat"})
        assert created["type"] == "threshold"
        assert created["threshold"] == 75.0
        listed = client.get("/api/rules")
        assert any(r["token"] == "crud-hot" for r in listed["threshold"])
        one = client.get("/api/rules/crud-hot")
        assert one["alert_type"] == "engine.overheat"
        gone = client.delete("/api/rules/crud-hot")
        assert gone["token"] == "crud-hot"
        listed = client.get("/api/rules")
        assert not any(r["token"] == "crud-hot"
                       for r in listed["threshold"])

    def test_validation_and_conflicts(self, rig):
        _instance, _rest, client = rig
        from sitewhere_tpu.client.rest import SiteWhereClientError

        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {"type": "threshold"})  # no token
        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {"type": "threshold", "token": "x",
                                       "operator": "~"})
        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {"type": "sorcery", "token": "x"})
        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {"type": "geofence", "token": "x"})
        client.post("/api/rules", {"type": "threshold", "token": "dup",
                                   "operator": ">", "threshold": 1.0})
        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {"type": "threshold", "token": "dup",
                                       "operator": "<", "threshold": 2.0})
        client.delete("/api/rules/dup")
        with pytest.raises(SiteWhereClientError):
            client.delete("/api/rules/dup")  # 404 after delete

    def test_enter_condition_over_rest(self, rig):
        _instance, _rest, client = rig
        from sitewhere_tpu.client.rest import SiteWhereClientError

        created = client.post("/api/rules", {
            "type": "geofence", "token": "toll", "zone_token": "seg-1",
            "condition": "enter", "alert_type": "toll.notification"})
        assert created["condition"] == "enter"
        assert client.get("/api/rules/toll")["condition"] == "enter"
        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {
                "type": "geofence", "token": "toll-2",
                "zone_token": "seg-1", "condition": "exit"})
        client.delete("/api/rules/toll")

    def test_admin_page_lists_rules_section(self, rig):
        _instance, rest, _client = rig
        import urllib.request

        with urllib.request.urlopen(f"{rest.base_url}/admin") as resp:
            page = resp.read().decode()
        assert "Pipeline rules" in page
        assert "/api/rules" in page

    def test_geofence_rule_posted_over_rest_fires_alert(self, rig):
        """The VERDICT scenario: serve, POST a geofence rule over REST,
        publish a location, see the alert."""
        instance, _rest, client = rig
        client.create_area({"token": "ra", "name": "Yard"})
        client.create_zone("ra", {
            "token": "rz", "name": "Fence",
            "bounds": [{"latitude": 0, "longitude": 0},
                       {"latitude": 0, "longitude": 1},
                       {"latitude": 1, "longitude": 1},
                       {"latitude": 1, "longitude": 0}]})
        client.create_device_type({"token": "rdt", "name": "T"})
        client.create_device({"token": "rdev", "device_type_token": "rdt"})
        client.create_assignment({"token": "ras", "device_token": "rdev"})
        client.post("/api/rules", {
            "type": "geofence", "token": "fence", "zone_token": "rz",
            "condition": "outside", "alert_type": "zone.breach"})

        from sitewhere_tpu.model.common import _asdict
        from sitewhere_tpu.model.event import (
            DeviceEventBatch, DeviceLocation)

        batch = DeviceEventBatch(
            device_token="rdev",
            locations=[DeviceLocation(latitude=5.0, longitude=5.0,
                                      event_date=int(time.time() * 1000))])
        instance.bus.publish(
            instance.naming.event_source_decoded_events("default"),
            b"rdev",
            msgpack.packb({"sourceId": "t", "deviceToken": "rdev",
                           "kind": "DeviceEventBatch",
                           "request": _asdict(batch), "metadata": {}},
                          use_bin_type=True))
        deadline = time.monotonic() + 90
        hits = {}
        while time.monotonic() < deadline:
            hits = client.get("/api/assignments/ras/alerts")
            if hits.get("numResults", 0):
                break
            time.sleep(0.2)
        assert hits.get("numResults", 0) >= 1
        assert hits["results"][0]["type"] == "zone.breach"


class TestRuleConfigBoot:
    def test_config_rules_installed_at_boot(self, tmp_path):
        import json

        from sitewhere_tpu.__main__ import (
            _apply_rule_config, _build_config)
        from sitewhere_tpu.instance import SiteWhereInstance

        config = {
            "instance": {"id": "cfgrules"},
            "pipeline": {"enabled": True},
            "rules": [
                {"type": "threshold", "token": "cfg-hot",
                 "measurement_name": "temp", "operator": ">",
                 "threshold": 60.0},
                {"type": "geofence", "token": "cfg-fence",
                 "zone_token": "z1", "condition": "inside"},
            ],
        }
        path = tmp_path / "sitewhere.json"
        path.write_text(json.dumps(config))
        cfg = _build_config(str(path))
        instance = SiteWhereInstance(
            instance_id="cfgrules", enable_pipeline=True,
            max_devices=64, batch_size=16, measurement_slots=4)
        instance.start()
        try:
            _apply_rule_config(instance, cfg)
            rules = instance.pipeline_engine.list_rules()
            assert [r.token for r in rules["threshold"]] == ["cfg-hot"]
            assert [r.token for r in rules["geofence"]] == ["cfg-fence"]
        finally:
            instance.stop()

    def test_bad_config_rule_raises(self, tmp_path):
        import json

        from sitewhere_tpu.__main__ import (
            _apply_rule_config, _build_config)
        from sitewhere_tpu.errors import SiteWhereError
        from sitewhere_tpu.instance import SiteWhereInstance

        path = tmp_path / "bad.json"
        path.write_text(json.dumps(
            {"rules": [{"type": "threshold", "token": "b",
                        "operator": "~"}]}))
        cfg = _build_config(str(path))
        instance = SiteWhereInstance(
            instance_id="badrules", enable_pipeline=True,
            max_devices=64, batch_size=16, measurement_slots=4)
        instance.start()
        try:
            with pytest.raises(SiteWhereError):
                _apply_rule_config(instance, cfg)
        finally:
            instance.stop()


class TestRuleReplication:
    def test_rule_mutations_gossip_between_hosts(self):
        from sitewhere_tpu.instance import SiteWhereInstance
        from sitewhere_tpu.parallel.cluster import RegistryGossip
        from sitewhere_tpu.pipeline.engine import ThresholdRule
        from sitewhere_tpu.runtime.bus import Record

        class Capture:
            def __init__(self):
                self.sent = []

            def publish(self, topic, key, value):
                self.sent.append(value)

        def host(iid):
            instance = SiteWhereInstance(
                instance_id=iid, enable_pipeline=True, max_devices=64,
                batch_size=16, measurement_slots=4)
            instance.start()
            cap = Capture()
            gossip = RegistryGossip(0, {1: cap}, instance, instance.naming)
            gossip.register_rules_engine(instance.pipeline_engine)
            return instance, gossip, cap

        ia, ga, cap_a = host("rule-gossip-a")
        ib, gb, _cap_b = host("rule-gossip-b")
        try:
            ia.pipeline_engine.add_threshold_rule(ThresholdRule(
                token="grule", measurement_name="m", operator=">",
                threshold=9.0))
            payloads = cap_a.drain() if hasattr(cap_a, "drain") \
                else cap_a.sent
            gb._handle([Record("t", 0, i, b"", p, 0)
                        for i, p in enumerate(payloads)])
            kind, rule = ib.pipeline_engine.get_rule("grule")
            assert kind == "threshold" and rule.threshold == 9.0
            # replace-on-add: redelivery is idempotent
            gb._handle([Record("t", 0, 0, b"", payloads[0], 0)])
            assert len(ib.pipeline_engine.list_rules()["threshold"]) == 1
            # removal replicates
            cap_a.sent.clear()
            ia.pipeline_engine.remove_rule("grule")
            gb._handle([Record("t", 0, 0, b"", cap_a.sent[0], 0)])
            assert ib.pipeline_engine.get_rule("grule") == (None, None)
        finally:
            ia.stop()
            ib.stop()


class TestRuleCheckpoint:
    def test_rest_added_rules_survive_checkpoint_restore(self, tmp_path):
        from sitewhere_tpu.persist.checkpoint import PipelineCheckpointer
        from sitewhere_tpu.pipeline import PipelineEngine
        from sitewhere_tpu.pipeline.engine import GeofenceRule, ThresholdRule
        from sitewhere_tpu.registry import RegistryTensors

        def build():
            engine = PipelineEngine(RegistryTensors(64, 4, 4),
                                    batch_size=16, measurement_slots=4)
            engine.start()
            return engine

        src = build()
        src.add_threshold_rule(ThresholdRule(
            token="ck-hot", measurement_name="m", operator=">",
            threshold=5.0))
        src.add_geofence_rule(GeofenceRule(token="ck-fence",
                                           zone_token="z"))
        ckpt = PipelineCheckpointer(str(tmp_path))
        ckpt.save(src)

        dst = build()
        ckpt.restore(dst)
        kind, rule = dst.get_rule("ck-hot")
        assert kind == "threshold" and rule.threshold == 5.0
        kind, rule = dst.get_rule("ck-fence")
        assert kind == "geofence" and rule.zone_token == "z"


class TestRuleEngineContract:
    def test_typed_validation_rejects_bad_values(self):
        from sitewhere_tpu.errors import SiteWhereError
        from sitewhere_tpu.pipeline.engine import rule_from_dict

        with pytest.raises(SiteWhereError):
            rule_from_dict({"type": "threshold", "token": "t",
                            "threshold": "abc"})
        with pytest.raises(SiteWhereError):
            rule_from_dict({"type": "threshold", "token": "t",
                            "alert_level": "NOT_A_LEVEL"})
        with pytest.raises(SiteWhereError):
            rule_from_dict({"type": "threshold", "token": "t",
                            "measurement_name": 7})
        # coercions that SHOULD work: numeric strings, level names
        _, rule = rule_from_dict({"type": "threshold", "token": "t",
                                  "threshold": "5.5",
                                  "alert_level": "CRITICAL"})
        assert rule.threshold == 5.5
        assert rule.alert_level.name == "CRITICAL"

    def test_upsert_replaces_create_raises(self):
        from sitewhere_tpu.errors import DuplicateTokenError
        from sitewhere_tpu.pipeline import PipelineEngine
        from sitewhere_tpu.pipeline.engine import ThresholdRule
        from sitewhere_tpu.registry import RegistryTensors

        engine = PipelineEngine(RegistryTensors(64, 4, 4), batch_size=16,
                                measurement_slots=4)
        engine.start()
        engine.create_rule("threshold", ThresholdRule(token="u",
                                                      threshold=1.0))
        with pytest.raises(DuplicateTokenError):
            engine.create_rule("threshold", ThresholdRule(token="u"))
        engine.upsert_rule("threshold", ThresholdRule(token="u",
                                                      threshold=2.0))
        rules = engine.list_rules()["threshold"]
        assert len(rules) == 1 and rules[0].threshold == 2.0


class TestScriptedRules:
    def test_scripted_rule_over_rest_fires(self, rig):
        """POST a scripted rule (the Groovy-processor role): its script
        sees every enriched event; deleting the rule detaches it live."""
        instance, _rest, client = rig
        from sitewhere_tpu.runtime.scripts import GLOBAL_SCOPE

        hits = []
        instance.script_manager.create_script(
            GLOBAL_SCOPE, "tag-hot", "def process(context, event):\n"
            "    _HITS.append((context.device_token,\n"
            "                  type(event).__name__))\n",
            activate=True)
        # inject the capture list into the active namespace (tests only)
        instance.script_manager._namespaces[
            (GLOBAL_SCOPE, "tag-hot")]["_HITS"] = hits

        client.create_device_type({"token": "sdt", "name": "S"})
        client.create_device({"token": "sdev",
                              "device_type_token": "sdt"})
        client.create_assignment({"token": "sas", "device_token": "sdev"})
        client.post("/api/rules", {"type": "scripted",
                                   "token": "tagger",
                                   "script": "tag-hot"})
        listed = client.get("/api/rules")
        assert any(r["token"] == "tagger" for r in listed["scripted"])
        assert client.get("/api/rules/tagger")["type"] == "scripted"

        instance.bus.publish(
            instance.naming.event_source_decoded_events("default"),
            b"sdev",
            msgpack.packb({"sourceId": "t", "deviceToken": "sdev",
                           "kind": "DeviceEventBatch",
                           "request": _asdict_event_batch(),
                           "metadata": {}}, use_bin_type=True))
        # a fresh consumer group replays the enriched topic from the
        # beginning (at-least-once), so earlier rig events arrive too —
        # wait for OUR device's hit specifically
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline \
                and not any(h[0] == "sdev" for h in hits):
            time.sleep(0.1)
        assert ("sdev", "DeviceMeasurement") in hits

        gone = client.delete("/api/rules/tagger")
        assert gone["type"] == "scripted"
        listed = client.get("/api/rules")
        assert not any(r["token"] == "tagger"
                       for r in listed["scripted"])

    def test_scripted_contract_hardening(self, rig):
        """Shared token namespace with fused rules, install-time entry
        validation, and script-id audit in GET/list."""
        instance, _rest, client = rig
        from sitewhere_tpu.client.rest import SiteWhereClientError
        from sitewhere_tpu.runtime.scripts import GLOBAL_SCOPE

        instance.script_manager.create_script(
            GLOBAL_SCOPE, "noop-rule",
            "def process(context, event):\n    pass\n", activate=True)
        instance.script_manager.create_script(
            GLOBAL_SCOPE, "no-entry",
            "def other(context, event):\n    pass\n", activate=True)
        # entry validation at install time, not silently-dead at runtime
        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {"type": "scripted", "token": "bad",
                                       "script": "no-entry"})
        # fused + scripted share one token namespace, both directions
        client.post("/api/rules", {"type": "threshold", "token": "ns1",
                                   "operator": ">", "threshold": 1.0})
        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {"type": "scripted", "token": "ns1",
                                       "script": "noop-rule"})
        client.post("/api/rules", {"type": "scripted", "token": "ns2",
                                   "script": "noop-rule"})
        with pytest.raises(SiteWhereClientError):
            client.post("/api/rules", {"type": "threshold", "token": "ns2",
                                       "operator": ">", "threshold": 2.0})
        # audit: GET and list report the backing script
        got = client.get("/api/rules/ns2")
        assert got["script"] == "noop-rule"
        listed = client.get("/api/rules")["scripted"]
        assert any(r["token"] == "ns2" and r["script"] == "noop-rule"
                   for r in listed)
        client.delete("/api/rules/ns1")
        client.delete("/api/rules/ns2")


def _asdict_event_batch():
    from sitewhere_tpu.model.common import _asdict
    from sitewhere_tpu.model.event import (
        DeviceEventBatch, DeviceMeasurement)

    return _asdict(DeviceEventBatch(
        device_token="sdev",
        measurements=[DeviceMeasurement(name="s1", value=7.0,
                                        event_date=int(time.time() * 1000))]))

