"""Configuration metamodel (runtime/config_model.py) — model shape,
validation semantics, REST exposure.

Reference parity: sitewhere-configuration ConfigurationModelProvider +
per-service *ModelProvider/*Roles (the admin UI's config editor model).
"""

import json

import pytest

from sitewhere_tpu.runtime.config_model import (
    AttributeType, instance_configuration_model, validate_config)


class TestModelShape:
    def test_model_is_jsonable_and_complete(self):
        model = instance_configuration_model()
        json.dumps(model)  # fully serializable
        names = {e["name"] for e in model["elements"]}
        # every rebuilt subsystem self-describes (SURVEY.md §2.4 services)
        assert {"pipeline", "event_sources", "event_management",
                "device_state", "rules", "outbound_connectors",
                "command_delivery", "registration", "batch_operations",
                "schedules", "labels", "web", "analytics"} <= names
        assert "event-source-receiver" in model["roles"]
        assert "command-destination" in model["roles"]

    def test_attributes_carry_types_and_defaults(self):
        model = instance_configuration_model()
        pipeline = next(e for e in model["elements"]
                        if e["name"] == "pipeline")
        batch = next(a for a in pipeline["attributes"]
                     if a["name"] == "batch_size")
        assert batch["type"] == "integer" and batch["default"] == 8192
        geo = next(a for a in pipeline["attributes"]
                   if a["name"] == "geofence_impl")
        assert "pallas" in geo["choices"]
        # interpret mode is a test harness, never a deployment choice
        assert "pallas_interpret" not in geo["choices"]


class TestValidation:
    def test_valid_config_passes(self):
        cfg = {
            "pipeline": {"batch_size": 4096, "geofence_impl": "xla"},
            "event_sources": [{
                "source_id": "mqtt-1",
                "decoder": {"type": "wire"},
                "mqtt": [{"topic": "SW/#", "qos": 1}],
            }],
            "rules": [{"token": "r1", "type": "threshold",
                       "measurement_name": "temp", "operator": ">",
                       "threshold": 90.5}],
            "registration": {"allow_new_devices": True},
        }
        assert validate_config(cfg) == []

    def test_type_errors_reported(self):
        issues = validate_config({"pipeline": {"batch_size": "big"}})
        assert any(i.path == "pipeline.batch_size"
                   and "integer" in i.message for i in issues)
        # bool is not a valid integer even though bool subclasses int
        issues = validate_config({"pipeline": {"batch_size": True}})
        assert any("boolean" in i.message for i in issues)

    def test_unknown_keys_reported(self):
        issues = validate_config({"pipeline": {"batchsize": 1},
                                  "nonsense": {}})
        paths = {i.path for i in issues}
        assert "pipeline.batchsize" in paths and "nonsense" in paths

    def test_required_attribute_enforced(self):
        issues = validate_config(
            {"event_sources": [{"decoder": {"type": "wire"}}]})
        assert any(i.path == "event_sources[0].source_id" for i in issues)

    def test_required_child_enforced(self):
        issues = validate_config({"event_sources": [{"source_id": "s"}]})
        assert any(i.path == "event_sources[0].decoder" for i in issues)

    def test_choice_constraint(self):
        issues = validate_config(
            {"rules": [{"token": "r", "type": "quantum"}]})
        assert any("not one of" in i.message for i in issues)

    def test_multiple_expects_list(self):
        issues = validate_config({"rules": {"token": "r"}})
        assert any(i.path == "rules" and "list" in i.message for i in issues)

    def test_tenant_overlays_validate_recursively(self):
        issues = validate_config({
            "tenants": {"acme": {"pipeline": {"batch_size": "nope"}}}})
        assert any(i.path == "tenants.acme.pipeline.batch_size"
                   for i in issues)


class TestRestExposure:
    @pytest.fixture(scope="class")
    def client(self):
        from sitewhere_tpu.client.rest import SiteWhereClient
        from sitewhere_tpu.instance import SiteWhereInstance
        from sitewhere_tpu.web.server import RestServer
        instance = SiteWhereInstance(instance_id="cfgmodel")
        instance.start()
        rest = RestServer(instance, port=0)
        rest.start()
        c = SiteWhereClient(rest.base_url)
        c.authenticate("admin", "password")
        yield c
        rest.stop()
        instance.stop()

    def test_model_endpoint(self, client):
        model = client.get("/api/instance/configuration/model")
        assert model["modelVersion"] == 1
        assert any(e["name"] == "pipeline" for e in model["elements"])

    def test_validate_endpoint(self, client):
        ok = client.post("/api/instance/configuration/validate",
                         {"pipeline": {"batch_size": 128}})
        assert ok == {"valid": True, "issues": []}
        bad = client.post("/api/instance/configuration/validate",
                          {"pipeline": {"batch_size": "x"}})
        assert not bad["valid"] and bad["issues"][0]["path"] == \
            "pipeline.batch_size"


def test_nested_tenants_block_flagged():
    """A tenants block inside a tenant overlay is dead config and must be
    rejected (runtime/config.py only reads top-level tenants.<id>)."""
    issues = validate_config({
        "tenants": {"acme": {"tenants": {"acme": {
            "pipeline": {"batch_size": 1}}}}}})
    assert any(i.path == "tenants.acme.tenants" for i in issues)
