"""Operate the fused rule engine entirely over REST.

Boot a full instance + REST gateway, provision an area/zone/device over
the API, POST a geofence rule and a threshold rule, publish events
through the ingest plane, and read the fired alerts back — the
operator's whole steering wheel for the 10M+ ev/s rule engine, no
Python engine access needed (reference: ZoneTestRuleProcessor wired by
spring config; here live CRUD at /api/rules).

Also shows the observability surface: Prometheus /metrics and the rule
panel data the /admin console renders.

Run: python examples/08_rules_over_rest.py   (CPU by default — see preamble)
"""

# Demos run on the CPU whatever JAX_PLATFORMS says, so they behave the same
# on any host. To run one on a TPU, delete these two lines.
import jax

jax.config.update("jax_platforms", "cpu")


import time
import urllib.request

import msgpack

from sitewhere_tpu.client.rest import SiteWhereClient
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.model.common import _asdict
from sitewhere_tpu.model.event import (
    DeviceEventBatch, DeviceLocation, DeviceMeasurement)
from sitewhere_tpu.web.server import RestServer


def main() -> None:
    instance = SiteWhereInstance(
        instance_id="rules-demo", enable_pipeline=True,
        max_devices=256, batch_size=32, measurement_slots=4)
    instance.start()
    rest = RestServer(instance, port=0)
    rest.start()
    client = SiteWhereClient(rest.base_url)
    client.authenticate("admin", "password")

    # provision over REST: area -> zone -> device type -> device ->
    # assignment (everything an edge fleet needs)
    client.create_area({"token": "yard", "name": "Storage yard"})
    client.create_zone("yard", {
        "token": "fence", "name": "Perimeter",
        "bounds": [{"latitude": 0, "longitude": 0},
                   {"latitude": 0, "longitude": 1},
                   {"latitude": 1, "longitude": 1},
                   {"latitude": 1, "longitude": 0}]})
    client.create_device_type({"token": "tracker", "name": "Tracker"})
    client.create_device({"token": "truck-1",
                          "device_type_token": "tracker"})
    client.create_assignment({"token": "truck-1-a",
                              "device_token": "truck-1"})

    # the steering wheel: rules as REST resources
    client.post("/api/rules", {
        "type": "geofence", "token": "perimeter-breach",
        "zone_token": "fence", "condition": "outside",
        "alert_type": "zone.breach", "alert_level": 3})
    client.post("/api/rules", {
        "type": "threshold", "token": "engine-hot",
        "measurement_name": "engine_temp", "operator": ">",
        "threshold": 95.0, "alert_type": "engine.overheat"})
    rules = client.get("/api/rules")
    print(f"rules installed: "
          f"{[r['token'] for r in rules['geofence'] + rules['threshold']]}")

    # events through the ingest plane (what event sources publish)
    def publish(request_events):
        batch = DeviceEventBatch(device_token="truck-1", **request_events)
        instance.bus.publish(
            instance.naming.event_source_decoded_events("default"),
            b"truck-1",
            msgpack.packb({"sourceId": "demo", "deviceToken": "truck-1",
                           "kind": "DeviceEventBatch",
                           "request": _asdict(batch), "metadata": {}},
                          use_bin_type=True))

    now = int(time.time() * 1000)
    publish({"locations": [DeviceLocation(latitude=5.0, longitude=5.0,
                                          event_date=now)]})
    publish({"measurements": [DeviceMeasurement(name="engine_temp",
                                                value=112.0,
                                                event_date=now + 1)]})

    deadline = time.monotonic() + 60
    alerts = {}
    while time.monotonic() < deadline:
        alerts = client.get("/api/assignments/truck-1-a/alerts")
        if alerts.get("numResults", 0) >= 2:
            break
        time.sleep(0.2)
    kinds = sorted(a["type"] for a in alerts.get("results", []))
    print(f"alerts fired: {kinds}")
    assert "zone.breach" in kinds and "engine.overheat" in kinds

    # observability: the same counters Prometheus scrapes
    with urllib.request.urlopen(f"{rest.base_url}/metrics") as resp:
        scraped = resp.read().decode()
    batches = [line for line in scraped.splitlines()
               if line.startswith("swtpu_pipeline_batches_processed")]
    print(f"prometheus: {batches[0]}")

    client.delete("/api/rules/engine-hot")
    print(f"rules after delete: "
          f"{[r['token'] for r in client.get('/api/rules')['threshold']]}")

    rest.stop()
    instance.stop()
    print("OK")


if __name__ == "__main__":
    main()
