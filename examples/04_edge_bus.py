"""Edge -> TPU-host event flow over the networked bus.

A "TPU host" process serves its event bus on TCP (BusServer); an "edge"
process — here a spawned subprocess standing in for a gateway box —
publishes device events with BusClient; the host consumes them with
committed-offset at-least-once semantics and feeds the inbound pipeline.

Run: python examples/04_edge_bus.py   (CPU by default — see preamble)
"""

# Demos run on the CPU whatever JAX_PLATFORMS says, so they behave the same
# on any host. To run one on a TPU, delete these two lines.
import jax

jax.config.update("jax_platforms", "cpu")


import subprocess
import sys
import time

from sitewhere_tpu.model import Device, DeviceAssignment, DeviceType
from sitewhere_tpu.persist.event_management import (
    DeviceEventManagement, EventIndex)
from sitewhere_tpu.persist.eventlog import ColumnarEventLog
from sitewhere_tpu.registry import DeviceManagement
from sitewhere_tpu.runtime.bus import EventBus, TopicNaming
from sitewhere_tpu.runtime.busnet import BusServer

EDGE = """
import json, sys
from sitewhere_tpu.runtime.busnet import BusClient

port = int(sys.argv[1])
client = BusClient("127.0.0.1", port)
records = []
for i in range(50):
    payload = json.dumps({"deviceToken": "edge-dev",
                          "type": "DeviceMeasurement",
                          "request": {"name": "temp", "value": 20.0 + i}})
    records.append((b"edge-dev", payload.encode()))
client.publish_batch("swtpu.default.tenant.default.event-source-decoded-events",
                     records)
print("edge published", len(records))
"""


def main():
    dm = DeviceManagement()
    dtype = dm.create_device_type(DeviceType(token="sensor"))
    dev = dm.create_device(Device(token="edge-dev", device_type_id=dtype.id))
    dm.create_device_assignment(DeviceAssignment(token="edge-as",
                                                 device_id=dev.id))
    bus = EventBus()
    naming = TopicNaming()
    log = ColumnarEventLog()
    events = DeviceEventManagement(log, dm)

    # host side: consume the decoded-events topic that edges publish into
    from sitewhere_tpu.model.event import DeviceMeasurement
    import json

    def handle(batch):
        for record in batch:
            doc = json.loads(record.value)
            req = doc["request"]
            events.add_measurements("edge-as", DeviceMeasurement(
                name=req["name"], value=float(req["value"])))

    from sitewhere_tpu.runtime.bus import ConsumerHost
    host = ConsumerHost(bus, naming.event_source_decoded_events("default"),
                        "tpu-host", handle, poll_timeout_s=0.1)
    host.start()

    server = BusServer(bus)
    server.start()
    print(f"bus server on 127.0.0.1:{server.port}")

    edge = subprocess.run([sys.executable, "-c", EDGE, str(server.port)],
                          capture_output=True, text=True, timeout=60)
    print(edge.stdout.strip())
    assert edge.returncode == 0, edge.stderr

    deadline = time.time() + 10
    while time.time() < deadline:
        found = events.list_measurements(EventIndex.ASSIGNMENT, "edge-as")
        if found.num_results == 50:
            break
        time.sleep(0.05)
    print(f"host persisted {found.num_results} events "
          f"(last value {found.results[0].value})")
    assert found.num_results == 50
    host.stop()
    server.stop()
    print("OK")


if __name__ == "__main__":
    main()
