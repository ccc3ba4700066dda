"""Quickstart: registry -> fused TPU pipeline -> rule alerts -> device state.

Run: python examples/01_quickstart.py
(runs on CPU by default — see the preamble; first compile takes ~30 s on one core)
"""

# Demos run on the CPU whatever JAX_PLATFORMS says, so they behave the same
# on any host. To run one on a TPU, delete these two lines.
import jax

jax.config.update("jax_platforms", "cpu")


import numpy as np

from sitewhere_tpu.model import (
    AlertLevel, Area, Device, DeviceAssignment, DeviceMeasurement,
    DeviceLocation, DeviceType, Zone)
from sitewhere_tpu.model.common import Location
from sitewhere_tpu.pipeline import PipelineEngine
from sitewhere_tpu.pipeline.engine import GeofenceRule, ThresholdRule
from sitewhere_tpu.registry import DeviceManagement, RegistryTensors


def main():
    # -- control plane: register a device type, area, zone, device ---------
    dm = DeviceManagement()
    sensor = dm.create_device_type(DeviceType(token="sensor",
                                              name="Temperature sensor"))
    area = dm.create_area(Area(token="plant-1", name="Plant 1"))
    dm.create_zone(Zone(token="safety-zone", area_id=area.id, bounds=[
        Location(0.0, 0.0), Location(0.0, 10.0), Location(10.0, 10.0),
        Location(10.0, 0.0)]))
    device = dm.create_device(Device(token="boiler-7",
                                     device_type_id=sensor.id))
    dm.create_device_assignment(DeviceAssignment(token="boiler-7-active",
                                                 device_id=device.id,
                                                 area_id=area.id))

    # -- hot path: registry mirror + fused engine + rules ------------------
    tensors = RegistryTensors(max_devices=1024, max_zones=16,
                              max_zone_vertices=16)
    tensors.attach(dm, "tenant-1")
    engine = PipelineEngine(tensors, batch_size=1024)
    engine.start()
    engine.add_threshold_rule(ThresholdRule(
        token="overheat", measurement_name="temp", operator=">",
        threshold=90.0, alert_level=AlertLevel.CRITICAL))
    engine.add_geofence_rule(GeofenceRule(
        token="escaped", zone_token="safety-zone", condition="outside"))

    # -- submit a batch of events ------------------------------------------
    events = [
        DeviceMeasurement(name="temp", value=85.0),
        DeviceMeasurement(name="temp", value=97.5),          # fires overheat
        DeviceLocation(latitude=5.0, longitude=5.0),         # inside zone
        DeviceLocation(latitude=55.0, longitude=55.0),       # fires escaped
    ]
    batch = engine.packer.pack_events(events, ["boiler-7"] * len(events))[0]
    outputs = engine.submit(batch)
    print(f"processed: {int(outputs.processed)}  "
          f"alerts fired: {int(outputs.alerts)}")
    for alert in engine.materialize_alerts(batch, outputs):
        print(f"  ALERT {alert.type} level={alert.level.name} "
              f"device={alert.device_id}")

    state = engine.get_device_state("boiler-7")
    print("last temp:", state.last_measurements["temp"][1])
    print("last location:", state.last_location)


if __name__ == "__main__":
    main()
