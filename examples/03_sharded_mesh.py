"""The fused pipeline over a device mesh (SPMD multi-chip).

On real hardware this runs over the pod's chips; to try it on a laptop use
a virtual mesh:

    python examples/03_sharded_mesh.py   # virtual 8-way CPU mesh by default
"""

# Demos run on the CPU whatever JAX_PLATFORMS says, so they behave the same
# on any host. To run one on a TPU, delete this preamble.
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform"
                                  "_device_count=8").strip()
import jax

jax.config.update("jax_platforms", "cpu")


import numpy as np

from sitewhere_tpu.model import (
    AlertLevel, Device, DeviceAssignment, DeviceType)
from sitewhere_tpu.model.event import DeviceEventType
from sitewhere_tpu.parallel import ShardedPipelineEngine, make_mesh
from sitewhere_tpu.pipeline.engine import ThresholdRule
from sitewhere_tpu.registry import DeviceManagement, RegistryTensors


def main():
    import jax
    n = min(8, max(len(jax.devices()), len(jax.devices("cpu"))))
    devs = jax.devices() if len(jax.devices()) >= n else jax.devices("cpu")
    mesh = make_mesh(n, devices=devs)
    print(f"mesh: {n} x {devs[0].platform}")

    dm = DeviceManagement()
    sensor = dm.create_device_type(DeviceType(token="sensor"))
    tensors = RegistryTensors(max_devices=1024, max_zones=8,
                              max_zone_vertices=8)
    tensors.attach(dm, "tenant-1")
    for i in range(100):
        d = dm.create_device(Device(token=f"dev-{i}",
                                    device_type_id=sensor.id))
        dm.create_device_assignment(DeviceAssignment(token=f"as-{i}",
                                                     device_id=d.id))

    engine = ShardedPipelineEngine(tensors, mesh=mesh, per_shard_batch=128)
    engine.packer.measurements.intern("temp")
    engine.add_threshold_rule(ThresholdRule(
        token="hot", measurement_name="temp", operator=">", threshold=90.0,
        alert_level=AlertLevel.CRITICAL))
    engine.start()

    # a host batch with GLOBAL device indices; the router sends each event
    # to the shard owning its device (d % n)
    rng = np.random.default_rng(0)
    B = 512
    idx = engine.packer.devices.lookup_batch(
        [f"dev-{int(i)}" for i in rng.integers(0, 100, B)])
    batch = engine.packer.pack_columns(
        idx.astype(np.int32),
        np.full(B, int(DeviceEventType.MEASUREMENT), np.int32),
        np.full(B, engine.packer.epoch_base_ms, np.int64),
        mm_idx=np.full(B, 1, np.int32),
        value=rng.uniform(50, 100, B).astype(np.float32))
    routed, outputs = engine.submit(batch)
    print(f"processed {int(outputs.processed)} events across {n} shards; "
          f"{int(outputs.alerts)} alerts (psum over ICI)")
    alerts = engine.materialize_alerts(routed, outputs, max_alerts=5)
    for alert in alerts[:3]:
        print("  ALERT", alert.device_id, alert.type)


if __name__ == "__main__":
    main()
