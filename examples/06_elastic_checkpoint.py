"""Elastic checkpoint/restore: scale the mesh across a restart.

Process events on a 4-shard mesh, checkpoint, "crash", then restore the
SAME snapshot onto an 8-shard mesh and keep processing — device state
(last values, presence, counters) survives the topology change because
checkpoints store a canonical flat device-major layout
(persist/checkpoint.py; parallel/engine.py canonical_state).

Run (CPU, virtual devices):
    # runs on a virtual 8-way CPU mesh by default (see the preamble):
        python examples/06_elastic_checkpoint.py
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


import tempfile

from sitewhere_tpu.model import Device, DeviceAssignment, DeviceType
from sitewhere_tpu.model.event import DeviceMeasurement
from sitewhere_tpu.parallel import ShardedPipelineEngine, make_mesh
from sitewhere_tpu.persist.checkpoint import PipelineCheckpointer
from sitewhere_tpu.pipeline.engine import ThresholdRule
from sitewhere_tpu.registry import DeviceManagement, RegistryTensors

N_DEVICES = 24


def build_world():
    dm = DeviceManagement()
    dtype = dm.create_device_type(DeviceType(token="sensor"))
    tensors = RegistryTensors(max_devices=64, max_zones=4,
                              max_zone_vertices=4)
    for i in range(N_DEVICES):
        device = dm.create_device(Device(token=f"d{i}",
                                         device_type_id=dtype.id))
        dm.create_device_assignment(DeviceAssignment(token=f"a{i}",
                                                     device_id=device.id))
    tensors.attach(dm, "tenant")
    return tensors


def build_engine(shards: int):
    engine = ShardedPipelineEngine(build_world(), mesh=make_mesh(shards),
                                   per_shard_batch=64 // shards)
    engine.start()
    engine.packer.measurements.intern("temp")
    engine.add_threshold_rule(ThresholdRule(
        token="hot", measurement_name="temp", operator=">", threshold=90.0))
    return engine


def main() -> None:
    # ---- phase 1: 4 shards ------------------------------------------------
    engine = build_engine(shards=4)
    batch = engine.packer.pack_events(
        [DeviceMeasurement(name="temp", value=float(i))
         for i in range(N_DEVICES)],
        [f"d{i}" for i in range(N_DEVICES)])[0]
    engine.submit(batch)
    print(f"4-shard engine processed {N_DEVICES} events; "
          f"d17 temp = {engine.get_device_state('d17').last_measurements['temp'][1]}")

    ckpt = PipelineCheckpointer(tempfile.mkdtemp(prefix="swtpu-ckpt-"))
    path = ckpt.save(engine)
    print(f"checkpoint written: {path}")
    del engine  # simulated crash

    # ---- phase 2: restore onto 8 shards ----------------------------------
    engine = build_engine(shards=8)
    ckpt.restore(engine)
    state = engine.get_device_state("d17")
    print(f"8-shard engine restored; d17 temp = "
          f"{state.last_measurements['temp'][1]}")

    routed, outputs = engine.submit(engine.packer.pack_events(
        [DeviceMeasurement(name="temp", value=99.0)], ["d17"])[0])
    alerts = engine.materialize_alerts(routed, outputs)
    print(f"post-restore step: processed={int(outputs.processed)}, "
          f"alerts={[a.device_id for a in alerts]}")


if __name__ == "__main__":
    main()
