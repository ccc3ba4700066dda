"""Two-host cluster: the deployable multi-host instance, self-contained.

Spawns two OS processes that join one `jax.distributed` mesh (2 virtual
CPU devices each -> 4 shards) and each boot a full SiteWhereInstance +
ClusterService (parallel/cluster.py): lockstep step loop, busnet edges,
ownership-routed inbound, heartbeats/topology. Host 0 then publishes an
event TO ITS OWN bus edge for a device OWNED BY HOST 1 — the record
forwards to its owner, which persists it, folds it into device state,
and fires the threshold alert. Both hosts print their view.

This mirrors `python -m sitewhere_tpu serve --cluster-*` (see
docs/OPERATIONS.md deployment shape 4) without needing two terminals.

Run: python examples/07_cluster_two_hosts.py   (CPU works; ~1 min)
"""

import os
import socket
import subprocess
import sys

HOST = r"""
import os, sys, time
pid = int(sys.argv[1]); coord = sys.argv[2]
bus0, bus1 = int(sys.argv[3]), int(sys.argv[4])
# both hosts of this demo run on two virtual CPU devices each: on a TPU
# host a child without JAX_PLATFORMS=cpu would take the chip instead
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(coordinator_address=coord, num_processes=2,
                           process_id=pid)
import msgpack
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.model import Device, DeviceAssignment, DeviceType
from sitewhere_tpu.model.common import _asdict
from sitewhere_tpu.model.event import DeviceEventBatch, DeviceMeasurement
from sitewhere_tpu.parallel.cluster import ClusterService
from sitewhere_tpu.parallel.distributed import make_global_mesh
from sitewhere_tpu.pipeline.engine import ThresholdRule

instance = SiteWhereInstance(
    instance_id="cluster-demo", enable_pipeline=True,
    mesh=make_global_mesh(), max_devices=64, batch_size=16,
    measurement_slots=4)
cluster = ClusterService(
    instance, pid, 2,
    peer_bus_addrs={0: ("127.0.0.1", bus0), 1: ("127.0.0.1", bus1)},
    bus_port=bus0 if pid == 0 else bus1, heartbeat_s=0.3)
cluster.start()
engine = instance.pipeline_engine

# identical provisioning on both hosts (a real deployment provisions
# every host from the same templates/bootstrap)
te = instance.get_tenant_engine("default")
dt = te.registry.create_device_type(DeviceType(token="sensor"))
for i in range(8):
    d = te.registry.create_device(Device(token=f"dev-{i}",
                                         device_type_id=dt.id))
    te.registry.create_device_assignment(
        DeviceAssignment(token=f"as-{i}", device_id=d.id))
engine.packer.measurements.intern("temp")
engine.add_threshold_rule(ThresholdRule(
    token="hot", measurement_name="temp", operator=">", threshold=50.0))
time.sleep(2.0)  # let both hosts finish provisioning

tokens = [f"dev-{i}" for i in range(8)]
mine = [t for t in tokens if cluster.owner_process(t) == pid]
theirs = [t for t in tokens if cluster.owner_process(t) != pid]
print(f"[host {pid}] owns {mine}", flush=True)

if pid == 0:
    target = theirs[0]  # a device the PEER owns, published to MY edge
    instance.bus.publish(
        instance.naming.event_source_decoded_events("default"),
        target.encode(),
        msgpack.packb({
            "sourceId": "demo", "deviceToken": target,
            "kind": "DeviceEventBatch",
            "request": _asdict(DeviceEventBatch(
                device_token=target,
                measurements=[DeviceMeasurement(
                    name="temp", value=99.0,
                    event_date=int(time.time() * 1000))])),
            "metadata": {}}, use_bin_type=True))
    print(f"[host 0] published temp=99.0 for {target} "
          f"(owned by host 1) to host 0's own edge", flush=True)

if pid == 1:
    expect = mine[0]
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        state = engine.get_device_state(expect)
        if state is not None and "temp" in state.last_measurements:
            print(f"[host 1] {expect} state: "
                  f"temp={state.last_measurements['temp'][1]} "
                  f"(forwarded from host 0, folded here)", flush=True)
            break
        time.sleep(0.2)
    else:
        raise SystemExit("event never arrived")

time.sleep(1.0)
topo = instance.topology()
live = {p: ("live" if not s["stale"] else "STALE")
        for p, s in topo["processes"].items()}
print(f"[host {pid}] topology processes: {live}", flush=True)
cluster.stop()
print(f"[host {pid}] clean coordinated shutdown", flush=True)
"""


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    coord, bus0, bus1 = free_port(), free_port(), free_port()
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, "-c", HOST, str(pid), f"127.0.0.1:{coord}",
         str(bus0), str(bus1)], env=env) for pid in range(2)]
    rc = [p.wait(timeout=300) for p in procs]
    if any(rc):
        raise SystemExit(f"host exit codes {rc}")
    print("cluster demo complete")


if __name__ == "__main__":
    main()
