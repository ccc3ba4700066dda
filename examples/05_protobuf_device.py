"""A reference-SDK device session: sitewhere.proto over MQTT, both ways.

The device speaks the reference's protobuf wire format
(sitewhere-communication sitewhere.proto): it registers, receives the
protobuf RegistrationAck, streams measurements, and receives a custom
command encoded against its device type's dynamic schema.

Run: python examples/05_protobuf_device.py   (CPU by default — see preamble)
"""

# Demos run on the CPU whatever JAX_PLATFORMS says, so they behave the same
# on any host. To run one on a TPU, delete these two lines.
import jax

jax.config.update("jax_platforms", "cpu")


import time

from sitewhere_tpu.commands.encoding import (
    CommandExecution, coerce_parameters)
from sitewhere_tpu.model import DeviceType
from sitewhere_tpu.model.device import CommandParameter, ParameterType
from sitewhere_tpu.model.device import DeviceCommand
from sitewhere_tpu.model.event import DeviceCommandInvocation
from sitewhere_tpu.persist.event_management import (
    DeviceEventManagement, EventIndex)
from sitewhere_tpu.persist.eventlog import ColumnarEventLog
from sitewhere_tpu.pipeline.inbound import InboundProcessingService
from sitewhere_tpu.registration import RegistrationManager
from sitewhere_tpu.registry import DeviceManagement
from sitewhere_tpu.runtime.bus import EventBus, TopicNaming
from sitewhere_tpu.sources.manager import InboundEventSource
from sitewhere_tpu.sources.receivers import EventLoopThread, MqttEventReceiver
from sitewhere_tpu.transport import protobuf_compat as pc
from sitewhere_tpu.transport.mqtt import MqttBroker, MqttClient


def main():
    dm = DeviceManagement()
    dtype = dm.create_device_type(DeviceType(token="thermostat"))
    dm.create_device_command(DeviceCommand(
        token="sp", device_type_id=dtype.id, name="setPoint",
        parameters=[CommandParameter("celsius", ParameterType.DOUBLE),
                    CommandParameter("hold", ParameterType.BOOL)]))

    bus, naming = EventBus(), TopicNaming()
    log = ColumnarEventLog()
    events = DeviceEventManagement(log, dm)
    inbound = InboundProcessingService(bus, dm, events=events)
    inbound.start()

    acks = {}

    class CaptureDelivery:
        def send_system_command(self, token, command):
            acks[token] = command

    registration = RegistrationManager(
        bus, dm, command_delivery=CaptureDelivery(),
        default_device_type_token="thermostat")
    registration.start()

    loop = EventLoopThread.shared()
    broker = MqttBroker()
    loop.run(broker.start())
    source = InboundEventSource(
        "proto", pc.ProtobufCompatDecoder(),
        [MqttEventReceiver("127.0.0.1", broker.port,
                           topic="SiteWhere/input/protobuf")],
        bus, naming)
    source.start()

    # -- the device registers and streams, in reference protobuf bytes ----
    device_client = MqttClient("127.0.0.1", broker.port, client_id="hw-42")
    loop.run(device_client.connect())
    loop.run(device_client.publish("SiteWhere/input/protobuf",
                                   pc.encode_registration("hw-42",
                                                          "thermostat")))
    deadline = time.time() + 10
    while time.time() < deadline and dm.get_device_by_token("hw-42") is None:
        time.sleep(0.05)
    device = dm.get_device_by_token("hw-42")
    assert device is not None
    print("registered:", device.token)

    ack = pc.ProtobufSpecCommandEncoder(dm).encode_system(
        acks["hw-42"], device)
    command_id, _, fields = pc.decode_device_payload(ack)
    print("ack:", command_id == pc.ACK_REGISTRATION,
          "state:", pc.RegistrationAckState(fields.int(1)).name)

    # registration auto-assigned the device; stream against that assignment
    assignment = dm.get_active_assignment(device.id)
    loop.run(device_client.publish(
        "SiteWhere/input/protobuf",
        pc.encode_measurements("hw-42", [("temp", 21.5), ("rh", 0.6)])))
    deadline = time.time() + 10
    while time.time() < deadline:
        found = events.list_measurements(EventIndex.ASSIGNMENT,
                                         assignment.token)
        if found.num_results == 2:
            break
        time.sleep(0.05)
    print("measurements persisted:", found.num_results)
    assert found.num_results == 2

    # -- cloud -> device: command per the device type's dynamic schema ----
    command = dm.list_device_commands("thermostat").results[0]
    execution = CommandExecution(
        invocation=DeviceCommandInvocation(id="inv-1"), command=command,
        parameters=coerce_parameters(command,
                                     {"celsius": 22.5, "hold": True}))
    payload = pc.ProtobufSpecCommandEncoder(dm).encode(execution, device,
                                                       None)
    number, originator, fields = pc.decode_device_payload(payload)
    print(f"device decoded command #{number} from {originator}: "
          f"celsius={fields.double(1)} hold={fields.bool(2)}")

    loop.run(device_client.disconnect())
    source.stop()
    inbound.stop()
    loop.run(broker.stop())
    print("OK")


if __name__ == "__main__":
    main()
