"""Domain model unit tests: paging, entity basics, event types."""

from sitewhere_tpu.model import (
    Device, DeviceAlert, DeviceAssignment, DeviceEventType, DeviceLocation,
    DeviceMeasurement, DeviceType, SearchCriteria, Zone,
)
import dataclasses
import enum
from typing import NamedTuple

import pytest

from sitewhere_tpu.model.common import Location, Pager, _asdict, page


def test_pager_pages_and_counts():
    criteria = SearchCriteria(page_number=2, page_size=10)
    results = page(list(range(35)), criteria)
    assert results.num_results == 35
    assert results.results == list(range(10, 20))


def test_pager_incremental_matches_page():
    criteria = SearchCriteria(page_number=1, page_size=3)
    pager = Pager(criteria)
    for item in "abcdefg":
        pager.process(item)
    out = pager.results()
    assert out.num_results == 7
    assert out.results == ["a", "b", "c"]


def test_entity_identity_and_touch():
    device = Device(token="dev-1", device_type_id="t1")
    assert device.id and device.created_date > 0
    assert device.updated_date is None
    device.touch("admin")
    assert device.updated_date is not None
    assert device.updated_by == "admin"


def test_event_types_are_stable_ints():
    # These codes are baked into packed tensors; they must never change.
    assert DeviceEventType.MEASUREMENT == 0
    assert DeviceEventType.LOCATION == 1
    assert DeviceEventType.ALERT == 2
    assert DeviceMeasurement(name="temp", value=1.5).event_type == 0
    assert DeviceLocation(latitude=1.0).event_type == 1
    assert DeviceAlert(type="x").event_type == 2


def test_event_to_dict_round_trip():
    m = DeviceMeasurement(name="temp", value=21.5, device_id="d1")
    d = m.to_dict()
    assert d["name"] == "temp"
    assert d["value"] == 21.5
    assert d["eventType"] == "MEASUREMENT"


def test_zone_holds_polygon():
    zone = Zone(token="z1", bounds=[Location(0, 0), Location(0, 1), Location(1, 1)])
    assert len(zone.bounds) == 3
    assert zone.bounds[1].longitude == 1


class _Color(enum.Enum):
    RED = "red"


class _Pair(NamedTuple):
    a: int
    b: Location


@dataclasses.dataclass
class _Nested:
    where: Location
    pairs: tuple
    by_name: dict
    color: _Color = _Color.RED
    level: DeviceEventType = DeviceEventType.ALERT
    other: object = None


def _asdict_reference(obj):
    """The two-walk form: dataclasses.asdict, then the leaf conversion."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {k: _asdict_reference(v)
                for k, v in dataclasses.asdict(obj).items()}
    if isinstance(obj, dict):
        return {k: _asdict_reference(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_asdict_reference(v) for v in obj]
    if isinstance(obj, (str, int, float, bool, bytes)) or obj is None:
        return obj
    if hasattr(obj, "value"):
        return obj.value
    return str(obj)


@pytest.mark.parametrize("obj", [
    DeviceMeasurement(name="t", value=1.5, metadata={"k": "v", "n": 2}),
    DeviceLocation(latitude=1.0, longitude=2.0),
    DeviceAlert(type="hot", message="m"),
    Device(token="d", device_type_id="t", metadata={"a": "b"}),
    _Nested(where=Location(1.0, 2.0), pairs=(_Pair(1, Location(3.0, 4.0)),),
            by_name={"x": [Location(5.0, 6.0), b"raw", None]},
            other=complex(1, 2)),
], ids=["measurement", "location", "alert", "device", "nested"])
def test_asdict_matches_the_two_walk_form(obj):
    out = _asdict(obj)
    assert out == _asdict_reference(obj)
    assert [type(v) for v in out.values()] == [
        type(v) for v in _asdict_reference(obj).values()]
