"""The deployment seam: what `tpcx_iot.ingest` reads is pinned (the world
snapshot's digest, the encoded bytes of the whole pool and the
reference's outcome for one seed); every configuration resolves a module
that provides the whole seam; a mix's generator and a cell's tiny sizes
are found by name; and a zoned deployment runs from files added beside
the harness, with nothing edited."""

import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from benchmark import harness, loader, traffic, world
from benchmark.tests import test_cells, tiny

SEED = 2 ** 31 + 11
BASE_MS = 1_700_000_000_000
HERE = os.path.dirname(os.path.abspath(__file__))


def _tpcx():
    cfg = world.load_config("tpcx_iot")
    dep = loader.deployment(cfg)
    n = dep.n_devices(cfg)
    tokens = [f"dev-{i}" for i in range(n)]
    w = world.World(cfg=cfg, tokens=np.array(tokens, dtype=object),
                    token_index={t: i for i, t in enumerate(tokens)},
                    device_idx=np.arange(1, n + 1, dtype=np.int64))
    return dep, w


def test_tpcx_iot_snapshot_digest():
    assert world.config_digest(world.load_config("tpcx_iot")) \
        == "713bd1b148f405da"


def test_tpcx_iot_pool_and_reference_are_pinned():
    dep, w = _tpcx()
    mix = traffic.load_mix("ingest")
    n_rec = traffic.pool_records(mix, dep.events_per_record(mix))
    gen = loader.generator(mix, dep)
    tr = gen.make_traffic(w, mix, SEED, n_rec, BASE_MS)
    records = gen.encode_records(w, tr, mix)
    h = hashlib.sha256()
    for key, value in records:
        h.update(len(key).to_bytes(8, "little") + key
                 + len(value).to_bytes(8, "little") + value)
    assert len(records) == 308192
    assert h.hexdigest() == ("0d8cbb63f9049021461989376a3a4e2b"
                             "4352bb7a50394274edba5e366b00b3b0")
    want = dep.expected(w, tr)
    h = hashlib.sha256()
    for a in (want.event_count, want.rows, want.alerts):
        h.update(np.ascontiguousarray(a, "<i8").tobytes())
    assert h.hexdigest() == ("5e343cc6ed11056e15d447d038c25219"
                             "0752cdafa1dc8e4411f607b654b931e9")


def test_every_configuration_provides_the_seam():
    for entry in harness.load_benchmark()["configs"]:
        dep = loader.deployment(world.load_config(entry["name"]))
        assert all(hasattr(dep, name) for name in loader.SEAM), entry
        assert set(dep.CONTROL_FAILS) <= set(dep.LIMITS), entry


def test_configurations_share_a_module_by_name():
    cfg = dict(world.load_config("tpcx_iot"), name="tpcx_iot_b",
               module="tpcx_iot")
    assert loader.deployment(cfg).__file__ == os.path.join(
        loader.DEPLOYMENT_DIR, "tpcx_iot.py")
    with pytest.raises(FileNotFoundError, match="tpcx_iot_b.py"):
        loader.deployment(dict(cfg, module="tpcx_iot_b"))


def test_a_mix_names_its_own_generator(tmp_path, monkeypatch):
    monkeypatch.setattr(loader, "TRAFFIC_DIR", str(tmp_path))
    (tmp_path / "zipf.py").write_text(
        "def make_traffic(*a):\n    return 'zipf'\n\n"
        "def encode_records(*a):\n    return []\n")
    (tmp_path / "half.py").write_text("def make_traffic(*a):\n    pass\n")
    dep, w = _tpcx()
    assert loader.generator({}, dep) is dep
    gen = loader.generator({"generator": "zipf"}, dep)
    assert gen.make_traffic(w, {}, 1, 1, 0) == "zipf"
    with pytest.raises(AttributeError, match="encode_records"):
        loader.generator({"generator": "half"}, dep)


def test_a_cell_without_tiny_sizes_names_the_file(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "SIZES_DIR", str(tmp_path))
    with pytest.raises(FileNotFoundError,
                       match=os.path.join("configs", "tpcx_iot.json")):
        tiny.run("tpcx_iot.ingest")


# ------------------------------------------- a zoned deployment, added --

ZONE = [[10.0, 20.0], [11.0, 20.0], [11.0, 21.0], [10.0, 21.0]]
TOY_CELL = {"name": "toy_zones.points", "config": "toy_zones",
            "traffic": "toy_points", "chips": 1}
TINY_SERVE = {"pipeline.max_devices": 256, "pipeline.batch_size": 128,
              "pipeline.max_zones": 8, "pipeline.max_zone_vertices": 8}


@pytest.fixture
def zoned(tmp_path, monkeypatch):
    """The files a zoned deployment adds, in directories of their own:
    configuration, module, mix and tiny sizes."""
    files = {
        "configs/toy_zones.json": {
            "name": "toy_zones", "tenant": "default", "devices": 4096,
            "zone": ZONE,
            "serve_config": {"pipeline.mode": "throughput",
                             "bus.partitions": 2}},
        # about half the points fall inside the zone
        "traffic/toy_points.json": {
            "box": [[10.0, 19.5], [11.0, 21.5]], "backlog_events": 16384,
            "pool_events": 300000, "warmup_records": 8192},
        "tiny_sizes/configs/toy_zones.json": {
            "devices": 100, "serve_config": TINY_SERVE},
        "tiny_sizes/mixes/toy_points.json": {
            "backlog_events": 300, "pool_events": 6000,
            "warmup_records": 300},
    }
    for name, body in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    (tmp_path / "deployments").mkdir()
    shutil.copy(os.path.join(HERE, "toy_zones.py"),
                tmp_path / "deployments" / "toy_zones.py")
    for name, sub in (("CONFIG_DIR", "configs"),
                      ("DEPLOYMENT_DIR", "deployments"),
                      ("TRAFFIC_DIR", "traffic")):
        monkeypatch.setattr(loader, name, str(tmp_path / sub))
    monkeypatch.setattr(tiny, "SIZES_DIR", str(tmp_path / "tiny_sizes"))
    monkeypatch.setattr(world, "CACHE_DIR", str(tmp_path / "cache"))
    return loader.deployment(world.load_config("toy_zones"))


def test_zoned_deployment_is_files_added(zoned):
    result = tiny.run_cell(TOY_CELL)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"]


def test_zoned_deployment_fails_with_latitude_altered(zoned):
    assert zoned.FAULT_FIELD == "latitude"
    result = tiny.run_cell(TOY_CELL, fault=lambda instance:
                           test_cells._value_altered(instance, zoned))
    assert not result["correct"], result["compared"]
    assert result["compared"]["geofence_alert_mismatches"]["value"] > 0
