"""chip_smoke.py's phases at a tiny size on the CPU, through the same
functions the script runs on the chip (Pallas in interpret mode), plus
the guards around it: `main` refuses the CPU, no CPU devices are
substituted for missing chips, the compile cache lands where it should,
and importing the package never initializes a backend."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from sitewhere_tpu.parallel.mesh import make_mesh
from sitewhere_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = chip_smoke.Size(devices=600, zones=24, events=20_000,
                       timeout_s=300.0)
TINY_CONFIG = {"pipeline.max_devices": 1024, "pipeline.batch_size": 256,
               "pipeline.max_zones": 24}


def test_single_chip_phases_tiny(tmp_path, capsys):
    checks = chip_smoke.run_single(TINY, 0, str(tmp_path), TINY_CONFIG,
                                   geofence_impl="pallas_interpret")
    out = capsys.readouterr().out
    assert checks.failed == [], out
    for name in ("processed", "event_counts", "last_value_per_slot",
                 "threshold_alerts", "geofence_alerts",
                 "rule_program_fires", "anomaly_model_fires",
                 "actuation_policy_fires", "dead_lettered", "step_retries",
                 "health", "http_device_state", "http_alert_listing",
                 "http_analytics_windows"):
        assert f"check {name}: ok" in out, name


def test_sharded_phase_tiny(tmp_path, capsys):
    checks = chip_smoke.run_sharded(TINY, 1, str(tmp_path), TINY_CONFIG,
                                    geofence_impl="pallas_interpret")
    out = capsys.readouterr().out
    assert checks.failed == [], out
    for name in ("mesh", "served_route", "state_placement",
                 "route_parity_lanes", "route_parity_state",
                 "sharded_replay_psum", "sharded_replay_ring"):
        assert f"check {name}: ok" in out, name


def test_reference_sees_every_rule_family():
    """The seeded traffic fires threshold and both geofence conditions,
    and no location sits within the clearance of a rule-zone edge."""
    zones, inside, outside = chip_smoke.make_zones(0, 24, 32)
    world = chip_smoke.World(
        n_devices=50, tokens=[f"d{i}" for i in range(50)],
        assignments=[f"a{i}" for i in range(50)], zones=zones,
        inside_zones=inside, outside_zones=outside,
        device_idx=np.arange(1, 51))
    traffic = chip_smoke.make_traffic(world, 0, 30_000, 0)
    ref = chip_smoke.build_reference(world, traffic)
    assert traffic.n == 30_000 and len(set(traffic.ts.tolist())) == 30_000
    assert ref.threshold_fired.sum() > 0
    loc = traffic.kind == 1
    rule_zones = zones[inside + outside]
    contained, gap = chip_smoke._crossings(traffic.lon[loc],
                                           traffic.lat[loc], rule_zones)
    assert gap.min() >= chip_smoke.EDGE_CLEARANCE
    assert contained[:, :len(inside)].any()          # an "inside" fires
    assert (~contained[:, len(inside):]).any()       # an "outside" fires
    assert ref.geofence_fired.sum() == (
        contained[:, :len(inside)].any(1)
        | (~contained[:, len(inside):]).any(1)).sum()


def test_main_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no CPU fallback" in proc.stderr


def test_make_mesh_substitutes_no_cpu_devices():
    default = jax.devices()
    with pytest.raises(ValueError, match="requested"):
        make_mesh(len(default) + 1)
    mesh = make_mesh(2, devices=default[:2])
    assert list(mesh.devices.flat) == default[:2]


def test_compile_cache_placement(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        assert compile_cache.configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.configure_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_imports_initialize_no_backend():
    """A feeder worker or the supervising parent imports these; none may
    take the chip."""
    code = ("import sitewhere_tpu, sitewhere_tpu.feeders, "
            "sitewhere_tpu.instance, sitewhere_tpu.__main__\n"
            "from jax._src import xla_bridge\n"
            "import json; print(json.dumps(sorted(xla_bridge._backends)))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == []
