"""A deployment's world: the instance as ``serve`` builds it, provisioned
through the control-plane API by the deployment's module
(``deployments/<name>.py``) from a configuration file, and kept as a
data-directory snapshot so that later runs boot from it.

The world is a function of the configuration alone; a run's seed varies
only its traffic. Provisioning 100,000 devices into the durable sqlite
registry commits once per entity, so it happens once per checkout, timed
apart from a run's set-up:
the data directory, taken before any traffic, is copied to
``benchmark/.cache/<config>/<digest>/`` and every later run copies it into
a fresh data directory and boots from it, as a restarted ``serve`` does.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from benchmark import loader

CACHE_DIR = os.path.join(loader.BENCH_DIR, ".cache")
# bump when provisioning changes what a snapshot holds
SNAPSHOT_VERSION = 1


def load_config(name: str, overrides: Optional[Dict] = None) -> Dict:
    """The configuration file `configs/<name>.json`, with `overrides`
    (tests shrink the scale) merged at the top level."""
    with open(os.path.join(loader.CONFIG_DIR, f"{name}.json")) as fh:
        cfg = json.load(fh)
    for key, value in (overrides or {}).items():
        if isinstance(value, dict) and isinstance(cfg.get(key), dict):
            cfg[key] = dict(cfg[key], **value)
        else:
            cfg[key] = value
    return cfg


def config_digest(cfg: Dict) -> str:
    text = json.dumps(cfg, sort_keys=True) + f"/v{SNAPSHOT_VERSION}"
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# ---------------------------------------------------------------- boot --

def serve_config(cfg: Dict, data_dir: str, rules: List[Dict]):
    """The `serve` configuration of this deployment over `data_dir`, with
    the deployment's fused `rules` (installed at every boot)."""
    from sitewhere_tpu.__main__ import _build_config

    serve = _build_config(None)
    serve.set("persist.data_dir", data_dir)
    for key, value in cfg["serve_config"].items():
        serve.set(key, value)
    serve.set("rules", rules)
    return serve


def boot(cfg: Dict, data_dir: str, rules: List[Dict],
         geofence_impl: Optional[str] = None):
    """Instance + REST gateway on port 0, as `serve` boots them:
    `_build_instance`, start, the config's rules, `RestServer`.
    `geofence_impl` is for CPU tests (the kernel in interpret mode)."""
    from sitewhere_tpu.__main__ import _apply_rule_config, _build_instance
    from sitewhere_tpu.web.server import RestServer

    serve = serve_config(cfg, data_dir, rules)
    instance = _build_instance(serve)
    engine = instance.pipeline_engine
    if geofence_impl is not None and engine.geofence_impl != geofence_impl:
        engine.geofence_impl = geofence_impl
        engine._build_step_blob()
    instance.start()
    _apply_rule_config(instance, serve)
    rest = RestServer(instance, host="127.0.0.1", port=0,
                      token_expiration_minutes=int(
                          serve.get("api.jwt_expiration_min")))
    rest.start()
    return instance, rest


def snapshot_dir(cfg: Dict) -> str:
    return os.path.join(CACHE_DIR, cfg["name"], config_digest(cfg))


def ensure_snapshot(cfg: Dict, dep, log) -> float:
    """Provision the world into a snapshot, by the deployment's module
    `dep`, unless this checkout has one; returns the seconds spent
    provisioning (0 when it was there)."""
    final = snapshot_dir(cfg)
    if os.path.isdir(final):
        return 0.0
    t0 = time.perf_counter()
    staging = final + ".partial"
    shutil.rmtree(staging, ignore_errors=True)
    os.makedirs(staging)
    instance, rest = boot(cfg, staging, dep.rule_dicts(cfg))
    try:
        dep.provision(instance, cfg)
    finally:
        rest.stop()
        instance.stop()
    os.replace(staging, final)
    seconds = time.perf_counter() - t0
    log(f"world: provisioned {cfg['name']} into a new snapshot "
        f"({dep.n_devices(cfg)} devices) provision_s={seconds:.3f}")
    return seconds


def fresh_data_dir(cfg: Dict, run_dir: str) -> None:
    """A copy of the snapshot at `run_dir` (replacing whatever was there)."""
    shutil.rmtree(run_dir, ignore_errors=True)
    shutil.copytree(snapshot_dir(cfg), run_dir)


@dataclass
class World:
    """What the traffic and the reference need to know."""

    cfg: Dict
    tokens: np.ndarray          # [n] device token (object)
    token_index: Dict[str, int]  # device token -> device number
    device_idx: np.ndarray      # [n] engine index per device

    @property
    def n(self) -> int:
        return int(self.tokens.shape[0])


def attach_devices(instance, cfg: Dict, tokens: List[str]) -> World:
    """The lookups the harness needs from a booted world whose devices
    are `tokens`."""
    engine = instance.pipeline_engine
    lookup = engine.registry.devices.lookup
    device_idx = np.array([lookup(t) for t in tokens], np.int64)
    if (device_idx <= 0).any():
        raise RuntimeError(f"{int((device_idx <= 0).sum())} devices of the "
                           f"snapshot are not registered after boot")
    return World(cfg=cfg, tokens=np.array(tokens, dtype=object),
                 token_index={t: i for i, t in enumerate(tokens)},
                 device_idx=device_idx)
