"""The one loader of the benchmark's per-name files: a metric's reader
(`metrics/<name>.py`), a deployment's module (`deployments/<name>.py`) and
a traffic generator (`traffic/<name>.py`), each found by the name that
`BENCHMARK.json`, a configuration or a mix gives it. Adding a file adds
the thing; there is no registry to edit.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from types import ModuleType
from typing import Dict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CONFIG_DIR = os.path.join(BENCH_DIR, "configs")
DEPLOYMENT_DIR = os.path.join(BENCH_DIR, "deployments")
TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")

# What a deployment's module provides (PERF.md §4, "Adding a deployment")
SEAM = ("n_devices", "rule_dicts", "provision", "attach", "describe",
        "events_per_record", "make_traffic", "encode_records", "expected",
        "observe", "control", "compare", "LIMITS", "CONTROL_FAILS",
        "FAULT_FIELD")
# what a mix's own generator provides
GENERATOR = ("make_traffic", "encode_records")


def load_module(directory: str, name: str) -> ModuleType:
    """The Python file `<directory>/<name>.py`, executed as a module of
    its own."""
    path = os.path.join(directory, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path}: no such file")
    module_name = (f"benchmark_{os.path.basename(directory)}_"
                   f"{name.replace('.', '_')}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    # dataclasses look their module up while the file executes
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def _provides(module: ModuleType, names) -> ModuleType:
    missing = [n for n in names if not hasattr(module, n)]
    if missing:
        raise AttributeError(f"{module.__file__} lacks {', '.join(missing)}")
    return module


def deployment(cfg: Dict) -> ModuleType:
    """The module of a configuration: `deployments/<module>.py` where the
    configuration names a `module`, else `deployments/<name>.py`."""
    return _provides(load_module(DEPLOYMENT_DIR,
                                 cfg.get("module", cfg["name"])), SEAM)


def generator(mix: Dict, dep: ModuleType) -> ModuleType:
    """What makes and encodes a mix's traffic: `traffic/<generator>.py`
    where the mix names a `generator`, else the deployment's module."""
    if "generator" not in mix:
        return dep
    return _provides(load_module(TRAFFIC_DIR, mix["generator"]), GENERATOR)
