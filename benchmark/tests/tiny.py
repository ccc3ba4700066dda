"""Tiny sizes of every configuration and mix, and a helper that runs a
cell at them on the CPU with the geofence kernel in interpret mode.

The sizes are files, one per configuration and one per mix, merged over
the real ones: `tiny_sizes/configs/<config>.json` and
`tiny_sizes/mixes/<mix>.json`."""

import json
import os
import time
from typing import Dict

from benchmark import harness

SIZES_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tiny_sizes")


def sizes(kind: str, name: str) -> Dict:
    """The tiny sizes of configuration or mix `name` (`kind` "configs" or
    "mixes")."""
    path = os.path.join(SIZES_DIR, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path}: every cell needs the tiny sizes "
                                f"of its configuration and its mix")
    with open(path) as fh:
        return json.load(fh)


def cells():
    return harness.load_benchmark()["workloads"]


def run_cell(cell: Dict, seed: int = 2 ** 31 + 11, seconds: float = 2.0,
             **kwargs):
    return harness.run_cell(
        harness.load_benchmark(), cell, seed, seconds, False,
        t_start=time.perf_counter(),
        cfg_overrides=sizes("configs", cell["config"]),
        mix_overrides=sizes("mixes", cell["traffic"]),
        geofence_impl="pallas_interpret", **kwargs)


def run(cell_name: str, **kwargs):
    return run_cell(next(c for c in cells() if c["name"] == cell_name),
                    **kwargs)
