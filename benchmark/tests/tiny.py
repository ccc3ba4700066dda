"""Tiny sizes of every configuration and mix, and a helper that runs a
cell at them on the CPU with the geofence kernel in interpret mode."""

import time

from benchmark import harness

TINY_SERVE = {"pipeline.max_devices": 1024, "pipeline.batch_size": 256,
              "pipeline.max_zones": 24}
CONFIGS = {
    "tpcx_iot": {"areas": 60, "devices_per_area": 10,
                 "serve_config": TINY_SERVE},
}
MIXES = {
    "ingest": {"backlog_events": 1000, "pool_events": 40000,
               "warmup_records": 1000},
}


def cells():
    return harness.load_benchmark()["workloads"]


def run(cell_name: str, seed: int = 2 ** 31 + 11, seconds: float = 2.0,
        **kwargs):
    c = next(c for c in cells() if c["name"] == cell_name)
    return harness.run_cell(
        harness.load_benchmark(), c, seed, seconds, False,
        t_start=time.perf_counter(), cfg_overrides=CONFIGS[c["config"]],
        mix_overrides=MIXES[c["traffic"]],
        geofence_impl="pallas_interpret", **kwargs)
