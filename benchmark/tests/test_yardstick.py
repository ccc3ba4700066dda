"""The trace reduction, the served-rate reader, the files the harness
finds by name, and the refusal to run without a chip."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, tracereduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_merge_gaps_and_union():
    busy = tracereduce.merge([(5, 7), (0, 2), (1, 3), (6, 9), (9, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tracereduce.covered([(0, 2), (1, 3), (5, 9)]) == 7
    assert tracereduce.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    host = [("pack", 3.5e9, 4.5e9), ("step", 3e9, 3.2e9),
            ("step", 9e9, 9.1e9)]
    named = tracereduce.attribute([(3e9, 5e9), (9e9, 12e9)], host)
    # "pack" covers half the first gap; "step" a thirtieth of the second
    assert named == [("pack", 2.0), ("unattributed", 3.0)]
    assert tracereduce.per_name([("a", 0, 2), ("b", 1, 2), ("a", 5, 6)]) \
        == {"a": 3, "b": 1}


def test_reduce_a_cpu_trace(tmp_path):
    """A trace recorded here: the CPU's XLA threads stand in for the
    device's op line."""

    @jax.jit
    def bench_probe(x):
        return jnp.sin(x) @ x.T

    x = jnp.ones((256, 256))
    bench_probe(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=tracereduce.trace_options())
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW_ANNOTATION):
        for _ in range(3):
            bench_probe(x).block_until_ready()
            time.sleep(0.02)
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    profile = tracereduce.load(str(tmp_path))
    ops = tracereduce.events(profile, lambda n: n == "/host:CPU",
                             lambda n: n.startswith("tf_XLA"))
    dw = tracereduce.reduce(profile, window_s,
                            plane_pred=lambda n: n == "/host:CPU",
                            ops_line=lambda n: n.startswith("tf_XLA"),
                            modules_line=lambda n: False)
    assert dw is not None and ops
    assert 0.0 < dw.busy_s < window_s
    assert 0.0 < dw.idle_pct < 100.0
    # the union never exceeds the summed op time
    assert dw.busy_s <= sum(dw.op_ns.values()) / 1e9 + 1e-9
    assert any("dot" in name for name in dw.op_ns)
    gaps = [g for g in dw.idle_gaps]
    assert gaps and all(s > 0 for _, s in gaps)
    brk = tracereduce.breakdown(dw)
    assert len(brk["device_ops"]) <= 10 and len(brk["idle_gaps"]) <= 10


def test_reduce_finds_nothing_without_a_device(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    assert tracereduce.reduce(tracereduce.load(str(tmp_path)), 1.0) is None


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "tpcx_iot.ingest", "--seed", str(2 ** 31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=600)


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(ROOT, env)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "needs 1 TPU device" in proc.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = _run(str(tmp_path), dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_benchmark_json_names_a_reader_for_every_metric():
    bench = harness.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(m["name"])), m["name"]
    for cell in bench["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", f"{cell['traffic']}.json"))
    for cfg in bench["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as fh:
            assert json.load(fh)["name"] == cfg["name"]


def test_served_rate_counts_the_whole_window():
    """Commits at the opening edge are before the window; those at the
    closing edge are in it; a record never committed is in none."""
    commit_at = np.array([0.0, 0.0, 1.0, 2.5, 2.5, 4.0, np.nan])
    run = harness.Run(cell={}, cfg={}, mix={}, seconds=3.5, setup_s=0.0,
                      per_record=10, t_open=0.0, t_close=4.0,
                      commit_at=commit_at)
    assert harness.load_reader("served_events_per_s")(run) == 40 / 4.0
    assert "commit_edges=3" in run.notes[0]
    # cycles: 1 record in 1 s, 2 in 1.5 s, 1 in 1.5 s
    assert harness.load_reader("cycle_events_per_s")(run) == 10 / 1.0
