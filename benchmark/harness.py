"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (`configs/<config>.json`) and a traffic mix
(`traffic/<traffic>.json`); the configuration's deployment module
(`deployments/<name>.py`) provisions its world and makes its traffic and
reference, and every metric is computed by its own reader
(`metrics/<name>.py`), each found by name (`loader.py`). Set-up boots the
deployment as `serve` does from the configuration's world snapshot
(provisioned first, and timed apart, where the checkout has none),
encodes the traffic pool and sends warm-up traffic through the served
path. The window then offers the mix from one commit edge to the first
edge `--seconds` later; afterwards the topic is drained, the program's
outcome is read back and compared with the plain reference, and one JSON
line is printed last. Earlier lines carry the device, sample counts,
warnings and each number compared beside its limit.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark import loader, traffic as traffic_mod, world

ROOT = os.path.dirname(loader.BENCH_DIR)
DRAIN_TIMEOUT_S = 120.0
WARMUP_TIMEOUT_S = 300.0
# how long past the nominal close the window waits for its closing edge
CLOSE_TIMEOUT_S = 30.0


def log(msg: str) -> None:
    print(msg, flush=True)


def load_benchmark() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def find_cell(bench: Dict, name: str) -> Dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"unknown workload {name!r}")


def configure_jax() -> str:
    """JAX's persistent compilation cache: the directory that
    JAX_COMPILATION_CACHE_DIR names where it is set, otherwise a fixed
    place in the checkout; every program is cached however fast it
    compiled."""
    import jax

    cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(world.CACHE_DIR, "jax"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def device_report(chips: int) -> Dict:
    """The device as JAX reports it; exits non-zero, printing no result,
    unless `chips` TPU devices are visible."""
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    log(f"device: platform={platform} kind={kind} count={len(devices)}")
    if platform != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU device(s), found "
              f"{len(devices)} x {platform}", file=sys.stderr, flush=True)
        raise SystemExit(2)
    return {"platform": platform, "kind": kind, "count": chips}


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while armed,
    by name."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        import jax

        self.count = 0
        self.names: List[str] = []
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1
            if "fun_name" in kw:
                self.names.append(str(kw["fun_name"]))

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class Collector:
    """Polls `fetch` every `period_s` from its own thread and keeps every
    item, by `key`, that was not there when it started."""

    def __init__(self, fetch: Callable[[], List], key: Callable,
                 period_s: float) -> None:
        self.fetch, self.key, self.period_s = fetch, key, period_s
        self.items: Dict = {}
        self.skip = set()
        self._stop = threading.Event()
        self._thread = None

    def poll(self) -> None:
        for item in self.fetch():
            self.items.setdefault(self.key(item), item)

    def start(self) -> None:
        self.poll()
        self.skip = set(self.items)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-collect")
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            self.poll()

    def stop(self) -> List:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
        self.poll()
        return [v for k, v in self.items.items() if k not in self.skip]


@dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: Dict
    cfg: Dict
    mix: Dict
    seconds: float
    setup_s: float
    per_record: int
    t_open: float
    t_close: float
    commit_at: np.ndarray
    flight: List[Dict] = field(default_factory=list)
    device: object = None            # tracereduce.DeviceWindow, traced runs
    notes: List[str] = field(default_factory=list)

    def note(self, line: str) -> None:
        self.notes.append(line)


def load_reader(name: str):
    return loader.load_module(loader.METRICS_DIR, name).read


def warm_compact_layout(engine) -> None:
    """Compile the fused step for the compact wire layout in set-up, with
    one batch of padding rows, which change no state. The packer sends a
    batch of readings in the packed layout while its dates span at most
    2^16 ms and in the compact one past that, and the window's polls pass
    that span once the consumer's partitions drift apart: without this
    the step compiles inside the window. Routed (sharded) steps always
    take the full layout."""
    import jax

    from sitewhere_tpu.ops.pack import (
        WIRE_ROWS_COMPACT, batch_to_blob, empty_batch)
    from sitewhere_tpu.parallel.engine import ShardedPipelineEngine

    if isinstance(engine, ShardedPipelineEngine):
        return
    blob = batch_to_blob(empty_batch(engine.packer.batch_size),
                         wire_rows=WIRE_ROWS_COMPACT)
    jax.block_until_ready(engine.submit_blob(blob))


def cell_metrics(bench: Dict, cell: str, traced: bool) -> List[Dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics, or
    with tracing its per-layer ones."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def wait_for(predicate: Callable[[], bool], timeout_s: float,
             what: str) -> None:
    deadline = time.perf_counter() + timeout_s
    while not predicate():
        if time.perf_counter() > deadline:
            raise TimeoutError(f"{what} not reached in {timeout_s:.0f} s")
        time.sleep(0.01)


def run_cell(bench: Dict, cell: Dict, seed: int, seconds: float,
             traced: bool, *, t_start: float,
             cfg_overrides: Optional[Dict] = None,
             mix_overrides: Optional[Dict] = None,
             geofence_impl: Optional[str] = None,
             fault: Optional[Callable] = None,
             use_control: bool = False) -> Dict:
    """One run: set-up, window, drain, outcome, reference, metrics. The
    keyword arguments after `t_start` are for tests and the control.

    The window opens at a commit edge of the inbound group, once the
    backlog stands, and closes at the first commit edge `seconds` or more
    later: every event it counts was committed inside it, and no part of
    it goes uncounted."""
    import jax

    from benchmark import tracereduce

    cfg = world.load_config(cell["config"], cfg_overrides)
    mix = traffic_mod.load_mix(cell["traffic"], mix_overrides)
    dep = loader.deployment(cfg)
    gen = loader.generator(mix, dep)
    provision_s = world.ensure_snapshot(cfg, dep, log)
    run_dir = os.path.join(world.CACHE_DIR, "run", cell["name"])
    world.fresh_data_dir(cfg, run_dir)
    compiles = CompileCounter()
    instance, rest = world.boot(cfg, run_dir, dep.rule_dicts(cfg),
                                geofence_impl)
    trace_dir = os.path.join(world.CACHE_DIR, "trace", cell["name"])
    try:
        w = dep.attach(instance, cfg)
        engine = instance.pipeline_engine
        if fault is not None:
            fault(instance)
        tenant = cfg["tenant"]
        topic_name = instance.naming.event_source_decoded_events(tenant)
        topic = instance.bus.topic(topic_name)
        group = instance.bus.consumer(topic_name,
                                      f"inbound-processing-{tenant}")
        per = dep.events_per_record(mix)
        n_rec = traffic_mod.pool_records(mix, per)
        warm = int(mix["warmup_records"])
        tr = gen.make_traffic(w, mix, seed, n_rec,
                              engine.packer.epoch_base_ms + 1000)
        records = gen.encode_records(w, tr, mix)
        pub = traffic_mod.Publisher(
            topic, group, records,
            backlog_records=-(-int(mix["backlog_events"]) // per))
        sampler = traffic_mod.CommitSampler(topic, group)
        sampler.start()
        # warm-up through the served path: compiles (or loads) the step,
        # warms persist, lanes and the consumer
        pub.publish_now(warm)
        wait_for(lambda: pub.committed_prefix(group.committed) >= warm,
                 WARMUP_TIMEOUT_S, "warm-up commit")
        warm_compact_layout(engine)
        log(f"world: {dep.describe(cfg)} "
            f"batch={engine.packer.batch_size} "
            f"geofence_impl={engine.geofence_impl} pool_records={n_rec} "
            f"events_per_record={per} warmup_records={warm} "
            f"provision_s={provision_s:.3f}")
        flight = Collector(lambda: engine.flight.export(last_n=256)["records"],
                           key=lambda r: r["seq"], period_s=0.5)
        gc.collect()
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(
                trace_dir, profiler_options=tracereduce.trace_options())
        # the window opens at the first commit edge once the backlog has
        # stood full: from then on every poll finds a full backlog
        pub.start()
        if not pub.filled.wait(WARMUP_TIMEOUT_S):
            raise TimeoutError("the backlog never filled")
        t_open = sampler.wait_edge(time.perf_counter(), WARMUP_TIMEOUT_S)
        if t_open is None:
            raise TimeoutError("no commit after the backlog filled")
        if traced:
            window_mark = jax.profiler.TraceAnnotation(
                tracereduce.WINDOW_ANNOTATION)
            window_mark.__enter__()
        compiles.armed = True
        flight.start()
        # provisioning happens once per checkout and is reported apart
        setup_s = t_open - t_start - provision_s
        t_close = sampler.wait_edge(t_open + seconds, CLOSE_TIMEOUT_S)
        if t_close is None:
            t_close = time.perf_counter()
            log(f"warning: no commit edge in the {CLOSE_TIMEOUT_S:.0f} s "
                f"after the nominal close; the window closes without one")
        published_close = pub.next
        compiles.armed = False
        if traced:
            window_mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
        pub.stop()
        flight_records = flight.stop()
        ends = lambda: list(topic.end_offsets())
        try:
            wait_for(lambda: list(group.committed) == ends(),
                     DRAIN_TIMEOUT_S, "drain")
        except TimeoutError as exc:
            log(f"warning: {exc}")
        sampler.stop()
        published = pub.next
        shown = tr.prefix(published)
        if use_control:
            observed = dep.control(w, shown, topic, group)
        else:
            observed = dep.observe(instance, w, topic, group)
        stats = engine.stats()
        log(f"served: published_records={published} "
            f"step_retries={int(engine._retry_counter.value)} "
            f"health={engine.health.state} "
            f"batches={stats.get('batches')} "
            f"compiles_in_window={compiles.count}"
            + (f" compiled={','.join(compiles.names)}" if compiles.names
               else ""))
        memory_peak = int(jax.devices()[0].memory_stats().get(
            "peak_bytes_in_use", 0)) if jax.devices()[0].platform != "cpu" \
            else 0
        kind = jax.devices()[0].device_kind
        exhausted = pub.exhausted_at
        backlog_low = pub.backlog_low
        part, offset = pub.part, pub.offset
    finally:
        compiles.close()
        rest.stop()
        instance.stop()
        del instance, rest
        gc.collect()
        shutil.rmtree(run_dir, ignore_errors=True)

    # ------------------------------------------------ after the window --
    if exhausted is not None and exhausted < t_close:
        log(f"warning: the pool ran out {exhausted - t_open:.3f} s into "
            f"the window")
    if backlog_low:
        log(f"warning: the unpolled backlog was empty at {backlog_low} "
            f"publisher checks")
    lag_t, lag = np.asarray(sampler.lag_t), np.asarray(sampler.lag)
    inside = lag[(lag_t >= t_open) & (lag_t <= t_close)]
    if inside.size >= 4:
        q = inside.size // 4
        log(f"backlog: lag_records first_quarter_mean="
            f"{inside[:q].mean():.1f} last_quarter_mean="
            f"{inside[-q:].mean():.1f} min={int(inside.min())} "
            f"samples={inside.size}")
    commit_at = sampler.commit_times(part[:published], offset[:published])
    run = Run(cell=cell, cfg=cfg, mix=mix, seconds=seconds, setup_s=setup_s,
              per_record=per, t_open=t_open, t_close=t_close,
              commit_at=commit_at, flight=flight_records)
    if traced:
        profile = tracereduce.load(trace_dir)
        run.device = tracereduce.reduce(profile, t_close - t_open)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if run.device is not None:
            log(f"trace: busy_s={run.device.busy_s:.6f} window_s="
                f"{run.device.window_s:.6f} programs="
                f"{json.dumps(run.device.module_runs)[:600]}")
        else:
            log("trace: no device operation in the window")
    compared = dep.compare(observed, dep.expected(w, shown))

    metrics = {}
    for m in cell_metrics(bench, cell["name"], traced):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for line in run.notes:
        log(line)
    # the window's records: published before it closed, not committed
    # when it opened
    due = commit_at[:published_close]
    window = ~(due <= t_open)
    device = {"platform": jax.devices()[0].platform, "kind": kind,
              "count": int(cell["chips"]),
              "memory_peak_bytes": memory_peak}
    result = {
        "correct": all(v <= limit for _, v, limit in compared),
        "attempted": int(window.sum() * per),
        "failed": int(np.isnan(due[window]).sum() * per),
        "metrics": metrics,
        "device": device,
    }
    if traced and run.device is not None:
        device["busy_s"] = run.device.busy_s
        device["window_s"] = run.device.window_s
        result["breakdown"] = tracereduce.breakdown(run.device)
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in compared}
    return result


def emit(result: Dict) -> None:
    """Each number compared beside its limit as the last lines on
    standard error, then the result as the last line of standard out."""
    for name, c in result["compared"].items():
        ok = c["value"] <= c["limit"]
        print(f"compare {name}: {c['value']} limit {c['limit']} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def parse_args(argv):
    parser = argparse.ArgumentParser(description="one run of one cell")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # a measurement aid, never passed by the benchmark's own runs
    parser.add_argument("--control", action="store_true",
                        help="put the reference in the program's place, "
                             "in bfloat16: the control's readings")
    return parser.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(world.CACHE_DIR, "tpu_logs"))
    cache = configure_jax()
    device_report(int(cell["chips"]))
    log(f"compile cache: {cache}")
    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, use_control=args.control)
    emit(result)
    return 0
