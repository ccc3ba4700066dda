"""Multi-host bootstrap + window-sharded analytics (the stream analog of
context parallelism).

Two concerns the reference solves with external infrastructure:

1. **Cluster bootstrap.** The reference joins processes through ZooKeeper +
   Kafka consumer-group rebalancing (ZookeeperManager.java:29,
   MicroserviceKafkaConsumer.java). A TPU pod slice instead forms one SPMD
   program over all hosts' chips: `initialize()` wraps
   `jax.distributed.initialize` (coordinator/process env auto-detected on
   Cloud TPU; explicit for DCN clusters) and `make_global_mesh()` builds a
   mesh spanning every process's devices — ICI inside a slice, DCN between
   slices, exactly the layering SURVEY.md §2.5 prescribes.

2. **Window-sharded replay analytics.** SURVEY.md §5: this workload's
   "long context" is the unbounded event stream; its sequence-parallel
   analog shards the replay window across chips. `sharded_windowed_stats`
   splits the event rows of a replay across the mesh, folds each shard into
   a [K, W] stat grid locally (segment reductions — analytics/windows.py),
   and combines the partial grids with collectives: `psum`-family trees or
   an explicit `ppermute` ring (ring-attention's communication pattern,
   profitable when the grid is large and ICI hops should stay
   neighbor-to-neighbor).
"""

from __future__ import annotations

import os
from functools import lru_cache
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sitewhere_tpu.analytics.windows import WindowedStats, _windowed_stats_impl
from sitewhere_tpu.parallel.mesh import SHARD_AXIS


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Join (or form) the multi-host JAX cluster.

    On Cloud TPU pods every argument auto-detects from the metadata server;
    on DCN clusters pass coordinator ("host:port"), process count and id (or
    set SWTPU_COORDINATOR / SWTPU_NUM_PROCESSES / SWTPU_PROCESS_ID). Returns
    True if distributed mode was initialized, False for single-process runs
    (no coordinator configured) — callers need no special-casing either way.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "SWTPU_COORDINATOR")
    if num_processes is None and "SWTPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["SWTPU_NUM_PROCESSES"])
    if process_id is None and "SWTPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["SWTPU_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        in_pod = bool(os.environ.get("TPU_WORKER_HOSTNAMES"))
        if not in_pod:
            return False  # single host, nothing to join
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def make_global_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """Mesh over every device of every process (1-D shard axis). Under
    `jax.distributed` this spans hosts; single-process it equals
    parallel.mesh.make_mesh()."""
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devs), (SHARD_AXIS,))


@lru_cache(maxsize=1)
def live_mesh() -> Optional[Mesh]:
    """The default replay mesh for the serving planner: a 1-D shard mesh
    over every visible device, or None when only one device exists (a
    single chip gains nothing from sharded replay — the host kernel plus
    one dispatch already wins). Cached: device topology is fixed for the
    process lifetime."""
    devs = jax.devices()
    if len(devs) < 2:
        return None
    return Mesh(np.asarray(devs), (SHARD_AXIS,))


def process_shard_indices(mesh: Mesh) -> np.ndarray:
    """Shard indices whose devices live on THIS process — the shards this
    host's ingest threads must feed (the multi-host data-loading contract:
    each host device_puts only its addressable shards)."""
    me = jax.process_index()
    return np.asarray([i for i, d in enumerate(mesh.devices.flat)
                       if d.process_index == me], np.int32)


# -- window-sharded analytics -------------------------------------------------

def _combine_ring(stats: WindowedStats, axis: str) -> WindowedStats:
    """Ring all-reduce of partial stat grids via ppermute: S-1 steps, each
    passing the accumulated grid to the right neighbor. Communication
    pattern of ring attention (neighbor-only ICI hops), applied to the
    stream-window analog."""
    size = jax.lax.axis_size(axis)
    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(_, carry):
        acc_count, acc_sum, acc_min, acc_max, cur = carry
        nxt = tuple(jax.lax.ppermute(x, axis, perm) for x in cur)
        return (acc_count + nxt[0], acc_sum + nxt[1],
                jnp.minimum(acc_min, nxt[2]), jnp.maximum(acc_max, nxt[3]),
                nxt)

    local = (stats.count, stats.sum,
             jnp.where(stats.count == 0, jnp.inf, stats.min),
             jnp.where(stats.count == 0, -jnp.inf, stats.max))
    init = (local[0], local[1], local[2], local[3], local)
    count, vsum, vmin, vmax, _ = jax.lax.fori_loop(0, size - 1, step, init)
    return _finalize(count, vsum, vmin, vmax)


def _combine_psum(stats: WindowedStats, axis: str) -> WindowedStats:
    count = jax.lax.psum(stats.count, axis)
    vsum = jax.lax.psum(stats.sum, axis)
    vmin = jax.lax.pmin(jnp.where(stats.count == 0, jnp.inf, stats.min), axis)
    vmax = jax.lax.pmax(jnp.where(stats.count == 0, -jnp.inf, stats.max),
                        axis)
    return _finalize(count, vsum, vmin, vmax)


def _finalize(count, vsum, vmin, vmax) -> WindowedStats:
    empty = count == 0
    nan = jnp.float32(jnp.nan)
    return WindowedStats(
        count=count.astype(jnp.int32), sum=vsum.astype(jnp.float32),
        mean=jnp.where(empty, nan,
                       vsum / jnp.maximum(count, 1)).astype(jnp.float32),
        min=jnp.where(empty, nan, vmin).astype(jnp.float32),
        max=jnp.where(empty, nan, vmax).astype(jnp.float32))


def sharded_windowed_stats(keys, ts_rel, value, valid, *, window_ms: int,
                           num_keys: int, n_windows: int, mesh: Mesh,
                           combine: str = "psum") -> WindowedStats:
    """windowed_stats over a mesh: replay rows sharded across devices, the
    [K, W] grid combined by collective (`combine` = "psum" | "ring").

    Row padding to a multiple of the mesh size is handled here (padding rows
    are invalid). Returns replicated global stats.
    """
    if combine not in ("psum", "ring"):
        raise ValueError(f"combine {combine!r}: expected 'psum' or 'ring'")
    S = mesh.shape[SHARD_AXIS]
    keys = np.asarray(keys, np.int32)
    ts_rel = np.asarray(ts_rel, np.int32)
    value = np.asarray(value, np.float32)
    valid = np.asarray(valid, bool)
    B = keys.shape[0]
    Bp = -(-max(B, 1) // S) * S

    def pad(a, fill=0):
        out = np.full(Bp, fill, a.dtype)
        out[:B] = a
        return out

    ks = pad(keys).reshape(S, -1)
    ts = pad(ts_rel).reshape(S, -1)
    vals = pad(value).reshape(S, -1)
    ok = pad(valid, False).reshape(S, -1)

    run = _compiled_sharded_stats(mesh, combine, int(num_keys),
                                  int(n_windows))
    shard0 = NamedSharding(mesh, P(SHARD_AXIS))
    return run(jax.device_put(ks, shard0), jax.device_put(ts, shard0),
               jax.device_put(vals, shard0), jax.device_put(ok, shard0),
               jnp.asarray(window_ms, jnp.int32))


@lru_cache(maxsize=64)
def _compiled_sharded_stats(mesh: Mesh, combine: str, num_keys: int,
                            n_windows: int):
    """One jitted executable per (mesh, combine, grid shape) — same static-
    shape bucketing contract as analytics.windows._compiled_stats, so
    repeated replays reuse the compiled program instead of retracing."""
    combiner = _combine_psum if combine == "psum" else _combine_ring

    def shard_fn(k, t, v, m, w):
        local = _windowed_stats_impl(k[0], t[0], v[0], m[0], w,
                                     num_keys, n_windows)
        return combiner(local, SHARD_AXIS)

    specs = dict(
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                  P(SHARD_AXIS), P()),
        out_specs=WindowedStats(count=P(), sum=P(), mean=P(), min=P(),
                                max=P()))
    # the ring combine's replication is a loop invariant the checker
    # cannot infer statically
    return jax.jit(jax.shard_map(shard_fn, check_vma=False, **specs))
