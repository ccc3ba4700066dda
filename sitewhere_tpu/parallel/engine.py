"""ShardedPipelineEngine: the fused step over a device mesh.

Scaling story (SURVEY.md §2.5): the reference adds replicas per microservice
and lets Kafka split partitions; here ONE SPMD program runs on every chip.
Each shard owns devices `d % S == s` (their state rows, their slice of the
registry mirror); events reach their owner shard either via the on-device
routing prologue (ops/route.py — bucketing + one all_to_all fused into the
step; default on multi-shard single-controller meshes) or the host arena
router (single-chip, multi-host, and skew spills); rule tables and zone
geometry are replicated (small, read-only). Cross-shard communication is
one row exchange (device routing) plus the psum of per-batch stats over
ICI per step, vs. the reference's per-event gRPC fan-out.

Multi-host note: the same program runs under `jax.distributed` across hosts —
the mesh spans all processes' devices and each host routes/feeds the
sub-batches of its local shards (the standard multi-host jax data-loading
contract). ICI carries the psum; DCN only carries control-plane traffic.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sitewhere_tpu.model import DeviceAlert
from sitewhere_tpu.ops.pack import EventBatch, blob_to_batch
from sitewhere_tpu.runtime.bus import jittered
from sitewhere_tpu.runtime.faults import fault_point
from sitewhere_tpu.parallel.mesh import SHARD_AXIS, make_mesh, shard_axis_size
from sitewhere_tpu.parallel.router import ShardRouter
from sitewhere_tpu.pipeline.engine import PipelineEngine
from sitewhere_tpu.pipeline.state_tensors import (
    DeviceStateTensors, init_device_state_np)
from sitewhere_tpu.pipeline.step import PipelineParams, ProcessOutputs, process_batch
from sitewhere_tpu.registry.tensors import RegistryTensors


def _tree_specs(tree, spec):
    return jax.tree_util.tree_map(lambda _: spec, tree)


def _put_global(value: np.ndarray, sharding: NamedSharding):
    """Place a host value onto a (possibly multi-host) sharding using ONLY
    local single-device transfers.

    jax 0.9's device_put supports cross-host placements by lowering them
    to COLLECTIVE transfers — under multi-controller that would have to
    run in lockstep on every process, but params/state refreshes fire at
    different ticks per host (registry versions bump independently), which
    desyncs the collective order and aborts the whole cluster (observed:
    gloo 'Received data size doesn't match expected size'). Every process
    holds the full host value here, so per-device local placement is
    always possible and never communicates."""
    value = np.asarray(value)
    shards = [
        jax.device_put(value[index], device)
        for device, index in sharding.addressable_devices_indices_map(
            value.shape).items()]
    return jax.make_array_from_single_device_arrays(
        value.shape, sharding, shards)


def _put_global_tree(tree, sharding_tree):
    return jax.tree_util.tree_map(
        lambda value, sharding: _put_global(value, sharding),
        tree, sharding_tree)


class RoutedBlobView:
    """Lazy routed-batch handle returned by ShardedPipelineEngine.submit:
    the staged wire blob IS the data; EventBatch columns unpack on first
    access (only alert materialization needs them, and only for steps
    that fired). Column attributes proxy to the unpacked batch, so code
    that treats the handle as an EventBatch keeps working.

    `shard_ids` maps the blob's leading axis to GLOBAL shard indices —
    under multi-process feeding the view holds only this process's local
    shard blocks.

    When the blob is a pooled staging buffer on loan from the router,
    `release` returns it for reuse once this view is garbage-collected —
    holding a view arbitrarily long is always safe (the buffer cannot be
    recycled underneath it). Recycling is additionally guarded against
    in-flight async H2D DMA: the release carries the consuming step's
    output as a transfer-completion guard that the pool blocks on before
    handing the buffer out again (router.release_staging_buffer). The cpu
    backend, where jax may zero-copy host buffers outright, never loans
    buffers (staging_ring=0)."""

    __slots__ = ("blob", "shard_ids", "_batch", "_release", "__weakref__")

    def __init__(self, blob: np.ndarray,
                 shard_ids: Optional[List[int]] = None,
                 release: Optional[Callable[[], None]] = None):
        self.blob = blob
        self.shard_ids = shard_ids
        self._batch = None
        self._release = release

    @property
    def batch(self) -> EventBatch:
        if self._batch is None:
            from sitewhere_tpu.ops.pack import blob_to_batch_np

            self._batch = blob_to_batch_np(self.blob)
        return self._batch

    def __getattr__(self, name):
        return getattr(self.batch, name)

    def __del__(self):
        release, self._release = self._release, None
        if release is not None:
            try:
                release()
            except Exception:
                pass


class DeviceRoutedView:
    """Lazy materialization handle for a DEVICE-routed step: the host
    never builds the routed [S, B] layout — the mesh does (ops/route.py)
    — so this view reconstructs only what alert materialization needs
    (the routed device_idx/ts columns), only when something actually
    fired, from the flat wire blob it keeps. The reconstruction is the
    same stable `_shard_sort` bucketing the host router uses, over TWO
    columns instead of a 5-row blob scatter — and it runs on the cold
    path, not per step.

    When the flat blob is a pooled loan (router.flat_staging_buffer),
    `release` hands it back on GC exactly like RoutedBlobView."""

    __slots__ = ("blob", "shard_ids", "_router", "_cols", "_flat",
                 "_full", "_release", "__weakref__")

    def __init__(self, blob: np.ndarray, router: ShardRouter,
                 release: Optional[Callable[[], None]] = None):
        self.blob = blob                 # flat [wire_rows, S*B]
        self.shard_ids = None
        self._router = router
        self._cols = None
        self._flat = None
        self._full = None
        self._release = release

    def _flat_batch(self) -> EventBatch:
        if self._flat is None:
            from sitewhere_tpu.ops.pack import blob_to_batch_np

            self._flat = blob_to_batch_np(self.blob)
        return self._flat

    def _sort(self):
        flat = self._flat_batch()
        rt = self._router
        valid = np.asarray(flat.valid)
        rows = None if valid.all() else np.nonzero(valid)[0]
        devcol = np.asarray(flat.device_idx)
        dev = devcol if rows is None else devcol[rows]
        ksorted, kept, _ = rt._shard_sort(dev, rows)
        kstarts = np.zeros(rt.n_shards + 1, np.int64)
        np.cumsum(kept, out=kstarts[1:])
        return ksorted, kept, kstarts

    def _routed_cols(self):
        if self._cols is None:
            flat = self._flat_batch()
            rt = self._router
            S, B = rt.n_shards, rt.per_shard_batch
            ksorted, kept, kstarts = self._sort()
            out_dev = np.zeros((S, B), np.int32)
            out_ts = np.zeros((S, B), np.int32)
            rt._place_sorted(out_dev,
                             np.asarray(flat.device_idx)[ksorted] // S,
                             kept, kstarts)
            rt._place_sorted(out_ts, np.asarray(flat.ts)[ksorted],
                             kept, kstarts)
            self._cols = (out_dev, out_ts)
        return self._cols

    @property
    def device_idx(self) -> np.ndarray:  # LOCAL indices, [S, B]
        return self._routed_cols()[0]

    @property
    def ts(self) -> np.ndarray:          # [S, B]
        return self._routed_cols()[1]

    @property
    def batch(self) -> EventBatch:
        """Full routed [S, B] EventBatch (wire-faithful: reconstructed
        from the flat blob, local device indices) — RoutedBlobView
        compat for oracle/differential consumers. Cold path only."""
        if self._full is None:
            import dataclasses as _dc

            flat = self._flat_batch()
            rt = self._router
            S, B = rt.n_shards, rt.per_shard_batch
            ksorted, kept, kstarts = self._sort()
            cols = {}
            for f in _dc.fields(flat):
                col = np.asarray(getattr(flat, f.name))
                gathered = col[ksorted]
                if f.name == "device_idx":
                    gathered = gathered // S   # global -> local rows
                out = np.zeros((S, B), col.dtype)
                rt._place_sorted(out, gathered, kept, kstarts)
                cols[f.name] = out
            self._full = EventBatch(**cols)
        return self._full

    def __getattr__(self, name):
        return getattr(self.batch, name)

    def __del__(self):
        release, self._release = self._release, None
        if release is not None:
            try:
                release()
            except Exception:
                pass


class _PreparedStep:
    """Host-side routing decision for one step, between _prepare_step and
    stage_prepared: `kind` is "host" (arena-routed [S, rows, B] blob,
    possibly a pooled loan) or "device" (unrouted flat [rows, S*B] blob;
    the mesh routes it in the step's prologue)."""

    __slots__ = ("kind", "blob", "flight")

    def __init__(self, kind: str, blob: np.ndarray, flight=None):
        self.kind = kind
        self.blob = blob
        # flight record opened by _prepare_step; rides the prepared ->
        # staged -> dispatched handoff so a pipelined feeder's stage-ahead
        # work lands on the SAME record its dispatch completes (explicit
        # cross-thread trace handoff)
        self.flight = flight


class _StagedStep:
    """In-flight staged blob between stage_prepared and dispatch_staged:
    the (possibly still transferring) global device array, the lazy
    materialization view, the host blob the events meter counts from,
    the loaned host blob to release after dispatch, and which compiled
    program ("host" routed / "device" routing-prologue) consumes it."""

    __slots__ = ("blob", "view", "counted", "routed_blob", "kind",
                 "flight", "slot")

    def __init__(self, blob, view, counted, routed_blob,
                 kind: str = "host", flight=None, slot=None):
        self.blob = blob
        self.view = view
        self.counted = counted
        self.routed_blob = routed_blob
        self.kind = kind
        self.flight = flight
        # staging-ring slot (pipeline/staging.py) the transfer occupies;
        # dispatch_staged releases it with the step output as guard.
        # None when the caller bypassed the ring (overflow drain blobs).
        self.slot = slot


class ShardedPipelineEngine(PipelineEngine):
    """Drop-in engine whose state/params/batches carry a leading shard axis.

    `per_shard_batch` is the per-chip batch; global throughput scales with the
    mesh. Device capacity must divide evenly by the mesh size.
    """

    def __init__(self, registry_tensors: RegistryTensors,
                 mesh: Optional[Mesh] = None, per_shard_batch: int = 4096,
                 device_routing: Optional[bool] = None,
                 **kwargs):
        self.mesh = mesh or make_mesh()
        self.n_shards = shard_axis_size(self.mesh)
        if registry_tensors.devices.capacity % self.n_shards:
            raise ValueError(
                f"max_devices {registry_tensors.devices.capacity} must be "
                f"divisible by {self.n_shards} shards")
        super().__init__(registry_tensors, batch_size=per_shard_batch, **kwargs)
        # staging-ring reuse only on accelerator meshes: the cpu backend
        # zero-copies aligned numpy arrays into device buffers, so a
        # recycled routed-blob slot could corrupt an in-flight step's
        # input (see PipelineEngine._staging_blob_buffer)
        ring = 0 if self._target_platform() == "cpu" else 4
        self.router = ShardRouter(self.n_shards, per_shard_batch,
                                  staging_ring=ring)
        if self.is_multiprocess:
            # lockstep invariant: every host must launch the SAME-shaped
            # collective program per tick; a per-host compact-vs-full wire
            # choice (driven by local batch content) would pair
            # differently-shaped collectives across hosts. Pin the full
            # layout cluster-wide.
            from sitewhere_tpu.ops.pack import WIRE_ROWS
            self.router.fixed_wire_rows = WIRE_ROWS
        # host packer accepts a full mesh's worth of events per flat batch
        from sitewhere_tpu.ops.pack import EventPacker
        self.packer = EventPacker(per_shard_batch * self.n_shards,
                                  registry_tensors.devices)
        # On-device shard routing (ops/route.py): the feeder ships the
        # UNROUTED flat blob (pack + one H2D) and a fused routing
        # prologue inside the step's shard_map buckets + all_to_all's
        # rows to their owner shards — no per-row host bucketing. Auto:
        # on for real multi-shard single-controller meshes; single-chip
        # "sharded" meshes keep the host path (nothing to exchange, and
        # the host-vs-device router micro-bench needs the host baseline);
        # multi-host clusters keep the host path (per-host feeding +
        # take_foreign owns cross-host rows there).
        if device_routing is None:
            device_routing = self.n_shards >= 2 and not self.is_multiprocess
        elif device_routing and self.is_multiprocess:
            raise ValueError(
                "device_routing is single-controller only: multi-host "
                "clusters feed per-host and forward foreign rows over "
                "the bus edge (take_foreign)")
        self.device_routing = bool(device_routing)
        from sitewhere_tpu.ops.route import route_lane_capacity
        self.route_lane_capacity = route_lane_capacity(
            per_shard_batch, self.n_shards)
        # loud accounting for the bounded host-spill fallback and the
        # (defensive, normally zero) on-device drop counter
        self.device_route_steps = 0
        self.device_route_fallbacks = 0
        self.device_route_dropped = 0
        self._sharded_step = None  # built lazily once specs are known
        self._sharded_step_device = None
        # shard-overflow events requeued ahead of the next submit; when the
        # backlog exceeds the bound, submit() drains it with extra steps
        # (backpressure) instead of dropping rows
        self._overflow: Optional[EventBatch] = None
        self.max_overflow_events = per_shard_batch * self.n_shards * 4
        # reusable flat staging for the overflow+batch merge: the requeue
        # path used to pay 12 fresh column allocations per carrying step
        from sitewhere_tpu.parallel.router import FlatBatchArena
        self._merge_arena = FlatBatchArena()
        self.total_dropped = 0  # kept for the stats contract; stays 0
        self.drain_steps = 0
        # alerts fired during drain steps, delivered on the next
        # materialize_alerts call (drain outputs never reach the caller);
        # bounded so a caller that never materializes can't leak memory —
        # overflow is counted on alerts_dropped like any bounded drop
        self._pending_alerts: List[DeviceAlert] = []
        self.max_pending_alerts = 65536

    def _target_platform(self) -> str:
        return self.mesh.devices.flat[0].platform

    # -- multi-process topology -------------------------------------------

    @property
    def local_shards(self) -> List[int]:
        """Global shard indices whose device lives in THIS process (mesh
        order). Single-process: all of them."""
        cached = getattr(self, "_local_shards", None)
        if cached is None:
            me = jax.process_index()
            cached = [i for i, d in enumerate(self.mesh.devices.flat)
                      if d.process_index == me]
            self._local_shards = cached
        return cached

    @property
    def is_multiprocess(self) -> bool:
        return len(self.local_shards) < self.n_shards

    def take_foreign(self) -> Optional[EventBatch]:
        """Events this host ingested whose owner shard lives on ANOTHER
        process. The multi-host data contract is per-host feeding: each
        host stages only its local shards' rows; rows owned elsewhere are
        handed back here for the caller to forward over the bus edge
        (keyed so the owning host's consumer picks them up) — never
        silently dropped. Returns a flat batch or None."""
        batch, self._foreign = getattr(self, "_foreign", None), None
        return batch

    # -- initialization -------------------------------------------------------

    def on_initialize(self, monitor) -> None:
        S = self.n_shards
        # Build the stacked initial state in host numpy and place it with ONE
        # device_put pinned to the mesh: no op may dispatch on the default
        # backend here — the mesh can be CPU devices inside a process whose
        # default backend is a TPU client that is broken or absent (the
        # driver's dryrun environment).
        local = init_device_state_np(
            self.registry.devices.capacity // S, self.measurement_slots,
            self.max_tenants)
        stacked = jax.tree_util.tree_map(
            lambda a: np.ascontiguousarray(
                np.broadcast_to(a, (S,) + a.shape)), local)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        self._state = _put_global_tree(stacked, _tree_specs(stacked, shard0))
        if self._rule_state is None:
            self._rule_state = self._init_rule_state()
        if self._model_state is None:
            self._model_state = self._init_model_state()
        if self._actuation_state is None:
            self._actuation_state = self._init_actuation_state()
        self._refresh_params()
        self._build_step()

    def _init_rule_state(self):
        # rule-program state rides the shard axis with the other state
        # tensors: per-shard [S, D/S, P, 4*slots+2] fused slab lanes plus
        # per-shard [S, P] generation/counter rows (counters are additive
        # partials, summed on read like the tenant counters). Sized by
        # _rule_state_dims: a [.., 1, 1] placeholder while no programs
        # are installed (the stage is dropped at trace time).
        from sitewhere_tpu.ops.stateful import init_rule_state_np

        dims = self._rule_state_dims()
        self._rule_state_built_dims = dims
        S = self.n_shards
        local = init_rule_state_np(
            self.registry.devices.capacity // S, *dims)
        stacked = jax.tree_util.tree_map(
            lambda a: np.ascontiguousarray(
                np.broadcast_to(a, (S,) + a.shape)), local)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        return _put_global_tree(stacked, _tree_specs(stacked, shard0))

    def _init_model_state(self):
        # anomaly-model state rides the shard axis exactly like the
        # rule-program state: per-shard [S, D/S, P, 4*F+2] fused slab
        # lanes plus
        # per-shard [S, P] generation/counter rows (fire/eval counters
        # are additive partials, summed on read). Sized by
        # _model_state_dims: a [.., 1, 1] placeholder while no models
        # are installed (the stage is dropped at trace time).
        from sitewhere_tpu.ops.anomaly import init_model_state_np

        dims = self._model_state_dims()
        self._model_state_built_dims = dims
        S = self.n_shards
        local = init_model_state_np(
            self.registry.devices.capacity // S, *dims)
        stacked = jax.tree_util.tree_map(
            lambda a: np.ascontiguousarray(
                np.broadcast_to(a, (S,) + a.shape)), local)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        return _put_global_tree(stacked, _tree_specs(stacked, shard0))

    def _init_actuation_state(self):
        # actuation debounce state rides the shard axis exactly like the
        # model state: per-shard [S, D/S, P, 6] fused slab lanes plus
        # per-shard [S, P] generation/counter rows (fire/debounce counters
        # are additive partials, summed on read). Sized by
        # _actuation_state_dims: a [.., 1, ..] placeholder while no
        # policies are installed (the stage is dropped at trace time).
        from sitewhere_tpu.ops.actuate import init_actuation_state_np

        dims = self._actuation_state_dims()
        self._actuation_state_built_dims = dims
        S = self.n_shards
        local = init_actuation_state_np(
            self.registry.devices.capacity // S, *dims)
        stacked = jax.tree_util.tree_map(
            lambda a: np.ascontiguousarray(
                np.broadcast_to(a, (S,) + a.shape)), local)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        return _put_global_tree(stacked, _tree_specs(stacked, shard0))

    def _build_step_blob(self) -> None:
        # the single-chip jit is never used by the sharded engine; the
        # collective program is built by _build_step instead
        self._step_blob = None
        self._step_built_config = self._step_static_config()

    def _ensure_step_current(self) -> None:
        if (self._sharded_step is not None
                and getattr(self, "_sharded_built_config", None)
                != self._step_static_config()):
            self._ensure_rule_state_sized()
            self._ensure_model_state_sized()
            self._ensure_actuation_state_sized()
            self._build_step()

    def _build_step(self) -> None:
        params_template = self._params
        dev = P(SHARD_AXIS)
        rep = P()
        params_specs = PipelineParams(
            assignment_status=dev, tenant_idx=dev, area_idx=dev,
            device_type_idx=dev,
            threshold=_tree_specs(params_template.threshold, rep),
            zones=_tree_specs(params_template.zones, rep),
            geofence=_tree_specs(params_template.geofence, rep),
            programs=_tree_specs(params_template.programs, rep),
            # model weight tables replicate like the rule tables (small,
            # read-only); only the feature STATE rides the shard axis
            models=_tree_specs(params_template.models, rep),
            # policy tables replicate too; the debounce STATE is sharded
            policies=_tree_specs(params_template.policies, rep))
        state_specs = _tree_specs(self._state, dev)
        rule_state_specs = _tree_specs(self._rule_state, dev)
        model_state_specs = _tree_specs(self._model_state, dev)
        actuation_state_specs = _tree_specs(self._actuation_state, dev)
        blob_specs = dev  # [S, WIRE_ROWS, B] single staging blob, sharded on S
        out_specs = ProcessOutputs(
            valid=dev, unregistered=dev, threshold_fired=dev,
            threshold_first_rule=dev, threshold_alert_level=dev,
            geofence_fired=dev, geofence_first_rule=dev,
            geofence_alert_level=dev, program_fired=dev,
            program_first_rule=dev, program_alert_level=dev,
            model_fired=dev, model_first=dev, model_level=dev,
            model_score=dev,
            tenant_counts=rep, processed=rep,
            alerts=rep,
            # per-shard compacted alert lanes ride the shard axis with
            # the other outputs — no extra collective, one host fetch
            alert_lanes=dev,
            # the command lane rides the same fetch, shard-major like the
            # alert lane
            command_lanes=dev)
        (programs_enabled, node_limit, models_enabled,
         actuation_enabled) = self._step_static_config()

        def sq(a):
            # shard_map hands blocks with the mapped axis kept (size 1); the
            # per-shard program works on squeezed local shapes.
            return a.reshape(a.shape[1:])

        def unsq(a):
            return a[None]

        def local_step(params, state, rule_state, model_state,
                       actuation_state, local_blob, route_dropped=None):
            """Shared per-shard body: fused step over an already-LOCAL
            [wire_rows, B] routed blob. `route_dropped` (device-routing
            prologue only) rides out on the alert lanes' spare counts
            slot — no extra output, no extra fetch."""
            params = params.replace(
                assignment_status=sq(params.assignment_status),
                tenant_idx=sq(params.tenant_idx),
                area_idx=sq(params.area_idx),
                device_type_idx=sq(params.device_type_idx))
            state = jax.tree_util.tree_map(sq, state)
            rule_state = jax.tree_util.tree_map(sq, rule_state)
            model_state = jax.tree_util.tree_map(sq, model_state)
            actuation_state = jax.tree_util.tree_map(sq, actuation_state)
            batch = blob_to_batch(local_blob)        # [12, B] -> columns
            (new_state, new_rule_state, new_model_state,
             new_actuation_state, out) = process_batch(
                params, state, rule_state, model_state, actuation_state,
                batch,
                geofence_impl=self.geofence_impl,
                alert_lane_capacity=self.alert_lane_capacity,
                programs_enabled=programs_enabled,
                program_node_limit=node_limit,
                models_enabled=models_enabled,
                actuation_enabled=actuation_enabled,
                command_lane_capacity=self.command_lane_capacity)
            lanes = out.alert_lanes
            if route_dropped is not None:
                from sitewhere_tpu.ops.route import ROUTE_DROPPED_SLOT
                lanes = lanes.at[3, ROUTE_DROPPED_SLOT].set(route_dropped)
            new_state = jax.tree_util.tree_map(unsq, new_state)
            new_rule_state = jax.tree_util.tree_map(unsq, new_rule_state)
            new_model_state = jax.tree_util.tree_map(unsq, new_model_state)
            new_actuation_state = jax.tree_util.tree_map(
                unsq, new_actuation_state)
            out = out.replace(
                valid=unsq(out.valid), unregistered=unsq(out.unregistered),
                threshold_fired=unsq(out.threshold_fired),
                threshold_first_rule=unsq(out.threshold_first_rule),
                threshold_alert_level=unsq(out.threshold_alert_level),
                geofence_fired=unsq(out.geofence_fired),
                geofence_first_rule=unsq(out.geofence_first_rule),
                geofence_alert_level=unsq(out.geofence_alert_level),
                program_fired=unsq(out.program_fired),
                program_first_rule=unsq(out.program_first_rule),
                program_alert_level=unsq(out.program_alert_level),
                model_fired=unsq(out.model_fired),
                model_first=unsq(out.model_first),
                model_level=unsq(out.model_level),
                model_score=unsq(out.model_score),
                alert_lanes=unsq(lanes),
                command_lanes=unsq(out.command_lanes),
                tenant_counts=jax.lax.psum(out.tenant_counts, SHARD_AXIS),
                processed=jax.lax.psum(out.processed, SHARD_AXIS),
                alerts=jax.lax.psum(out.alerts, SHARD_AXIS))
            return (new_state, new_rule_state, new_model_state,
                    new_actuation_state, out)

        def sharded(params, state, rule_state, model_state, actuation_state,
                    blob):
            return local_step(params, state, rule_state, model_state,
                              actuation_state, sq(blob))

        def build(fn, blob_spec):
            specs = dict(mesh=self.mesh,
                         in_specs=(params_specs, state_specs,
                                   rule_state_specs, model_state_specs,
                                   actuation_state_specs, blob_spec),
                         out_specs=(state_specs, rule_state_specs,
                                    model_state_specs,
                                    actuation_state_specs, out_specs))
            # the geofence containment scan's carry is replicated only
            # through the psum at the end of the step — a loop invariant
            # the replication checker cannot infer statically (same as
            # parallel/distributed.py's ring combine)
            mapped = jax.shard_map(fn, check_vma=False, **specs)
            return jax.jit(mapped, donate_argnums=(1, 2, 3, 4))

        self._sharded_step = build(sharded, blob_specs)
        if self.device_routing:
            from sitewhere_tpu.ops.route import device_route_chunk
            n_shards = self.n_shards
            per_shard = self.batch_size
            lane_cap = self.route_lane_capacity

            def sharded_device(params, state, rule_state, model_state,
                               actuation_state, flat_blob):
                # flat_blob block: [wire_rows, B] UNROUTED lane chunk
                # (the flat blob split along lanes, P(None, shard)) —
                # the routing prologue buckets + all_to_all's it to the
                # owner shards inside the same program as the step
                local_blob, dropped = device_route_chunk(
                    flat_blob, n_shards, per_shard, lane_cap, SHARD_AXIS)
                return local_step(params, state, rule_state, model_state,
                                  actuation_state, local_blob,
                                  route_dropped=dropped)

            self._sharded_step_device = build(
                sharded_device, P(None, SHARD_AXIS))
        else:
            self._sharded_step_device = None
        self._sharded_built_config = (programs_enabled, node_limit,
                                      models_enabled, actuation_enabled)

    # -- params ---------------------------------------------------------------

    def _refresh_params(self) -> None:
        snap = self.registry.snapshot()
        threshold = self._compile_threshold_table()
        geofence = self._compile_geofence_table()
        programs = self._compile_program_table()
        models = self._compile_model_table()
        policies = self._compile_policy_table()
        from sitewhere_tpu.ops.geofence import ZoneTable
        zones = ZoneTable(vertices=snap.zone_vertices, nvert=snap.zone_nvert,
                          tenant_idx=snap.zone_tenant, active=snap.zone_active)
        router = getattr(self, "router", None) or ShardRouter(
            self.n_shards, self.batch_size)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        rep = NamedSharding(self.mesh, P())
        params = PipelineParams(
            assignment_status=router.shard_param(snap.assignment_status),
            tenant_idx=router.shard_param(snap.tenant_idx),
            area_idx=router.shard_param(snap.area_idx),
            device_type_idx=router.shard_param(snap.device_type_idx),
            threshold=threshold, zones=zones, geofence=geofence,
            programs=programs, models=models, policies=policies)
        shardings = PipelineParams(
            assignment_status=shard0, tenant_idx=shard0, area_idx=shard0,
            device_type_idx=shard0,
            threshold=_tree_specs(threshold, rep),
            zones=_tree_specs(zones, rep),
            geofence=_tree_specs(geofence, rep),
            programs=_tree_specs(programs, rep),
            models=_tree_specs(models, rep),
            policies=_tree_specs(policies, rep))
        self._params = _put_global_tree(params, shardings)
        self._params_built_for = (snap.version, self._rules_version)

    # -- processing -----------------------------------------------------------

    def submit(self, batch: EventBatch, age=None
               ) -> Tuple[EventBatch, ProcessOutputs]:
        """Route a flat host batch (global indices, any length) to shards and
        run one collective step. Returns (the LAST routed batch with a
        [S, B] layout, outputs of the last step). Events overflowing a
        shard's capacity are requeued ahead of the next submit
        (at-least-once; order per device preserved because overflow rows
        predate the next batch's rows).

        Backpressure instead of loss: when sustained skew piles overflow
        past `max_overflow_events`, submit runs extra drain steps (overflow
        only, no new events) until the backlog fits. The call gets slower —
        which is the signal the caller needs — and `total_dropped` stays 0;
        `drain_steps` counts the extra steps for observability."""
        params = self._ensure_params()
        batch = self.merge_pending_overflow(batch)
        # Device routing (default on real multi-shard meshes): pack the
        # flat blob, one H2D, and let the mesh route it inside the step
        # (ops/route.py). Host arena route (fused native pack+route into
        # a pooled routed blob) remains the fallback for skewed batches
        # that would overflow a device lane — and the only path on
        # single-chip meshes and multi-host clusters.
        prepared, over_rows = self._prepare_step(batch, age=age)
        try:
            routed_batch, outputs = self._one_step(params, prepared)
        except BaseException:
            if not self.is_multiprocess:
                # transfer state unknown mid-failure: drop the loaned
                # buffer from the pool instead of leaking it (or recycling
                # a possibly-in-DMA one). The multiprocess path already
                # released it before the step (it never reaches jax there
                # — only the local copy does), so discarding again would
                # under-count the pool.
                self.router.discard_staging_buffer(prepared.blob)
            raise
        self.park_overflow(batch, over_rows)
        # Multi-process lockstep: every host must launch the SAME number of
        # collective programs per submit — extra drain steps on one host
        # would pair its psums with a peer's NEXT step (undefined). The
        # cluster step loop applies backpressure instead (it stops pulling
        # new work while pending_overflow exceeds the bound, so the
        # backlog drains one lockstep tick at a time).
        while (not self.is_multiprocess
               and self._overflow is not None
               and int(self._overflow.valid.sum()) > self.max_overflow_events):
            # the caller only sees the LAST step; materialize the alerts of
            # the step that is about to be superseded so they aren't lost
            self._stash_pending_alerts(
                self._materialize_routed(routed_batch, outputs))
            backlog = self._overflow
            self._overflow = None
            self.drain_steps += 1
            self._metrics.counter("overflow.drain_steps").inc()
            prepared, over_rows = self._prepare_step(backlog)
            routed_batch, outputs = self._one_step(params, prepared)
            self.park_overflow(backlog, over_rows)
        return routed_batch, outputs

    def _prepare_step(self, batch: EventBatch, age=None
                      ) -> Tuple["_PreparedStep", np.ndarray]:
        """Host half of one step's routing decision. Device-routing mode:
        when the flat batch fits the mesh's fixed lanes (cheap bincount
        guard, ops/route.py), pack it UNROUTED — the mesh routes it — and
        no overflow is possible. Otherwise (skew past lane capacity, a
        merged backlog longer than the global batch, host-routing mode):
        the host arena route, whose overflow rows requeue as always —
        the bounded, loudly-counted spill path."""
        rec = self.flight.begin_step(engine=self.name)
        if age is not None:
            # ingest-age sidecar rides the flight record through the
            # stage_prepared/dispatch_staged handoffs (feeder threads);
            # _materialize_routed closes it (runtime/eventage.py)
            rec.age = age
        self._sample_tenant_mix(rec, batch)
        if self.device_routing and self._device_route_fits(batch):
            self.device_route_steps += 1
            self._metrics.counter("route.device_steps").inc()
            rec.begin_stage("route_device")
            fault_point("pack_fail")
            blob = self._pack_flat_blob(batch)
            rec.end_stage("route_device")
            self._stage_hist.observe(rec.stage_s("route_device"),
                                     engine=self.name, stage="route_device")
            return (_PreparedStep("device", blob, flight=rec),
                    np.empty(0, np.int64))
        if self.device_routing:
            self.device_route_fallbacks += 1
            self._metrics.counter("route.host_fallbacks").inc()
        rec.begin_stage("route_host")
        fault_point("pack_fail")
        routed_blob, over_rows = self.router.route_batch(batch)
        rec.end_stage("route_host")
        self._stage_hist.observe(rec.stage_s("route_host"),
                                 engine=self.name, stage="route_host")
        return _PreparedStep("host", routed_blob, flight=rec), over_rows

    def _device_route_fits(self, batch: EventBatch) -> bool:
        from sitewhere_tpu.ops.route import host_fits_device_route

        n = batch.device_idx.shape[0]
        if n > self.batch_size * self.n_shards:
            return False  # longer than the global batch: host path requeues
        return host_fits_device_route(
            batch.device_idx, batch.valid, self.n_shards, self.batch_size,
            self.route_lane_capacity)

    def _pack_flat_blob(self, batch: EventBatch) -> np.ndarray:
        """Pack a flat batch into the UNROUTED [wire_rows, S*B] staging
        blob (pooled when the mesh is an accelerator), zero-padding short
        batches to the global width. This—plus one device_put—is ALL the
        host does per step in device-routing mode."""
        from sitewhere_tpu.ops.pack import batch_to_blob, wire_variant_for

        G = self.batch_size * self.n_shards
        rows, ts_base = wire_variant_for(batch)
        rows, ts_base = self.router._routable_variant(rows, ts_base)
        buf = self.router.flat_staging_buffer(rows)
        n = batch.device_idx.shape[0]
        if n == G:
            return batch_to_blob(batch, out=buf, wire_rows=rows)
        small = batch_to_blob(batch, wire_rows=rows)
        if buf is None:
            buf = np.empty((small.shape[0], G), np.int32)
        buf[:, :n] = small
        buf[:, n:] = 0
        return buf

    @staticmethod
    def _slice_flat(batch: EventBatch,
                    rows: np.ndarray) -> Optional[EventBatch]:
        if len(rows) == 0:
            return None
        return jax.tree_util.tree_map(lambda a: np.asarray(a)[rows], batch)

    # -- overflow backlog (shared by submit and the pipelined feeder) ------

    def merge_pending_overflow(self, batch: EventBatch) -> EventBatch:
        """Fold the parked overflow backlog AHEAD of `batch` (per-device
        order: requeued rows predate the new batch's rows) and clear it.
        The merge is an arena concat — the returned batch is a set of
        views into reused buffers, valid until the next merge; route it
        immediately."""
        if self._overflow is None:
            return batch
        merged = self._merge_arena.concat([self._overflow, batch])
        self._overflow = None
        return merged

    def park_overflow(self, batch: EventBatch, over_rows: np.ndarray) -> None:
        """Park `batch`'s capacity-overflow rows (flat indices from
        route_batch) for the next merge. Fancy-index copies — safe even
        when `batch` is an arena view about to be overwritten."""
        self._overflow = self._slice_flat(batch, over_rows)

    def _one_step(self, params, prepared: "_PreparedStep"
                  ) -> Tuple["RoutedBlobView", ProcessOutputs]:
        return self.dispatch_staged(params, self.stage_prepared(prepared))

    def stage_prepared(self, prepared: "_PreparedStep",
                       order: Optional[int] = None,
                       use_ring: bool = True) -> "_StagedStep":
        """Start the host->mesh transfer of a prepared step WITHOUT
        dispatching it. device_put is async on accelerator runtimes, so a
        pipelined feeder can overlap this staging (and the host prep that
        produced the blob) with the previous step's device execution —
        the sharded half of pipeline/feed.py's double-buffered contract.
        Returns a staged handle for dispatch_staged; a pooled blob's
        release is wired there (its H2D guard is the step's output).

        The transfer goes through the H2D staging ring: `order` is the
        feeder's sequence so slots are granted in dispatch order, and
        overflow-drain blobs bypass the ring (`use_ring=False`) — a
        drain blob dispatches before its step reaches the ready heap, so
        blocking on a slot held by its own siblings would self-deadlock;
        the first blob of each step still provides the backpressure."""
        rec = prepared.flight
        if prepared.kind == "device":
            # UNROUTED flat blob, split along the LANE axis: shard i's
            # chunk is flat lanes [i*B, (i+1)*B) — the routing prologue
            # inside the step exchanges rows to their owners
            flat = NamedSharding(self.mesh, P(None, SHARD_AXIS))
            slot = self._acquire_staging_slot(rec, order, use_ring)
            if rec is not None:
                rec.begin_stage("h2d")
            try:
                blob = self._h2d_with_retry(
                    lambda: jax.device_put(prepared.blob, flat))
            except BaseException:
                if slot is not None:
                    self.staging_ring.release(slot)
                raise
            finally:
                if rec is not None:
                    rec.end_stage("h2d")
            if slot is not None:
                slot.device_blob = blob
            view = DeviceRoutedView(prepared.blob, self.router)
            return _StagedStep(blob, view, prepared.blob, prepared.blob,
                               kind="device", flight=rec, slot=slot)
        return self.stage_routed_blob(prepared.blob, flight_rec=rec,
                                      order=order, use_ring=use_ring)

    def stage_routed_blob(self, routed_blob: np.ndarray,
                          flight_rec=None, order: Optional[int] = None,
                          use_ring: bool = True) -> "_StagedStep":
        """Start the host->mesh transfer of a HOST-routed [S, WIRE_ROWS,
        B] blob (see stage_prepared; this is the host-arena half, and the
        only one multi-process feeding uses)."""
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        slot = self._acquire_staging_slot(flight_rec, order, use_ring)
        if flight_rec is not None:
            flight_rec.begin_stage("h2d")
        try:
            if self.is_multiprocess:
                # Per-host feeding (the multi-host jax data contract): this
                # process stages ONLY its local shards' rows; rows routed to
                # shards on other processes are stashed for take_foreign()
                # (the caller forwards them over the bus edge —
                # at-least-once, never dropped here).
                local = self.local_shards
                self._stash_foreign(routed_blob)
                local_blob = np.ascontiguousarray(routed_blob[local])
                # the view holds the local copy; the pooled routed blob is
                # fully consumed at this point and can go back on the shelf
                self.router.release_staging_buffer(routed_blob)
                blob = self._h2d_with_retry(
                    lambda: jax.make_array_from_process_local_data(
                        shard0, local_blob, routed_blob.shape))
                view = RoutedBlobView(local_blob, shard_ids=local)
                counted = local_blob
            else:
                blob = self._h2d_with_retry(
                    lambda: jax.device_put(routed_blob, shard0))
                # release wired after the step runs, carrying the step
                # output as the transfer-completion guard
                view = RoutedBlobView(routed_blob)
                counted = routed_blob
        except BaseException:
            if slot is not None:
                self.staging_ring.release(slot)
            raise
        finally:
            if flight_rec is not None:
                flight_rec.end_stage("h2d")
        if slot is not None:
            slot.device_blob = blob
        return _StagedStep(blob, view, counted, routed_blob,
                           flight=flight_rec, slot=slot)

    def dispatch_staged(self, params, staged: "_StagedStep"
                        ) -> Tuple["RoutedBlobView", ProcessOutputs]:
        """Dispatch the fused collective step on a staged blob (state
        donation preserved — the jitted program is unchanged)."""
        from sitewhere_tpu.ops.pack import _VALID_SHIFT

        view = staged.view
        step = (self._sharded_step_device if staged.kind == "device"
                else self._sharded_step)
        rec = staged.flight
        if rec is None:
            rec = self.flight.begin_step(engine=self.name)
        rec.begin_stage("dispatch")
        # h2d_error is staged separately here (stage_prepared /
        # stage_routed_blob) — only the dispatch point arms on this edge
        try:
            outputs = self._dispatch_with_retry(
                lambda: step(params, self._state, self._rule_state,
                             self._model_state, self._actuation_state,
                             staged.blob),
                points=("dispatch_error",))
        except BaseException:
            if staged.slot is not None:
                # guard-free: a failed step never recycles the slot's
                # array into anything — next reuse just drops it
                self.staging_ring.release(staged.slot)
            raise
        rec.end_stage("dispatch")
        if staged.slot is not None:
            # the step executed => its input transfer completed; the
            # output's readiness is the slot's reuse guard
            self.staging_ring.release(staged.slot, outputs.processed)
        self._flight_last = rec
        self._stage_hist.observe(rec.stage_s("dispatch"),
                                 engine=self.name, stage="dispatch")
        if not self.is_multiprocess and staged.routed_blob is not None:
            # pooled-blob loan (routed OR flat): returns on view GC;
            # outputs.processed is the transfer-completion guard (step
            # executed => input read)
            view._release = partial(self.router.release_staging_buffer,
                                    staged.routed_blob, outputs.processed)
        self.batches_processed += 1
        # rows actually stepped BY THIS PROCESS this call: overflow rows
        # are counted by the step that eventually carries them, so each
        # event marks exactly once. Counted from the blob head bits — the
        # full column unpack is deferred until alert materialization
        # actually needs it (most steps don't), which was ~25% of sharded
        # submit host time.
        n_events = int(
            ((staged.counted[..., 0, :] >> _VALID_SHIFT) & 1).sum())
        rec.events = n_events
        self._metrics.meter("events").mark(n_events)
        return view, outputs

    def _stash_foreign(self, routed_blob: np.ndarray) -> None:
        """Extract valid rows routed to NON-local shards as a flat batch
        with GLOBAL device indices; accumulate for take_foreign()."""
        from sitewhere_tpu.ops.pack import _VALID_SHIFT, blob_to_batch_np
        from sitewhere_tpu.parallel.router import concat_flat_batches

        others = [s for s in range(self.n_shards)
                  if s not in set(self.local_shards)]
        if not others:
            return
        sub = routed_blob[others]                       # [F, 5, B]
        if not ((sub[:, 0, :] >> _VALID_SHIFT) & 1).any():
            return
        batch = blob_to_batch_np(sub)                   # local dev indices
        shard_of = np.repeat(np.array(others, np.int32), sub.shape[-1])
        flat = jax.tree_util.tree_map(
            lambda a: np.asarray(a).reshape((-1,) + np.asarray(a).shape[2:]),
            batch)
        flat = flat.replace(
            device_idx=flat.device_idx * self.n_shards + shard_of)
        rows = np.nonzero(flat.valid)[0]
        flat = jax.tree_util.tree_map(lambda a: a[rows], flat)
        self._foreign = (flat if getattr(self, "_foreign", None) is None
                         else concat_flat_batches([self._foreign, flat]))

    def submit_routed(self, batch: EventBatch, age=None):
        """See PipelineEngine.submit_routed: sharded submit already returns
        (routed [S, B] batch, outputs)."""
        return self.submit(batch, age=age)

    def materialize_alerts(self, routed_batch: EventBatch,
                           outputs: ProcessOutputs,
                           max_alerts: Optional[int] = None
                           ) -> List[DeviceAlert]:
        """Alerts for the last submit, plus any stashed during overflow
        drain steps (see submit())."""
        pending, self._pending_alerts = self._pending_alerts, []
        return pending + self._materialize_routed(routed_batch, outputs,
                                                  max_alerts)

    def _gather_local(self, arr) -> np.ndarray:
        """Local [S_local, B, ...] block of a shard-axis-sharded output —
        each process materializes its own shards' rows only (np.asarray on
        the global array would require non-addressable shards)."""
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)

    def _materialize_routed(self, routed_batch,
                            outputs: ProcessOutputs,
                            max_alerts: Optional[int] = None
                            ) -> List[DeviceAlert]:
        """Materialize from the per-shard compacted alert lanes: ONE
        fixed-shape [S, ALERT_LANE_ROWS, K] fetch for the whole mesh
        (the lanes travel shard-axis-sharded with the existing outputs —
        no extra collective). Shards decode shard-major, rows ascending
        within a shard, so the alert order matches the flattened mask
        scan exactly. Accepts the lazy RoutedBlobView (sharded submit's
        return) or a plain routed EventBatch; the wire blob only unpacks
        when something actually fired. Under multi-process feeding the
        lanes gather local shard blocks only — each host materializes the
        alerts of its own devices."""
        from sitewhere_tpu.ops.compact import (
            DecodedAlertLanes, decode_alert_lanes)

        shard_ids = None
        if isinstance(routed_batch, RoutedBlobView):
            shard_ids = routed_batch.shard_ids
        rec = self._flight_last
        if rec is not None:
            rec.begin_stage("lane_fetch")
        if self.is_multiprocess:
            lanes = self._gather_local(outputs.alert_lanes)
            cmd_lanes = self._gather_local(outputs.command_lanes)
        else:
            # [S, ROWS, K] alert lanes + [S, 4, Kc] command lanes
            lanes, cmd_lanes = self._fetch_lanes_with_retry(outputs)
        if rec is not None:
            rec.end_stage("lane_fetch")
            self._stage_hist.observe(rec.stage_s("lane_fetch"),
                                     engine=self.name, stage="lane_fetch")
        self.d2h_fetches += 2
        self.d2h_bytes += lanes.nbytes + cmd_lanes.nbytes
        if rec is not None:
            rec.begin_stage("materialize")
        try:
            decs = [decode_alert_lanes(lanes[s])
                    for s in range(lanes.shape[0])]
            self._account_lane_overflow(
                sum(d.dropped_alerts for d in decs))
            self._account_route_dropped(
                sum(d.route_dropped for d in decs))
            if not any(d.n for d in decs):
                return []
            if isinstance(routed_batch, RoutedBlobView):
                routed_batch = routed_batch.batch
            dev = np.asarray(routed_batch.device_idx)        # [S_rows, B]
            ts = np.asarray(routed_batch.ts)
            S_rows, B = dev.shape
            ids = (np.arange(S_rows, dtype=np.int32) if shard_ids is None
                   else np.array(shard_ids, np.int32))
            # shard-major flat rows + the per-row GLOBAL device remap
            # (local index l on shard s is global l * S + s)
            rows_flat = np.concatenate(
                [s * B + d.rows for s, d in enumerate(decs)])
            shard_of = np.concatenate(
                [np.full(d.n, ids[s], np.int32) for s, d in enumerate(decs)])
            combined = DecodedAlertLanes(
                rows=rows_flat,
                thr_fired=np.concatenate([d.thr_fired for d in decs]),
                geo_fired=np.concatenate([d.geo_fired for d in decs]),
                thr_rule=np.concatenate([d.thr_rule for d in decs]),
                geo_rule=np.concatenate([d.geo_rule for d in decs]),
                thr_level=np.concatenate([d.thr_level for d in decs]),
                geo_level=np.concatenate([d.geo_level for d in decs]),
                fired_rows=sum(d.fired_rows for d in decs),
                dropped_alerts=sum(d.dropped_alerts for d in decs),
                total_alerts=sum(d.total_alerts for d in decs),
                prog_fired=np.concatenate([d.prog_fired for d in decs]),
                prog_rule=np.concatenate([d.prog_rule for d in decs]),
                prog_level=np.concatenate([d.prog_level for d in decs]),
                model_fired=np.concatenate([d.model_fired for d in decs]),
                model_slot=np.concatenate([d.model_slot for d in decs]))
            dev_rows = (dev.reshape(-1)[rows_flat] * self.n_shards
                        + shard_of)
            ts_rows = ts.reshape(-1)[rows_flat]
            bounded = self._bound_alert_rows(combined, max_alerts)
            n = bounded.n
            return self._emit_alerts(bounded, dev_rows[:n], ts_rows[:n])
        finally:
            if rec is not None:
                rec.end_stage("materialize")
                self._stage_hist.observe(
                    rec.stage_s("materialize"),
                    engine=self.name, stage="materialize")
            self._materialize_commands_sharded(cmd_lanes, rec, shard_ids)
            if rec is not None:
                self._close_age(rec)

    def _materialize_commands_sharded(self, cmd_lanes: np.ndarray, rec,
                                      shard_ids) -> None:
        """Decode the per-shard command lanes ([S, 4, Kc], same fetch as
        the alert lanes) and resolve fires with GLOBAL device indices
        (local l on shard s is global l * S + s); accounting, token
        resolution, and fan-out are shared with the single-chip engine.
        Rows remap shard-major like the alert lanes so the fire order
        matches the flattened oracle scan."""
        from sitewhere_tpu.ops.actuate import (
            DecodedCommandLanes, decode_command_lanes)

        if rec is not None:
            rec.begin_stage("actuate")
        try:
            S = cmd_lanes.shape[0]
            ids = (np.arange(S, dtype=np.int32) if shard_ids is None
                   else np.array(shard_ids, np.int32))
            decs = [decode_command_lanes(cmd_lanes[s]) for s in range(S)]
            B = self.batch_size
            combined = DecodedCommandLanes(
                rows=np.concatenate(
                    [s * B + d.rows for s, d in enumerate(decs)]),
                policy_slot=np.concatenate(
                    [d.policy_slot for d in decs]),
                level=np.concatenate([d.level for d in decs]),
                source=np.concatenate([d.source for d in decs]),
                dev=np.concatenate(
                    [d.dev * self.n_shards + ids[s]
                     for s, d in enumerate(decs)]),
                fired=sum(d.fired for d in decs),
                dropped=sum(d.dropped for d in decs),
                debounced=sum(d.debounced for d in decs))
            self._account_command_activity(combined)
            fires = (self._emit_command_fires(combined)
                     if combined.n else [])
            if rec is not None:
                rec.commands = len(fires)
        finally:
            if rec is not None:
                rec.end_stage("actuate")
                self._stage_hist.observe(rec.stage_s("actuate"),
                                         engine=self.name, stage="actuate")
        self._fanout_commands(fires, rec)

    def _account_route_dropped(self, dropped: int) -> None:
        """Defensive on-device route drop accounting (lane counts slot 3,
        ops/route.py): the host lane-fit guard makes this zero on every
        normal step, so any nonzero count is loud — it means a row was
        lost between the guard and the exchange (a bug, not weather)."""
        if not dropped:
            return
        self.device_route_dropped += dropped
        self._metrics.counter("route.device_dropped").inc(dropped)
        import logging
        logging.getLogger("sitewhere.parallel").error(
            "device route dropped %d rows past the %d-slot lanes despite "
            "the host fit guard (device_route_dropped=%d total) — "
            "investigate: the guard and the kernel disagree",
            dropped, self.route_lane_capacity, self.device_route_dropped)

    # -- reads ----------------------------------------------------------------

    _STATE_ROW_FIELDS = ("last_interaction", "present",
                         "presence_missing_since", "event_count",
                         "last_location", "last_location_ts",
                         "last_measurement", "last_measurement_ts",
                         "last_alert_type", "last_alert_level",
                         "last_alert_ts")

    def _state_row(self, idx: int):
        s, l = idx % self.n_shards, idx // self.n_shards

        class Row:
            pass

        row = Row()
        with self._state_lock:  # vs concurrent donation (base __init__)
            state = self._state
            if self.is_multiprocess:
                # Multi-controller jax is SPMD: per-process single-element
                # indexing of a distributed array is NOT a valid program
                # (each process would issue a different computation).
                # Read straight from the addressable shard's host data; a
                # device owned by another host returns None (query that
                # host — device ownership is static, d % S).
                if s not in self.local_shards:
                    return None
                for field_name in self._STATE_ROW_FIELDS:
                    arr = getattr(state, field_name)
                    block = next(
                        sh for sh in arr.addressable_shards
                        if (sh.index[0].start or 0) == s)
                    setattr(row, field_name,
                            np.asarray(block.data)[0, l])
                return row
            for field_name in self._STATE_ROW_FIELDS:
                setattr(row, field_name,
                        np.asarray(getattr(state, field_name)[s, l]))
        return row

    def presence_sweep(self) -> List[str]:
        params = self._ensure_params()
        now_rel = np.int32(self.packer.rel_ts(int(time.time() * 1000)))
        registered = params.assignment_status == 1
        with self._state_lock:
            self._state, newly_missing = self._presence(
                self._state, registered, now_rel,
                np.int32(min(self.presence_missing_interval_ms, 2 ** 31 - 1)))
        if self.is_multiprocess:
            # each host sweeps (and notifies for) its LOCAL shards only
            missing_np = self._gather_local(newly_missing)
            shard_ids = np.array(self.local_shards, np.int32)
        else:
            missing_np = np.asarray(newly_missing)
            shard_ids = np.arange(self.n_shards, dtype=np.int32)
        rows, locals_ = np.nonzero(missing_np)
        if rows.size == 0:
            return []
        # vectorized: global index = local * S + shard, one fancy index
        # into the cached token array (no per-row token_of loop)
        global_idx = locals_ * self.n_shards + shard_ids[rows]
        tokens = self.registry.devices.token_array()[global_idx].tolist()
        return [t for t in tokens if t]

    # -- elastic checkpoint layout ----------------------------------------

    _TENANT_STATE_FIELDS = ("tenant_event_count", "tenant_alert_count")

    def canonical_state(self) -> DeviceStateTensors:
        """Flat device-major snapshot: device-indexed tensors un-shard via
        the router layout (global d lives at (d % S, d // S)); per-shard
        tenant counters are additive and sum to the global totals. The
        result is bit-identical to a single-chip engine that processed the
        same events — a checkpoint taken on ANY mesh restores onto ANY
        other (elastic recovery)."""
        import dataclasses as _dc

        import jax.numpy as jnp

        if self.is_multiprocess:
            # graceful degradation, not a 500 traceback: a live multi-host
            # canonical gather would need a collective inside the lockstep
            # protocol. SiteWhereError carries a structured code +
            # http_status, so the REST layer surfaces the offline recipe
            # as a 409 with the command the operator actually needs.
            from sitewhere_tpu.errors import ErrorCode, SiteWhereError

            raise SiteWhereError(
                "multi-host canonical gather is not available on a live "
                "cluster (it would need a collective inside the lockstep "
                "protocol); each host saves its own shard blocks "
                "(local_state_shards — no collective, any host any time). "
                "Merge every host's checkpoint into the canonical "
                "any-topology snapshot offline with the assemble-checkpoint "
                "recipe: `python -m sitewhere_tpu assemble-checkpoint "
                "<host0-ckpt> <host1-ckpt> ... --out <dir>`",
                ErrorCode.GENERIC, http_status=409)
        # device-side copy under the lock only (see base canonical_state);
        # the D2H gather + host re-layout run outside it
        with self._state_lock:
            snap = jax.tree_util.tree_map(jnp.copy, self._state)
        out = {}
        for f in _dc.fields(snap):
            a = np.asarray(getattr(snap, f.name))
            out[f.name] = (a.sum(0, dtype=a.dtype)
                           if f.name in self._TENANT_STATE_FIELDS
                           else self.router.unshard_param(a))
        return DeviceStateTensors(**out)

    def _canonical_shape_of(self, field_name: str):
        # resident layout is stacked [S, L, ...]; canonical flattens the
        # device axes ([S*L, ...]); tenant counters lose the shard axis
        c = getattr(self._state, field_name).shape
        if field_name in self._TENANT_STATE_FIELDS:
            return c[1:]
        return (c[0] * c[1],) + tuple(c[2:])

    def load_canonical_state(self, state: DeviceStateTensors) -> None:
        """Re-shard a flat snapshot onto this engine's mesh. Tenant
        counters (additive) land on shard 0; device tensors re-lay to the
        (d % S, d // S) owner. Dimensions validated by
        _validate_canonical (shared with the single-chip engine)."""
        import dataclasses as _dc

        self._validate_canonical(state)
        S = self.n_shards
        out = {}
        for f in _dc.fields(state):
            a = np.asarray(getattr(state, f.name))
            if f.name in self._TENANT_STATE_FIELDS:
                stacked = np.zeros((S,) + a.shape, a.dtype)
                stacked[0] = a
                out[f.name] = stacked
            else:
                out[f.name] = self.router.shard_param(a)
        stacked_state = DeviceStateTensors(**out)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        with self._state_lock:
            self._state = _put_global_tree(
                stacked_state, _tree_specs(stacked_state, shard0))

    def set_state(self, state: DeviceStateTensors) -> None:
        """The sharded engine's resident layout is stacked [S, D/S, ...];
        checkpoints use the flat canonical layout — there is no native
        set_state. Use load_canonical_state (flat) explicitly."""
        raise TypeError(
            "ShardedPipelineEngine state is mesh-resident; restore flat "
            "canonical snapshots via load_canonical_state()")

    # -- per-host shard checkpoint layout (multi-host gang restart) --------

    def local_state_shards(self):
        """(local shard ids, {field: [S_local, ...] blocks}) — THIS host's
        slice of the device state, read via addressable shards only (pure
        local D2H; no collective, so any host checkpoints at any time
        without lockstep). The per-host complement of canonical_state:
        each host of a gang-restarting cluster saves its own blocks and
        restores them onto the SAME topology (elastic any-mesh restores
        stay the single-controller canonical layout's job)."""
        import dataclasses as _dc

        with self._state_lock:
            state = self._state
            blocks = {}
            for f in _dc.fields(state):
                arr = getattr(state, f.name)
                if self.is_multiprocess:
                    blocks[f.name] = self._gather_local(arr)
                else:
                    blocks[f.name] = np.asarray(arr)
        return list(self.local_shards), blocks

    def load_local_state_shards(self, shard_ids, blocks) -> None:
        """Inverse of local_state_shards on the same mesh topology: place
        this host's blocks back onto its local devices
        (make_array_from_process_local_data — local transfers only)."""
        import dataclasses as _dc

        if list(shard_ids) != list(self.local_shards):
            raise ValueError(
                f"host-shard checkpoint was taken for shards {shard_ids}; "
                f"this process owns {self.local_shards} — per-host "
                f"checkpoints restore onto the same cluster topology only "
                f"(use a single-controller canonical checkpoint to change "
                f"topology)")
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        out = {}
        for f in _dc.fields(DeviceStateTensors):
            local = np.ascontiguousarray(blocks[f.name])
            expect = getattr(self._state, f.name).shape
            global_shape = (self.n_shards,) + tuple(local.shape[1:])
            if tuple(global_shape) != tuple(expect):
                raise ValueError(
                    f"host-shard checkpoint field {f.name}: global shape "
                    f"{global_shape} != engine {tuple(expect)}")
            if self.is_multiprocess:
                out[f.name] = jax.make_array_from_process_local_data(
                    shard0, local, global_shape)
            else:
                out[f.name] = jax.device_put(local, shard0)
        with self._state_lock:
            self._state = DeviceStateTensors(**out)

    # -- rule-program state layouts ----------------------------------------

    _RULE_STATE_DEVICE_FIELDS = ("slab",)
    _RULE_STATE_PROGRAM_FIELDS = ("gen", "fire_count", "suppress_count")

    def canonical_rule_state(self):
        """Flat device-major rule-program state snapshot, mirroring
        canonical_state: device-indexed lanes un-shard via the router
        layout; per-shard fire/suppress counters (additive partials) sum;
        `gen` takes the per-slot max (every shard steps in lockstep, so
        they agree whenever a step has run since the last install)."""
        import dataclasses as _dc

        import jax.numpy as jnp

        if self._rule_state is None:
            return None
        if self.is_multiprocess:
            from sitewhere_tpu.errors import ErrorCode, SiteWhereError

            raise SiteWhereError(
                "multi-host canonical gather is not available on a live "
                "cluster; merge per-host checkpoints offline with "
                "assemble-checkpoint", ErrorCode.GENERIC, http_status=409)
        with self._state_lock:
            snap = jax.tree_util.tree_map(jnp.copy, self._rule_state)
        out = {}
        for f in _dc.fields(snap):
            a = np.asarray(getattr(snap, f.name))
            if f.name in ("fire_count", "suppress_count"):
                out[f.name] = a.sum(0, dtype=a.dtype)
            elif f.name == "gen":
                out[f.name] = a.max(0)
            else:
                out[f.name] = self.router.unshard_param(a)
        from sitewhere_tpu.ops.stateful import RuleStateTensors
        return RuleStateTensors(**out)

    def load_canonical_rule_state(self, rule_state) -> None:
        import dataclasses as _dc

        from sitewhere_tpu.ops.stateful import RuleStateTensors

        self._validate_canonical_rule_state(rule_state)
        S = self.n_shards
        out = {}
        for f in _dc.fields(RuleStateTensors):
            a = np.asarray(getattr(rule_state, f.name))
            if f.name in self._RULE_STATE_PROGRAM_FIELDS:
                stacked = np.zeros((S,) + a.shape, a.dtype)
                if f.name == "gen":
                    # generations must match on EVERY shard or the next
                    # step's stale check would wipe the restored state
                    stacked[:] = a
                else:
                    stacked[0] = a  # additive counters land on shard 0
                out[f.name] = stacked
            else:
                out[f.name] = self.router.shard_param(a)
        stacked_state = RuleStateTensors(**out)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        with self._state_lock:
            self._rule_state = _put_global_tree(
                stacked_state, _tree_specs(stacked_state, shard0))
            self._rule_state_built_dims = self._rule_state_dims()

    def local_rule_state_blocks(self):
        """THIS host's shard blocks of the rule-program state (the
        per-host complement of canonical_rule_state; same contract as
        local_state_shards — pure local D2H, no collective)."""
        import dataclasses as _dc

        if self._rule_state is None:
            return None
        with self._state_lock:
            blocks = {}
            for f in _dc.fields(self._rule_state):
                arr = getattr(self._rule_state, f.name)
                blocks[f.name] = (self._gather_local(arr)
                                  if self.is_multiprocess
                                  else np.asarray(arr))
        return blocks

    def load_local_rule_state_blocks(self, blocks) -> None:
        import dataclasses as _dc

        from sitewhere_tpu.ops.stateful import RuleStateTensors

        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        S = self.n_shards
        canonical = self._expected_rule_state_shapes()
        out = {}
        for f in _dc.fields(RuleStateTensors):
            local = np.ascontiguousarray(blocks[f.name])
            flat = canonical[f.name]
            expect = ((S, flat[0] // S) + flat[1:]
                      if f.name not in self._RULE_STATE_PROGRAM_FIELDS
                      else (S,) + flat)
            global_shape = (S,) + tuple(local.shape[1:])
            if tuple(global_shape) != tuple(expect):
                raise ValueError(
                    f"host-shard rule-state field {f.name}: global shape "
                    f"{global_shape} != engine {tuple(expect)}")
            if self.is_multiprocess:
                out[f.name] = jax.make_array_from_process_local_data(
                    shard0, local, global_shape)
            else:
                out[f.name] = jax.device_put(local, shard0)
        with self._state_lock:
            self._rule_state = RuleStateTensors(**out)
            self._rule_state_built_dims = self._rule_state_dims()

    # -- anomaly-model state layouts ---------------------------------------

    _MODEL_STATE_DEVICE_FIELDS = ("slab",)
    _MODEL_STATE_MODEL_FIELDS = ("gen", "fire_count", "eval_count")

    def canonical_model_state(self):
        """Flat device-major anomaly-model state snapshot, mirroring
        canonical_rule_state: device-indexed feature lanes un-shard via
        the router layout; per-shard fire/eval counters (additive
        partials) sum; `gen` takes the per-slot max (shards step in
        lockstep, so they agree whenever a step has run since the last
        install)."""
        import dataclasses as _dc

        import jax.numpy as jnp

        if self._model_state is None:
            return None
        if self.is_multiprocess:
            from sitewhere_tpu.errors import ErrorCode, SiteWhereError

            raise SiteWhereError(
                "multi-host canonical gather is not available on a live "
                "cluster; merge per-host checkpoints offline with "
                "assemble-checkpoint", ErrorCode.GENERIC, http_status=409)
        with self._state_lock:
            snap = jax.tree_util.tree_map(jnp.copy, self._model_state)
        out = {}
        for f in _dc.fields(snap):
            a = np.asarray(getattr(snap, f.name))
            if f.name in ("fire_count", "eval_count"):
                out[f.name] = a.sum(0, dtype=a.dtype)
            elif f.name == "gen":
                out[f.name] = a.max(0)
            else:
                out[f.name] = self.router.unshard_param(a)
        from sitewhere_tpu.ops.anomaly import ModelStateTensors
        return ModelStateTensors(**out)

    def load_canonical_model_state(self, model_state) -> None:
        import dataclasses as _dc

        from sitewhere_tpu.ops.anomaly import ModelStateTensors

        self._validate_canonical_model_state(model_state)
        S = self.n_shards
        out = {}
        for f in _dc.fields(ModelStateTensors):
            a = np.asarray(getattr(model_state, f.name))
            if f.name in self._MODEL_STATE_MODEL_FIELDS:
                stacked = np.zeros((S,) + a.shape, a.dtype)
                if f.name == "gen":
                    # generations must match on EVERY shard or the next
                    # step's stale check would wipe the restored rows
                    stacked[:] = a
                else:
                    stacked[0] = a  # additive counters land on shard 0
                out[f.name] = stacked
            else:
                out[f.name] = self.router.shard_param(a)
        stacked_state = ModelStateTensors(**out)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        with self._state_lock:
            self._model_state = _put_global_tree(
                stacked_state, _tree_specs(stacked_state, shard0))
            self._model_state_built_dims = self._model_state_dims()

    def local_model_state_blocks(self):
        """THIS host's shard blocks of the anomaly-model state (the
        per-host complement of canonical_model_state; same contract as
        local_state_shards — pure local D2H, no collective)."""
        import dataclasses as _dc

        if self._model_state is None:
            return None
        with self._state_lock:
            blocks = {}
            for f in _dc.fields(self._model_state):
                arr = getattr(self._model_state, f.name)
                blocks[f.name] = (self._gather_local(arr)
                                  if self.is_multiprocess
                                  else np.asarray(arr))
        return blocks

    def load_local_model_state_blocks(self, blocks) -> None:
        import dataclasses as _dc

        from sitewhere_tpu.ops.anomaly import ModelStateTensors

        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        S = self.n_shards
        canonical = self._expected_model_state_shapes()
        out = {}
        for f in _dc.fields(ModelStateTensors):
            local = np.ascontiguousarray(blocks[f.name])
            flat = canonical[f.name]
            expect = ((S, flat[0] // S) + flat[1:]
                      if f.name not in self._MODEL_STATE_MODEL_FIELDS
                      else (S,) + flat)
            global_shape = (S,) + tuple(local.shape[1:])
            if tuple(global_shape) != tuple(expect):
                raise ValueError(
                    f"host-shard model-state field {f.name}: global shape "
                    f"{global_shape} != engine {tuple(expect)}")
            if self.is_multiprocess:
                out[f.name] = jax.make_array_from_process_local_data(
                    shard0, local, global_shape)
            else:
                out[f.name] = jax.device_put(local, shard0)
        with self._state_lock:
            self._model_state = ModelStateTensors(**out)
            self._model_state_built_dims = self._model_state_dims()

    _ACTUATION_STATE_POLICY_FIELDS = ("gen", "fire_count", "debounce_count")

    def canonical_actuation_state(self):
        """Flat device-major actuation debounce-state snapshot, mirroring
        canonical_model_state: device-indexed slab lanes un-shard via the
        router layout; per-shard fire/debounce counters (additive
        partials) sum; `gen` takes the per-slot max."""
        import dataclasses as _dc

        import jax.numpy as jnp

        if self._actuation_state is None:
            return None
        if self.is_multiprocess:
            from sitewhere_tpu.errors import ErrorCode, SiteWhereError

            raise SiteWhereError(
                "multi-host canonical gather is not available on a live "
                "cluster; merge per-host checkpoints offline with "
                "assemble-checkpoint", ErrorCode.GENERIC, http_status=409)
        with self._state_lock:
            snap = jax.tree_util.tree_map(jnp.copy, self._actuation_state)
        out = {}
        for f in _dc.fields(snap):
            a = np.asarray(getattr(snap, f.name))
            if f.name in ("fire_count", "debounce_count"):
                out[f.name] = a.sum(0, dtype=a.dtype)
            elif f.name == "gen":
                out[f.name] = a.max(0)
            else:
                out[f.name] = self.router.unshard_param(a)
        from sitewhere_tpu.ops.actuate import ActuationStateTensors
        return ActuationStateTensors(**out)

    def load_canonical_actuation_state(self, actuation_state) -> None:
        import dataclasses as _dc

        from sitewhere_tpu.ops.actuate import ActuationStateTensors

        self._validate_canonical_actuation_state(actuation_state)
        S = self.n_shards
        out = {}
        for f in _dc.fields(ActuationStateTensors):
            a = np.asarray(getattr(actuation_state, f.name))
            if f.name in self._ACTUATION_STATE_POLICY_FIELDS:
                stacked = np.zeros((S,) + a.shape, a.dtype)
                if f.name == "gen":
                    # generations must match on EVERY shard or the next
                    # step's stale check would wipe the restored rows
                    stacked[:] = a
                else:
                    stacked[0] = a  # additive counters land on shard 0
                out[f.name] = stacked
            else:
                out[f.name] = self.router.shard_param(a)
        stacked_state = ActuationStateTensors(**out)
        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        with self._state_lock:
            self._actuation_state = _put_global_tree(
                stacked_state, _tree_specs(stacked_state, shard0))
            self._actuation_state_built_dims = self._actuation_state_dims()

    def local_actuation_state_blocks(self):
        """THIS host's shard blocks of the actuation debounce state (the
        per-host complement of canonical_actuation_state; pure local D2H,
        no collective)."""
        import dataclasses as _dc

        if self._actuation_state is None:
            return None
        with self._state_lock:
            blocks = {}
            for f in _dc.fields(self._actuation_state):
                arr = getattr(self._actuation_state, f.name)
                blocks[f.name] = (self._gather_local(arr)
                                  if self.is_multiprocess
                                  else np.asarray(arr))
        return blocks

    def load_local_actuation_state_blocks(self, blocks) -> None:
        import dataclasses as _dc

        from sitewhere_tpu.ops.actuate import ActuationStateTensors

        shard0 = NamedSharding(self.mesh, P(SHARD_AXIS))
        S = self.n_shards
        canonical = self._expected_actuation_state_shapes()
        out = {}
        for f in _dc.fields(ActuationStateTensors):
            local = np.ascontiguousarray(blocks[f.name])
            flat = canonical[f.name]
            expect = ((S, flat[0] / S) + flat[1:]
                      if f.name not in self._ACTUATION_STATE_POLICY_FIELDS
                      else (S,) + flat)
            global_shape = (S,) + tuple(local.shape[1:])
            if tuple(global_shape) != tuple(expect):
                raise ValueError(
                    f"host-shard actuation-state field {f.name}: global "
                    f"shape {global_shape} != engine {tuple(expect)}")
            if self.is_multiprocess:
                out[f.name] = jax.make_array_from_process_local_data(
                    shard0, local, global_shape)
            else:
                out[f.name] = jax.device_put(local, shard0)
        with self._state_lock:
            self._actuation_state = ActuationStateTensors(**out)
            self._actuation_state_built_dims = self._actuation_state_dims()

    def pending_overflow_batch(self) -> Optional[EventBatch]:
        """The parked overflow rows as a flat host batch (checkpoint saves
        them verbatim when draining is impossible — multi-host lockstep)."""
        return self._overflow

    def set_pending_overflow_batch(self, batch: Optional[EventBatch]) -> None:
        self._overflow = batch

    def drain_pending(self) -> int:
        """Fold any parked overflow backlog into device state (empty-batch
        drain steps). Checkpoint save calls this first: backlogged rows'
        bus offsets may already be committed, so a snapshot that omitted
        them would break the offsets<=state invariant. Alerts fired by the
        drained events stash on _pending_alerts (picked up by the next
        materialize_alerts; PipelineCheckpointer.save also persists the
        stash in the manifest, so a crash before pickup recovers them)
        with the same bounded-room accounting as submit()'s internal
        drain — never silently lost. Returns the number of drain steps
        run."""
        from sitewhere_tpu.ops.pack import empty_batch

        if self.is_multiprocess:
            # a host-local drain loop would run a varying number of
            # collective steps per host (lockstep violation); the cluster
            # checkpoint instead snapshots the pending overflow batch
            # itself (parallel/cluster.py checkpoint path)
            raise RuntimeError(
                "drain_pending is single-controller only; multi-host "
                "checkpoints persist the overflow batch in the manifest")
        steps = 0
        while self.pending_overflow > 0:
            routed, outputs = self.submit(empty_batch(1))
            self._stash_pending_alerts(
                self._materialize_routed(routed, outputs))
            steps += 1
        return steps

    def drain_parked(self) -> List[DeviceAlert]:
        if not self.is_multiprocess:  # lockstep hosts drain per tick
            self.drain_pending()
        return super().drain_parked()

    def _stash_pending_alerts(self, alerts: List[DeviceAlert]) -> None:
        """Bounded-room stash shared by submit()'s internal drain and
        drain_pending: overflow past max_pending_alerts is counted on
        alerts_dropped, never silently truncated."""
        room = self.max_pending_alerts - len(self._pending_alerts)
        if len(alerts) > room:
            dropped = len(alerts) - max(0, room)
            self.alerts_dropped += dropped
            self._metrics.counter("alerts.dropped").inc(dropped)
        self._pending_alerts.extend(alerts[:max(0, room)])

    @property
    def pending_overflow(self) -> int:
        return 0 if self._overflow is None else int(self._overflow.valid.sum())

    def stats(self):
        with self._state_lock:  # tenant-count reads vs donation
            s = self._state
            if self.is_multiprocess:
                # per-process view: counts of THIS host's shards (global
                # totals need an allgather; tenant psums per step already
                # travel replicated in ProcessOutputs.tenant_counts)
                tenant_events = self._gather_local(
                    s.tenant_event_count).sum(0).tolist()
                tenant_alerts = self._gather_local(
                    s.tenant_alert_count).sum(0).tolist()
            else:
                tenant_events = np.asarray(
                    s.tenant_event_count).sum(0).tolist()
                tenant_alerts = np.asarray(
                    s.tenant_alert_count).sum(0).tolist()
        return {
            "batches": self.batches_processed,
            "dropped": self.total_dropped,
            "drain_steps": self.drain_steps,
            "pending_overflow": self.pending_overflow,
            # on-device shard routing accounting (ops/route.py):
            # fallbacks = steps the skew guard spilled to the host arena
            # path; route_dropped stays 0 unless guard and kernel disagree
            "device_routing": self.device_routing,
            "device_route_steps": self.device_route_steps,
            "device_route_fallbacks": self.device_route_fallbacks,
            "device_route_dropped": self.device_route_dropped,
            "tenant_event_count": tenant_events,
            "tenant_alert_count": tenant_alerts,
            # multi-process: tenant totals above cover THIS host's shards
            # only (global totals need an allgather); REST/admin readers
            # must not misread per-host partials as global
            "scope": "local" if self.is_multiprocess else "global",
        }
