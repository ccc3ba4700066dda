"""PipelineEngine: host orchestrator for the fused TPU step.

Owns the jitted `process_batch`, the HBM device-state, the registry tensor
mirror, and the compiled rule tables; refreshes device-side params when the
registry or rules change (version counter — the reference reacts to ZK config
watches and Kafka model-update topics the same way); materializes rule-fired
alerts back into API-level DeviceAlert events; runs the presence sweep.

This is the rebuild of the *composition* of service-inbound-processing +
service-rule-processing + service-device-state (their per-service manager
classes collapse into one engine because the stages fused into one step).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.model import DeviceAlert, AlertLevel, AlertSource, DeviceState, PresenceState
from sitewhere_tpu.model.event import DeviceEventType
from sitewhere_tpu.ops.geofence import GeofenceCondition, GeofenceRuleTable, ZoneTable, empty_geofence_table
from sitewhere_tpu.ops.pack import (
    EventBatch, EventPacker, batch_to_blob, blob_to_batch)
from sitewhere_tpu.ops.threshold import ThresholdOp, ThresholdRuleTable, empty_threshold_table
from sitewhere_tpu.pipeline.staging import StagedBlob, StagingRing
from sitewhere_tpu.pipeline.state_tensors import DeviceStateTensors, init_device_state
from sitewhere_tpu.pipeline.step import PipelineParams, ProcessOutputs, check_presence, process_batch
from sitewhere_tpu.registry.tensors import RegistryTensors
from sitewhere_tpu.runtime.bus import jittered
from sitewhere_tpu.runtime.eventage import age_histogram, observe_summary
from sitewhere_tpu.runtime.faults import fault_point
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent
from sitewhere_tpu.runtime.flight import GLOBAL_FLIGHT
from sitewhere_tpu.runtime.metrics import GLOBAL_METRICS

_NEG = -(2 ** 31)

# int -> AlertLevel member (enum __call__ costs ~1 us/row in a storm;
# the materialize hot loop indexes this instead)
_ALERT_LEVELS = {int(level): level for level in AlertLevel}


def materialize_alerts_maskscan(engine, batch, outputs,
                                ) -> List[DeviceAlert]:
    """The pre-lane mask-scan materializer, kept verbatim as the
    differential-test oracle and micro-bench reference for the
    device-compacted alert lanes (docs/ALERT_LANES.md): fetch the
    per-row mask/level/rule arrays (two phases on big batches), nonzero
    the fired mask on the host, and walk fired rows with per-row
    `token_of` lookups. Flat batches/outputs only (the sharded engine
    flattens before delegating — tests do the same); returns ALL fired
    rows' alerts and never touches engine counters or pending stashes.
    Rule-program fires (outputs.program_*) emit after the per-row
    threshold/geofence alerts, and anomaly-model fires (outputs.model_*)
    after those — the same within-row order the lane materializer
    uses."""
    small_batch = outputs.threshold_fired.size <= 16384
    if small_batch:
        (thr_fired, geo_fired, prog_fired, model_fired,
         thr_level, geo_level, prog_level,
         thr_rule, geo_rule, prog_rule, model_first) = jax.device_get(
            (outputs.threshold_fired, outputs.geofence_fired,
             outputs.program_fired, outputs.model_fired,
             outputs.threshold_alert_level, outputs.geofence_alert_level,
             outputs.program_alert_level,
             outputs.threshold_first_rule, outputs.geofence_first_rule,
             outputs.program_first_rule, outputs.model_first))
    else:
        thr_fired, geo_fired, prog_fired, model_fired = jax.device_get(
            (outputs.threshold_fired, outputs.geofence_fired,
             outputs.program_fired, outputs.model_fired))
    fired_rows = np.nonzero(thr_fired | geo_fired | prog_fired
                            | model_fired)[0]
    if fired_rows.size == 0:
        return []
    if not small_batch:
        (thr_level, geo_level, prog_level, thr_rule, geo_rule,
         prog_rule, model_first) = jax.device_get(
            (outputs.threshold_alert_level, outputs.geofence_alert_level,
             outputs.program_alert_level,
             outputs.threshold_first_rule, outputs.geofence_first_rule,
             outputs.program_first_rule, outputs.model_first))
    device_idx = np.asarray(batch.device_idx)
    ts = np.asarray(batch.ts)
    rules = engine.list_rules()
    thr_rules, geo_rules = rules["threshold"], rules["geofence"]
    programs = engine.rule_programs_by_slot()
    models = engine.anomaly_models_by_slot()
    alerts: List[DeviceAlert] = []
    for row in fired_rows:
        token = engine.registry.devices.token_of(int(device_idx[row])) or ""
        if thr_fired[row] and 0 <= thr_rule[row] < len(thr_rules):
            rule = thr_rules[int(thr_rule[row])]
            alerts.append(DeviceAlert(
                device_id=token, source=AlertSource.SYSTEM,
                level=AlertLevel(int(thr_level[row])), type=rule.alert_type,
                message=rule.alert_message
                or f"threshold rule {rule.token} fired",
                event_date=engine.packer.abs_ts(int(ts[row]))))
        if geo_fired[row] and 0 <= geo_rule[row] < len(geo_rules):
            rule = geo_rules[int(geo_rule[row])]
            alerts.append(DeviceAlert(
                device_id=token, source=AlertSource.SYSTEM,
                level=AlertLevel(int(geo_level[row])), type=rule.alert_type,
                message=rule.alert_message
                or f"geofence rule {rule.token} fired",
                event_date=engine.packer.abs_ts(int(ts[row]))))
        if prog_fired[row] and int(prog_rule[row]) in programs:
            spec = programs[int(prog_rule[row])]
            alerts.append(DeviceAlert(
                device_id=token, source=AlertSource.SYSTEM,
                level=AlertLevel(int(prog_level[row])),
                type=spec["alert_type"],
                message=spec["alert_message"]
                or f"rule program {spec['token']} fired",
                event_date=engine.packer.abs_ts(int(ts[row]))))
        if model_fired[row] and int(model_first[row]) in models:
            # the lane path carries only the model SLOT; level/type come
            # from the installed spec on both paths so they match exactly
            spec = models[int(model_first[row])]
            alerts.append(DeviceAlert(
                device_id=token, source=AlertSource.SYSTEM,
                level=AlertLevel(int(spec["alert_level"])),
                type=spec["alert_type"],
                message=spec["alert_message"]
                or f"anomaly model {spec['token']} fired",
                event_date=engine.packer.abs_ts(int(ts[row]))))
    return alerts


@dataclass
class ThresholdRule:
    """Host-side rule definition; compiled into ThresholdRuleTable rows."""

    token: str
    measurement_name: str = ""       # "" = any
    operator: str = ">"
    threshold: float = 0.0
    alert_type: str = "threshold.violation"
    alert_level: AlertLevel = AlertLevel.WARNING
    alert_message: str = ""
    tenant_token: str = ""           # "" = any
    device_type_token: str = ""      # "" = any
    active: bool = True


@dataclass
class GeofenceRule:
    """Host-side geofence rule (the reference's ZoneTestRuleProcessor config:
    zone token + containment condition + alert to fire)."""

    token: str
    zone_token: str = ""
    # fire when the point is inside|outside the zone, or `enter`: inside
    # it, and the device's previous location was not
    # (ops/geofence.py previous_locations)
    condition: str = "outside"
    alert_type: str = "zone.violation"
    alert_level: AlertLevel = AlertLevel.ERROR
    alert_message: str = ""
    active: bool = True


def rule_to_dict(kind: str, rule) -> Dict:
    """Wire/REST form of a rule: plain JSON types plus a `type` tag."""
    import dataclasses

    data = dataclasses.asdict(rule)
    data["alert_level"] = int(rule.alert_level)
    data["type"] = kind
    return data


def rule_from_dict(data: Dict):
    """(kind, rule) from the wire/REST form; validates against the same
    choices the config metamodel declares (runtime/config_model.py
    rule_processing_model) AND coerces field types — a rule that passes
    here must compile into the rule tables without crashing the hot path.
    Raises SiteWhereError on bad input."""
    from sitewhere_tpu.errors import ErrorCode, SiteWhereError

    kind = data.get("type")
    token = data.get("token") or ""
    if not token or not isinstance(token, str):
        raise SiteWhereError("rule requires a string token",
                             ErrorCode.GENERIC)

    def fields_for(cls):
        import dataclasses

        names = {f.name for f in dataclasses.fields(cls)}
        out = {k: v for k, v in data.items() if k in names and v is not None}
        try:
            if "threshold" in out:
                out["threshold"] = float(out["threshold"])
            if "active" in out:
                out["active"] = bool(out["active"])
            if "alert_level" in out:
                level = out["alert_level"]
                out["alert_level"] = (AlertLevel[level]
                                      if isinstance(level, str)
                                      and not level.lstrip("-").isdigit()
                                      else AlertLevel(int(level)))
        except (KeyError, ValueError, TypeError) as exc:
            raise SiteWhereError(f"invalid rule field value: {exc}",
                                 ErrorCode.GENERIC)
        for name, value in out.items():
            if name not in ("threshold", "active", "alert_level") \
                    and not isinstance(value, str):
                raise SiteWhereError(
                    f"rule field '{name}' must be a string",
                    ErrorCode.GENERIC)
        return out

    if kind == "threshold":
        rule = ThresholdRule(**fields_for(ThresholdRule))
        if rule.operator not in ThresholdOp.BY_NAME:
            raise SiteWhereError(
                f"unknown operator {rule.operator!r} (one of "
                f"{sorted(ThresholdOp.BY_NAME)})", ErrorCode.GENERIC)
        return kind, rule
    if kind == "geofence":
        rule = GeofenceRule(**fields_for(GeofenceRule))
        if rule.condition not in GeofenceCondition.BY_NAME:
            raise SiteWhereError(
                f"geofence condition must be inside|outside|enter, got "
                f"{rule.condition!r}", ErrorCode.GENERIC)
        if not rule.zone_token:
            raise SiteWhereError("geofence rule requires zone_token",
                                 ErrorCode.GENERIC)
        return kind, rule
    raise SiteWhereError(
        f"unknown rule type {kind!r} (threshold|geofence)",
        ErrorCode.GENERIC)


class PipelineEngine(LifecycleComponent):
    """One engine per process; multi-tenant by construction (tenant axis is a
    tensor column, not a separate engine — SURVEY.md §2.5 tenant parallelism).
    """

    def __init__(self, registry_tensors: RegistryTensors, batch_size: int = 8192,
                 measurement_slots: int = 32, max_tenants: int = 16,
                 max_threshold_rules: int = 256, max_geofence_rules: int = 256,
                 presence_missing_interval_ms: int = 8 * 60 * 60 * 1000,
                 name: str = "pipeline-engine", geofence_impl: str = "auto",
                 alert_lane_capacity: Optional[int] = None,
                 max_rule_programs: int = 32,
                 rule_program_nodes: int = 16,
                 rule_program_state_slots: int = 8,
                 max_anomaly_models: int = 8,
                 anomaly_model_features: int = 4,
                 anomaly_model_layers: int = 2,
                 anomaly_model_width: int = 8,
                 h2d_buffer_depth: int = 3,
                 max_actuation_policies: int = 8,
                 command_lane_capacity: Optional[int] = None,
                 max_command_tokens: int = 1024):
        from sitewhere_tpu.actuation.compiler import MAX_POLICY_BUCKET
        from sitewhere_tpu.ml.compiler import MAX_MODEL_BUCKET
        from sitewhere_tpu.ops.actuate import (
            DEFAULT_COMMAND_LANE_CAPACITY, MIN_COMMAND_LANE_CAPACITY)
        from sitewhere_tpu.ops.compact import (
            DEFAULT_ALERT_LANE_CAPACITY, MIN_ALERT_LANE_CAPACITY)
        from sitewhere_tpu.registry.interning import TokenInterner
        from sitewhere_tpu.rules.compiler import MAX_PROGRAM_BUCKET

        super().__init__(name)
        self.registry = registry_tensors
        self.batch_size = batch_size
        self.max_tenants = max_tenants
        self.measurement_slots = measurement_slots
        self.max_threshold_rules = max_threshold_rules
        self.max_geofence_rules = max_geofence_rules
        # rule ids travel in int16 halves of the alert-lane rules row
        if max(max_threshold_rules, max_geofence_rules) >= (1 << 15):
            raise ValueError("rule table capacity must be < 32768 "
                             "(alert-lane rule-id field width)")
        # rule-program slot ids travel in 8 alert-lane meta bits
        if not (0 < max_rule_programs <= MAX_PROGRAM_BUCKET):
            raise ValueError(
                f"max_rule_programs must be in 1..{MAX_PROGRAM_BUCKET} "
                f"(alert-lane program-id field width)")
        self.max_rule_programs = max_rule_programs
        self.rule_program_nodes = rule_program_nodes
        self.rule_program_state_slots = rule_program_state_slots
        # anomaly-model slot ids travel in 8 alert-lane meta bits
        # (ops/compact.py: the two spare level nibbles)
        if not (0 < max_anomaly_models <= MAX_MODEL_BUCKET):
            raise ValueError(
                f"max_anomaly_models must be in 1..{MAX_MODEL_BUCKET} "
                f"(alert-lane model-id field width)")
        if anomaly_model_features > anomaly_model_width:
            raise ValueError(
                "anomaly_model_features must be <= anomaly_model_width "
                "(features embed in the activation vector)")
        self.max_anomaly_models = max_anomaly_models
        self.anomaly_model_features = anomaly_model_features
        self.anomaly_model_layers = anomaly_model_layers
        self.anomaly_model_width = anomaly_model_width
        # actuation-policy slot ids travel in 8 command-lane meta bits
        # (ops/actuate.py lane meta packing)
        if not (0 < max_actuation_policies <= MAX_POLICY_BUCKET):
            raise ValueError(
                f"max_actuation_policies must be in 1..{MAX_POLICY_BUCKET} "
                f"(command-lane policy-id field width)")
        self.max_actuation_policies = max_actuation_policies
        self.command_lane_capacity = (
            command_lane_capacity if command_lane_capacity is not None
            else DEFAULT_COMMAND_LANE_CAPACITY)
        if self.command_lane_capacity < MIN_COMMAND_LANE_CAPACITY:
            raise ValueError(
                f"command_lane_capacity must be >= "
                f"{MIN_COMMAND_LANE_CAPACITY}")
        # command tokens the dispatcher resolves lane rows back through
        # (the same dense-index discipline the device interner uses)
        self.commands = TokenInterner(max_command_tokens, "commands")
        self.alert_lane_capacity = (alert_lane_capacity
                                    if alert_lane_capacity is not None
                                    else DEFAULT_ALERT_LANE_CAPACITY)
        if self.alert_lane_capacity < MIN_ALERT_LANE_CAPACITY:
            raise ValueError(
                f"alert_lane_capacity must be >= {MIN_ALERT_LANE_CAPACITY}")
        self.presence_missing_interval_ms = presence_missing_interval_ms
        self.packer = EventPacker(batch_size, registry_tensors.devices)

        self._threshold_rules: List[ThresholdRule] = []
        self._geofence_rules: List[GeofenceRule] = []
        # (rules version, GeofenceCondition code per geofence rule id)
        self._geo_codes: Tuple[int, np.ndarray] = (-1, np.zeros(0, int))
        # rule programs: token -> {"slot", "epoch", "spec"} with STABLE
        # slot assignment (lowest free slot on install) — per-(device,
        # program) temporal state is keyed by slot, and the epoch
        # generation makes a recycled slot reset its state inside the
        # fused step (rules/compiler.py RuleProgramTable.epoch)
        self._rule_programs: Dict[str, Dict] = {}
        self._program_epoch = 0
        self._programs_enabled = False
        self._rule_state = None
        # anomaly models: same token -> {"slot", "epoch", "spec"} shape
        # and stable-slot/epoch discipline as the rule programs
        # (ml/compiler.py AnomalyModelTable.epoch)
        self._anomaly_models: Dict[str, Dict] = {}
        self._model_epoch = 0
        self._models_enabled = False
        self._model_state = None
        # actuation policies: token -> {"slot", "epoch", "spec"}, the same
        # stable-slot/epoch discipline (actuation/compiler.py
        # ActuationPolicyTable.epoch drives lazy debounce-state reset)
        self._actuation_policies: Dict[str, Dict] = {}
        self._actuation_epoch = 0
        self._actuation_enabled = False
        self._actuation_state = None
        # command fan-out: decoded lane rows hand off here. With no
        # dispatcher attached (tests, bare engines) fires park on the
        # pending list and drain via take_command_fires().
        self.command_dispatcher = None
        self._pending_commands: List[Dict] = []
        self.commands_fired = 0
        self.commands_debounced = 0
        self.commands_dropped = 0
        self._rules_version = 0
        # (op, kind, rule-or-token) feed over rule mutations — the rule
        # management surface rides it (REST audit, cluster replication)
        self._rules_listeners: List[Callable[[str, str, object], None]] = []
        # serializes rule mutation + listener fire (see _mutate_rule)
        self._rules_io_lock = threading.RLock()
        self._params_built_for: Tuple[int, int] = (-1, -1)
        self._params: Optional[PipelineParams] = None
        self._state: Optional[DeviceStateTensors] = None
        self._lock = threading.RLock()
        # Serializes state ADVANCE (submit/presence donate the old buffers,
        # deleting them at dispatch) against state READS/SWAPS from other
        # threads (REST get_device_state, presence sweep thread, checkpoint
        # save, restore) — without it a reader holding the pre-donation
        # reference crashes on "Array has been deleted". Held only around
        # dispatch + the reference swap / the row copy, never around
        # block_until_ready, so hot-path cost is nanoseconds.
        self._state_lock = threading.RLock()
        self._metrics = GLOBAL_METRICS.scoped(f"pipeline.{name}")
        # step flight recorder: one fixed-shape record per step with the
        # stage timeline (runtime/flight.py); feeders pass records they
        # opened on stager threads via submit_blob(flight_rec=...)
        self.flight = GLOBAL_FLIGHT
        self._flight_last = None
        self._flight_step_n = 0
        # Prometheus bucketed histogram for step-path stage durations
        # (labels: engine, stage) — replaces the reservoir summaries the
        # step path used to feed via timer("pack")/timer("step")
        self._stage_hist = GLOBAL_METRICS.histogram(
            "pipeline.step_stage_seconds")
        # per-tenant event volume, sampled every Nth step (a full-batch
        # tenant bincount per step would not hold the <1% overhead pin)
        self._tenant_hist = GLOBAL_METRICS.histogram(
            "pipeline.step_tenant_events",
            buckets=(1.0, 8.0, 64.0, 512.0, 4096.0, 32768.0))
        # ingest->effect event-age histogram (runtime/eventage.py);
        # ingest attaches an AgeSidecar per submit, materialize closes it
        self._age_hist = age_histogram(GLOBAL_METRICS)
        self._flight_sample_every = 16
        from sitewhere_tpu.ops.geofence import resolve_geofence_impl
        self.geofence_impl = resolve_geofence_impl(
            geofence_impl, self._target_platform())
        self._build_step_blob()
        self._presence = jax.jit(check_presence, donate_argnums=(0,))
        self.batches_processed = 0
        # bounded materialization (max_alerts) AND alert-lane overflow
        # (> capacity fired rows in one step) both count here
        self.alerts_dropped = 0
        # the most fired rows one step's alert lane (one shard's, on a
        # mesh) was handed, against alert_lane_capacity
        self.alert_lane_fill_max = 0
        # geofence fires by condition (see _account_lane_fill)
        self._geo_fire_counters = (
            self._metrics.counter("geofence.fires_inside"),
            self._metrics.counter("geofence.fires_outside"),
            self._metrics.counter("geofence.fires_enter"))
        # D2H materialization accounting: how many fetches / bytes the
        # alert path ships per step — the latency tier's fetch budget
        # (perf_gate latency_fetch_budget) reads the per-offer deltas
        self.d2h_fetches = 0
        self.d2h_bytes = 0
        # alerts stashed outside the submit->materialize cycle (overflow
        # restored from a checkpoint, restored manifests): drained by the
        # next materialize_alerts, persisted by checkpoint save
        self._pending_alerts: List[DeviceAlert] = []
        # rotating staging buffers for the wire blob (see
        # _staging_blob_buffer) — fresh 2.6 MB mmap-backed allocations per
        # step cost page faults on the hot path. _blob_ring_guards[i] is a
        # device array whose readiness proves slot i's H2D transfer
        # completed; slot reuse blocks on it (async PJRT DMA reads the
        # host buffer after dispatch returns).
        self._blob_ring: Optional[list] = None
        self._blob_ring_guards: Optional[list] = None
        self._blob_ring_pos = 0
        self._blob_ring_lock = threading.Lock()
        # on-device H2D staging ring (pipeline/staging.py): every hot-path
        # device_put first takes a slot, so at most `h2d_buffer_depth`
        # transfers are in flight and slot reuse recycles the same
        # fixed-shape HBM destinations. Depth 1 degenerates to today's
        # serial transfer behavior; built lazily so config can tune it
        # before first submit.
        if not (1 <= int(h2d_buffer_depth) <= 8):
            raise ValueError("h2d_buffer_depth must be in 1..8")
        self.h2d_buffer_depth = int(h2d_buffer_depth)
        self._staging_ring = None
        self._staging_ring_lock = threading.Lock()
        # Degradation machinery (runtime/health.py, runtime/faults.py):
        # transient H2D/dispatch failures retry with backoff + jitter
        # (step_retries attempts past the first) instead of poisoning the
        # submitter; the health state machine tracks the ladder
        # healthy -> degraded -> draining -> failed and is surfaced on
        # /api/instance/topology and the pipeline.health_state gauge.
        from sitewhere_tpu.runtime.health import EngineHealth
        self.step_retries = 2
        self.health = EngineHealth(name, metrics=self._metrics)
        self._retry_counter = self._metrics.counter("step_retries")

    def _target_platform(self) -> str:
        """Platform the step will compile for (sharded engines override from
        their mesh devices)."""
        return jax.default_backend()

    def _step_static_config(self):
        """Trace-time statics of the stateful stages: (programs enabled,
        node trim, models enabled, actuation enabled, geofence `enter`
        installed). A change — programs or models going
        empty<->non-empty, or a program using more node slots than any
        before — rebuilds the jit (rare; a normal table edit reuses the
        compiled program like any other params refresh)."""
        return (self._programs_enabled,
                getattr(self, "_program_nodes_in_use", 0),
                self._models_enabled,
                self._actuation_enabled,
                self._geofence_enter_enabled())

    def _geofence_enter_enabled(self) -> bool:
        """Whether an `enter` geofence rule is installed: the step then
        traces the previous-location containment, and only then."""
        codes = self._geofence_condition_codes()
        return bool((codes == GeofenceCondition.ENTER).any())

    def _build_step_blob(self) -> None:
        """(Re)build the jitted fused step. Called at construction and on
        the rare program-stage static transitions: the stage is dropped
        at TRACE time when no programs are installed, so the common case
        pays nothing — one recompile per transition, like any other
        static-shape change."""
        (programs_enabled, node_limit, models_enabled,
         actuation_enabled, geofence_enter) = self._step_static_config()

        def step_blob(params, state, rule_state, model_state,
                      actuation_state, blob):
            return process_batch(params, state, rule_state, model_state,
                                 actuation_state, blob_to_batch(blob),
                                 geofence_impl=self.geofence_impl,
                                 alert_lane_capacity=self.alert_lane_capacity,
                                 programs_enabled=programs_enabled,
                                 program_node_limit=node_limit,
                                 models_enabled=models_enabled,
                                 actuation_enabled=actuation_enabled,
                                 command_lane_capacity=(
                                     self.command_lane_capacity),
                                 geofence_enter=geofence_enter)

        self._step_blob = jax.jit(step_blob, donate_argnums=(1, 2, 3, 4))
        self._step_built_config = (programs_enabled, node_limit,
                                   models_enabled, actuation_enabled,
                                   geofence_enter)

    def _ensure_step_current(self) -> None:
        if self._step_built_config != self._step_static_config():
            self._ensure_rule_state_sized()
            self._ensure_model_state_sized()
            self._ensure_actuation_state_sized()
            self._build_step_blob()

    def _rule_state_dims(self):
        """(P, S) the resident RuleStateTensors are sized for. With NO
        programs installed the stage is dropped at trace time and the
        state is a pass-through, so a [D, 1, 1] placeholder keeps the
        empty case free — the full [D, P, S] group allocates on the
        empty->non-empty transition, alongside the step rebuild."""
        if self._programs_enabled:
            return (self.max_rule_programs, self.rule_program_state_slots)
        return (1, 1)

    def _init_rule_state(self):
        from sitewhere_tpu.ops.stateful import init_rule_state

        dims = self._rule_state_dims()
        self._rule_state_built_dims = dims
        return init_rule_state(self.registry.devices.capacity, *dims)

    def _ensure_rule_state_sized(self) -> None:
        if (self._rule_state is not None
                and getattr(self, "_rule_state_built_dims", None)
                != self._rule_state_dims()):
            with self._state_lock:
                self._rule_state = self._init_rule_state()

    def _model_state_dims(self):
        """(P, F) the resident ModelStateTensors are sized for — the same
        placeholder-when-empty discipline as _rule_state_dims."""
        if self._models_enabled:
            return (self.max_anomaly_models, self.anomaly_model_features)
        return (1, 1)

    def _init_model_state(self):
        from sitewhere_tpu.ops.anomaly import init_model_state

        dims = self._model_state_dims()
        self._model_state_built_dims = dims
        return init_model_state(self.registry.devices.capacity, *dims)

    def _ensure_model_state_sized(self) -> None:
        if (self._model_state is not None
                and getattr(self, "_model_state_built_dims", None)
                != self._model_state_dims()):
            with self._state_lock:
                self._model_state = self._init_model_state()

    def _actuation_state_dims(self):
        """(P,) the resident ActuationStateTensors are sized for — the
        same placeholder-when-empty discipline as _rule_state_dims."""
        if self._actuation_enabled:
            return (self.max_actuation_policies,)
        return (1,)

    def _init_actuation_state(self):
        from sitewhere_tpu.ops.actuate import init_actuation_state

        dims = self._actuation_state_dims()
        self._actuation_state_built_dims = dims
        return init_actuation_state(self.registry.devices.capacity, *dims)

    def _ensure_actuation_state_sized(self) -> None:
        if (self._actuation_state is not None
                and getattr(self, "_actuation_state_built_dims", None)
                != self._actuation_state_dims()):
            with self._state_lock:
                self._actuation_state = self._init_actuation_state()

    # -- lifecycle ------------------------------------------------------------

    def on_initialize(self, monitor) -> None:
        self._state = init_device_state(self.registry.devices.capacity,
                                        self.measurement_slots, self.max_tenants)
        if self._rule_state is None:
            self._rule_state = self._init_rule_state()
        if self._model_state is None:
            self._model_state = self._init_model_state()
        if self._actuation_state is None:
            self._actuation_state = self._init_actuation_state()
        self._refresh_params()

    def on_start(self, monitor) -> None:
        if self._state is None:
            self.on_initialize(monitor)

    # -- rules ----------------------------------------------------------------

    def add_rules_listener(
            self, callback: Callable[[str, str, object], None]) -> None:
        """Subscribe to rule mutations: callback(op, kind, payload) with
        op 'add' (payload = the rule) or 'remove' (payload = token)."""
        self._rules_listeners.append(callback)

    def _fire_rules(self, op: str, kind: str, payload) -> None:
        for callback in list(self._rules_listeners):
            callback(op, kind, payload)

    def _mutate_rule(self, kind: str, rule, replace: bool) -> None:
        """Single mutation path for rule installs. `_rules_io_lock` is
        held across mutate + listener fire so listeners (cluster gossip)
        observe mutations in the order they happened; `_lock` (shared
        with the hot path's params compile) is held only around the list
        mutation — a stalled gossip publish must never block a step."""
        from sitewhere_tpu.errors import (
            DuplicateTokenError, ErrorCode, SiteWhereError)

        if kind == "threshold" and not isinstance(rule, ThresholdRule):
            raise SiteWhereError("threshold rule expected", ErrorCode.GENERIC)
        if kind == "geofence" and not isinstance(rule, GeofenceRule):
            raise SiteWhereError("geofence rule expected", ErrorCode.GENERIC)
        with self._rules_io_lock:
            with self._lock:
                exists = any(
                    r.token == rule.token
                    for r in self._threshold_rules + self._geofence_rules)
                if exists and not replace:
                    raise DuplicateTokenError(
                        f"rule '{rule.token}' already exists")
                target, cap = (
                    (self._threshold_rules, self.max_threshold_rules)
                    if kind == "threshold"
                    else (self._geofence_rules, self.max_geofence_rules))
                # capacity BEFORE any removal: a failed upsert must leave
                # the rule set untouched (the replaced rule frees a slot
                # only when it lives in the same kind's table)
                freed = exists and any(r.token == rule.token
                                       for r in target)
                if len(target) - (1 if freed else 0) >= cap:
                    raise SiteWhereError(f"{kind} rule capacity exceeded",
                                         ErrorCode.CAPACITY_EXCEEDED)
                if exists:
                    self._threshold_rules = [
                        r for r in self._threshold_rules
                        if r.token != rule.token]
                    self._geofence_rules = [
                        r for r in self._geofence_rules
                        if r.token != rule.token]
                    target = (self._threshold_rules if kind == "threshold"
                              else self._geofence_rules)
                target.append(rule)
                self._rules_version += 1
            self._fire_rules("add", kind, rule)

    def create_rule(self, kind: str, rule) -> None:
        """Install a NEW rule; raises DuplicateTokenError on a token
        collision (atomically — the REST create contract)."""
        self._mutate_rule(kind, rule, replace=False)

    def upsert_rule(self, kind: str, rule) -> None:
        """Install or replace the rule with this token — the idempotent
        entry used by boot config, checkpoint restore, and cluster
        replication."""
        self._mutate_rule(kind, rule, replace=True)

    # upsert semantics: in a cluster, replication may install the same
    # rule concurrently with local provisioning (every host boots the
    # same config) — programmatic installs must be idempotent. The strict
    # duplicate check lives in create_rule (the REST create contract).
    def add_threshold_rule(self, rule: ThresholdRule) -> None:
        self.upsert_rule("threshold", rule)

    def add_geofence_rule(self, rule: GeofenceRule) -> None:
        self.upsert_rule("geofence", rule)

    def remove_rule(self, token: str) -> bool:
        with self._rules_io_lock:
            with self._lock:
                n = len(self._threshold_rules) + len(self._geofence_rules)
                self._threshold_rules = [r for r in self._threshold_rules
                                         if r.token != token]
                self._geofence_rules = [r for r in self._geofence_rules
                                        if r.token != token]
                changed = n != (len(self._threshold_rules)
                                + len(self._geofence_rules))
                if changed:
                    self._rules_version += 1
            if changed:
                self._fire_rules("remove", "", token)
        return changed

    def get_rule(self, token: str):
        """(kind, rule) for a token, or (None, None)."""
        with self._lock:
            for rule in self._threshold_rules:
                if rule.token == token:
                    return "threshold", rule
            for rule in self._geofence_rules:
                if rule.token == token:
                    return "geofence", rule
        return None, None

    def list_rules(self) -> Dict[str, list]:
        with self._lock:
            return {"threshold": list(self._threshold_rules),
                    "geofence": list(self._geofence_rules)}

    def _compile_threshold_table(self) -> ThresholdRuleTable:
        table = empty_threshold_table(self.max_threshold_rules)
        for i, rule in enumerate(self._threshold_rules):
            active = rule.active
            tenant_idx = mm_idx = dtype_idx = 0
            # A scoping token that doesn't resolve must deactivate the rule,
            # not silently widen to "any" (index 0 means wildcard on device).
            if rule.tenant_token:
                tenant_idx = self.registry.tenants.lookup(rule.tenant_token)
                active = active and tenant_idx > 0
            if rule.device_type_token:
                dtype_idx = self.registry.device_types.lookup(rule.device_type_token)
                active = active and dtype_idx > 0
            if rule.measurement_name:
                mm_idx = self.packer.measurements.intern(rule.measurement_name)
            table.active[i] = active
            table.tenant_idx[i] = tenant_idx
            table.mm_idx[i] = mm_idx
            table.device_type_idx[i] = dtype_idx
            table.op[i] = ThresholdOp.BY_NAME[rule.operator]
            table.threshold[i] = rule.threshold
            table.alert_level[i] = int(rule.alert_level)
            table.alert_type_idx[i] = self.packer.alert_types.intern(rule.alert_type)
        return table

    def _compile_geofence_table(self) -> GeofenceRuleTable:
        table = empty_geofence_table(self.max_geofence_rules)
        for i, rule in enumerate(self._geofence_rules):
            zidx = self.registry.zones_interner.lookup(rule.zone_token)
            table.active[i] = rule.active and zidx > 0
            table.zone_row[i] = max(0, zidx - 1)
            table.condition[i] = GeofenceCondition.BY_NAME.get(
                rule.condition, GeofenceCondition.OUTSIDE)
            table.alert_level[i] = int(rule.alert_level)
            table.alert_type_idx[i] = self.packer.alert_types.intern(rule.alert_type)
        return table

    # -- rule programs (CEP-lite compiler; rules/compiler.py) ---------------

    def _compile_program_table(self):
        from sitewhere_tpu.rules.compiler import (
            compile_program_into, empty_program_table)

        table = empty_program_table(self.max_rule_programs,
                                    self.rule_program_nodes)
        for entry in self._rule_programs.values():
            compile_program_into(
                table, entry["slot"], entry["spec"], entry["epoch"],
                intern_measurement=self.packer.measurements.intern,
                intern_alert_type=self.packer.alert_types.intern,
                lookup_tenant=self.registry.tenants.lookup,
                lookup_device_type=self.registry.device_types.lookup,
                measurement_slots=self.measurement_slots,
                max_state_slots=self.rule_program_state_slots)
        # node slots actually populated, for the static unroll trim (the
        # NOP opcode is 0, and node 0 of a used program is never NOP)
        used = np.nonzero((table.opcode != 0).any(axis=0))[0]
        self._program_nodes_in_use = int(used.max()) + 1 if used.size else 0
        return table

    def _validate_program_spec(self, spec: Dict) -> Dict:
        """Full dry-run compile against THIS engine's static buckets and
        interners: a spec that passes here turns into table rows without
        crashing the hot path. Raises RuleProgramError (409, names the
        offending node) otherwise — the structured-validation contract
        shared by the REST and replicated-apply paths."""
        from sitewhere_tpu.rules.compiler import dry_run_compile

        return dry_run_compile(
            spec, measurement_slots=self.measurement_slots,
            max_nodes=self.rule_program_nodes,
            max_state_slots=self.rule_program_state_slots,
            intern_measurement=self.packer.measurements.intern)

    def upsert_rule_program(self, spec: Dict, *, slot: Optional[int] = None,
                            epoch: Optional[int] = None) -> Dict:
        """Install or replace a rule program (idempotent — boot config,
        checkpoint restore, cluster replication). A replace bumps the
        slot's epoch so its temporal state resets inside the fused step.
        `slot`/`epoch` pin the assignment on checkpoint restore so
        mid-window temporal state lines back up with its program."""
        from sitewhere_tpu.errors import ErrorCode, SiteWhereError

        spec = self._validate_program_spec(spec)
        token = spec["token"]
        with self._rules_io_lock:
            with self._lock:
                existing = self._rule_programs.get(token)
                if slot is None:
                    if existing is not None:
                        slot = existing["slot"]
                    else:
                        used = {e["slot"]
                                for e in self._rule_programs.values()}
                        free = [s for s in range(self.max_rule_programs)
                                if s not in used]
                        if not free:
                            raise SiteWhereError(
                                "rule program capacity exceeded "
                                f"({self.max_rule_programs} slots)",
                                ErrorCode.CAPACITY_EXCEEDED,
                                http_status=409)
                        slot = free[0]
                if epoch is None:
                    self._program_epoch += 1
                    epoch = self._program_epoch
                else:
                    self._program_epoch = max(self._program_epoch, epoch)
                entry = {"slot": int(slot), "epoch": int(epoch),
                         "spec": spec}
                self._rule_programs[token] = entry
                self._programs_enabled = True
                self._rules_version += 1
            self._fire_rules("add", "program", dict(spec))
        return entry

    def create_rule_program(self, spec: Dict) -> Dict:
        """REST create semantics: duplicate token 409s atomically."""
        from sitewhere_tpu.errors import DuplicateTokenError

        with self._lock:
            token = (spec or {}).get("token")
            if token in self._rule_programs:
                raise DuplicateTokenError(
                    f"rule program '{token}' already exists")
        return self.upsert_rule_program(spec)

    def remove_rule_program(self, token: str) -> bool:
        with self._rules_io_lock:
            with self._lock:
                entry = self._rule_programs.pop(token, None)
                if entry is None:
                    return False
                self._programs_enabled = bool(self._rule_programs)
                self._rules_version += 1
            self._fire_rules("remove", "program", token)
        return True

    def get_rule_program(self, token: str) -> Optional[Dict]:
        with self._lock:
            entry = self._rule_programs.get(token)
            return dict(entry["spec"]) if entry else None

    def list_rule_programs(self) -> List[Dict]:
        """Program specs in slot order (the order fires resolve in)."""
        with self._lock:
            entries = sorted(self._rule_programs.values(),
                             key=lambda e: e["slot"])
            return [dict(e["spec"]) for e in entries]

    def rule_programs_by_slot(self) -> Dict[int, Dict]:
        with self._lock:
            return {e["slot"]: dict(e["spec"])
                    for e in self._rule_programs.values()}

    def rule_program_manifest(self) -> List[Dict]:
        """Checkpoint form: spec + the runtime (slot, epoch) assignment,
        so a restore re-pins temporal state to its program mid-window."""
        with self._lock:
            return [{"slot": e["slot"], "epoch": e["epoch"],
                     "spec": dict(e["spec"])}
                    for e in sorted(self._rule_programs.values(),
                                    key=lambda e: e["slot"])]

    def rule_program_counters(self) -> Dict[str, Dict[str, int]]:
        """Per-program cumulative fire/suppress counters (one on-demand
        D2H fetch of two [P] vectors — never on the hot path). Counters
        live in the rule state so they survive checkpoints; sharded
        engines hold per-shard partials summed here."""
        if self._rule_state is None:
            return {}
        with self._state_lock:
            fires = np.asarray(self._rule_state.fire_count)
            supp = np.asarray(self._rule_state.suppress_count)
        if fires.ndim == 2:  # sharded [S, P] partials
            fires, supp = fires.sum(0), supp.sum(0)
        with self._lock:
            # a slot past the resident counter row means the full-size
            # state hasn't stepped yet (program installed, no submit) —
            # its counters are zero by definition
            return {token: {"fires": int(fires[e["slot"]])
                            if e["slot"] < fires.shape[0] else 0,
                            "suppressed": int(supp[e["slot"]])
                            if e["slot"] < supp.shape[0] else 0}
                    for token, e in self._rule_programs.items()}

    # -- rule-program state (checkpointing) ---------------------------------

    def canonical_rule_state(self):
        """Host snapshot of the rule-program temporal state, flat
        device-major like canonical_state (sharded engine overrides)."""
        import jax.numpy as jnp

        if self._rule_state is None:
            return None
        with self._state_lock:
            snap = jax.tree_util.tree_map(jnp.copy, self._rule_state)
        return jax.tree_util.tree_map(lambda a: np.asarray(a), snap)

    def _expected_rule_state_shapes(self):
        """Canonical (flat device-major) shape per rule-state field for
        THIS engine's current program dims — what checkpoints must match
        (computed, not allocated: the resident state may still be the
        no-programs placeholder when a restore re-installs programs)."""
        from sitewhere_tpu.ops.stateful import state_slab_lanes

        D = self.registry.devices.capacity
        P, S = self._rule_state_dims()
        return {"slab": (D, P, state_slab_lanes(S)), "gen": (P,),
                "fire_count": (P,), "suppress_count": (P,)}

    def _validate_canonical_rule_state(self, rule_state) -> None:
        for name, want in self._expected_rule_state_shapes().items():
            got = tuple(np.asarray(getattr(rule_state, name)).shape)
            if got != want:
                raise ValueError(
                    f"rule-state checkpoint shape mismatch for {name}: "
                    f"got {got}, engine expects {want} (program bucket/"
                    f"state slots/device capacity must match)")

    def load_canonical_rule_state(self, rule_state) -> None:
        self._validate_canonical_rule_state(rule_state)
        with self._state_lock:
            self._rule_state = jax.device_put(rule_state)
            self._rule_state_built_dims = self._rule_state_dims()

    # -- anomaly models (on-TPU inference; ml/compiler.py) ------------------

    def _compile_model_table(self):
        from sitewhere_tpu.ml.compiler import (
            compile_model_into, empty_model_table)

        table = empty_model_table(
            self.max_anomaly_models, self.anomaly_model_features,
            self.anomaly_model_layers, self.anomaly_model_width)
        for entry in self._anomaly_models.values():
            compile_model_into(
                table, entry["slot"], entry["spec"], entry["epoch"],
                intern_measurement=self.packer.measurements.intern,
                intern_alert_type=self.packer.alert_types.intern,
                lookup_tenant=self.registry.tenants.lookup,
                lookup_device_type=self.registry.device_types.lookup,
                measurement_slots=self.measurement_slots)
        return table

    def _validate_model_spec(self, spec: Dict) -> Dict:
        """Dry-run compile against THIS engine's static buckets: a spec
        that passes turns into table rows without crashing the hot path.
        Raises AnomalyModelError (409, names the field) otherwise — the
        contract shared by the REST and replicated-apply paths."""
        from sitewhere_tpu.ml.compiler import dry_run_compile

        return dry_run_compile(
            spec, measurement_slots=self.measurement_slots,
            max_features=self.anomaly_model_features,
            max_layers=self.anomaly_model_layers,
            width=self.anomaly_model_width,
            intern_measurement=self.packer.measurements.intern)

    def upsert_anomaly_model(self, spec: Dict, *,
                             slot: Optional[int] = None,
                             epoch: Optional[int] = None) -> Dict:
        """Install or replace an anomaly model (idempotent — boot config,
        checkpoint restore, cluster replication). A replace bumps the
        slot's epoch so its feature state resets inside the fused step;
        `slot`/`epoch` pin the assignment on checkpoint restore so
        mid-flight EWMA/rate state lines back up with its model."""
        from sitewhere_tpu.errors import ErrorCode, SiteWhereError

        spec = self._validate_model_spec(spec)
        token = spec["token"]
        with self._rules_io_lock:
            with self._lock:
                existing = self._anomaly_models.get(token)
                if slot is None:
                    if existing is not None:
                        slot = existing["slot"]
                    else:
                        used = {e["slot"]
                                for e in self._anomaly_models.values()}
                        free = [s for s in range(self.max_anomaly_models)
                                if s not in used]
                        if not free:
                            raise SiteWhereError(
                                "anomaly model capacity exceeded "
                                f"({self.max_anomaly_models} slots)",
                                ErrorCode.CAPACITY_EXCEEDED,
                                http_status=409)
                        slot = free[0]
                if epoch is None:
                    self._model_epoch += 1
                    epoch = self._model_epoch
                else:
                    self._model_epoch = max(self._model_epoch, epoch)
                entry = {"slot": int(slot), "epoch": int(epoch),
                         "spec": spec}
                self._anomaly_models[token] = entry
                self._models_enabled = True
                self._rules_version += 1
        return entry

    def create_anomaly_model(self, spec: Dict) -> Dict:
        """REST create semantics: duplicate token 409s atomically."""
        from sitewhere_tpu.errors import DuplicateTokenError

        with self._lock:
            token = (spec or {}).get("token")
            if token in self._anomaly_models:
                raise DuplicateTokenError(
                    f"anomaly model '{token}' already exists")
        return self.upsert_anomaly_model(spec)

    def remove_anomaly_model(self, token: str) -> bool:
        with self._rules_io_lock:
            with self._lock:
                entry = self._anomaly_models.pop(token, None)
                if entry is None:
                    return False
                self._models_enabled = bool(self._anomaly_models)
                self._rules_version += 1
        return True

    def get_anomaly_model(self, token: str) -> Optional[Dict]:
        with self._lock:
            entry = self._anomaly_models.get(token)
            return dict(entry["spec"]) if entry else None

    def list_anomaly_models(self) -> List[Dict]:
        """Model specs in slot order (the order fires resolve in)."""
        with self._lock:
            entries = sorted(self._anomaly_models.values(),
                             key=lambda e: e["slot"])
            return [dict(e["spec"]) for e in entries]

    def anomaly_models_by_slot(self) -> Dict[int, Dict]:
        with self._lock:
            return {e["slot"]: dict(e["spec"])
                    for e in self._anomaly_models.values()}

    def anomaly_model_manifest(self) -> List[Dict]:
        """Checkpoint form: spec + the runtime (slot, epoch) assignment,
        so a restore re-pins feature state to its model mid-flight."""
        with self._lock:
            return [{"slot": e["slot"], "epoch": e["epoch"],
                     "spec": dict(e["spec"])}
                    for e in sorted(self._anomaly_models.values(),
                                    key=lambda e: e["slot"])]

    def anomaly_model_counters(self) -> Dict[str, Dict[str, int]]:
        """Per-model cumulative fire/eval counters (one on-demand D2H
        fetch of two [P] vectors — never on the hot path). Counters live
        in the model state so they survive checkpoints; sharded engines
        hold per-shard partials summed here."""
        if self._model_state is None:
            return {}
        with self._state_lock:
            fires = np.asarray(self._model_state.fire_count)
            evals = np.asarray(self._model_state.eval_count)
        if fires.ndim == 2:  # sharded [S, P] partials
            fires, evals = fires.sum(0), evals.sum(0)
        with self._lock:
            return {token: {"fires": int(fires[e["slot"]])
                            if e["slot"] < fires.shape[0] else 0,
                            "evals": int(evals[e["slot"]])
                            if e["slot"] < evals.shape[0] else 0}
                    for token, e in self._anomaly_models.items()}

    # -- anomaly-model state (checkpointing) --------------------------------

    def canonical_model_state(self):
        """Host snapshot of the model feature state, flat device-major
        like canonical_state (sharded engine overrides)."""
        import jax.numpy as jnp

        if self._model_state is None:
            return None
        with self._state_lock:
            snap = jax.tree_util.tree_map(jnp.copy, self._model_state)
        return jax.tree_util.tree_map(lambda a: np.asarray(a), snap)

    def _expected_model_state_shapes(self):
        from sitewhere_tpu.ops.stateful import state_slab_lanes

        D = self.registry.devices.capacity
        P, F = self._model_state_dims()
        return {"slab": (D, P, state_slab_lanes(F)), "gen": (P,),
                "fire_count": (P,), "eval_count": (P,)}

    def _validate_canonical_model_state(self, model_state) -> None:
        for name, want in self._expected_model_state_shapes().items():
            got = tuple(np.asarray(getattr(model_state, name)).shape)
            if got != want:
                raise ValueError(
                    f"model-state checkpoint shape mismatch for {name}: "
                    f"got {got}, engine expects {want} (model bucket/"
                    f"feature slots/device capacity must match)")

    def load_canonical_model_state(self, model_state) -> None:
        self._validate_canonical_model_state(model_state)
        with self._state_lock:
            self._model_state = jax.device_put(model_state)
            self._model_state_built_dims = self._model_state_dims()

    # -- actuation policies (alert->command; actuation/compiler.py) ---------

    def _compile_policy_table(self):
        from sitewhere_tpu.actuation.compiler import (
            compile_policy_into, empty_policy_table)

        table = empty_policy_table(self.max_actuation_policies)
        for entry in self._actuation_policies.values():
            compile_policy_into(
                table, entry["slot"], entry["spec"], entry["epoch"],
                intern_command=self.commands.intern,
                lookup_tenant=self.registry.tenants.lookup)
        return table

    def _validate_policy_spec(self, spec: Dict) -> Dict:
        """Dry-run compile against THIS engine's command interner: a spec
        that passes turns into table rows without crashing the hot path.
        Raises ActuationPolicyError (409, names the field) otherwise —
        the contract shared by the REST and replicated-apply paths."""
        from sitewhere_tpu.actuation.compiler import dry_run_compile

        return dry_run_compile(spec, intern_command=self.commands.intern)

    def upsert_actuation_policy(self, spec: Dict, *,
                                slot: Optional[int] = None,
                                epoch: Optional[int] = None) -> Dict:
        """Install or replace an actuation policy (idempotent — boot
        config, checkpoint restore, cluster replication). A replace bumps
        the slot's epoch so its per-(device, policy) debounce state resets
        inside the fused step; `slot`/`epoch` pin the assignment on
        checkpoint restore so mid-window debounce state lines back up
        with its policy."""
        from sitewhere_tpu.errors import ErrorCode, SiteWhereError

        spec = self._validate_policy_spec(spec)
        token = spec["token"]
        with self._rules_io_lock:
            with self._lock:
                existing = self._actuation_policies.get(token)
                if slot is None:
                    if existing is not None:
                        slot = existing["slot"]
                    else:
                        used = {e["slot"]
                                for e in self._actuation_policies.values()}
                        free = [s for s
                                in range(self.max_actuation_policies)
                                if s not in used]
                        if not free:
                            raise SiteWhereError(
                                "actuation policy capacity exceeded "
                                f"({self.max_actuation_policies} slots)",
                                ErrorCode.CAPACITY_EXCEEDED,
                                http_status=409)
                        slot = free[0]
                if epoch is None:
                    self._actuation_epoch += 1
                    epoch = self._actuation_epoch
                else:
                    self._actuation_epoch = max(self._actuation_epoch,
                                                epoch)
                entry = {"slot": int(slot), "epoch": int(epoch),
                         "spec": spec}
                self._actuation_policies[token] = entry
                self._actuation_enabled = True
                self._rules_version += 1
        return entry

    def create_actuation_policy(self, spec: Dict) -> Dict:
        """REST create semantics: duplicate token 409s atomically."""
        from sitewhere_tpu.errors import DuplicateTokenError

        with self._lock:
            token = (spec or {}).get("token")
            if token in self._actuation_policies:
                raise DuplicateTokenError(
                    f"actuation policy '{token}' already exists")
        return self.upsert_actuation_policy(spec)

    def remove_actuation_policy(self, token: str) -> bool:
        with self._rules_io_lock:
            with self._lock:
                entry = self._actuation_policies.pop(token, None)
                if entry is None:
                    return False
                self._actuation_enabled = bool(self._actuation_policies)
                self._rules_version += 1
        return True

    def get_actuation_policy(self, token: str) -> Optional[Dict]:
        with self._lock:
            entry = self._actuation_policies.get(token)
            return dict(entry["spec"]) if entry else None

    def list_actuation_policies(self) -> List[Dict]:
        """Policy specs in slot order (the order lane rows resolve in)."""
        with self._lock:
            entries = sorted(self._actuation_policies.values(),
                             key=lambda e: e["slot"])
            return [dict(e["spec"]) for e in entries]

    def actuation_policies_by_slot(self) -> Dict[int, Dict]:
        with self._lock:
            return {e["slot"]: dict(e["spec"])
                    for e in self._actuation_policies.values()}

    def actuation_policy_manifest(self) -> List[Dict]:
        """Checkpoint form: spec + the runtime (slot, epoch) assignment,
        so a restore re-pins debounce state to its policy mid-window."""
        with self._lock:
            return [{"slot": e["slot"], "epoch": e["epoch"],
                     "spec": dict(e["spec"])}
                    for e in sorted(self._actuation_policies.values(),
                                    key=lambda e: e["slot"])]

    def actuation_policy_counters(self) -> Dict[str, Dict[str, int]]:
        """Per-policy cumulative fire/debounce counters (one on-demand
        D2H fetch of two [P] vectors — never on the hot path). Counters
        live in the actuation state so they survive checkpoints; sharded
        engines hold per-shard partials summed here."""
        if self._actuation_state is None:
            return {}
        with self._state_lock:
            fires = np.asarray(self._actuation_state.fire_count)
            deb = np.asarray(self._actuation_state.debounce_count)
        if fires.ndim == 2:  # sharded [S, P] partials
            fires, deb = fires.sum(0), deb.sum(0)
        with self._lock:
            return {token: {"fires": int(fires[e["slot"]])
                            if e["slot"] < fires.shape[0] else 0,
                            "debounced": int(deb[e["slot"]])
                            if e["slot"] < deb.shape[0] else 0}
                    for token, e in self._actuation_policies.items()}

    # -- actuation state (checkpointing) ------------------------------------

    def canonical_actuation_state(self):
        """Host snapshot of the per-(device, policy) debounce state, flat
        device-major like canonical_state (sharded engine overrides)."""
        import jax.numpy as jnp

        if self._actuation_state is None:
            return None
        with self._state_lock:
            snap = jax.tree_util.tree_map(jnp.copy, self._actuation_state)
        return jax.tree_util.tree_map(lambda a: np.asarray(a), snap)

    def _expected_actuation_state_shapes(self):
        from sitewhere_tpu.ops.stateful import state_slab_lanes

        D = self.registry.devices.capacity
        (P,) = self._actuation_state_dims()
        return {"slab": (D, P, state_slab_lanes(1)), "gen": (P,),
                "fire_count": (P,), "debounce_count": (P,)}

    def _validate_canonical_actuation_state(self, actuation_state) -> None:
        for name, want in self._expected_actuation_state_shapes().items():
            got = tuple(np.asarray(getattr(actuation_state, name)).shape)
            if got != want:
                raise ValueError(
                    f"actuation-state checkpoint shape mismatch for "
                    f"{name}: got {got}, engine expects {want} (policy "
                    f"bucket/device capacity must match)")

    def load_canonical_actuation_state(self, actuation_state) -> None:
        self._validate_canonical_actuation_state(actuation_state)
        with self._state_lock:
            self._actuation_state = jax.device_put(actuation_state)
            self._actuation_state_built_dims = self._actuation_state_dims()

    def take_command_fires(self) -> List[Dict]:
        """Drain command fires parked while no dispatcher was attached
        (tests, bare engines). With a dispatcher set this is empty."""
        out, self._pending_commands = self._pending_commands, []
        return out

    # -- params refresh -------------------------------------------------------

    def _refresh_params(self) -> None:
        with self._lock:
            snap = self.registry.snapshot()
            threshold = self._compile_threshold_table()
            geofence = self._compile_geofence_table()
            programs = self._compile_program_table()
            models = self._compile_model_table()
            policies = self._compile_policy_table()
            zones = ZoneTable(vertices=snap.zone_vertices, nvert=snap.zone_nvert,
                              tenant_idx=snap.zone_tenant, active=snap.zone_active)
            self._params = jax.device_put(PipelineParams(
                assignment_status=snap.assignment_status,
                tenant_idx=snap.tenant_idx,
                area_idx=snap.area_idx,
                device_type_idx=snap.device_type_idx,
                threshold=threshold, zones=zones, geofence=geofence,
                programs=programs, models=models, policies=policies))
            self._params_built_for = (snap.version, self._rules_version)

    def _ensure_params(self) -> PipelineParams:
        if self._params_built_for != (self.registry.version, self._rules_version):
            self._refresh_params()
        self._ensure_step_current()
        assert self._params is not None
        return self._params

    # -- processing -----------------------------------------------------------

    def _staging_blob_buffer(self, batch: EventBatch,
                             flight_rec=None) -> Optional[np.ndarray]:
        """Rotating reusable [WIRE_ROWS, B] staging buffer for full-size flat
        batches (ring of 6: blob contents stay stable through dispatch +
        async H2D even with pipelined staging depth 3 and two stager
        threads). Odd-size batches allocate fresh (returns None).

        ACCELERATOR BACKENDS ONLY: on the cpu backend jax zero-copies
        suitably-aligned numpy arrays into device buffers — a later pack
        into the recycled slot would corrupt an in-flight step's input
        (observed as a flaky one-row diff under pytest). On cpu the
        "transfer" IS a host copy anyway, so reuse saves nothing; on
        TPU/GPU device memory is separate and device_put always copies."""
        from sitewhere_tpu.ops.pack import WIRE_ROWS

        if (self._target_platform() == "cpu"
                or batch.device_idx.ndim != 1
                or batch.device_idx.shape[0] != self.batch_size):
            return None
        with self._blob_ring_lock:
            if self._blob_ring is None:
                self._blob_ring = [
                    np.empty((WIRE_ROWS, self.batch_size), np.int32)
                    for _ in range(6)]
                self._blob_ring_guards = [None] * len(self._blob_ring)
            pos = self._blob_ring_pos
            self._blob_ring_pos = (pos + 1) % len(self._blob_ring)
            buf = self._blob_ring[pos]
            guard, self._blob_ring_guards[pos] = (
                self._blob_ring_guards[pos], None)
        if guard is not None:
            # slot reuse must wait for the slot's previous H2D transfer:
            # the guard (consuming step's output, or the transferred
            # array itself) is ready no earlier than the transfer. By the
            # time a 6-slot ring cycles back this is almost always ready.
            if flight_rec is not None:
                flight_rec.begin_stage("guard")
            try:
                guard.block_until_ready()
            except Exception:
                pass  # a failed step still implies the transfer finished
            if flight_rec is not None:
                flight_rec.end_stage("guard")
        return buf

    def _note_blob_guard(self, buf, guard) -> None:
        """Record the transfer-completion guard for a ring slot after its
        blob was handed to jax (no-op for non-ring buffers). Compact
        4-row blobs are VIEWS into the 5-row ring slots — match through
        .base as well as identity."""
        base = getattr(buf, "base", None)
        with self._blob_ring_lock:
            if self._blob_ring is None:
                return
            for i, ring_buf in enumerate(self._blob_ring):
                if ring_buf is buf or ring_buf is base:
                    self._blob_ring_guards[i] = guard
                    return

    @property
    def staging_ring(self) -> StagingRing:
        """Lazily-built on-device H2D staging ring (pipeline/staging.py).
        Lazy so config can set `h2d_buffer_depth` before first use and so
        engines that never stage explicitly (pure serial submit of numpy
        blobs) pay nothing."""
        ring = self._staging_ring
        if ring is None:
            with self._staging_ring_lock:
                if self._staging_ring is None:
                    self._staging_ring = StagingRing(
                        self.h2d_buffer_depth, metrics=self._metrics)
                ring = self._staging_ring
        return ring

    def _h2d_with_retry(self, put):
        """Bounded retry/backoff around a host->device transfer. The host
        blob is intact regardless of how far a failed transfer got (no
        donation on this edge), so re-issuing the put is always safe."""
        attempt = 0
        while True:
            try:
                fault_point("h2d_error")
                return put()
            except Exception:
                attempt += 1
                if attempt > self.step_retries:
                    raise
                self._retry_counter.inc()
                self.health.note_retry()
                time.sleep(jittered(0.01 * (2 ** (attempt - 1))))

    def _acquire_staging_slot(self, flight_rec, order: Optional[int],
                              use_ring: bool):
        """Ring-slot acquisition for a staging edge: ordered + blocking
        on the normal path (backpressure when the ring is full), skipped
        entirely when the caller bypasses (overflow drain blobs — see
        stage_prepared). Stamps the at-acquire ring snapshot on the
        flight record for the occupancy rollup."""
        if not use_ring:
            return None
        ring = self.staging_ring
        slot = ring.acquire(order=order, flight_rec=flight_rec)
        if flight_rec is not None:
            flight_rec.ring = (ring.occupancy(), ring.depth)
        return slot

    def stage_blob(self, blob, flight_rec=None,
                   order: Optional[int] = None) -> StagedBlob:
        """Stage a packed wire blob through the H2D staging ring: acquire
        a slot (backpressure when all `h2d_buffer_depth` transfers are in
        flight), start the async device_put — arming the `h2d_error`
        fault point with the same bounded retry/backoff as every transfer
        edge — and return a handle submit_blob dispatches and releases.
        The pipelined feeder passes its sequence as `order` so slots are
        granted in dispatch order (see staging.py on why that matters).
        A failed transfer releases the slot guard-free and propagates, so
        neighboring in-flight slots are never disturbed."""
        slot = self._acquire_staging_slot(flight_rec, order, True)
        if flight_rec is not None:
            flight_rec.begin_stage("h2d")
        try:
            dev = self._h2d_with_retry(lambda: jax.device_put(blob))
        except BaseException:
            self.staging_ring.release(slot)
            raise
        finally:
            if flight_rec is not None:
                flight_rec.end_stage("h2d")
        slot.device_blob = dev
        if isinstance(blob, np.ndarray):
            # host-side blob-ring guard unchanged: the device array's
            # readiness proves the host staging buffer was fully read
            self._note_blob_guard(blob, dev)
        return StagedBlob(dev, slot, self.staging_ring)

    def submit(self, batch: EventBatch, age=None) -> ProcessOutputs:
        """Run one fused step; state advances in place (donated). `age`
        is the optional ingest-age sidecar (runtime/eventage.py) the
        caller opened at the receive edge — it rides the flight record
        and is closed by materialize_alerts."""
        # single-transfer host->device staging (see ops.pack.batch_to_blob).
        # The flight record's "pack" segment keeps host staging visible
        # now that "dispatch" covers only the jit call (pack used to be
        # inside it); the staging-ring guard wait is marked separately.
        rec = self.flight.begin_step(engine=self.name)
        if age is not None:
            rec.age = age
        # buffer acquisition first: its ring-guard wait is the "guard"
        # segment and must not nest inside (double-count with) "pack"
        out_buf = self._staging_blob_buffer(batch, flight_rec=rec)
        rec.begin_stage("pack")
        fault_point("pack_fail")
        blob = batch_to_blob(batch, out=out_buf)
        rec.end_stage("pack")
        self._stage_hist.observe(rec.stage_s("pack"),
                                 engine=self.name, stage="pack")
        self._sample_tenant_mix(rec, batch)
        return self.submit_blob(
            blob, n_events=int(np.asarray(batch.valid).sum()),
            flight_rec=rec)

    def _sample_tenant_mix(self, rec, batch: EventBatch) -> None:
        """Every Nth step, attach the batch's tenant mix (host bincount
        over the registry's tenant mirror — never a device fetch) to the
        flight record and the per-tenant event histogram."""
        self._flight_step_n += 1
        if self._flight_step_n % self._flight_sample_every:
            return
        try:
            dev = np.asarray(batch.device_idx).ravel()
            valid = np.asarray(batch.valid).ravel().astype(bool)
            tenants = self.registry._tenant_idx[dev[valid]]
            mix = np.bincount(tenants, minlength=1)
        except Exception:
            return
        rec.tenant_mix = tuple(int(x) for x in mix[:self.max_tenants])
        for tenant, count in enumerate(rec.tenant_mix):
            if count:
                self._tenant_hist.observe(
                    float(count), engine=self.name, tenant=str(tenant))

    def submit_blob(self, blob, n_events: Optional[int] = None,
                    flight_rec=None) -> ProcessOutputs:
        """Run one fused step on an already-packed wire blob (numpy or
        device-resident). The pipelined feeder (pipeline/feed.py) stages
        blobs — pack + async device_put — on worker threads so host staging
        of batch N+1 overlaps device compute of step N. `n_events` feeds
        the events meter (counting valid bits of a device-resident blob
        here would force a D2H sync on the hot path). `flight_rec` is a
        flight record opened by the caller (submit(), or a feeder's
        stager thread — the explicit cross-thread handoff); when None
        this opens a dispatch-only record."""
        slot = None
        if isinstance(blob, StagedBlob):
            # stage_blob already ran the transfer through a ring slot;
            # dispatch here, then hand the slot back with the step output
            # as the reuse guard
            slot, blob = blob.slot, blob.blob
        if self._state is None:  # lazy init for direct (un-started) use
            self.initialize()  # full lifecycle init so a later start() won't re-init
        if self._rule_state is None:  # set_state() without lifecycle init
            self._rule_state = self._init_rule_state()
        if self._model_state is None:
            self._model_state = self._init_model_state()
        if self._actuation_state is None:
            self._actuation_state = self._init_actuation_state()
        params = self._ensure_params()
        rec = flight_rec if flight_rec is not None else (
            self.flight.begin_step(engine=self.name))
        rec.begin_stage("dispatch")
        try:
            outputs = self._dispatch_with_retry(
                lambda: self._step_blob(params, self._state, self._rule_state,
                                        self._model_state,
                                        self._actuation_state, blob))
        except BaseException:
            if slot is not None:
                # guard-free release: the failed step's input array is
                # dropped at next reuse without waiting on anything
                self.staging_ring.release(slot)
            raise
        rec.end_stage("dispatch")
        if slot is not None:
            self.staging_ring.release(slot, outputs.processed)
        if n_events is not None:
            rec.events = int(n_events)
        self._flight_last = rec
        self._stage_hist.observe(rec.stage_s("dispatch"),
                                 engine=self.name, stage="dispatch")
        if isinstance(blob, np.ndarray):
            # ring-slot transfer guard: the implicit jit transfer of a
            # numpy blob completes no later than the step's outputs
            self._note_blob_guard(blob, outputs.processed)
        self.batches_processed += 1
        if n_events is not None:
            self._metrics.meter("events").mark(n_events)
        return outputs

    def _dispatch_with_retry(self, step_call,
                             points=("h2d_error", "dispatch_error")):
        """Run one state-advancing step call with bounded retry around
        transient H2D/dispatch failures: `step_retries` extra attempts
        with exponential backoff + jitter, then the error propagates so
        the consumer layer can park the batch on its dead-letter topic —
        the submitter is never wedged. Injected faults (runtime/faults.py
        `h2d_error`/`dispatch_error`) raise BEFORE the jitted call, so
        drill retries are always state-safe; an organic failure inside
        the call may have consumed the donated state buffers, in which
        case the retries fail too and the error escalates through the
        same path. `step_call` returns (state, rule_state, model_state,
        actuation_state, outputs). `points` lists the fault points armed
        on this path — the sharded engine stages H2D separately, so its
        dispatch drops h2d_error."""
        attempt = 0
        while True:
            try:
                for point in points:
                    fault_point(point)
                with self._state_lock:
                    (self._state, self._rule_state, self._model_state,
                     self._actuation_state, outputs) = step_call()
                self.health.note_success()
                return outputs
            except Exception:
                attempt += 1
                if attempt > self.step_retries:
                    raise
                self._retry_counter.inc()
                self.health.note_retry()
                time.sleep(jittered(0.01 * (2 ** (attempt - 1))))

    def submit_routed(self, batch: EventBatch, age=None):
        """Engine-agnostic submit: returns (batch_for_materialization,
        outputs) on both engine kinds. The sharded engine's submit already
        returns its routed [S, B] batch; here the input batch doubles as the
        materialization batch. Callers that support either engine
        (pipeline/inbound.py, sources/fastlane.py) use this instead of
        type-sniffing submit()'s return."""
        return batch, self.submit(batch, age=age)

    def _fetch_lanes_with_retry(self, outputs: ProcessOutputs):
        """D2H fetch of BOTH fixed-shape lanes (alert + command) in one
        device_get, with the same bounded retry/backoff contract as
        `_dispatch_with_retry`. Unlike dispatch, the fetch never donates
        buffers, so retrying a genuinely failed device_get is always safe."""
        attempt = 0
        while True:
            try:
                fault_point("lane_fetch_error")
                lanes = jax.device_get((outputs.alert_lanes,
                                        outputs.command_lanes))
                self.health.note_success()
                return lanes
            except Exception:
                attempt += 1
                if attempt > self.step_retries:
                    raise
                self._retry_counter.inc()
                self.health.note_retry()
                time.sleep(jittered(0.01 * (2 ** (attempt - 1))))

    def materialize_alerts(self, batch: EventBatch, outputs: ProcessOutputs,
                           max_alerts: Optional[int] = None
                           ) -> List[DeviceAlert]:
        """Turn the step's device-compacted alert lanes back into
        API-level DeviceAlert events.

        Fetch count and fetch bytes — not compute — set the latency
        floor (every fetch is a host<->device round trip), so the step
        packs fired
        rows into fixed-capacity lanes ON DEVICE (ops/compact.py +
        ops/actuate.py) and this ships exactly TWO fixed-shape,
        lane-sized fetches per step — the alert lane and the command lane,
        in one device_get — regardless of batch size, replacing the
        six-array / two-phase fetch. Device tokens resolve through the
        interner's cached token array (one fancy-index, no per-row Python
        lookups).

        A `max_alerts` bound and lane overflow (> capacity fired rows)
        both count on `alerts_dropped`, surface as a metric, and log —
        never a silent drop. Differential contract: the returned list is
        exactly what the mask-scan reference (materialize_alerts_maskscan)
        produces for the first `alert_lane_capacity` fired rows, order
        included (tests/test_alert_lanes.py)."""
        from sitewhere_tpu.ops.compact import decode_alert_lanes

        pending, self._pending_alerts = self._pending_alerts, []
        # amend the last-dispatched flight record: the fetch/materialize
        # segments belong to the step whose outputs these are
        rec = self._flight_last
        if rec is not None:
            rec.begin_stage("lane_fetch")
        # THE one device_get: both fixed-shape lanes in a single round trip
        lanes, cmd_lanes = self._fetch_lanes_with_retry(outputs)
        if rec is not None:
            rec.end_stage("lane_fetch")
            rec.begin_stage("materialize")
            self._stage_hist.observe(rec.stage_s("lane_fetch"),
                                     engine=self.name, stage="lane_fetch")
        try:
            self.d2h_fetches += 2
            self.d2h_bytes += lanes.nbytes + cmd_lanes.nbytes
            dec = decode_alert_lanes(lanes)
            self._account_lane_overflow(dec.dropped_alerts)
            self._account_lane_fill([dec], rec)
            dec = self._bound_alert_rows(dec, max_alerts)
            if dec.n == 0:
                return pending
            rows = dec.rows
            dev_rows = np.asarray(batch.device_idx)[rows]
            ts_rows = np.asarray(batch.ts)[rows]
            return pending + self._emit_alerts(dec, dev_rows, ts_rows)
        finally:
            if rec is not None:
                rec.end_stage("materialize")
                self._stage_hist.observe(
                    rec.stage_s("materialize"),
                    engine=self.name, stage="materialize")
            self._materialize_commands(cmd_lanes, rec)
            if rec is not None:
                self._close_age(rec)

    def _close_age(self, rec) -> None:
        """Close the step's ingest-age sidecar at the materialize edge:
        the open AgeSidecar resolves (pure close — the ingest service
        re-closes the same sidecar at its persist/alert edges) into the
        AgeSummary that replaces it on the record, feeding the rollup
        ride-along and the (engine, edge) histogram."""
        age = rec.age
        if age is None or not hasattr(age, "close"):
            return
        summary = age.close()
        rec.age = summary
        observe_summary(self._age_hist, summary,
                        engine=self.name, edge="materialize")
        if getattr(rec, "commands", 0):
            # the closing waterfall edge: ingest -> command fan-out done.
            # Fan-out ran synchronously inside this materialize pass, so
            # the same summary closed after it IS the detection->actuation
            # age for every event in the step.
            observe_summary(self._age_hist, summary, engine=self.name,
                            edge="detection_to_actuation")

    def _materialize_commands(self, cmd_lanes, rec) -> None:
        """Decode the step's command lane, account fire/debounce/overflow
        activity, and hand resolved fires to the dispatcher (or the
        pending list when none is attached). Differential contract: the
        resolved fires are bit-derived from the lane the NumPy oracle
        reproduces (tests/test_actuation.py)."""
        from sitewhere_tpu.ops.actuate import decode_command_lanes

        if rec is not None:
            rec.begin_stage("actuate")
        dec = decode_command_lanes(np.asarray(cmd_lanes))
        self._account_command_activity(dec)
        fires = self._emit_command_fires(dec) if dec.n else []
        if rec is not None:
            rec.commands = len(fires)
            rec.end_stage("actuate")
            self._stage_hist.observe(rec.stage_s("actuate"),
                                     engine=self.name, stage="actuate")
        self._fanout_commands(fires, rec)

    def _fanout_commands(self, fires: List[Dict], rec) -> None:
        """Hand resolved fires to the attached dispatcher (or park them);
        shared by both engines' materialize passes."""
        if not fires:
            return
        if rec is not None:
            rec.begin_stage("command_fanout")
        try:
            if self.command_dispatcher is not None:
                self.command_dispatcher.dispatch(self, fires)
            else:
                self._pending_commands.extend(fires)
        finally:
            if rec is not None:
                rec.end_stage("command_fanout")
                self._stage_hist.observe(
                    rec.stage_s("command_fanout"),
                    engine=self.name, stage="command_fanout")

    def _account_command_activity(self, dec) -> None:
        fired = int(dec.fired) - int(dec.dropped)
        if fired:
            self.commands_fired += fired
            self._metrics.counter("actuation.fires").inc(fired)
        if dec.debounced:
            self.commands_debounced += int(dec.debounced)
            self._metrics.counter("actuation.debounced").inc(
                int(dec.debounced))
        if dec.dropped:
            self.commands_dropped += int(dec.dropped)
            self._metrics.counter("commands.dropped").inc(int(dec.dropped))
            import logging
            logging.getLogger("sitewhere.pipeline").warning(
                "command-lane overflow: %d policy fires beyond the %d-row "
                "lane capacity dropped on device (commands_dropped=%d "
                "total)", int(dec.dropped), self.command_lane_capacity,
                self.commands_dropped)

    def _emit_command_fires(self, dec) -> List[Dict]:
        """Resolve decoded command-lane slots into dispatchable fire
        records: device token via the cached interner array (one fancy
        index), command token + params from the installed policy spec."""
        policies = self.actuation_policies_by_slot()
        tokens = self.registry.devices.token_array()[dec.dev].tolist()
        slots = dec.policy_slot.tolist()
        levels = dec.level.tolist()
        sources = dec.source.tolist()
        fires: List[Dict] = []
        for i in range(dec.n):
            spec = policies.get(slots[i])
            if spec is None:  # policy removed between dispatch and fetch
                continue
            fires.append({
                "policy": spec["token"], "slot": slots[i],
                "device": tokens[i], "command": spec["command"],
                "params": list(spec.get("params", ())),
                "level": levels[i], "source": sources[i],
                "tenant": spec.get("tenant_token", "")})
        return fires

    def _account_lane_overflow(self, dropped: int) -> None:
        if not dropped:
            return
        self.alerts_dropped += dropped
        self._metrics.counter("alerts.dropped").inc(dropped)
        import logging
        logging.getLogger("sitewhere.pipeline").warning(
            "alert-lane overflow: %d alerts beyond the %d-row lane "
            "capacity dropped on device (alerts_dropped=%d total)",
            dropped, self.alert_lane_capacity, self.alerts_dropped)

    def _geofence_condition_codes(self) -> np.ndarray:
        """GeofenceCondition code of each installed geofence rule, by
        rule id; rebuilt when the rules change."""
        version, codes = self._geo_codes
        if version != self._rules_version:
            with self._lock:
                version = self._rules_version
                codes = np.array(
                    [GeofenceCondition.BY_NAME.get(
                        r.condition, GeofenceCondition.OUTSIDE)
                     for r in self._geofence_rules], np.int64)
            self._geo_codes = (version, codes)
        return codes

    def _account_lane_fill(self, decs, rec) -> None:
        """One step's decoded alert lanes (one per shard): the fill of
        the fullest lane against alert_lane_capacity, and the geofence
        fires by the condition of each fired row's first geofence rule
        (the rule its alert names; rows past capacity are not seen).
        Both ride the step's flight record (`lane`, `geofence_fires`);
        the fires also count on `geofence.fires_<condition>`."""
        fill = max((d.fired_rows for d in decs), default=0)
        self.alert_lane_fill_max = max(self.alert_lane_fill_max, fill)
        codes = self._geofence_condition_codes()
        fires = np.zeros(3, np.int64)
        for d in decs:
            rule = d.geo_rule[d.geo_fired]
            rule = rule[(rule >= 0) & (rule < codes.shape[0])]
            if rule.size:
                fires += np.bincount(codes[rule], minlength=3)[:3]
        for counter, n in zip(self._geo_fire_counters, fires.tolist()):
            if n:
                counter.inc(n)
        if rec is not None:
            rec.lane = (fill, self.alert_lane_capacity)
            rec.geo_fires = tuple(fires.tolist())

    def _bound_alert_rows(self, dec, max_alerts: Optional[int]):
        """Apply a caller's max_alerts bound to decoded lanes (row count,
        matching the pre-lane contract) with the same loud accounting."""
        if max_alerts is None or dec.n <= max_alerts:
            return dec
        dropped = dec.n - max_alerts
        self.alerts_dropped += dropped
        self._metrics.counter("alerts.dropped").inc(dropped)
        import logging
        logging.getLogger("sitewhere.pipeline").warning(
            "alert storm: %d fired rows exceed max_alerts=%d; "
            "dropping %d (alerts_dropped=%d total)",
            dec.n, max_alerts, dropped, self.alerts_dropped)
        return dec.head(max_alerts)

    def _emit_alerts(self, dec, dev_rows: np.ndarray,
                     ts_rows: np.ndarray) -> List[DeviceAlert]:
        """DeviceAlert list for decoded lane slots. `dev_rows`/`ts_rows`
        are the fired rows' device indices and relative timestamps;
        everything vectorizable (tokens, dates, level enums) is resolved
        by array ops before the per-alert object loop."""
        with self._lock:
            thr_rules = list(self._threshold_rules)
            geo_rules = list(self._geofence_rules)
        programs = self.rule_programs_by_slot()
        # model-fire resolution gets its own flight segment (nested inside
        # materialize): the lane carries only slot ids, so the spec lookup
        # + bit decode here is the host-side cost of on-device scoring
        flight = self._flight_last
        if flight is not None:
            flight.begin_stage("model_eval")
        models = self.anomaly_models_by_slot()
        model_f = dec.model_fired.tolist()
        model_s = dec.model_slot.tolist()
        if flight is not None:
            flight.end_stage("model_eval")
        tokens = self.registry.devices.token_array()[dev_rows].tolist()
        dates = (ts_rows.astype(np.int64)
                 + self.packer.epoch_base_ms).tolist()
        thr_f = dec.thr_fired.tolist()
        geo_f = dec.geo_fired.tolist()
        prog_f = dec.prog_fired.tolist()
        thr_r = dec.thr_rule.tolist()
        geo_r = dec.geo_rule.tolist()
        prog_r = dec.prog_rule.tolist()
        thr_l = dec.thr_level.tolist()
        geo_l = dec.geo_level.tolist()
        prog_l = dec.prog_level.tolist()
        n_thr, n_geo = len(thr_rules), len(geo_rules)
        levels = _ALERT_LEVELS
        alerts: List[DeviceAlert] = []
        for i in range(dec.n):
            token = tokens[i]
            if thr_f[i] and 0 <= thr_r[i] < n_thr:
                rule = thr_rules[thr_r[i]]
                alerts.append(DeviceAlert(
                    device_id=token, source=AlertSource.SYSTEM,
                    level=levels.get(thr_l[i]) or AlertLevel(thr_l[i]),
                    type=rule.alert_type,
                    message=rule.alert_message
                    or f"threshold rule {rule.token} fired",
                    event_date=dates[i]))
            if geo_f[i] and 0 <= geo_r[i] < n_geo:
                rule = geo_rules[geo_r[i]]
                alerts.append(DeviceAlert(
                    device_id=token, source=AlertSource.SYSTEM,
                    level=levels.get(geo_l[i]) or AlertLevel(geo_l[i]),
                    type=rule.alert_type,
                    message=rule.alert_message
                    or f"geofence rule {rule.token} fired",
                    event_date=dates[i]))
            if prog_f[i] and prog_r[i] in programs:
                spec = programs[prog_r[i]]
                alerts.append(DeviceAlert(
                    device_id=token, source=AlertSource.SYSTEM,
                    level=levels.get(prog_l[i]) or AlertLevel(prog_l[i]),
                    type=spec["alert_type"],
                    message=spec["alert_message"]
                    or f"rule program {spec['token']} fired",
                    event_date=dates[i]))
            if model_f[i] and model_s[i] in models:
                # the lane carries only the 8-bit model slot; level and
                # type resolve from the installed spec host-side
                spec = models[model_s[i]]
                alerts.append(DeviceAlert(
                    device_id=token, source=AlertSource.SYSTEM,
                    level=levels.get(int(spec["alert_level"]))
                    or AlertLevel(int(spec["alert_level"])),
                    type=spec["alert_type"],
                    message=spec["alert_message"]
                    or f"anomaly model {spec['token']} fired",
                    event_date=dates[i]))
        return alerts

    def drain_parked(self) -> List[DeviceAlert]:
        """Fold every row parked for a later step and hand back the
        alerts no materialize pass has returned yet. This engine parks no
        rows; its stash only holds alerts restored from a checkpoint."""
        pending, self._pending_alerts = self._pending_alerts, []
        return pending

    # -- presence -------------------------------------------------------------

    def presence_sweep(self) -> List[str]:
        """Run the presence check; returns tokens of newly-missing devices."""
        params = self._ensure_params()
        now_rel = np.int32(self.packer.rel_ts(int(time.time() * 1000)))
        registered = params.assignment_status == 1
        with self._state_lock:
            self._state, newly_missing = self._presence(
                self._state, registered, now_rel,
                np.int32(min(self.presence_missing_interval_ms, 2 ** 31 - 1)))
        rows = np.nonzero(np.asarray(newly_missing))[0]
        if rows.size == 0:
            return []
        # vectorized token resolution (cached dense array, one fancy
        # index) — "" marks unknown/gap slots
        tokens = self.registry.devices.token_array()[rows].tolist()
        return [t for t in tokens if t]

    # -- state reads ----------------------------------------------------------

    @property
    def state(self) -> DeviceStateTensors:
        assert self._state is not None, "engine not initialized"
        return self._state

    def set_state(self, state: DeviceStateTensors) -> None:
        """Checkpoint restore."""
        with self._state_lock:
            self._state = jax.device_put(state)

    def canonical_state(self) -> DeviceStateTensors:
        """Topology-independent host snapshot: flat device-major layout,
        identical no matter how many shards produced it — what checkpoints
        store, so a checkpoint taken on one mesh restores onto any other
        (elastic recovery; the reference's equivalent is Kafka replay into
        a rebuilt store)."""
        import jax.numpy as jnp

        # device-side copy under the lock (fast HBM copy that detaches
        # from the donate-able buffers); the slow D2H conversion runs
        # OUTSIDE the lock so checkpoint saves don't stall the hot path
        with self._state_lock:
            snap = jax.tree_util.tree_map(jnp.copy, self.state)
        return jax.tree_util.tree_map(lambda a: np.asarray(a), snap)

    def _canonical_shape_of(self, field_name: str):
        """Expected canonical (flat) shape for one state field — .shape on
        the resident array costs nothing (no device transfer)."""
        return getattr(self.state, field_name).shape

    def _validate_canonical(self, state: DeviceStateTensors) -> None:
        """Every dimension must match this engine — a silent
        measurement-slot or tenant-width mismatch would corrupt state via
        clamped scatters. Shared by both engines (expected shapes differ
        via _canonical_shape_of)."""
        import dataclasses as _dc

        for f in _dc.fields(state):
            got = tuple(getattr(state, f.name).shape)
            expect = self._canonical_shape_of(f.name)
            if got != tuple(expect):
                raise ValueError(
                    f"checkpoint shape mismatch for {f.name}: got {got}, "
                    f"engine expects {tuple(expect)} (device capacity/"
                    f"measurement slots/tenant width must match)")

    def load_canonical_state(self, state: DeviceStateTensors) -> None:
        """Inverse of canonical_state (single-chip: plain placement)."""
        self._validate_canonical(state)
        self.set_state(state)

    def _state_row(self, idx: int):
        """Fetch one device's row from every state tensor (overridden by the
        sharded engine, which remaps global -> (shard, local))."""
        class Row:
            pass

        row = Row()
        with self._state_lock:  # vs concurrent donation (see __init__)
            s = self._state
            for field_name in ("last_interaction", "present",
                               "presence_missing_since",
                               "event_count", "last_location",
                               "last_location_ts",
                               "last_measurement", "last_measurement_ts",
                               "last_alert_type", "last_alert_level",
                               "last_alert_ts"):
                setattr(row, field_name,
                        np.asarray(getattr(s, field_name)[idx]))
        return row

    def get_device_state(self, device_token: str) -> Optional[DeviceState]:
        """Materialize one device's state row as the API-level DeviceState."""
        idx = self.registry.devices.lookup(device_token)
        if idx == 0 or self._state is None:
            return None
        row = self._state_row(idx)
        if row is None:  # multi-host: owned by another process
            return None
        state = DeviceState(device_id=device_token)
        if int(row.last_interaction) > _NEG:
            state.last_interaction_date = self.packer.abs_ts(int(row.last_interaction))
        state.presence = (PresenceState.PRESENT if bool(row.present)
                          else PresenceState.NOT_PRESENT)
        if int(row.presence_missing_since) > _NEG:
            state.presence_missing_date = self.packer.abs_ts(
                int(row.presence_missing_since))
        if int(row.last_location_ts) > _NEG:
            lat, lon, elev = (float(x) for x in row.last_location)
            state.last_location = (self.packer.abs_ts(int(row.last_location_ts)),
                                   lat, lon, elev)
        # cached dense slot -> name array instead of a token_of call per
        # measurement slot (this runs per REST device-state read)
        names = self.packer.measurements.token_array()
        for slot in range(self.measurement_slots):
            ts_slot = int(row.last_measurement_ts[slot])
            if ts_slot > _NEG:
                name = names[slot] or f"slot{slot}"
                state.last_measurements[name] = (self.packer.abs_ts(ts_slot),
                                                 float(row.last_measurement[slot]))
        if int(row.last_alert_ts) > _NEG:
            atype = self.packer.alert_types.token_of(int(row.last_alert_type)) or ""
            state.last_alerts[atype] = (self.packer.abs_ts(int(row.last_alert_ts)),
                                        int(row.last_alert_level), "")
        return state

    def stats(self) -> Dict[str, int]:
        with self._state_lock:  # tenant-count reads vs donation
            s = self._state
            tenant_events = np.asarray(s.tenant_event_count).tolist()
            tenant_alerts = np.asarray(s.tenant_alert_count).tolist()
        return {
            "batches": self.batches_processed,
            "tenant_event_count": tenant_events,
            "tenant_alert_count": tenant_alerts,
            "scope": "global",  # single-controller: totals are global
        }

    # -- device profiling (the reference's Jaeger span surface; on-device
    # the equivalent is an XLA profiler trace — runtime/tracing.py) ---------

    def start_device_trace(self, log_dir: str) -> None:
        """Begin capturing an XLA/jax profiler trace (HLO timelines, memory)
        to `log_dir` (view with TensorBoard or xprof). Idempotent: a second
        call while tracing is a no-op."""
        if getattr(self, "_tracing", False):
            return
        jax.profiler.start_trace(log_dir)
        self._tracing = True

    def stop_device_trace(self) -> None:
        if getattr(self, "_tracing", False):
            jax.profiler.stop_trace()
            self._tracing = False

    def on_stop(self, monitor) -> None:
        # never leave an XLA profiler trace open past the engine
        self.stop_device_trace()
