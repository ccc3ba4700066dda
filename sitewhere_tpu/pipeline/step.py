"""The fused pipeline step: validate + rules + device-state in ONE jit.

This function is the TPU rebuild of the reference's entire hot path
(SURVEY.md §3.2-3.3). What the reference does with five microservices, three
Kafka round-trips and two gRPC hops per event —
  InboundPayloadProcessingLogic (validate, gRPC device lookup)
  -> UnaryEventStorageStrategy (gRPC persist per event)
  -> OutboundPayloadEnrichmentLogic (re-fetch + enrich)
  -> ZoneTestRuleProcessor (JTS containment per event)
  -> DeviceStateProcessingLogic (Mongo upsert per event)
— happens here as one XLA program over an 8k-event batch: gathers against the
registry mirror replace the gRPC lookups, broadcast compares replace the rule
hosts, keyed reductions replace the Mongo upserts. Stage boundaries are
registers/HBM, not broker round-trips.

Persistence (the reference's event-management store) is intentionally NOT in
the jit: the host appends the raw batch to the columnar event log
(persist/eventlog.py) in parallel with device compute.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct

from sitewhere_tpu.model.event import DeviceEventType
from sitewhere_tpu.ops.compact import (
    DEFAULT_ALERT_LANE_CAPACITY, compact_alert_lanes,
)
from sitewhere_tpu.ops.geofence import (
    GeofenceRuleTable, ZoneTable, eval_geofence_rules, previous_locations,
)
from sitewhere_tpu.ops.pack import EventBatch
from sitewhere_tpu.ops.segments import (
    batch_device_order, count_by_key, last_by_key, scatter_max_by_key,
)
from sitewhere_tpu.ops.actuate import (
    COMMAND_LANE_ROWS, DEFAULT_COMMAND_LANE_CAPACITY,
    ActuationStateTensors, eval_actuation_policies,
)
from sitewhere_tpu.ops.anomaly import ModelStateTensors, eval_anomaly_models
from sitewhere_tpu.ops.stateful import (
    RuleStateTensors, eval_rule_programs, observations_of_batch,
)
from sitewhere_tpu.ops.threshold import ThresholdRuleTable, eval_threshold_rules
from sitewhere_tpu.pipeline.state_tensors import DeviceStateTensors
from sitewhere_tpu.actuation.compiler import ActuationPolicyTable
from sitewhere_tpu.ml.compiler import AnomalyModelTable
from sitewhere_tpu.rules.compiler import RuleProgramTable

_NEG = -(2 ** 31)


@struct.dataclass
class PipelineParams:
    """Everything the step reads but does not write: registry mirror + rule
    tables. A pytree of device arrays; contents change without recompiling."""

    # registry mirror (registry/tensors.py), [D]
    assignment_status: jnp.ndarray
    tenant_idx: jnp.ndarray
    area_idx: jnp.ndarray
    device_type_idx: jnp.ndarray
    # rule tables
    threshold: ThresholdRuleTable
    zones: ZoneTable
    geofence: GeofenceRuleTable
    # compiled rule programs (rules/compiler.py); replicated like the
    # other rule tables on sharded meshes
    programs: RuleProgramTable
    # compiled anomaly-model weight tables (ml/compiler.py); also
    # replicated — features ride the shard axis, weights don't
    models: AnomalyModelTable
    # compiled actuation policies (actuation/compiler.py); replicated —
    # debounce state rides the shard axis, the policy table doesn't
    policies: ActuationPolicyTable


@struct.dataclass
class ProcessOutputs:
    """Per-batch outputs consumed host-side (alert materialization, failed
    events -> registration topic, stats)."""

    valid: jnp.ndarray              # bool [B] passed validation
    unregistered: jnp.ndarray       # bool [B] had no active assignment
    threshold_fired: jnp.ndarray    # bool [B]
    threshold_first_rule: jnp.ndarray  # int32 [B]
    threshold_alert_level: jnp.ndarray  # int32 [B]
    geofence_fired: jnp.ndarray     # bool [B]
    geofence_first_rule: jnp.ndarray   # int32 [B]
    geofence_alert_level: jnp.ndarray  # int32 [B]
    # composite rule-program fires mapped to their attach rows (the
    # device's last tracked-measurement row this step — ops/stateful.py)
    program_fired: jnp.ndarray      # bool [B]
    program_first_rule: jnp.ndarray    # int32 [B] program slot, -1 = none
    program_alert_level: jnp.ndarray   # int32 [B]
    # anomaly-model scoring fires, also attach-row mapped (ops/anomaly.py)
    model_fired: jnp.ndarray        # bool [B]
    model_first: jnp.ndarray        # int32 [B] model slot, -1 = none
    model_level: jnp.ndarray        # int32 [B] max fired level, -1 = none
    model_score: jnp.ndarray        # f32 [B] lowest scored slot's score
    tenant_counts: jnp.ndarray      # int32 [T] events this batch per tenant
    processed: jnp.ndarray          # int32 scalar, valid events
    alerts: jnp.ndarray             # int32 scalar, alerts fired
    # device-compacted alert lanes (ops/compact.py): fired rows packed by
    # prefix sum into a fixed [ALERT_LANE_ROWS, K] int32 array so alert
    # materialization is ONE tiny fixed-shape D2H fetch per step — the
    # per-row masks above stay for device-side consumers and tests; the
    # host fast path never fetches them
    alert_lanes: jnp.ndarray        # int32 [ALERT_LANE_ROWS, K]
    # device-compacted command lane (ops/actuate.py): actuation-policy
    # fires packed the same way into a SECOND fixed [4, K_cmd] int32
    # array, fetched in the SAME materialize pass as the alert lanes —
    # the fetch budget is exactly TWO fixed-shape arrays per step
    command_lanes: jnp.ndarray      # int32 [COMMAND_LANE_ROWS, K_cmd]


def process_batch(params: PipelineParams, state: DeviceStateTensors,
                  rule_state: RuleStateTensors,
                  model_state: ModelStateTensors,
                  actuation_state: ActuationStateTensors,
                  batch: EventBatch, *,
                  geofence_impl: str = "xla",
                  alert_lane_capacity: int = DEFAULT_ALERT_LANE_CAPACITY,
                  programs_enabled: bool = True,
                  program_node_limit: int = 0,
                  models_enabled: bool = True,
                  actuation_enabled: bool = True,
                  command_lane_capacity: int = DEFAULT_COMMAND_LANE_CAPACITY,
                  geofence_enter: bool = False
                  ) -> Tuple[DeviceStateTensors, RuleStateTensors,
                             ModelStateTensors, ActuationStateTensors,
                             ProcessOutputs]:
    """One fused step. Shapes static; jit/shard_map safe; donate `state`,
    `rule_state`, `model_state` and `actuation_state`.

    `geofence_impl` selects the containment kernel ("xla" scan,
    "pallas" TPU kernel, "pallas_interpret" for CPU tests) — resolved by the
    engines via ops.geofence.resolve_geofence_impl.
    `alert_lane_capacity` is the K of the compacted alert lanes (static;
    one cached program per capacity like any other shape).
    `programs_enabled` (trace-time static) drops the whole rule-program
    stage when no programs are installed, so the empty-table common case
    costs nothing on the hot path (the engines rebuild the jit on the
    rare empty<->non-empty transition, like any other shape change).
    `program_node_limit` (also static) trims the unrolled node pass to
    the slots the compiled table populates.
    `models_enabled` (trace-time static) likewise drops the anomaly-model
    scoring stage when the model table is empty.
    `actuation_enabled` (trace-time static) drops the actuation stage
    when no policies are installed — the command lane is then a zero
    placeholder so the materialize fetch shape never changes.
    `command_lane_capacity` is the K of the compacted command lane.
    `geofence_enter` (trace-time static) adds the `enter` condition's
    previous locations (ops/geofence.py previous_locations) and their
    containment; without an `enter` rule installed the step is traced
    without them.
    """
    D = state.num_devices
    M = state.num_measurement_slots
    T = state.tenant_event_count.shape[0]

    # ---- stage 1: validation (replaces gRPC hop #1 + assignment check) -----
    # Unknown tokens intern to index 0 whose registry row always holds
    # status 0, so a single status gather covers both "unknown device" and
    # "no active assignment" (local index 0 is a real device on shards > 0).
    # named_scope labels carry the flight recorder's stage vocabulary into
    # device profiler traces (trace-time only, no runtime cost).
    with jax.named_scope("step_validate"):
        status = params.assignment_status[batch.device_idx]      # gather [B]
        registered = status == 1  # DeviceAssignmentStatus.ACTIVE
        unregistered = batch.valid & ~registered
        valid = batch.valid & registered
        tenant = params.tenant_idx[batch.device_idx]
        device_type = params.device_type_idx[batch.device_idx]
        batch = batch.replace(tenant_idx=tenant, valid=valid)

    # ---- stage 2: rule evaluation (replaces rule-processing service) -------
    with jax.named_scope("step_rules"):
        thr = eval_threshold_rules(batch, params.threshold, device_type)
        prev = None
        if geofence_enter:
            prev = previous_locations(
                batch.device_idx, batch.ts,
                valid & (batch.event_type == DeviceEventType.LOCATION),
                batch.lat, batch.lon, state.last_location,
                state.last_location_ts)
        geo = eval_geofence_rules(batch, params.zones, params.geofence,
                                  impl=geofence_impl, prev=prev)

    # ---- stage 3: device-state fold (replaces device-state service) --------
    with jax.named_scope("step_state_fold"):
        dev = batch.device_idx
        ts = batch.ts
        last_interaction = scatter_max_by_key(dev, ts, valid, D,
                                              state.last_interaction)
        event_count = state.event_count + count_by_key(dev, valid, D)

        # presence restore: any device with a valid event is present again
        touched = count_by_key(dev, valid, D) > 0
        present = state.present | touched
        presence_missing_since = jnp.where(touched, _NEG,
                                           state.presence_missing_since)

        # last location (location events only)
        is_loc = valid & (batch.event_type == DeviceEventType.LOCATION)
        loc_vals = jnp.stack([batch.lat, batch.lon, batch.elevation], axis=1)
        loc_ts, (last_location,) = last_by_key(
            dev, ts, is_loc, D, state.last_location_ts,
            (state.last_location,), (loc_vals,))

        # last measurement per (device, slot<M)
        is_mm = (valid & (batch.event_type == DeviceEventType.MEASUREMENT)
                 & (batch.mm_idx < M))
        mm_key = dev * M + batch.mm_idx
        mm_ts_flat, (mm_val_flat,) = last_by_key(
            mm_key, ts, is_mm, D * M, state.last_measurement_ts.reshape(-1),
            (state.last_measurement.reshape(-1),), (batch.value,))
        last_measurement_ts = mm_ts_flat.reshape(D, M)
        last_measurement = mm_val_flat.reshape(D, M)

        # last alert per device (device-sent alerts; rule-fired alerts
        # merge on the next batch once materialized as events)
        is_alert = valid & (batch.event_type == DeviceEventType.ALERT)
        alert_ts, (last_alert_type, last_alert_level) = last_by_key(
            dev, ts, is_alert, D, state.last_alert_ts,
            (state.last_alert_type, state.last_alert_level),
            (batch.alert_type_idx, batch.alert_level))

    # ---- stage 3b: stateful rule programs (CEP-lite; ops/stateful.py) ------
    # Runs BETWEEN the built-in rules and the stats so composite fires
    # feed the same alert-lane compaction; reads the POST-fold
    # measurement state so conditions across measurements that arrived in
    # different events compose. Dropped at trace time when no programs
    # are installed.
    B = batch.device_idx.shape[0]
    if programs_enabled or models_enabled:
        # the observation masks and attach rows feed BOTH stateful stages.
        # ONE shared stable argsort groups batch rows by device so both
        # kernels' HBM slab gathers and attach scatters run over
        # contiguous device segments; per-row outputs un-sort with the
        # inverse permutation. Per-row math depends only on own-row
        # inputs and the attach scatter targets are unique, so results
        # are bit-identical to the unsorted evaluation.
        obs_mm, _touched, now_d, attach_row = observations_of_batch(
            batch, M, D)
        order, inv = batch_device_order(dev)
        sdev = dev[order]
        sattach = attach_row[order]
        s_obs = obs_mm[sdev]
        s_lm = last_measurement[sdev]
        s_lmts = last_measurement_ts[sdev]
        s_tenant = params.tenant_idx[sdev]
        s_dtype = params.device_type_idx[sdev]
    if programs_enabled:
        with jax.named_scope("step_rule_programs"):
            # per-ROW evaluation over attach-sorted rows: state gathers/
            # scatters ride contiguous device segments (attach rows are
            # the unique writers), so program evaluation costs O(batch),
            # not O(device capacity)
            rule_state, prog = eval_rule_programs(
                params.programs, rule_state,
                dev=sdev, attach=sattach,
                obs_row=s_obs, now_row=now_d[sdev],
                lm_row=s_lm, lmts_row=s_lmts,
                tenant_row=s_tenant, dtype_row=s_dtype,
                node_limit=program_node_limit)
            prog = {k: v[inv] for k, v in prog.items()}
    else:
        prog = {"fired": jnp.zeros((B,), bool),
                "first_rule": jnp.full((B,), -1, jnp.int32),
                "alert_level": jnp.full((B,), -1, jnp.int32)}

    # ---- stage 3c: anomaly-model scoring (ops/anomaly.py) ------------------
    # After the rule programs so both stateful stages read the same
    # post-fold measurement state; fires ride the spare alert-lane meta
    # bits, so the one-fetch-per-step budget is untouched. Dropped at
    # trace time when no models are installed, like the programs stage.
    if models_enabled:
        with jax.named_scope("step_model_eval"):
            model_state, model = eval_anomaly_models(
                params.models, model_state,
                dev=sdev, attach=sattach,
                obs_row=s_obs,
                lm_row=s_lm, lmts_row=s_lmts,
                tenant_row=s_tenant, dtype_row=s_dtype)
            model = {k: v[inv] for k, v in model.items()}
    else:
        model = {"fired": jnp.zeros((B,), bool),
                 "first_model": jnp.full((B,), -1, jnp.int32),
                 "alert_level": jnp.full((B,), -1, jnp.int32),
                 "score": jnp.zeros((B,), jnp.float32)}

    # ---- stage 3d: actuation policies (ops/actuate.py) ---------------------
    # After every alert family has fired so policies see the step's full
    # fire bits; per-(device, policy) debounce state advances in HBM and
    # fired commands compact into the second fixed-shape lane. Dropped
    # at trace time when no policies are installed.
    if actuation_enabled:
        with jax.named_scope("step_actuate"):
            actuation_state, command_lanes = eval_actuation_policies(
                params.policies, actuation_state,
                dev=dev, ts=ts, tenant_row=tenant,
                thr=thr, geo=geo, prog=prog, model=model,
                capacity=command_lane_capacity)
    else:
        # fixed-shape placeholder: the materialize pass always fetches
        # two lanes, so enabling actuation never changes the fetch count
        command_lanes = jnp.zeros(
            (COMMAND_LANE_ROWS, command_lane_capacity), jnp.int32)

    # ---- stage 4: stats (replaces Dropwizard meters / Kafka state topics) --
    with jax.named_scope("step_stats_compact"):
        tenant_counts = count_by_key(tenant, valid, T)
        alerts = (jnp.sum(thr["fired"], dtype=jnp.int32)
                  + jnp.sum(geo["fired"], dtype=jnp.int32)
                  + jnp.sum(prog["fired"], dtype=jnp.int32)
                  + jnp.sum(model["fired"], dtype=jnp.int32))
        alert_lanes = compact_alert_lanes(thr, geo, alert_lane_capacity,
                                          prog, model)

    new_state = DeviceStateTensors(
        last_interaction=last_interaction,
        present=present,
        presence_missing_since=presence_missing_since,
        event_count=event_count,
        last_location=last_location,
        last_location_ts=loc_ts,
        last_measurement=last_measurement,
        last_measurement_ts=last_measurement_ts,
        last_alert_type=last_alert_type,
        last_alert_level=last_alert_level,
        last_alert_ts=alert_ts,
        tenant_event_count=state.tenant_event_count + tenant_counts,
        tenant_alert_count=state.tenant_alert_count + count_by_key(
            tenant,
            valid & (thr["fired"] | geo["fired"] | prog["fired"]
                     | model["fired"]),
            T),
    )
    outputs = ProcessOutputs(
        valid=valid,
        unregistered=unregistered,
        threshold_fired=thr["fired"],
        threshold_first_rule=thr["first_rule"],
        threshold_alert_level=thr["alert_level"],
        geofence_fired=geo["fired"],
        geofence_first_rule=geo["first_rule"],
        geofence_alert_level=geo["alert_level"],
        program_fired=prog["fired"],
        program_first_rule=prog["first_rule"],
        program_alert_level=prog["alert_level"],
        model_fired=model["fired"],
        model_first=model["first_model"],
        model_level=model["alert_level"],
        model_score=model["score"],
        tenant_counts=tenant_counts,
        processed=jnp.sum(valid, dtype=jnp.int32),
        alerts=alerts,
        alert_lanes=alert_lanes,
        command_lanes=command_lanes,
    )
    return new_state, rule_state, model_state, actuation_state, outputs


def check_presence(state: DeviceStateTensors, registered: jnp.ndarray,
                   now_rel: jnp.ndarray, missing_interval_ms: jnp.ndarray
                   ) -> Tuple[DeviceStateTensors, jnp.ndarray]:
    """Periodic presence sweep (replaces DevicePresenceManager's
    PresenceChecker thread, DevicePresenceManager.java:110-135).

    A registered device that has interacted before and whose last interaction
    is older than `missing_interval_ms` transitions to NOT_PRESENT exactly
    once (send-once notification strategy): returns the newly-missing mask so
    the host can emit PresenceState change events.
    """
    has_interacted = state.last_interaction > _NEG
    overdue = (now_rel - state.last_interaction) > missing_interval_ms
    newly_missing = registered & has_interacted & state.present & overdue
    new_state = state.replace(
        present=state.present & ~newly_missing,
        presence_missing_since=jnp.where(newly_missing, now_rel,
                                         state.presence_missing_since),
    )
    return new_state, newly_missing
