"""Mechanical perf gate: compare link-independent ratios across rounds.

SURVEY.md §7 step 8 calls for perf CI against the north-star metric; the
absolute numbers from `bench.py` swing with the link's burst-bucket state
(docs/PERF.md), so the gate compares two drift-stable families measured
within one run: RATIOS between sections that share the same dominant
resource (telemetry/headline, sharded/headline, multitenant/sharded — all
link-transfer-bound, so the link state cancels), and ABSOLUTES for
host-CPU-only sections that never touch the link (persist, router cost,
narrow-window query). Ratio drift past tolerance is a hard failure.
Absolute drift hard-fails only between runs on the SAME hardware
(`link_probe_pre.host_cpu_model`/`host_cpu_cores` identity) whose
host-CPU timing fingerprints (`host_argsort_1m_ms`) are also comparable —
VM CPU steal moves host absolutes 4x on unchanged code (docs/PERF.md) —
and is otherwise reported as advisory with the reason in the verdict.
Link-sensitive checks (latency/age budgets, H2D overlap, the offload
speedup bounds) consume the same probe the bench records: on a degraded
H2D link (below MIN_LINK_H2D_MBPS) a miss becomes a structured
`link_waived` verdict object with the probe attached instead of a hard
FAIL, so `ok` keeps meaning "the code regressed".

One anomalous round must not poison the gate forever, so a current run
passes if its ratios are within tolerance of EITHER of the two most recent
recorded rounds (`BENCH_r0N.json`); both comparisons are reported. The
driver-recorded files wrap the bench line under `"parsed"` / `"tail"` —
both layouts are accepted.

Used two ways:
- `bench.py` calls `gate_against_recorded()` at the end of every run and
  embeds the verdict in its JSON line (plus a loud stderr warning).
- CLI: `python perf_gate.py PREV.json CURRENT.json [--tol 0.25]` exits
  nonzero on failure — the CI hook.

Reference has no perf CI at all (SURVEY.md §6); this exceeds it.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

# (ratio name, numerator key, denominator key). A ratio only cancels link
# state when BOTH sections share the same dominant resource — here, all
# are link-transfer-bound submit loops, so the ratio isolates workload
# shape. Ratios that mix resource domains (e.g. device-resident
# compute_only over the transfer-bound headline) track the weather, not
# the workload — the recorded rounds prove it (compute/headline swung
# -55% r03->r04) — so those sections are gated as absolutes below or not
# at all.
RATIO_KEYS: List[Tuple[str, str, str]] = [
    ("telemetry_vs_headline", "telemetry_packed_events_per_sec", "value"),
    ("sharded_vs_headline", "sharded_1chip_events_per_sec", "value"),
    ("multitenant_vs_sharded", "multitenant_sharded_events_per_sec",
     "sharded_1chip_events_per_sec"),
    # from-encoded-bytes over pre-interned: both are the same sharded
    # submit loop on the same engine; the quotient isolates the host
    # decode+intern edge (absent from rounds before r06 — the drift set
    # is the key intersection, so old comparisons are unaffected)
    ("sharded_bytes_vs_sharded", "sharded_from_bytes_events_per_sec",
     "sharded_1chip_events_per_sec"),
]

# Host-CPU-only sections never touch the link, and the host is the same
# machine across rounds — their ABSOLUTE values are comparable (wider
# tolerance: host scheduling noise). VERDICT r4 sketched persist/headline
# as a ratio, but persist is host-bound while the headline is
# transfer-bound, so that quotient rises whenever the link slows; the
# absolute is the honest comparison.
ABS_KEYS: List[str] = [
    "persist_events_per_sec",
    # the sustained composite is persist/consumer-bound (host CPU), not
    # link-bound — same reasoning as persist: its ratio to the
    # transfer-bound headline would track link weather
    "system_sustained_events_per_sec",
    "sharded_1chip_router_ms_per_step",
    "query_10m_narrow_window_ms",
]

DEFAULT_TOL = float(os.environ.get("BENCH_GATE_TOL", "0.25"))
DEFAULT_ABS_TOL = float(os.environ.get("BENCH_GATE_ABS_TOL", "0.35"))

# "Same machine across rounds" (the ABS_KEYS premise) is only true when
# the VM's effective CPU is comparable: round 5 measured the UNCHANGED
# router code at 1.9 ms and 7.9 ms on different days (CPU steal). The
# bench's link_probe carries a fixed-workload host fingerprint
# (host_argsort_1m_ms); absolute drift HARD-fails only between runs whose
# fingerprints are within this factor — otherwise the drift is still
# reported, marked advisory, with the reason in the verdict. Rounds
# recorded before the fingerprint existed can never prove comparability,
# so vs those the absolutes are advisory too (the ratio family plus
# self-consistency remain the hard gate). The bound must sit INSIDE
# abs_tol in the unfavorable direction — time-based keys scale linearly
# with host slowdown, so an admitted factor f inflates them by (f-1):
# 1.25 keeps +25% of pure CPU steal below the 35% hard-fail line.
HOST_STATE_RATIO_BOUND = 1.25

# Degraded-link threshold for the H2D probe the bench already records
# (link_probe_pre/post h2d_4mb_mbps_last): the link's sustained floor
# has been observed from 9 MB/s to 1.4 GB/s on the SAME code and day.
# Below this, every round trip in the link-sensitive checks (latency/age
# budgets, overlap, the offload speedup micro-benches whose finish line
# is a device_put) is measuring link weather, not code health — those
# checks then return a structured `link_waived` verdict object with the
# probe attached instead of a hard FAIL, so perf_gate.ok keeps meaning
# "the code regressed", and the waiver is mechanically auditable.
MIN_LINK_H2D_MBPS = 100.0

# intra-run self-consistency: the step_breakdown's parts must explain the
# synchronous step total (VERDICT r4: 16.7 ms total vs 3.1 ms of parts)
MAX_UNACCOUNTED_PCT = 25.0

# BASELINE.json's end-to-end latency budget, checked against the latency
# tier's measured p99 (offer -> linger -> pack -> H2D -> step -> alerts).
# The budget is a TPU deployment target: it gates only runs whose bench
# fingerprinted a real accelerator; on a CPU-only host (r05's 228 ms p99
# came from a CPU bench run) the check records the number as advisory
# instead of hard-failing every CI round.
LATENCY_BUDGET_MS = 10.0

# On-device shard routing (ops/route.py): the routed blob the mesh
# produces must be bit-identical to the host arena router's (any host —
# parity is a workload fact, hard everywhere), and the device route must
# at least match the host arena route it replaces at EVERY scale — the
# sort-based bucketing rewrite removed the O(B*S) one-hot work that made
# small batches lose, so the claim now gates on every
# accelerator-fingerprinted run. On a CPU-only host the ratio measures
# XLA-vs-native-C++ dispatch, not the workload: advisory there.
MIN_ROUTER_OFFLOAD_SPEEDUP = 1.0

# Device-compacted alert + command lanes pin the latency tier's
# materialize path to exactly TWO fixed-shape D2H fetches per offer (one
# batched device_get of both lanes), sized lane_capacity slots of
# ALERT_LANE_ROWS int32 rows (ops/compact.py) plus command_lane_capacity
# slots of COMMAND_LANE_ROWS int32 rows (ops/actuate.py). A regression
# back to per-array fetches (or a fatter lane layout) fails this on ANY
# host — fetch count and bytes are workload facts, not link weather.
ALERT_LANE_BYTES_PER_SLOT = 16
COMMAND_LANE_BYTES_PER_SLOT = 16
MATERIALIZE_FETCHES_PER_OFFER = 2
DEFAULT_COMMAND_LANE_CAPACITY = 64

# Compiled rule programs must at least match the host-side per-event
# RuleProcessor dispatch path they replace (marginal in-step cost per
# event vs host cost per event) at EVERY scale: the fused state slabs +
# segment-fold gather rewrite (ops/stateful.py) removed the per-row
# one-hot HBM round trips that made small batches lose, so small scale
# is no longer excused. On a CPU-only host the comparison measures
# XLA-vs-Python dispatch overhead, not the workload — advisory there,
# same reasoning that makes host absolutes advisory across
# non-comparable hosts. Every host always gates the fetch budget.
MIN_RULE_PROGRAM_SPEEDUP = 1.0

# Compiled anomaly models (ml/compiler.py scoring inside the fused
# step): model fires ride the spare alert-lane meta bits, so alert
# delivery must stay exactly TWO fixed-shape D2H fetches per offer
# (alert + command lanes in one batched device_get) with models scoring
# every tick — a workload fact, gated at every scale.
# The scoring stage's marginal step cost must stay under 10% of the
# model-free step, and its marginal per-event cost must at least match
# the host-side per-event scoring loop it replaces — both judged at
# EVERY scale on accelerator-fingerprinted hosts (the slab rewrite in
# ops/anomaly.py makes the small-batch claim winnable), advisory on
# CPU-only hosts (XLA-vs-Python dispatch, not the workload; same
# policy as rule_programs).
MIN_ANOMALY_MODEL_SPEEDUP = 1.0
MAX_ANOMALY_MODEL_MARGINAL_PCT = 10.0

# Actuation lanes (ops/actuate.py evaluating policies inside the fused
# step): command fires compact into their own fixed [4, K] int32 lane
# fetched in the SAME materialize device_get as the alert lane, so the
# fetch count stays at the two-fetch bit-fact — gated at every scale.
# The policy-evaluation stage's marginal step cost must stay under 10%
# of the policy-free step on accelerator-fingerprinted hosts (advisory
# on CPU-only hosts, same policy as anomaly_models); the speedup vs the
# host-side per-fire policy loop is recorded advisory everywhere — the
# lane exists for the fetch shape, not raw throughput.
MIN_ACTUATION_SPEEDUP = 1.0
MAX_ACTUATION_MARGINAL_PCT = 10.0

# The step flight recorder (runtime/flight.py) is ALWAYS ON, so its cost
# rides every step: the recorder's per-step self-cost (slot claim + a
# full set of stage marks, measured by bench's probe loop) must stay
# under 1% of the synchronous step time. Judged at FULL scale: on the
# cpu smoke a step is sub-millisecond, so the ratio measures the probe
# constant against scheduler noise, not the recorder against the
# workload — the smoke records it advisory like the other
# accelerator-scale claims.
MAX_OBSERVABILITY_OVERHEAD_PCT = 1.0

# Fault points (runtime/faults.py) + the ingest admission check
# (sources/manager.py) also ride every step/request. Disarmed, a fault
# point is one module-global load + identity test and a disabled
# admission controller is two attribute loads; bench probes the per-step
# crossing set and the sum must stay under 0.5% of the synchronous step
# wall. Same small-scale advisory policy as observability_overhead.
MAX_FAULT_OVERHEAD_PCT = 0.5
MAX_FENCING_OVERHEAD_PCT = 1.0

# Feeder fleet (sitewhere_tpu/feeders/): with feeders attached the mesh
# host's per-blob work must be H2D + dispatch — the receiver-side handoff
# overhead (decode + watermark + lock bookkeeping around the step) must
# stay under 5% of the step wall at feeders=1. Advisory on CPU-only
# hosts: the cpu backend's step is host CPU too, so the ratio there
# measures Python dispatch against a synchronous step, not the
# accelerator deployment the bound is about.
MAX_FEEDER_HANDOFF_PCT = 5.0

# Event-age telemetry (runtime/eventage.py): per step the hot path pays
# one sidecar stamp at ingest + one pure close() + one aggregate bucket
# fold into the labeled histogram; bench probes the full set and the sum
# must stay under 1% of the synchronous step wall. Same small-scale
# advisory policy as the other always-on observability planes.
MAX_TELEMETRY_OVERHEAD_PCT = 1.0

# Ingest->materialize age budget (bench's age_p99_ms, measured through
# the latency tier's deployed path: receiver stamp -> sidecar -> close at
# materialize). HARD on accelerator-fingerprinted hosts, advisory on the
# cpu smoke: age is end-to-end freshness — a deployment target like the
# latency budget — and with the staging ring overlapping H2D with
# dispatch the deployed path is expected to hold it wherever the
# latency budget itself is enforced. The cpu host stays advisory for the
# same reason latency_budget_met does: the budget is a TPU target.
AGE_P99_BUDGET_MS = 25.0

# H2D overlap (runtime/flight.py h2d_overlap_fraction, ROADMAP item 2):
# with the multi-buffered staging ring (pipeline/staging.py) the
# staging-side work of step N+1 (pack/route/guard/h2d) must mostly run
# under step N's dispatch window, and the critical stage must no longer
# be dispatch. HARD on accelerator hosts at full scale; advisory on the
# cpu smoke (no async dispatch on the cpu backend — device_put and the
# fused step are synchronous there, so overlap is structurally ~0).
MIN_H2D_OVERLAP = 0.6

# Query serving tier (sitewhere_tpu/serving/): the incremental window
# cache must make a repeat window ≥5x cheaper than the cold full rescan
# (delta-scan + exact merge vs scanning every sealed segment), and the
# vectorized replay decode must beat the per-record loop oracle it
# replaced by ≥3x — both are host-vs-host comparisons of the same
# workload on the same machine, so they gate HARD on every host (the
# bench takes the best trial: steal noise only shrinks the ratio). The
# concurrency claims — 64 dashboard clients degrade full-rate ingest
# < 10% and keep query p99 inside budget — are deployment targets like
# the latency budget: hard on accelerator-fingerprinted hosts, advisory
# on the cpu smoke (readers and the synchronous cpu step fight for the
# same cores there, which is not the deployment), link-waiver eligible
# (a degraded link stalls the ingest baseline and the loaded run
# differently, poisoning the quotient).
MIN_CACHE_DELTA_SPEEDUP = 5.0
MIN_REPLAY_VEC_SPEEDUP = 3.0
MAX_INGEST_DEGRADATION_PCT = 10.0
QUERY_P99_BUDGET_MS = 50.0

# Trial-spread bounds: full scale judges the accelerator-scale claim; the
# BENCH_SCALE=small smoke still EVALUATES the check (bench's sections now
# measure steady-state windows with explicit warmup exclusion, so the
# smoke must stay bounded too) but against a wider bound — its sub-ms
# section timings are scheduler-noise-dominated on shared CI hosts.
MAX_SPREAD_PCT = 60.0
MAX_SPREAD_PCT_SMALL = 150.0


def extract_bench(doc: Dict) -> Optional[Dict]:
    """The bench result dict from either a raw bench line or a
    driver-recorded BENCH_r0N.json ({"parsed": ...} or {"tail": "..."})."""
    if not isinstance(doc, dict):
        return None
    if "value" in doc and "metric" in doc:
        return doc
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "value" in parsed:
        return parsed
    tail = doc.get("tail")
    if isinstance(tail, str):
        for line in reversed(tail.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    cand = json.loads(line)
                except ValueError:
                    continue
                if isinstance(cand, dict) and "value" in cand:
                    return cand
    return None


def link_state(bench: Dict) -> Dict:
    """Degraded-link verdict from the run's own probes: worst
    h2d_4mb_mbps_last across link_probe_pre/post (the compact line may
    carry only the pre probe; the sidecar has both) against
    MIN_LINK_H2D_MBPS. Runs recorded before the probe existed are never
    'degraded' — absence of evidence keeps the checks hard."""
    probes: Dict[str, float] = {}
    worst: Optional[float] = None
    for key in ("link_probe_pre", "link_probe_post"):
        probe = bench.get(key)
        if isinstance(probe, dict):
            v = probe.get("h2d_4mb_mbps_last")
            if isinstance(v, (int, float)) and v > 0:
                probes[key] = v
                worst = v if worst is None else min(worst, v)
    return {"degraded": worst is not None and worst < MIN_LINK_H2D_MBPS,
            "h2d_4mb_mbps": probes,
            "threshold_mbps": MIN_LINK_H2D_MBPS}


def _link_waiver(link: Dict, what: str) -> Dict:
    """The structured link_waived object: what was waived, why, and the
    probe evidence — everything a reader needs to adjudicate the waiver
    without the run's shell logs."""
    return {"waived": "link_degraded",
            "what": what,
            "reason": (f"H2D probe below {MIN_LINK_H2D_MBPS} MB/s — the "
                       "check measures link weather on this link, not "
                       "code health"),
            "h2d_4mb_mbps": link["h2d_4mb_mbps"],
            "threshold_mbps": MIN_LINK_H2D_MBPS}


def ratios_of(bench: Dict) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, num_key, den_key in RATIO_KEYS:
        num, den = bench.get(num_key), bench.get(den_key)
        if isinstance(num, (int, float)) and isinstance(den, (int, float)) \
                and den:
            out[name] = num / den
    return out


def compare(prev_bench: Dict, cur_bench: Dict, tol: float = DEFAULT_TOL,
            abs_tol: float = DEFAULT_ABS_TOL) -> Dict:
    """Drift comparison of one run against one baseline run: ratio drift
    on the link-cancelling pairs + absolute drift on the host-CPU-only
    sections.

    Returns {"ok", "tol", "abs_tol", "ratios": {name: {prev, cur,
    drift_pct}}, "absolutes": {...}, "failures": [name...]} — drift is
    cur/prev - 1. |ratio drift| past tolerance is always a failure.
    |absolute drift| past tolerance is a failure only when both runs
    carry comparable host fingerprints (link_probe_pre.host_argsort_1m_ms
    within HOST_STATE_RATIO_BOUND); otherwise the entry is annotated
    "advisory_exceeded": true, the reason lands in top-level
    "absolutes_advisory", and ok stays unaffected by it.
    """
    # Comparisons only hold when both runs measured the SAME workload
    # config; the metric string embeds devices/batch, so a
    # BENCH_SCALE=small smoke never gets judged against a recorded
    # full-scale round.
    if prev_bench.get("metric") != cur_bench.get("metric"):
        return {"ok": True, "tol": tol, "abs_tol": abs_tol, "ratios": {},
                "absolutes": {}, "failures": [],
                "skipped": "scale_mismatch"}
    failures: List[str] = []

    def drifts(prev_vals: Dict[str, float], cur_vals: Dict[str, float],
               bound: float, gated: bool = True) -> Dict[str, Dict]:
        out: Dict[str, Dict] = {}
        for name in sorted(set(prev_vals) & set(cur_vals)):
            if not prev_vals[name]:
                continue
            drift = cur_vals[name] / prev_vals[name] - 1.0
            out[name] = {"prev": round(prev_vals[name], 4),
                         "cur": round(cur_vals[name], 4),
                         "drift_pct": round(drift * 100, 1)}
            if abs(drift) > bound:
                if gated:
                    failures.append(name)
                else:
                    out[name]["advisory_exceeded"] = True
        return out

    def host_fp(bench: Dict):
        probe = bench.get("link_probe_pre") or {}
        v = probe.get("host_argsort_1m_ms")
        return v if isinstance(v, (int, float)) and v > 0 else None

    def host_identity(bench: Dict):
        """(cpu model, core count) hardware identity, None when the run
        predates the fingerprint. Unlike the argsort timing (CPU-steal
        sensitive), this is stable — two runs with DIFFERENT identities
        are different machines and can never hard-fail each other's
        host-CPU absolutes."""
        probe = bench.get("link_probe_pre") or {}
        model, cores = probe.get("host_cpu_model"), probe.get(
            "host_cpu_cores")
        if not model or not isinstance(cores, int) or cores <= 0:
            return None
        return (str(model), cores)

    prev_fp, cur_fp = host_fp(prev_bench), host_fp(cur_bench)
    prev_id, cur_id = host_identity(prev_bench), host_identity(cur_bench)
    if prev_fp is None or cur_fp is None:
        host_comparable = False
        host_note = ("no host fingerprint in "
                     + ("baseline" if prev_fp is None else "current")
                     + " run; host-absolute drift is advisory")
    elif prev_id is not None and cur_id is not None and prev_id != cur_id:
        host_comparable = False
        host_note = (f"different host hardware ({prev_id[0]!r} x{prev_id[1]}"
                     f" -> {cur_id[0]!r} x{cur_id[1]}); host-absolute "
                     f"drift is advisory")
    else:
        factor = cur_fp / prev_fp
        host_comparable = (1.0 / HOST_STATE_RATIO_BOUND <= factor
                           <= HOST_STATE_RATIO_BOUND)
        host_note = (None if host_comparable else
                     f"host CPU state mismatch (argsort {prev_fp} -> "
                     f"{cur_fp} ms); host-absolute drift is advisory")

    # A degraded link is whole-VM I/O weather: the same runs that show
    # it also show host-absolute swings on unchanged code, so absolute
    # drift between a degraded run and anything else carries a
    # structured waiver instead of hard-failing (satellite: perf_gate
    # consumes the link probe it records).
    prev_link, cur_link = link_state(prev_bench), link_state(cur_bench)
    link_waived = None
    if prev_link["degraded"] or cur_link["degraded"]:
        which = ("baseline" if prev_link["degraded"] else "current") \
            if prev_link["degraded"] != cur_link["degraded"] else "both"
        link_waived = _link_waiver(
            cur_link if cur_link["degraded"] else prev_link,
            f"host-absolute drift vs a degraded-link run ({which})")
    ratios = drifts(ratios_of(prev_bench), ratios_of(cur_bench), tol)
    absolutes = drifts(
        {k: prev_bench[k] for k in ABS_KEYS
         if isinstance(prev_bench.get(k), (int, float))},
        {k: cur_bench[k] for k in ABS_KEYS
         if isinstance(cur_bench.get(k), (int, float))}, abs_tol,
        gated=host_comparable and link_waived is None)
    out = {"ok": not failures, "tol": tol, "abs_tol": abs_tol,
           "ratios": ratios, "absolutes": absolutes,
           "failures": failures}
    if link_waived:
        out["link_waived"] = link_waived
    if host_note:
        out["absolutes_advisory"] = host_note
    return out


def self_consistency(bench: Dict) -> Dict:
    """Intra-run checks that need no baseline: the breakdown must explain
    the synchronous total, and trial spreads must not be wild."""
    checks: Dict[str, Dict] = {}
    small = bench.get("scale") == "small"
    # on the cpu backend the plain submit path zero-copies its input, so
    # the explicitly-staged decomposition is not the same program — the
    # reconciliation claim (like the spread bound) is about full scale
    bd = {} if small else bench.get("step_breakdown") or {}
    unacc = bd.get("unaccounted_pct")
    if isinstance(unacc, (int, float)):
        checks["breakdown_explains_sync_total"] = {
            "ok": abs(unacc) <= MAX_UNACCOUNTED_PCT,
            "unaccounted_pct": unacc, "max_pct": MAX_UNACCOUNTED_PCT}
    # Budget semantics: the best TRIAL's p99 must meet the budget — one
    # trial is a full run of back-to-back STEADY-STATE offers (bench's
    # latency section excludes its per-trial warmup from the samples), so
    # a passing trial demonstrates the system meets the budget end-to-end
    # whenever the link isn't in its degraded regime (which poisons
    # every round trip in a trial at once, ~100 ms each; see
    # docs/PERF.md). The pooled p99 rides along in the artifact for the
    # honest worst case. Evaluated at EVERY scale: the cpu smoke's warm
    # path must meet the budget too, or CI cannot vouch for the tier.
    trial_p99 = bench.get("latency_mode_trial_p99_ms")
    cpu_host = "cpu" in str(bench.get("device") or "").lower()
    link = link_state(bench)
    if isinstance(trial_p99, list):
        numeric = [v for v in trial_p99 if isinstance(v, (int, float))]
        if numeric:
            best = min(numeric)
            met = best <= LATENCY_BUDGET_MS
            entry = {
                "ok": met or cpu_host,
                "best_trial_p99_ms": best,
                "trial_p99_ms": trial_p99, "budget_ms": LATENCY_BUDGET_MS}
            if cpu_host and not met:
                entry["advisory"] = (
                    "over budget on a CPU-only bench host (advisory; the "
                    "10 ms p99 is a TPU target and gates only "
                    "accelerator-fingerprinted runs)")
            elif not met and link["degraded"]:
                # every offer in the tier rides the degraded link once
                # per round trip — budget misses there are link weather
                entry["ok"] = True
                entry["link_waived"] = _link_waiver(
                    link, "end-to-end latency budget missed")
            checks["latency_budget_met"] = entry
    # Fetch budget: the latency tier's materialize path must perform
    # exactly 2 fixed-shape D2H fetches per offer (alert lane + command
    # lane, one batched device_get), bytes bounded by the two lane
    # capacities — self-consistent on every host, fast or slow link
    # alike (absent from rounds before the lanes existed: no check).
    fetch = bench.get("latency_fetch")
    if isinstance(fetch, dict):
        fpo = fetch.get("d2h_fetches_per_offer")
        bpo = fetch.get("d2h_bytes_per_offer")
        cap = fetch.get("lane_capacity")
        if all(isinstance(v, (int, float)) for v in (fpo, bpo, cap)):
            cmd_cap = fetch.get("command_lane_capacity")
            if not isinstance(cmd_cap, (int, float)):
                cmd_cap = DEFAULT_COMMAND_LANE_CAPACITY
            max_bytes = (cap * ALERT_LANE_BYTES_PER_SLOT
                         + cmd_cap * COMMAND_LANE_BYTES_PER_SLOT)
            checks["latency_fetch_budget"] = {
                "ok": fpo == MATERIALIZE_FETCHES_PER_OFFER
                and bpo <= max_bytes,
                "d2h_fetches_per_offer": fpo,
                "d2h_bytes_per_offer": bpo,
                "max_bytes_per_offer": max_bytes}
    # Rule-program budget: with compiled programs ACTIVE in the fused
    # step, alert delivery must still be exactly 2 fixed-shape D2H
    # fetches per offer (program fires ride the spare alert-lane meta
    # bits — the lane budget is unchanged), and the compiled path must
    # beat the host-side per-event RuleProcessor loop it replaces. Both
    # are workload facts, valid on any host (absent before the tier
    # existed).
    rp = bench.get("rule_programs")
    if isinstance(rp, dict):
        rp_fpo = rp.get("d2h_fetches_per_offer")
        rp_speedup = rp.get("compiled_vs_host_speedup_x")
        if all(isinstance(v, (int, float))
               for v in (rp_fpo, rp_speedup)):
            speedup_ok = rp_speedup >= MIN_RULE_PROGRAM_SPEEDUP
            entry = {
                "ok": rp_fpo == MATERIALIZE_FETCHES_PER_OFFER
                and (speedup_ok or cpu_host),
                "d2h_fetches_per_offer": rp_fpo,
                "compiled_vs_host_speedup_x": rp_speedup,
                "min_speedup_x": MIN_RULE_PROGRAM_SPEEDUP}
            if cpu_host and not speedup_ok:
                entry["speedup_advisory"] = (
                    "below bound on a CPU-only bench host (advisory; "
                    "XLA-vs-native-dispatch, not the workload — the "
                    "bound gates accelerator-fingerprinted runs at "
                    "every scale)")
            elif not speedup_ok and link["degraded"]:
                entry["ok"] = rp_fpo == MATERIALIZE_FETCHES_PER_OFFER
                entry["link_waived"] = _link_waiver(
                    link, "rule-program offload speedup below bound")
            checks["rule_programs"] = entry
    # Anomaly-model budget: with compiled models scoring every tick in
    # the fused step, alert delivery must still be exactly 2 fixed-shape
    # D2H fetches per offer (model fires ride the spare alert-lane meta
    # bits); the scoring stage's marginal step cost and its per-event
    # cost vs the host scorer gate at full scale (absent before the
    # tier existed: no check).
    am = bench.get("anomaly_models")
    if isinstance(am, dict):
        am_fpo = am.get("d2h_fetches_per_offer")
        am_speedup = am.get("offload_speedup_x")
        am_marginal = am.get("marginal_step_pct")
        if all(isinstance(v, (int, float))
               for v in (am_fpo, am_speedup, am_marginal)):
            cost_ok = (am_speedup >= MIN_ANOMALY_MODEL_SPEEDUP
                       and am_marginal < MAX_ANOMALY_MODEL_MARGINAL_PCT)
            entry = {
                "ok": am_fpo == MATERIALIZE_FETCHES_PER_OFFER
                and (cost_ok or cpu_host),
                "d2h_fetches_per_offer": am_fpo,
                "offload_speedup_x": am_speedup,
                "marginal_step_pct": am_marginal,
                "min_speedup_x": MIN_ANOMALY_MODEL_SPEEDUP,
                "max_marginal_step_pct": MAX_ANOMALY_MODEL_MARGINAL_PCT}
            if cpu_host and not cost_ok:
                entry["cost_advisory"] = (
                    "below bound on a CPU-only bench host (advisory; "
                    "XLA-vs-Python-dispatch, not the workload — the "
                    "bounds gate accelerator-fingerprinted runs at "
                    "every scale)")
            elif not cost_ok and link["degraded"]:
                entry["ok"] = am_fpo == MATERIALIZE_FETCHES_PER_OFFER
                entry["link_waived"] = _link_waiver(
                    link, "anomaly-model offload cost bounds missed")
            checks["anomaly_models"] = entry
    # Actuation-lane budget: with actuation policies ACTIVE, command
    # fires ride their own [4, K] lane inside the SAME materialize
    # device_get — the fetch count must stay at the two-fetch bit-fact
    # on every host. The policy stage's marginal step cost gates under
    # 10% on accelerator-fingerprinted hosts; the speedup vs the
    # host-side per-fire policy loop is recorded advisory everywhere
    # (absent before the tier existed: no check).
    act = bench.get("actuation")
    if isinstance(act, dict):
        act_fpo = act.get("d2h_fetches_per_offer")
        act_marginal = act.get("marginal_step_pct")
        if all(isinstance(v, (int, float))
               for v in (act_fpo, act_marginal)):
            marginal_ok = act_marginal < MAX_ACTUATION_MARGINAL_PCT
            entry = {
                "ok": act_fpo == MATERIALIZE_FETCHES_PER_OFFER
                and (marginal_ok or cpu_host),
                "d2h_fetches_per_offer": act_fpo,
                "marginal_step_pct": act_marginal,
                "max_marginal_step_pct": MAX_ACTUATION_MARGINAL_PCT}
            act_speedup = act.get("lane_vs_host_speedup_x")
            if isinstance(act_speedup, (int, float)):
                entry["lane_vs_host_speedup_x"] = act_speedup
                entry["min_speedup_x"] = MIN_ACTUATION_SPEEDUP
                if act_speedup < MIN_ACTUATION_SPEEDUP:
                    entry["speedup_advisory"] = (
                        "below bound (advisory everywhere; the command "
                        "lane exists for the fixed fetch shape, not raw "
                        "throughput)")
            act_p99 = act.get("detection_to_actuation_p99_ms")
            if isinstance(act_p99, (int, float)):
                entry["detection_to_actuation_p99_ms"] = act_p99
            if cpu_host and not marginal_ok:
                entry["cost_advisory"] = (
                    "over bound on a CPU-only bench host (advisory; "
                    "XLA-vs-Python-dispatch, not the workload — the "
                    "bound gates accelerator-fingerprinted runs at "
                    "every scale)")
            elif not marginal_ok and link["degraded"]:
                entry["ok"] = act_fpo == MATERIALIZE_FETCHES_PER_OFFER
                entry["link_waived"] = _link_waiver(
                    link, "actuation marginal step cost over bound")
            checks["actuation_lanes"] = entry
    # Device routing: the on-device route's output must be bit-identical
    # to the host arena router's (parity_ok — a workload fact on any
    # host), and the pinned full-batch micro-bench must show the device
    # route at least matching the host route it replaces (full scale
    # only; the cpu smoke records it advisory).
    dr = bench.get("device_routing")
    if isinstance(dr, dict):
        dr_parity = dr.get("parity_ok")
        dr_speedup = dr.get("router_offload_speedup_x")
        if dr_parity is not None and isinstance(dr_speedup, (int, float)):
            dr_speedup_ok = dr_speedup >= MIN_ROUTER_OFFLOAD_SPEEDUP
            entry = {
                "ok": bool(dr_parity) and (dr_speedup_ok or cpu_host),
                "parity_ok": bool(dr_parity),
                "router_offload_speedup_x": dr_speedup,
                "min_speedup_x": MIN_ROUTER_OFFLOAD_SPEEDUP}
            if cpu_host and not dr_speedup_ok:
                entry["speedup_advisory"] = (
                    "below bound on a CPU-only bench host (advisory; "
                    "XLA-vs-native-C++-dispatch, not the workload — "
                    "the bound gates accelerator-fingerprinted runs "
                    "at every scale)")
            elif not dr_speedup_ok and link["degraded"]:
                # parity stays HARD: bit-identity is a workload fact on
                # any link; only the timing ratio rides the link
                entry["ok"] = bool(dr_parity)
                entry["link_waived"] = _link_waiver(
                    link, "router offload speedup below bound")
            checks["device_routing"] = entry
    # Observability overhead: the always-on flight recorder's per-step
    # self-cost must stay under 1% of the synchronous step time (full
    # scale; the cpu smoke's sub-ms steps make the ratio advisory).
    fl = bench.get("flight")
    if isinstance(fl, dict):
        ov_pct = fl.get("recorder_overhead_pct_of_step")
        if isinstance(ov_pct, (int, float)):
            ov_ok = ov_pct < MAX_OBSERVABILITY_OVERHEAD_PCT
            entry = {
                "ok": ov_ok or small,
                "recorder_overhead_pct_of_step": ov_pct,
                "max_pct": MAX_OBSERVABILITY_OVERHEAD_PCT}
            if small and not ov_ok:
                entry["advisory"] = (
                    "over bound on the cpu smoke host (advisory; sub-ms "
                    "steps make the ratio noise — the bound gates at "
                    "full scale)")
            checks["observability_overhead"] = entry
    # Telemetry overhead: the event-age plane (sidecar stamp + close +
    # histogram fold, always on once a receiver stamps deliveries) must
    # stay under 1% of the step wall (full scale; advisory on the cpu
    # smoke for the same sub-ms-step reason as the recorder probe).
    tel_pct = bench.get("telemetry_overhead_pct")
    if isinstance(tel_pct, (int, float)):
        tel_ok = tel_pct < MAX_TELEMETRY_OVERHEAD_PCT
        entry = {
            "ok": tel_ok or small,
            "telemetry_overhead_pct": tel_pct,
            "max_pct": MAX_TELEMETRY_OVERHEAD_PCT}
        if small and not tel_ok:
            entry["advisory"] = (
                "over bound on the cpu smoke host (advisory; sub-ms "
                "steps make the ratio noise — the bound gates at "
                "full scale)")
        checks["telemetry_overhead"] = entry
    # Age budget: ingest->materialize p99 through the deployed latency
    # path. Hard on accelerator hosts, advisory on the cpu smoke (see
    # AGE_P99_BUDGET_MS) — the freshness target gates wherever the
    # latency budget itself does.
    age_p99 = bench.get("age_p99_ms")
    if isinstance(age_p99, (int, float)) and age_p99 > 0:
        age_ok = age_p99 <= AGE_P99_BUDGET_MS
        entry = {"ok": age_ok or cpu_host, "age_p99_ms": age_p99,
                 "budget_ms": AGE_P99_BUDGET_MS}
        if cpu_host and not age_ok:
            entry["advisory"] = (
                f"age p99 {age_p99} ms over the {AGE_P99_BUDGET_MS} ms "
                "freshness target on a CPU-only bench host (advisory; "
                "the budget is a TPU target and gates only "
                "accelerator-fingerprinted runs)")
        elif not age_ok and link["degraded"]:
            entry["ok"] = True
            entry["link_waived"] = _link_waiver(
                link, "ingest->materialize age budget missed")
        checks["age_p99_budget_ms"] = entry
    # H2D overlap: the staging ring must actually overlap — most of the
    # staging-side work under the previous dispatch window, and dispatch
    # no longer the modal critical stage. Hard on accelerator hosts at
    # full scale; advisory on the cpu smoke (synchronous backend, no
    # async dispatch window to hide transfers under) and at small scale
    # (sub-ms steps make the fraction noise). Keys live in the full
    # in-run result only — recorded compact lines skip the check.
    fl = bench.get("flight")
    if isinstance(fl, dict) and "h2d_overlap_fraction" in fl:
        overlap = fl.get("h2d_overlap_fraction")
        crit = fl.get("critical_stage") or ""
        if isinstance(overlap, (int, float)):
            met = overlap >= MIN_H2D_OVERLAP and crit != "dispatch"
            entry = {
                "ok": met or small or cpu_host,
                "h2d_overlap_fraction": overlap,
                "critical_stage": crit,
                "min_overlap": MIN_H2D_OVERLAP}
            if (small or cpu_host) and not met:
                entry["advisory"] = (
                    "overlap under bound on a CPU-only/smoke host "
                    "(advisory; the cpu backend dispatches "
                    "synchronously, so there is no dispatch window to "
                    "overlap — the bound gates accelerator-"
                    "fingerprinted full-scale runs)")
            elif not met and link["degraded"]:
                entry["ok"] = True
                entry["link_waived"] = _link_waiver(
                    link, "H2D overlap fraction under bound")
            checks["h2d_overlap"] = entry
    # Fault-injection overhead: disarmed fault points + the admission
    # check must stay under 0.5% of the step wall (full scale; advisory
    # on the cpu smoke for the same sub-ms-step reason).
    fa = bench.get("faults")
    if isinstance(fa, dict):
        fa_pct = fa.get("disarmed_overhead_pct_of_step")
        if isinstance(fa_pct, (int, float)):
            fa_ok = fa_pct < MAX_FAULT_OVERHEAD_PCT
            entry = {
                "ok": fa_ok or small,
                "disarmed_overhead_pct_of_step": fa_pct,
                "max_pct": MAX_FAULT_OVERHEAD_PCT}
            if small and not fa_ok:
                entry["advisory"] = (
                    "over bound on the cpu smoke host (advisory; sub-ms "
                    "steps make the ratio noise — the bound gates at "
                    "full scale)")
            checks["fault_injection_overhead"] = entry
    # Fencing overhead: the steady-state failover-plane crossings
    # (inactive replay-barrier check + per-origin fence admit + lease
    # renewal) must stay under 1% of the step wall (full scale; advisory
    # on the cpu smoke for the same sub-ms-step reason).
    fe = bench.get("fencing")
    if isinstance(fe, dict):
        fe_pct = fe.get("disarmed_overhead_pct_of_step")
        if isinstance(fe_pct, (int, float)):
            fe_ok = fe_pct < MAX_FENCING_OVERHEAD_PCT
            entry = {
                "ok": fe_ok or small,
                "disarmed_overhead_pct_of_step": fe_pct,
                "max_pct": MAX_FENCING_OVERHEAD_PCT}
            if small and not fe_ok:
                entry["advisory"] = (
                    "over bound on the cpu smoke host (advisory; sub-ms "
                    "steps make the ratio noise — the bound gates at "
                    "full scale)")
            checks["fencing_overhead"] = entry
    # Feeder-fleet handoff budget: at feeders=1 the blob receiver's
    # non-step work must stay under 5% of the step wall — the subsystem's
    # whole point is that the mesh host no longer decodes/interns/packs.
    # Hard on accelerator-fingerprinted hosts; advisory on the cpu smoke
    # (see MAX_FEEDER_HANDOFF_PCT). Absent before the tier existed: no
    # check.
    ff = bench.get("feeder_fleet")
    if isinstance(ff, dict):
        ff_pct = ff.get("handoff_pct_of_step")
        if isinstance(ff_pct, (int, float)):
            ff_ok = ff_pct < MAX_FEEDER_HANDOFF_PCT
            entry = {
                "ok": ff_ok or cpu_host or small,
                "handoff_pct_of_step": ff_pct,
                "max_pct": MAX_FEEDER_HANDOFF_PCT}
            if (cpu_host or small) and not ff_ok:
                entry["advisory"] = (
                    "over bound on a CPU-only/smoke host (advisory; the "
                    "cpu backend's step is host CPU too, so the ratio "
                    "measures dispatch noise — the bound gates "
                    "accelerator-fingerprinted runs)")
            checks["feeder_fleet"] = entry
    # Query-serving budget: the window cache's delta-scan speedup and
    # replay parity are same-host workload facts — hard everywhere. The
    # vectorized-replay pin is also host-vs-host (numpy chunk decode vs
    # the per-record loop oracle, same compiled kernel on both sides)
    # but its advantage amortizes a fixed per-call cost over rows, so it
    # gates hard at full scale only and is advisory on the small smoke's
    # abbreviated corpus. The 64-client concurrency targets (ingest
    # degradation, query p99) gate on accelerator hosts only; the cpu
    # smoke runs readers and the synchronous step on the same cores, so
    # the degradation there measures core contention, not the
    # deployment. Absent before the tier existed: no check.
    sv = bench.get("serving")
    if isinstance(sv, dict):
        cache_x = sv.get("cache_delta_speedup_x")
        replay_x = sv.get("replay_vec_speedup_x")
        parity = sv.get("replay_parity_ok")
        if all(isinstance(v, (int, float)) for v in (cache_x, replay_x)):
            degr = bench.get("ingest_degradation_pct")
            p99 = bench.get("query_p99_ms")
            replay_ok = replay_x >= MIN_REPLAY_VEC_SPEEDUP
            host_ok = (cache_x >= MIN_CACHE_DELTA_SPEEDUP
                       and (replay_ok or small)
                       and bool(parity))
            conc_known = all(isinstance(v, (int, float))
                             for v in (degr, p99))
            conc_ok = (not conc_known
                       or (degr < MAX_INGEST_DEGRADATION_PCT
                           and p99 <= QUERY_P99_BUDGET_MS))
            entry = {
                "ok": host_ok and (conc_ok or cpu_host or small),
                "cache_delta_speedup_x": cache_x,
                "min_cache_speedup_x": MIN_CACHE_DELTA_SPEEDUP,
                "replay_vec_speedup_x": replay_x,
                "min_replay_speedup_x": MIN_REPLAY_VEC_SPEEDUP,
                "replay_parity_ok": bool(parity)}
            if small and not replay_ok:
                entry["replay_advisory"] = (
                    "replay vectorization under bound on the small "
                    "smoke (advisory; the abbreviated replay corpus "
                    "does not amortize the fixed per-call decode cost "
                    "— the bound gates full-scale runs on every host)")
            if conc_known:
                entry["ingest_degradation_pct"] = degr
                entry["max_degradation_pct"] = MAX_INGEST_DEGRADATION_PCT
                entry["query_p99_ms"] = p99
                entry["query_p99_budget_ms"] = QUERY_P99_BUDGET_MS
            if (cpu_host or small) and not conc_ok:
                entry["concurrency_advisory"] = (
                    "ingest-degradation/p99 over bound on a CPU-only/"
                    "smoke host (advisory; readers and the synchronous "
                    "cpu step contend for the same cores — the bounds "
                    "gate accelerator-fingerprinted runs)")
            elif not conc_ok and link["degraded"]:
                entry["ok"] = host_ok
                entry["link_waived"] = _link_waiver(
                    link, "serving concurrency bounds missed")
            checks["query_serving"] = entry
    # Spread judged against the steady-state windows at every scale; the
    # BENCH_SCALE=small smoke gets the wider bound (sub-millisecond CPU
    # section timings ride scheduler noise on shared CI hosts).
    spreads = bench.get("spread_pct") or {}
    bound = MAX_SPREAD_PCT_SMALL if small else MAX_SPREAD_PCT
    wild = {k: v for k, v in spreads.items()
            if isinstance(v, (int, float)) and v > bound}
    if spreads:
        checks["trial_spread_bounded"] = {"ok": not wild, "wild": wild,
                                          "max_pct": bound}
    return {"ok": all(c["ok"] for c in checks.values()) if checks else True,
            "checks": checks}


def recorded_rounds(root: str = ".") -> List[Tuple[int, str]]:
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r0*(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def gate_against_recorded(cur_bench: Dict, root: str = ".",
                          tol: float = DEFAULT_TOL) -> Dict:
    """Full gate for a fresh bench result: self-consistency plus ratio
    drift vs the two most recent recorded rounds (pass if within tolerance
    of either — one anomalous round must not poison the gate)."""
    consistency = self_consistency(cur_bench)
    rounds = recorded_rounds(root)[-2:]
    comparisons: Dict[str, Dict] = {}
    ratio_ok = True if not rounds else False
    compared = False  # did at least one REAL drift comparison run?
    for n, path in rounds:
        try:
            with open(path) as fh:
                prev = extract_bench(json.load(fh))
        except (OSError, ValueError):
            continue
        if prev is None:
            continue
        cmp = compare(prev, cur_bench, tol)
        comparisons[f"r{n:02d}"] = cmp
        if "skipped" not in cmp:
            compared = True
        if cmp["ok"]:
            ratio_ok = True
    if not comparisons:
        ratio_ok = True  # nothing recorded yet: nothing to drift from
    # `compared: false` + ok means the gate FAILED OPEN (no recorded
    # round was comparable — first round, scale mismatch, or unreadable
    # files), not that drift was checked and passed. Callers surface it.
    return {"ok": bool(consistency["ok"] and ratio_ok),
            "compared": compared,
            "link": link_state(cur_bench),
            "self_consistency": consistency,
            "vs_recorded": comparisons}


def main(argv: List[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("prev", help="baseline BENCH json (raw or recorded)")
    ap.add_argument("cur", help="current BENCH json (raw or recorded)")
    ap.add_argument("--tol", type=float, default=DEFAULT_TOL)
    args = ap.parse_args(argv)
    with open(args.prev) as fh:
        prev = extract_bench(json.load(fh))
    with open(args.cur) as fh:
        cur = extract_bench(json.load(fh))
    if prev is None or cur is None:
        print("perf_gate: could not extract a bench result", file=sys.stderr)
        return 2
    cmp = compare(prev, cur, args.tol)
    consistency = self_consistency(cur)
    print(json.dumps({"compare": cmp, "self_consistency": consistency},
                     indent=2))
    if not cmp["ok"]:
        print(f"perf_gate: FAIL — ratio drift past {args.tol:.0%} on: "
              f"{', '.join(cmp['failures'])}", file=sys.stderr)
        return 1
    if not consistency["ok"]:
        print("perf_gate: FAIL — self-consistency", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
