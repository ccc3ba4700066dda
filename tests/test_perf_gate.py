"""Perf gate (perf_gate.py): the mechanical ratio comparison SURVEY §7
step 8 calls for. The gate must cancel link state (ratios, not
absolutes), tolerate one anomalous recorded round, accept every recorded
file layout the driver produces, and flag intra-run inconsistency
(VERDICT r4: sync_total 16.7 ms vs 3.1 ms of parts went unflagged)."""

import json

import pytest

from perf_gate import (
    compare, extract_bench, gate_against_recorded, main, ratios_of,
    self_consistency)


def _bench(headline=40e6, telemetry=44e6, sharded=36e6, persist=8e6,
           multitenant=34e6, analytics=10e6, compute=600e6,
           unaccounted_pct=5.0, spreads=None, host_ms=5.0,
           cpu_model="Xeon-Test 2.0GHz", cpu_cores=16):
    out = {
        "metric": "events/sec ...", "value": headline,
        "telemetry_packed_events_per_sec": telemetry,
        "sharded_1chip_events_per_sec": sharded,
        "persist_events_per_sec": persist,
        "multitenant_sharded_events_per_sec": multitenant,
        "analytics_replay_events_per_sec": analytics,
        "compute_only_events_per_sec": compute,
        "step_breakdown": {"unaccounted_pct": unaccounted_pct},
        "spread_pct": spreads or {"headline": 8.0},
    }
    if host_ms is not None:
        out["link_probe_pre"] = {"host_argsort_1m_ms": host_ms}
        if cpu_model is not None:
            out["link_probe_pre"]["host_cpu_model"] = cpu_model
            out["link_probe_pre"]["host_cpu_cores"] = cpu_cores
    return out


def test_extract_bench_raw_parsed_and_tail_layouts():
    raw = _bench()
    assert extract_bench(raw) is raw
    assert extract_bench({"parsed": raw, "rc": 0}) is raw
    tail = "WARNING: noise\n" + json.dumps(raw) + "\n"
    got = extract_bench({"tail": tail, "rc": 0})
    assert got["value"] == raw["value"]
    # garbage after the result line: the LAST parseable bench line wins
    got = extract_bench({"tail": tail + "{not json\n"})
    assert got["value"] == raw["value"]
    assert extract_bench({"tail": "no json here"}) is None
    assert extract_bench({"rc": 1}) is None


def test_ratios_cancel_link_scale():
    # a slower link scales every link-transfer-bound section together;
    # the gated ratios are between exactly those sections, so they cancel
    fast, slow = _bench(), _bench()
    for key in ("value", "telemetry_packed_events_per_sec",
                "sharded_1chip_events_per_sec",
                "multitenant_sharded_events_per_sec"):
        slow[key] = slow[key] * 0.4
    assert ratios_of(fast) == pytest.approx(ratios_of(slow))
    assert compare(fast, slow, tol=0.05)["ok"]


def test_compare_flags_shape_change():
    prev = _bench()
    cur = _bench(sharded=36e6 * 0.6)  # sharded regressed 40% vs headline
    out = compare(prev, cur, tol=0.25)
    assert not out["ok"]
    # both ratios involving the sharded rate move past tolerance
    assert set(out["failures"]) == {"sharded_vs_headline",
                                    "multitenant_vs_sharded"}
    assert out["ratios"]["sharded_vs_headline"]["drift_pct"] == -40.0


def test_compare_absolute_host_sections():
    # persist never touches the link: judged absolutely (both runs
    # carry comparable host fingerprints), not vs headline
    prev = _bench()
    out = compare(prev, _bench(persist=8e6 * 0.5))
    assert not out["ok"]
    assert out["failures"] == ["persist_events_per_sec"]
    assert out["absolutes"]["persist_events_per_sec"]["drift_pct"] == -50.0
    # a uniformly slower link does NOT move the absolute host sections
    slow = _bench(headline=40e6 * 0.4, telemetry=44e6 * 0.4,
                  sharded=36e6 * 0.4, multitenant=34e6 * 0.4)
    assert compare(prev, slow, tol=0.05)["ok"]
    # compute_only mixes resource domains: never part of the gate
    assert compare(prev, _bench(compute=600e6 * 3.0))["ok"]


def test_host_state_mismatch_makes_absolutes_advisory():
    """Host-absolute drift hard-fails ONLY between host-comparable runs:
    VM CPU steal moves host absolutes 4x on unchanged code."""
    prev = _bench()
    # 4x slower host fingerprint: the same persist regression is now
    # unattributable -> advisory, not a failure (but still reported)
    cur = _bench(persist=8e6 * 0.5, host_ms=20.0)
    out = compare(prev, cur)
    assert out["ok"]
    assert out["failures"] == []
    assert out["absolutes"]["persist_events_per_sec"][
        "advisory_exceeded"] is True
    assert "host CPU state mismatch" in out["absolutes_advisory"]
    # ratio drift still hard-fails regardless of host state
    out = compare(prev, _bench(sharded=36e6 * 0.6, host_ms=20.0))
    assert not out["ok"]
    # a baseline recorded before the fingerprint existed can never prove
    # comparability -> advisory there too
    out = compare(_bench(host_ms=None), _bench(persist=8e6 * 0.5))
    assert out["ok"]
    assert "no host fingerprint" in out["absolutes_advisory"]


def test_host_hardware_identity_gates_absolutes():
    """cpu model + core count make "same machine" provable instead of
    inferred: different hardware can NEVER hard-fail host absolutes, same
    hardware (with comparable CPU-steal fingerprints) always does."""
    prev = _bench()
    # same model+cores, comparable argsort: absolute drift hard-fails
    out = compare(prev, _bench(persist=8e6 * 0.5))
    assert not out["ok"]
    assert out["failures"] == ["persist_events_per_sec"]
    # a DIFFERENT machine (other cpu model) with identical argsort
    # timing: advisory, never a hard failure
    out = compare(prev, _bench(persist=8e6 * 0.5, cpu_model="EPYC-Other"))
    assert out["ok"]
    assert out["absolutes"]["persist_events_per_sec"][
        "advisory_exceeded"] is True
    assert "different host hardware" in out["absolutes_advisory"]
    # core-count change alone (resized VM) is also different hardware
    out = compare(prev, _bench(persist=8e6 * 0.5, cpu_cores=8))
    assert out["ok"]
    assert "different host hardware" in out["absolutes_advisory"]
    # identity present on only one side (old baseline): falls back to
    # the argsort-only rule — still gated when argsort is comparable
    out = compare(_bench(cpu_model=None), _bench(persist=8e6 * 0.5))
    assert not out["ok"]
    # ratio drift hard-fails regardless of hardware identity
    out = compare(prev, _bench(sharded=36e6 * 0.6, cpu_model="EPYC-Other"))
    assert not out["ok"]


def test_compact_result_line_parses_and_fits_tail_capture():
    """The bench stdout line (with the new host fingerprint fields) must
    stay parseable JSON and <= the driver-tail budget (bench.py
    MAX_RESULT_LINE_BYTES), or the recorded round loses its numbers
    (VERDICT r5 weak #1)."""
    import bench as bench_mod

    # a representative full-scale result: every compact key populated
    # with realistic magnitudes, plus the gate verdict structure
    result = _bench()
    result.update({
        "unit": "events/sec", "vs_baseline": 40.1, "scale": "full",
        "trials": 3, "p50_step_ms": 1.234, "p99_step_ms": 5.678,
        "p99_rule_eval_ms": 2.345,
        "system_sustained_events_per_sec": 1.23e6,
        "latency_mode_p50_ms": 3.2, "latency_mode_p99_ms": 8.9,
        "latency_mode_trial_p99_ms": [112.4, 4.2, 97.0],
        "latency_mode": {"batch_size": 4096, "linger_ms": 1.0,
                         "adaptive_linger": True, "warm_flushes": 4,
                         "trial_warmup_offers": 2},
        "latency_fetch": {"d2h_fetches_per_offer": 2.0,
                          "d2h_bytes_per_offer": 2048.0,
                          "lane_capacity": 128,
                          "command_lane_capacity": 64},
        "materialize_lane_speedup_x": 12.34,
        "actuation": {"lane_vs_host_speedup_x": 1.8,
                      "marginal_step_pct": 3.2,
                      "detection_to_actuation_p99_ms": 4.1,
                      "d2h_fetches_per_offer": 2.0},
        "drift": {"time_to_adapt_s": 0.42},
        "telemetry_wire_bytes_per_event": 13.7,
        "analytics_replay_events_per_sec": 1.0e7,
        "sharded_from_bytes_events_per_sec": 2.1e7,
        "sharded_1chip_router_ms_per_step": 1.93,
        "device_routing": {"device_route_ms_per_step": 0.82,
                           "host_route_ms_per_step": 2.46,
                           "router_offload_speedup_x": 3.0,
                           "parity_ok": True, "lane_capacity": 32768},
        "query_10m_narrow_window_ms": 14.2,
        "spread_pct": {"headline": 8.0, "sharded": 11.0, "latency": 22.0},
        "device": "TPU v5e-8",
        "metric": "events/sec (fused step, 65536 devices, batch 8192, "
                  "8 shards)",
        "step_breakdown": {"pack_ms": 0.8, "h2d_ms": 1.1, "device_ms": 0.9,
                           "sync_total_ms": 3.0, "unaccounted_pct": 5.0,
                           "wire_bytes_per_event": 36.0},
    })
    # worst-case long cpu model string is still bounded by the probe
    result["link_probe_pre"].update({
        "dispatch_rtt_ms_p50": 0.123, "h2d_4mb_mbps_last": 1432.1,
        "host_cpu_model": "X" * 64, "host_cpu_cores": 256})
    result["perf_gate"] = gate_against_recorded(result, root="/nonexistent")
    compact = bench_mod._compact_result(result, "BENCH_DETAIL.json")
    line = json.dumps(compact, separators=(",", ":"))
    assert len(line) <= bench_mod.MAX_RESULT_LINE_BYTES, len(line)
    parsed = json.loads(line)
    assert parsed["link_probe_pre"]["host_cpu_model"] == "X" * 64
    assert parsed["link_probe_pre"]["host_cpu_cores"] == 256
    # and the gate can read its own fingerprint back from the line
    assert extract_bench(parsed) is parsed


def test_live_host_identity_shape():
    """_host_cpu_identity returns a bounded model string + positive core
    count on this machine (whatever it is)."""
    import bench as bench_mod

    model, cores = bench_mod._host_cpu_identity()
    assert isinstance(model, str) and len(model) <= 64
    assert isinstance(cores, int) and cores > 0


def test_self_consistency_breakdown_and_spread():
    assert self_consistency(_bench())["ok"]
    bad = self_consistency(_bench(unaccounted_pct=80.0))
    assert not bad["ok"]
    assert not bad["checks"]["breakdown_explains_sync_total"]["ok"]
    wild = self_consistency(_bench(spreads={"headline": 75.0}))
    assert not wild["ok"]
    assert wild["checks"]["trial_spread_bounded"]["wild"] == {
        "headline": 75.0}
    # a bench with no breakdown/spread fields (old rounds) has nothing to
    # check and must not crash
    assert self_consistency({"value": 1.0})["ok"]


def test_gate_accepts_either_of_last_two_rounds(tmp_path):
    # r03 is a healthy round; r04 is the anomalous one (sharded ratio
    # collapsed). A current run matching r03's shape must PASS even though
    # it drifts >tol from r04 — one bad round must not poison the gate.
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"parsed": _bench()}))
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(
        {"parsed": _bench(sharded=36e6 * 0.6)}))
    gate = gate_against_recorded(_bench(), root=str(tmp_path))
    assert gate["ok"]
    assert not gate["vs_recorded"]["r04"]["ok"]
    assert gate["vs_recorded"]["r03"]["ok"]
    # drifted from BOTH (host-comparable fingerprints) -> fail
    gate = gate_against_recorded(_bench(persist=8e6 * 3.0),
                                 root=str(tmp_path))
    assert not gate["ok"]


def test_gate_with_no_recorded_rounds_passes_on_consistency_alone(tmp_path):
    assert gate_against_recorded(_bench(), root=str(tmp_path))["ok"]
    assert not gate_against_recorded(
        _bench(unaccounted_pct=60.0), root=str(tmp_path))["ok"]


def test_scale_mismatch_skips_ratio_comparison(tmp_path):
    # A BENCH_SCALE=small smoke must never be judged against a recorded
    # full-scale round — the metric string embeds the workload config.
    full = _bench()
    small = _bench(sharded=36e6 * 0.3)
    small["metric"] = "events/sec ... (fused step, 2000 devices, batch 2048)"
    out = compare(full, small)
    assert out["ok"] and out["skipped"] == "scale_mismatch"
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({"parsed": full}))
    gate = gate_against_recorded(small, root=str(tmp_path))
    # fails OPEN but visibly: ok without compared means no drift check ran
    assert gate["ok"] and not gate["compared"]


def test_gate_compared_flag_reflects_real_comparisons(tmp_path):
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(
        {"parsed": _bench()}))
    gate = gate_against_recorded(_bench(), root=str(tmp_path))
    assert gate["ok"] and gate["compared"]
    # corrupt recorded file -> fail-open, flagged
    (tmp_path / "BENCH_r04.json").write_text("{broken")
    gate = gate_against_recorded(_bench(), root=str(tmp_path))
    assert gate["ok"] and not gate["compared"]


def test_small_scale_spread_judged_against_wider_bound():
    noisy = _bench(spreads={"sync_total": 110.0}, unaccounted_pct=40.0)
    # the small smoke's steady-state windows are still judged, but
    # against the wider scheduler-noise bound (150% vs 60%)
    noisy["scale"] = "small"
    out = self_consistency(noisy)
    assert out["ok"]
    assert out["checks"]["trial_spread_bounded"]["max_pct"] == 150.0
    wild = _bench(spreads={"sync_total": 180.0})
    wild["scale"] = "small"
    assert not self_consistency(wild)["ok"]
    noisy["scale"] = "full"
    out = self_consistency(noisy)
    assert not out["ok"]
    assert not out["checks"]["breakdown_explains_sync_total"]["ok"]
    assert not out["checks"]["trial_spread_bounded"]["ok"]


def test_latency_budget_check():
    ok = _bench()
    # a degraded-link trial does not fail the budget if another trial met
    # it — one passing trial is the capability proof
    ok["latency_mode_trial_p99_ms"] = [112.4, 4.2, 97.0]
    assert self_consistency(ok)["ok"]
    bad = _bench()
    bad["latency_mode_trial_p99_ms"] = [112.4, 12.5, 97.0]
    out = self_consistency(bad)
    assert not out["ok"]
    assert out["checks"]["latency_budget_met"]["best_trial_p99_ms"] == 12.5
    # the budget is judged at EVERY scale since the steady-state window:
    # the CPU smoke's warm path must meet it too, or CI cannot vouch for
    # the latency tier
    bad["scale"] = "small"
    assert not self_consistency(bad)["ok"]
    small_ok = _bench()
    small_ok["latency_mode_trial_p99_ms"] = [112.4, 4.2, 97.0]
    small_ok["scale"] = "small"
    assert self_consistency(small_ok)["ok"]


def test_latency_fetch_budget_check():
    """The latency tier must ship exactly TWO fixed-shape D2H fetches
    per offer (alert lane + command lane, one batched device_get), bytes
    bounded by the two lane capacities — a regression to per-array
    fetches fails loudly on any host, any link state."""
    ok = _bench()
    ok["latency_fetch"] = {"d2h_fetches_per_offer": 2.0,
                           "d2h_bytes_per_offer": 2048.0,
                           "lane_capacity": 128,
                           "command_lane_capacity": 64}
    out = self_consistency(ok)
    assert out["ok"]
    assert out["checks"]["latency_fetch_budget"]["ok"]
    assert out["checks"]["latency_fetch_budget"][
        "max_bytes_per_offer"] == 128 * 16 + 64 * 16
    # an extra fetch per offer (regression to per-array fetching) fails
    bad = _bench()
    bad["latency_fetch"] = {"d2h_fetches_per_offer": 3.0,
                            "d2h_bytes_per_offer": 2048.0,
                            "lane_capacity": 128,
                            "command_lane_capacity": 64}
    assert not self_consistency(bad)["ok"]
    # so does losing the command lane's ride-along (one bare fetch)
    one = _bench()
    one["latency_fetch"] = {"d2h_fetches_per_offer": 1.0,
                            "d2h_bytes_per_offer": 2048.0,
                            "lane_capacity": 128,
                            "command_lane_capacity": 64}
    assert not self_consistency(one)["ok"]
    # fatter-than-budget bytes fail even at the pinned fetch count
    fat = _bench()
    fat["latency_fetch"] = {"d2h_fetches_per_offer": 2.0,
                            "d2h_bytes_per_offer": 128 * 16 + 64 * 16 + 4,
                            "lane_capacity": 128,
                            "command_lane_capacity": 64}
    assert not self_consistency(fat)["ok"]
    # rounds recorded before the command lane reported its capacity get
    # the default allowance, not a failure
    old = _bench()
    old["latency_fetch"] = {"d2h_fetches_per_offer": 2.0,
                            "d2h_bytes_per_offer": 2048.0,
                            "lane_capacity": 128}
    assert self_consistency(old)["ok"]
    # rounds recorded before the lanes existed have nothing to check
    assert self_consistency(_bench())["ok"]


def test_cli_exit_codes(tmp_path, capsys):
    prev, cur = tmp_path / "prev.json", tmp_path / "cur.json"
    prev.write_text(json.dumps(_bench()))
    cur.write_text(json.dumps(_bench()))
    assert main([str(prev), str(cur)]) == 0
    cur.write_text(json.dumps(_bench(sharded=36e6 * 0.5)))
    assert main([str(prev), str(cur)]) == 1
    assert "sharded_vs_headline" in capsys.readouterr().err
    cur.write_text(json.dumps({"rc": 1}))
    assert main([str(prev), str(cur)]) == 2


def test_latency_budget_advisory_on_cpu_host():
    """The 10 ms p99 is a TPU target: a CPU-only bench host (r05's
    228 ms) records the miss as advisory instead of hard-failing, while
    accelerator-fingerprinted runs still gate."""
    cpu = _bench()
    cpu["device"] = "TFRT_CPU_0"
    cpu["latency_mode_trial_p99_ms"] = [233.2, 228.2, 802.7]
    out = self_consistency(cpu)
    assert out["ok"]
    entry = out["checks"]["latency_budget_met"]
    assert entry["ok"] and "advisory" in entry
    tpu = _bench()
    tpu["latency_mode_trial_p99_ms"] = [233.2, 228.2, 802.7]
    assert not self_consistency(tpu)["ok"]  # device is TPU in _bench()


def test_device_routing_check():
    """Parity is a hard fact on any host; the offload speedup gates at
    EVERY scale on accelerator hosts (the sort-based bucketing makes
    small batches winnable) and is advisory on CPU-only hosts."""
    ok = _bench()
    ok["device_routing"] = {"router_offload_speedup_x": 3.0,
                            "parity_ok": True}
    out = self_consistency(ok)
    assert out["ok"] and out["checks"]["device_routing"]["ok"]
    # broken parity fails at EVERY scale
    broken = _bench()
    broken["device_routing"] = {"router_offload_speedup_x": 3.0,
                                "parity_ok": False}
    assert not self_consistency(broken)["ok"]
    broken["scale"] = "small"
    assert not self_consistency(broken)["ok"]
    # a sub-1x offload fails on an accelerator host at EVERY scale...
    slow = _bench()
    slow["device_routing"] = {"router_offload_speedup_x": 0.4,
                              "parity_ok": True}
    assert not self_consistency(slow)["ok"]
    slow["scale"] = "small"
    assert not self_consistency(slow)["ok"]
    # ...and is advisory only on a CPU-only bench host
    slow["device"] = "TFRT_CPU_0"
    out = self_consistency(slow)
    assert out["ok"]
    assert "speedup_advisory" in out["checks"]["device_routing"]
    # parity stays hard even on the cpu host
    slow["device_routing"]["parity_ok"] = False
    assert not self_consistency(slow)["ok"]
    # rounds recorded before the device route existed have no check
    assert "device_routing" not in self_consistency(_bench())["checks"]


def test_link_waiver_on_degraded_h2d():
    """On a degraded H2D link (probe below MIN_LINK_H2D_MBPS) the
    link-sensitive misses become structured link_waived verdicts with
    the probe attached; the same misses stay hard on a healthy link,
    and the bit-fact checks (parity, fetch budget) never waive."""
    slow = _bench()
    slow["device_routing"] = {"router_offload_speedup_x": 0.4,
                              "parity_ok": True}
    slow["rule_programs"] = {"d2h_fetches_per_offer": 2,
                             "compiled_vs_host_speedup_x": 0.2}
    slow["anomaly_models"] = {"d2h_fetches_per_offer": 2,
                              "offload_speedup_x": 0.75,
                              "marginal_step_pct": 2.0}
    slow["actuation"] = {"d2h_fetches_per_offer": 2,
                         "marginal_step_pct": 22.0}
    slow["latency_mode_trial_p99_ms"] = [233.2, 228.2]
    # accelerator host, healthy link (no probe evidence of degradation):
    # every miss is a hard FAIL
    assert not self_consistency(slow)["ok"]
    slow["link_probe_pre"]["h2d_4mb_mbps_last"] = 1200.0
    assert not self_consistency(slow)["ok"]
    # degraded link: the misses carry waiver objects and ok holds
    slow["link_probe_pre"]["h2d_4mb_mbps_last"] = 9.0
    out = self_consistency(slow)
    assert out["ok"]
    for name in ("device_routing", "rule_programs", "anomaly_models",
                 "actuation_lanes", "latency_budget_met"):
        entry = out["checks"][name]
        assert entry["ok"], name
        waiver = entry["link_waived"]
        assert waiver["waived"] == "link_degraded"
        assert waiver["h2d_4mb_mbps"] == {"link_probe_pre": 9.0}
    # parity + the fetch budget stay hard even on a degraded link
    slow["device_routing"]["parity_ok"] = False
    assert not self_consistency(slow)["ok"]
    slow["device_routing"]["parity_ok"] = True
    slow["rule_programs"]["d2h_fetches_per_offer"] = 3
    assert not self_consistency(slow)["ok"]


def test_link_waiver_makes_absolute_drift_advisory():
    """Absolute drift against (or from) a degraded-link run is recorded
    with a structured waiver instead of hard-failing: a degraded link
    is whole-VM I/O weather, the same condition that swings host
    absolutes on unchanged code."""
    prev, cur = _bench(), _bench(persist=8e6 * 3)   # 3x host drift
    assert not compare(prev, cur)["ok"]             # comparable hosts: FAIL
    cur["link_probe_pre"]["h2d_4mb_mbps_last"] = 12.0
    out = compare(prev, cur)
    assert out["ok"]
    assert out["link_waived"]["waived"] == "link_degraded"
    entry = out["absolutes"]["persist_events_per_sec"]
    assert entry["advisory_exceeded"]
    # ratio drift is NEVER link-waived (ratios cancel the link by
    # construction — drift there is workload shape, not weather)
    worse = _bench(sharded=36e6 * 0.5)
    worse["link_probe_pre"]["h2d_4mb_mbps_last"] = 12.0
    assert not compare(_bench(), worse)["ok"]
