"""decode_validate_us_per_record: the inbound consumer's decode and
validate time over the records its window cycles handled (benchmark/
cycles.py), in microseconds per record.

Its note gives every inbound stage per record, the persist split, poll
and commit per cycle, the three busiest other consumers and the share of
the inbound cycles' wall the leaf stages cover."""

from benchmark import cycles

STAGES = ("decode", "validate", "persist", "persist.context",
          "persist.append", "persist.fanout", "pack_events", "step",
          "materialize", "alert_persist")


def read(run):
    value = cycles.per_record_us(run, ("decode", "validate"))
    if value is None:
        return None
    recs = cycles.window_cycles(run)
    stages = " ".join(f"{s}={cycles.per_record_us(run, (s,)):.3f}"
                      for s in STAGES)
    per_cycle = " ".join(
        f"{s}_ms_per_cycle={cycles.stage_ms(recs, (s,)) / len(recs):.3f}"
        for s in ("poll", "commit"))
    others = cycles.others_us_per_inbound_record() or {}
    busiest = " ".join(f"{c}={us:.3f}" for c, us in sorted(
        others.items(), key=lambda kv: -kv[1])[:3])
    cover = cycles.coverage(recs)
    run.note(f"inbound_us_per_record: {stages}; {per_cycle} "
             f"cycles={len(recs)}; busiest_other (us per inbound record, "
             f"whole run): {busiest or 'none'}; coverage="
             + (f"{100.0 * cover:.2f}%" if cover is not None else "none"))
    return value
