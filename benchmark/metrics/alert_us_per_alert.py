"""alert_us_per_alert: the inbound consumer's `materialize` and
`alert_persist` time in its window cycles (benchmark/cycles.py) over the
rule alerts those cycles persisted (each cycle record's `alerts`), in
microseconds per alert. A program whose cycle records carry no `alerts`
gives nothing."""

from benchmark import cycles


def read(run):
    recs = cycles.window_cycles(run)
    if recs is None:
        return None
    alerts = sum(rec.get("alerts", 0) for rec in recs)
    if alerts <= 0:
        return None
    ms = cycles.stage_ms(recs, ("materialize", "alert_persist"))
    run.note(f"alert_us_per_alert: {1e3 * ms / alerts:.3f} over {alerts} "
             f"alerts in {len(recs)} cycles")
    return 1e3 * ms / alerts
