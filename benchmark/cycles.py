"""The program's consumer cycles, as the per-layer readers see them
after the run.

The inbound consumer's cycles come from the program's ring of cycle
records (`GLOBAL_CYCLES`, one ring per consumer label): those whose
commit began inside the window, (t_open, t_close], which are the cycles
that committed the window's records. Other consumers cycle too fast for
a ring to keep the window, so their numbers come from the lossless
`bus.consumer_*` histograms, summed over the whole run (warm-up, window
and drain) and taken per record the inbound consumer handled over the
same run. A program that keeps neither gives nothing, and every
function here then returns None."""

from typing import Dict, Iterable, List, Optional

INBOUND = "inbound-processing"
STAGE_FAMILY = "bus.consumer_stage_seconds"
RECORDS_FAMILY = "bus.consumer_cycle_records"
# the inbound cycle's leaf stages: every stage but the parents
# `handler` and `persist`
INBOUND_LEAVES = ("poll", "decode", "validate", "persist.context",
                  "persist.append", "persist.fanout", "pack_events", "step",
                  "materialize", "alert_persist", "commit")


def ring_records(consumer: str) -> Optional[List[Dict]]:
    """Every cycle record the program's ring still holds for `consumer`;
    None where the program keeps no ring."""
    from sitewhere_tpu.runtime import flight

    ring = getattr(flight, "GLOBAL_CYCLES", None)
    if ring is None:
        return None
    return ring.export(last_n=ring.capacity, consumer=consumer)["records"]


def stage_totals() -> Dict[tuple, float]:
    """Summed seconds per (consumer, stage) over the run."""
    from sitewhere_tpu.runtime.metrics import GLOBAL_METRICS

    snap = GLOBAL_METRICS.histogram(STAGE_FAMILY).snapshot()
    return {(dict(key)["consumer"], dict(key)["stage"]): child["sum_s"]
            for key, child in snap.items()}


def records_total(consumer: str = INBOUND) -> float:
    """Records `consumer` handled over the run; 0 where the program
    counts none."""
    from sitewhere_tpu.runtime.metrics import GLOBAL_METRICS

    snap = GLOBAL_METRICS.histogram(RECORDS_FAMILY).snapshot()
    return sum(child["sum_s"] for key, child in snap.items()
               if dict(key).get("consumer") == consumer)


def window_cycles(run, consumer: str = INBOUND) -> Optional[List[Dict]]:
    """`consumer`'s cycles whose commit began in (t_open, t_close]; None
    where there are none."""
    out = []
    for rec in ring_records(consumer) or []:
        commit = rec["stages"].get("commit")
        if commit is not None \
                and run.t_open < commit["begin_s"] <= run.t_close:
            out.append(rec)
    return out or None


def stage_ms(recs: Iterable[Dict], stages: Iterable[str]) -> float:
    stages = tuple(stages)
    return sum(rec["stages"][s]["ms"] for rec in recs for s in stages
               if s in rec["stages"])


def per_record_us(run, stages: Iterable[str]) -> Optional[float]:
    """Inbound's summed `stages` time over its records in the window, in
    microseconds per record."""
    recs = window_cycles(run)
    if recs is None:
        return None
    records = sum(rec["records"] for rec in recs)
    return stage_ms(recs, stages) / records * 1e3 if records else None


def coverage(recs: List[Dict]) -> Optional[float]:
    """The leaf stages' summed time over the cycles' summed wall (poll
    start to commit end)."""
    wall = sum(rec["span_ms"] for rec in recs)
    return stage_ms(recs, INBOUND_LEAVES) / wall if wall > 0.0 else None


def busy_totals() -> Dict[str, float]:
    """Handler + commit seconds per consumer over the run: the wall its
    thread spent on its batches."""
    out: Dict[str, float] = {}
    for (consumer, stage), secs in stage_totals().items():
        if stage in ("handler", "commit"):
            out[consumer] = out.get(consumer, 0.0) + secs
    return out


def others_us_per_inbound_record() -> Optional[Dict[str, float]]:
    """Every other consumer's handler + commit wall over the run, in
    microseconds per record the inbound consumer handled; None where the
    program counts no inbound records."""
    records = records_total()
    if records <= 0:
        return None
    return {consumer: secs / records * 1e6
            for consumer, secs in busy_totals().items()
            if consumer != INBOUND}
