"""The consumer-cycle readers over hand-made cycle records and counters:
each value, nothing where the program keeps no cycles, and the note."""

import numpy as np
import pytest

from benchmark import cycles, harness


def _cycle(seq, commit_at, records=100, cpu_ms=60.0, consumer=None):
    """An inbound cycle of 100 ms: poll 1, decode 20, validate 10,
    persist 50 (context 10, append 15, fanout 25), step 9, commit 1;
    `commit_at` is when its commit began."""
    t = commit_at - 0.099
    stages = {
        "poll": (t, 1.0), "handler": (t + 0.001, 98.0),
        "decode": (t + 0.001, 20.0), "validate": (t + 0.002, 10.0),
        "persist": (t + 0.003, 50.0), "persist.context": (t + 0.003, 10.0),
        "persist.append": (t + 0.004, 15.0),
        "persist.fanout": (t + 0.005, 25.0), "step": (t + 0.09, 9.0),
        "commit": (commit_at, 1.0)}
    return {"seq": seq, "consumer": consumer or cycles.INBOUND,
            "records": records, "events": records,
            "stages": {k: {"begin_s": b, "ms": ms}
                       for k, (b, ms) in stages.items()},
            "span_ms": 100.0, "cpu_ms": cpu_ms, "steps": []}


RING = [_cycle(0, 9.9), _cycle(1, 10.2), _cycle(2, 10.3, records=300),
        _cycle(3, 11.0), _cycle(4, 11.1)]
TOTALS = {(cycles.INBOUND, "handler"): 4.0, (cycles.INBOUND, "commit"): 1.0,
          (cycles.INBOUND, "poll"): 0.5, ("enrichment", "handler"): 2.0,
          ("enrichment", "commit"): 0.5, ("command-delivery", "handler"): 0.25,
          ("enrichment", "poll"): 9.0}
# records the inbound consumer handled over the run
INBOUND_RECORDS = 2750


def _run():
    # the window (10.0, 11.0]: cycles 1, 2 and 3 committed in it
    return harness.Run(cell={}, cfg={}, mix={}, seconds=1.0, setup_s=0.0,
                       per_record=1, t_open=10.0, t_close=11.0,
                       commit_at=np.array([]))


@pytest.fixture
def program(monkeypatch):
    monkeypatch.setattr(cycles, "ring_records", lambda consumer: [
        r for r in RING if r["consumer"] == consumer])
    monkeypatch.setattr(cycles, "stage_totals", lambda: dict(TOTALS))
    monkeypatch.setattr(cycles, "records_total", lambda: INBOUND_RECORDS)


@pytest.fixture
def no_program(monkeypatch):
    monkeypatch.setattr(cycles, "ring_records", lambda consumer: None)
    monkeypatch.setattr(cycles, "stage_totals", lambda: {})
    monkeypatch.setattr(cycles, "records_total", lambda: 0)


def test_window_takes_the_cycles_that_committed_in_it(program):
    assert [r["seq"] for r in cycles.window_cycles(_run())] == [1, 2, 3]


def test_reader_values(program):
    read = harness.load_reader
    run = _run()
    # 3 cycles, 500 records: 30 ms decode + validate each
    assert read("decode_validate_us_per_record")(run) == pytest.approx(
        3 * 30.0 / 500 * 1e3)
    assert read("persist_us_per_record")(run) == pytest.approx(
        3 * 50.0 / 500 * 1e3)
    assert read("inbound_cpu_share_pct")(run) == pytest.approx(
        100.0 * 180.0 / 297.0)
    # (2.5 + 0.25) s over the run's 2,750 inbound records
    assert read("other_consumers_us_per_record")(run) == pytest.approx(
        1000.0)


def test_readers_give_nothing_without_the_program(no_program):
    run = _run()
    for name in ("decode_validate_us_per_record", "persist_us_per_record",
                 "inbound_cpu_share_pct", "other_consumers_us_per_record"):
        assert harness.load_reader(name)(run) is None, name
    assert run.notes == []


def test_the_note_carries_stages_consumers_and_coverage(program):
    run = _run()
    harness.load_reader("decode_validate_us_per_record")(run)
    [note] = run.notes
    assert note.startswith("inbound_us_per_record: decode=120.000 "
                           "validate=60.000 persist=300.000 "
                           "persist.context=60.000 persist.append=90.000 "
                           "persist.fanout=150.000 pack_events=0.000 "
                           "step=54.000")
    assert "poll_ms_per_cycle=1.000 commit_ms_per_cycle=1.000 cycles=3" \
        in note
    assert "enrichment=909.091 command-delivery=90.909" in note
    # leaves: poll 1 + decode 20 + validate 10 + context 10 + append 15 +
    # fanout 25 + step 9 + commit 1 = 91 ms of each 100 ms cycle
    assert note.endswith("coverage=91.00%")
