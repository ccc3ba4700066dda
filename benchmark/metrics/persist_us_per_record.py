"""persist_us_per_record: the inbound consumer's persist time (the
device and assignment lookups and context, the event-log append and the
triggers' fan-out) over the records its window cycles handled
(benchmark/cycles.py), in microseconds per record."""

from benchmark import cycles


def read(run):
    return cycles.per_record_us(run, ("persist",))
