"""other_consumers_us_per_record: the handler and commit wall of every
bus consumer other than inbound, summed, over the records the inbound
consumer handled, in microseconds per inbound record. Both come from the
program's lossless counters over the whole run (`bus.consumer_stage_
seconds` and `bus.consumer_cycle_records`, benchmark/cycles.py). Taken
per inbound record, it holds still when inbound alone gets faster; the
consumers overlap in wall time, so each one's wall counts in full."""

from benchmark import cycles


def read(run):
    others = cycles.others_us_per_inbound_record()
    return None if others is None else sum(others.values())
