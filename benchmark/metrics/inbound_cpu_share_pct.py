"""inbound_cpu_share_pct: 100 x the inbound consumer thread's CPU time
(`time.thread_time`) over its handler and commit wall, in its window
cycles (benchmark/cycles.py). 100 less this is the share of those stages
the thread waited: for the interpreter lock, or for I/O."""

from benchmark import cycles


def read(run):
    recs = cycles.window_cycles(run)
    if recs is None:
        return None
    busy = cycles.stage_ms(recs, ("handler", "commit"))
    if busy <= 0.0:
        return None
    return 100.0 * sum(rec["cpu_ms"] for rec in recs) / busy
