"""served_events_per_s: events of the records the inbound consumer
committed inside the window, over the window's length. The window opens
at a commit edge and closes at the first edge at or after its nominal
length, so every second of it and every event committed in it count."""

import numpy as np


def read(run):
    ct = run.commit_at
    inside = (ct > run.t_open) & (ct <= run.t_close)
    window_s = run.t_close - run.t_open
    events = int(inside.sum()) * run.per_record
    edges = np.unique(ct[inside])
    cycles = np.diff(np.concatenate([[run.t_open], edges]))
    run.note(f"served_events_per_s: {events / window_s:.3f} = {events} "
             f"events over {window_s:.3f} s; commit_edges={edges.size} "
             f"cycle_s p50={np.median(cycles) if cycles.size else 0:.3f} "
             f"max={cycles.max() if cycles.size else 0:.3f}")
    return events / window_s
