"""cycle_events_per_s: the inbound consumer's rate within its commit
cycles, the median over the window's cycles of the events committed at an
edge over the time since the edge before. Steadier than
served_events_per_s, which also carries the rare long cycle."""

import numpy as np


def read(run):
    ct = run.commit_at
    inside = (ct > run.t_open) & (ct <= run.t_close)
    edges, records = np.unique(ct[inside], return_counts=True)
    if edges.size == 0:
        return None
    cycle_s = np.diff(np.concatenate([[run.t_open], edges]))
    rates = records * run.per_record / cycle_s
    run.note(f"cycle_events_per_s: p50={np.median(rates):.3f} "
             f"min={rates.min():.3f} max={rates.max():.3f} "
             f"cycles={edges.size}")
    return float(np.median(rates))
